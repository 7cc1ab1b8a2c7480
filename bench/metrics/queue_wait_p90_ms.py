"""Scheduler: how long the program kept a request waiting for a slot.

Program counter: each request's ``queued_s`` (the scheduler's clock from
entering the queue to admission, summed over admissions), copied by the
load loop; the nearest-rank 90th percentile over the requests due in the
window that were admitted.  A request never admitted has no first token
and is counted by ``ttft_p90_ms`` and ``failed`` instead."""
from bench import stats


def read(run):
    waits = [r.queued_s for r in run.counted if r.queued_s is not None]
    return stats.percentile(waits, 0.90) * 1e3 if waits else None
