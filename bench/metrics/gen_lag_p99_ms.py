"""Load generator: how late the generator submitted the window's requests.

Host clock: submit time minus due time, 99th percentile over the requests
due in the window.  A late generator is read as a slow
server by ``ttft_p90_ms``; this says how much of it was the generator."""
from bench import stats


def read(run):
    lags = [r.submitted - r.due_at for r in run.counted
            if r.submitted is not None]
    return stats.percentile(lags, 0.99) * 1e3 if lags else None
