"""KV pool: share of the leased KV blocks that hold written context.

Program counters of the paged pool, summed by the engine over its ticks:
``written_block_ticks`` over ``leased_block_ticks``, each as its delta
between the window's open and close (``engine_stats``).  A pool that
leases a request's whole budget at admission reads low while its requests
are young; an engine without a paged pool has no such counters and the
metric is left out."""

LEASED = "kv_pool.leased_block_ticks"
WRITTEN = "kv_pool.written_block_ticks"


def read(run):
    s0, s1 = run.engine_stats
    if LEASED not in s0 or LEASED not in s1:
        return None
    leased = s1[LEASED] - s0[LEASED]
    if leased <= 0:
        return None
    return 100.0 * (s1[WRITTEN] - s0[WRITTEN]) / leased
