"""KV pool: share of the prompt tokens admitted in the window that the
prefix cache served without prefill (window delta of the pool's
``prefill_tokens_saved`` over the prompt tokens of the requests the
scheduler admitted in the window's ticks).  An engine without a paged
pool saves nothing."""

SAVED = "prefill_tokens_saved"


def read(run):
    s0, s1 = run.engine_stats
    lo, hi = run.window
    admitted = sum(t.admitted for t in run.ticks if lo <= t.t1 <= hi)
    if not admitted:
        return None
    saved = s1.get(SAVED, 0) - s0.get(SAVED, 0)
    return 100.0 * saved / admitted
