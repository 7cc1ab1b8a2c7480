"""KV pool: share of the prompt tokens admitted in the window that the
prefix cache served without prefill (window delta of the pool's
``prefill_tokens_saved`` over the prompt tokens of the requests the
scheduler admitted in the window's ticks)."""


def read(run):
    c0, c1 = run.counters
    lo, hi = run.window
    admitted = sum(t.admitted for t in run.ticks if lo <= t.t1 <= hi)
    if not admitted:
        return None
    saved = c1["prefill_tokens_saved"] - c0["prefill_tokens_saved"]
    return 100.0 * saved / admitted
