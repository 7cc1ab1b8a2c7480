"""Scheduler: mean number of rows in the decode steps of the window (rows
the tick plans asked to decode, over the ticks that decoded)."""


def read(run):
    lo, hi = run.window
    rows = [len(t.decode) for t in run.ticks if lo <= t.t1 <= hi and t.decode]
    return sum(rows) / len(rows) if rows else None
