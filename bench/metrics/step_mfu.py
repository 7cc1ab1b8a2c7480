"""Whole step: operations of the useful work over the chip's peak.

Useful work is what the tick plans of the window asked for: the real
prompt tokens of every prefill chunk (each attending to the positions
before it, plus the logits row of a finished prompt) and every decoding
row (attending to its context), counted by ``bench/flops.py`` with the
architecture's per-token terms; padding rows and cached prefix tokens are
not work.  The time is the host-clock wall of those engine ticks, times
the chips, times the bf16 peak."""
from bench import flops


def read(run):
    lo, hi = run.window
    ticks = [t for t in run.ticks if lo <= t.t1 <= hi]
    wall = sum(t.t1 - t.t0 for t in ticks)
    if not ticks or wall <= 0:
        return None
    m = run.shape
    work = 0.0
    for t in ticks:
        work += flops.decode_step_flops(run.arch, m, t.decode)
        for start, stop, last in t.prefill:
            work += flops.prefill_flops(run.arch, m, start, stop, last)
    return 100.0 * work / (wall * run.chips * run.peak["bf16_flops_s"])
