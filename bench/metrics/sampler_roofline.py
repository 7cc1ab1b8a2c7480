"""Kernels: the fused top-k/top-p Pallas filter against its roofline.

Every call filters the whole slot batch, one float32 vocabulary row per
slot, read once and written once (``bench/flops.py``); the least time of
a call is that over the HBM bandwidth (its few operations per entry are
far below the compute bound).  The share is the least time of all calls
in the trace over their device time."""
from bench import flops

KERNEL = "fused_sample"


def read(run):
    n, t = run.trace.ops(KERNEL)
    if not n or t <= 0:
        return None
    per_call = flops.least_time(
        *flops.sampler_call(run.shape, run.engine["slots"]), run.peak)
    return 100.0 * n * per_call / t
