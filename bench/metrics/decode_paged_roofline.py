"""Kernels: the paged decode-attention Pallas kernel against its roofline.

Each decode tick calls the kernel once per layer that has a paged KV
cache (the architecture's ``paged_decode_layers``, with each layer's kv
heads); each call needs, per decoding row, the keys and values of that
row's context once (``bench/flops.py``).  The least time of a call is the
larger of its operations over the bf16 peak and its bytes over the HBM
bandwidth; the share is the sum of those least times over the kernel's
device time in the trace.  The ticks counted are those of the traced
window."""
from bench import flops

KERNEL = "gqa_decode_paged"


def read(run):
    n, t = run.trace.ops(KERNEL)
    if not n or t <= 0:
        return None
    lo, hi = run.traced
    m = run.shape
    groups = [(layers, {**m, "kv_heads": kv})
              for layers, kv in run.arch.paged_decode_layers(m)]
    least = 0.0
    for tick in run.ticks:
        if lo <= tick.t0 and tick.t1 <= hi and tick.decode:
            for layers, mk in groups:
                least += layers * flops.least_time(
                    *flops.decode_paged_call(mk, tick.decode), run.peak)
    return 100.0 * least / t if least else None
