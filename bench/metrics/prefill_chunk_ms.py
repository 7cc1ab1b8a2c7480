"""Model step: device time per call of the chunked-prefill program, from
the trace.  It runs in the same tick as the decode step, so it lengthens
every decoding row's gap.

The engine jits the chunk step from a lambda; in these cells it is the
only lambda program that runs (decode goes through ``serve_sample_body``,
the paged pool needs no row reset, speculation is off), so the reader
takes the programs whose name says ``lambda``."""

MODULE = "jit__lambda"


def read(run):
    n, t = run.trace.modules(MODULE)
    return 1e3 * t / n if n else None
