"""Model step: device time per call of the fused decode-and-sample program
(``serve_sample_body``), from the trace."""

MODULE = "jit_serve_sample_body"


def read(run):
    n, t = run.trace.modules(MODULE)
    return 1e3 * t / n if n else None
