"""Device: share of the traced window in which no operation ran on the
chip (1 - union of operation intervals / window), averaged over the
cell's chips."""


def read(run):
    tr = run.trace
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
