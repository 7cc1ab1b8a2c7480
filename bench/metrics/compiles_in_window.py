"""Entry: programs compiled, or loaded from the compilation cache, inside
the measured window (JAX's ``backend_compile`` monitoring events).  Set-up
warms every shape the traffic reaches, so this should read 0: a program
met for the first time mid-window stalls every decoding slot."""


def read(run):
    return float(run.compiles_in_window)
