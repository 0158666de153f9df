"""Model step: backend compiles (persistent-cache reads included) inside
the measured window, from JAX's monitoring events.  Set-up warms every
shape the mix can form, so this should read 0."""


def read(run):
    return float(run.compiles)
