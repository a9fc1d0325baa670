"""Backend compilations (JAX monitoring events) inside the window: a
shape the warm-up missed.  Persistent-cache loads count too."""


def read(run):
    return run.compiles.between(run.w0, run.w1)
