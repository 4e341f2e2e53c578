"""New executables (XLA compiles and persistent-cache loads) inside the
measured window, from ``jax.monitoring`` events.  Should read 0: a
compile in the window stalls every stream behind it."""


def read(run):
    return float(run.compiles)
