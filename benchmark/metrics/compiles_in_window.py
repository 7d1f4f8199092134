"""XLA compilations inside the measured window (JAX's backend-compile
events; the program cache's own counter is held to 0 by `correct`)."""


def read(ctx):
    return float(ctx["compiles_in_window"])
