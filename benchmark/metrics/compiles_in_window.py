"""Programs asked of the compiler inside the window (``jax.monitoring``
backend-compile events, persistent-cache loads included): the eager
programs input batch building dispatches at a new shape every tick.
Layer: input batch building (zset/batch.py). Counts may be 0; a count is
not a share, so 0 is reported."""


def read(ctx):
    n = ctx["measures"].compiles_in_window(ctx["run"], ctx["compile_events"])
    return None if n is None else float(n)
