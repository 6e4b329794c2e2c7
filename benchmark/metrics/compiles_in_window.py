"""Programs asked of the compiler inside the window (``jax.monitoring``
backend-compile events, persistent-cache loads included): the eager
programs input batch building dispatches at a new shape every tick.
Layer: input batch building (zset/batch.py). Counts may be 0; a count is
not a share, so 0 is reported."""


def read(ctx):
    run = ctx["run"]
    if run["open"] is None or run["close"] is None:
        return None
    return float(sum(1 for t, _ in ctx["compile_events"]
                     if run["open"] < t <= run["close"]))
