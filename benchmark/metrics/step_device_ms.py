"""Device time of one run of the step program (``step_fn``), mean over
the runs that lie wholly in the traced window, ms, from the trace's module line.
Layer: step program (compiled/compiler.py)."""


def read(ctx):
    s = ctx["measures"].mean_module_seconds(ctx["trace"], "step_fn")
    return None if s is None else s * 1e3
