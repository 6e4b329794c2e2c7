"""Peak device bytes in use after the window, GB (1e9 bytes), from
``memory_stats()["peak_bytes_in_use"]``: the trace state, its snapshot copy
and the step program's temporaries.
Layer: trace state (compiled/cnodes.py CTrace)."""


def read(ctx):
    peak = ctx["memory_peak_bytes"]
    return None if not peak else peak / 1e9
