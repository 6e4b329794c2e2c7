"""95th percentile (nearest rank; with under 20 ticks, the slowest) of the
client-clock time around POST /step over the window's ticks, s.
Layer: tick (io/controller.py, compiled/driver.py)."""


def read(ctx):
    return ctx["measures"].percentile(
        ctx["measures"].tick_seconds(ctx["run"]), 95)
