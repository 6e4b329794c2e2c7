"""The interval-start snapshot of the engine's state (``tick.snapshot``),
median over the window's ticks, ms.
Layer: tick (compiled/driver.py)."""

import span_measures as sm


def read(ctx):
    return sm.per_tick_ms(ctx, lambda t: t.total("tick.snapshot"))
