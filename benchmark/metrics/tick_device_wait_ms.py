"""The fetch of the tick's capacity requirements (``tick.device_wait``):
the host's wait for the step program on the device plus the transfer,
median over the window's ticks, ms.
Layer: step program (compiled/compiler.py)."""

import span_measures as sm


def read(ctx):
    return sm.per_tick_ms(ctx, lambda t: t.total("tick.device_wait"))
