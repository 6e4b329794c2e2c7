"""Time inside a tick spent asking the compiler for programs (backend
compile requests, persistent-cache loads included): the ``compile`` spans
anywhere under ``tick``, summed per tick, median over the window's ticks,
ms. 0 is a reading: a tick that asked for nothing.
Layer: input batch building (zset/batch.py)."""

import span_measures as sm


def read(ctx):
    return sm.per_tick_ms(ctx, lambda t: t.total("compile"))
