"""Spine maintenance between ticks (``tick.maintain``: host planning and
the dispatch of the drains), median over the window's ticks, ms.
Layer: trace state (compiled/compiler.py ``maintain``)."""

import span_measures as sm


def read(ctx):
    return sm.per_tick_ms(ctx, lambda t: t.total("tick.maintain"))
