"""From validated state to a readable view: ``tick.deliver`` +
``tick.emit_outputs`` + ``tick.publish``, median over the window's ticks,
ms.
Layer: tick (compiled/driver.py, io/controller.py, serving.py)."""

import span_measures as sm


def read(ctx):
    return sm.per_tick_ms(ctx, lambda t: t.total(
        "tick.deliver", "tick.emit_outputs", "tick.publish"))
