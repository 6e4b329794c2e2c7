"""Host time to dispatch the step program (``tick.dispatch``: feed
indexing, pytree flattening, the call) less the compiles asked inside it,
median over the window's ticks, ms.
Layer: step program (compiled/compiler.py)."""

import span_measures as sm


def read(ctx):
    def dispatch(tick):
        return sum(s.seconds - s.total("compile") for s in tick.descendants()
                   if s.name == "tick.dispatch")

    return sm.per_tick_ms(ctx, dispatch)
