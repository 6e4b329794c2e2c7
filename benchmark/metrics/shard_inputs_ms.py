"""The tick's input batches onto the workers: the ``tick.shard_inputs``
spans (one per input, inside its ``tick.build_inputs``: bucket by key hash,
place one slice a chip, consolidate per worker, shrink to fit) summed per
tick, median over the window's ticks, ms. None where no window tick has
the span: one worker, or a program that does not record it.
Layer: input batch building (operators/io_handles.py, parallel/exchange.py)."""

import span_measures as sm

SPAN = "tick.shard_inputs"


def read(ctx):
    win = sm.window_of(ctx)
    if win is None or not any(
            s.name == SPAN for t in win.ticks.values()
            for s in t.descendants()):
        return None
    return sm.per_tick_ms(ctx, lambda t: t.total(SPAN))
