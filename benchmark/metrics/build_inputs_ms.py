"""Rows -> device batches: the ``tick.build_inputs`` spans (one per
input: the eager batch build and its consolidation sort, compiles and
all) summed per tick, median over the window's ticks, ms.
Layer: input batch building (zset/batch.py, operators/io_handles.py)."""

import span_measures as sm


def read(ctx):
    return sm.per_tick_ms(ctx, lambda t: t.total("tick.build_inputs"))
