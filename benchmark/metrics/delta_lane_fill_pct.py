"""How full the step program's per-tick buffers are, %: the median
(``measures.percentile``) over the window's ticks of the rows the tick's
buffers held over their lanes. The buffers are those of every checked
capacity the step program fills anew each tick (inputs, join fan-outs, the
queries and gathers of aggregates and top-K nodes, output deltas, window
slides, exchange buckets); the rows are their validated requirements,
summed, the lanes their capacities, summed. A sort network or a merge over
a buffer costs the same for a padding lane as for a row: the rest is
padding. From ``dbsp_tpu.timeseries.counters.VALIDATED_TICKS``
(``tick_live_rows``, ``tick_capacity_rows``), which validation fills from
the requirement vector it fetches anyway. None where the program keeps no
such record (the parent of the PR that added it) or kept fewer records than
the window has ticks: never a partial number.
Layer: step program (compiled/compiler.py validate)."""

import time_counters as tc


def fill_pct(live: int, capacity: int):
    return None if not capacity else 100.0 * live / capacity


def read(ctx):
    ticks = tc.window_records(ctx)
    if ticks is None or not all("tick_capacity_rows" in t for t in ticks):
        return None
    fills = [fill_pct(t["tick_live_rows"], t["tick_capacity_rows"])
             for t in ticks]
    if None in fills:
        return None
    return float(ctx["measures"].percentile(fills, 50))
