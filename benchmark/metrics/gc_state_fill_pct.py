"""How full the traces under a GC bound are, %: their live rows after the
truncation of the last window tick over the capacity of their levels. The
rest is padding that ``truncate_below``, the window's slices and the
snapshot walk every tick. From ``dbsp_tpu.timeseries.counters.
VALIDATED_TICKS`` (``gc_live_rows``, ``gc_capacity_rows``). None where the
program has no such counter or no trace under a bound.
Layer: time windows (compiled/compiler.py ``_run_nodes``: truncate_below)."""

import time_counters as tc


def fill_pct(live: int, capacity: int):
    return None if not capacity else 100.0 * live / capacity


def read(ctx):
    ticks = tc.window_records(ctx)
    if ticks is None:
        return None
    return fill_pct(ticks[-1]["gc_live_rows"], ticks[-1]["gc_capacity_rows"])
