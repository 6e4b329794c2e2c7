"""Rows the windows slid out of the view, a tick: the median
(``measures.percentile``) over the window's ticks of the rows that every
``CWindow`` retracted because the watermark moved on, summed over the
levels of its trace. 0 means no window retired and the cell measured
nothing. From the program's per-tick record
``dbsp_tpu.timeseries.counters.VALIDATED_TICKS`` (``retired_rows``), which
validation fills from the requirement vector it fetches anyway. None where
the program has no such counter (the parent of the PR that added it) or
kept fewer records than the window has ticks.
Layer: time windows (compiled/cnodes.py CWindow)."""

import time_counters as tc


def read(ctx):
    ticks = tc.window_records(ctx)
    if ticks is None:
        return None
    return float(ctx["measures"].percentile(
        [t["retired_rows"] for t in ticks], 50))
