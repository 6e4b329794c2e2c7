"""Whether the state of a windowed view stays bounded, %: the live rows of
all the circuit's leveled traces after the last window tick as a share of
those after the first. 100 is a view in steady state; a number that climbs
with the window's length is state that nothing retires. From
``dbsp_tpu.timeseries.counters.VALIDATED_TICKS`` (``trace_live_rows``).
None where the program has no such counter.
Layer: time windows (compiled/cnodes.py CTrace under CWindow)."""

import time_counters as tc


def plateau_pct(first: int, last: int):
    return None if not first else 100.0 * last / first


def read(ctx):
    ticks = tc.window_records(ctx)
    if ticks is None:
        return None
    return plateau_pct(ticks[0]["trace_live_rows"],
                       ticks[-1]["trace_live_rows"])
