"""Rows the top-K nodes re-read a tick: the median (``measures.percentile``)
over the window's ticks of the rows every ``CTopK`` gathered from its input
trace — the whole histories of the groups its delta touched — summed over
the circuit's top-K nodes. From the program's per-tick record
``dbsp_tpu.timeseries.counters.VALIDATED_TICKS`` (``topk_gathered_rows``),
which validation fills from the requirement vector it fetches anyway. None
where the program has no such counter (the parent of the PR that added
it), the circuit has no ``CTopK``, or the ring kept fewer records than the
window has ticks: never a partial number.
Layer: top-k (compiled/cnodes.py CTopK)."""

import time_counters as tc


def read(ctx):
    ticks = tc.window_records(ctx)
    if ticks is None or not all("topk_gathered_rows" in t for t in ticks):
        return None
    return float(ctx["measures"].percentile(
        [t["topk_gathered_rows"] for t in ticks], 50))
