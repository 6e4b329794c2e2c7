"""How full the top-K nodes' gathers are, %: the rows every ``CTopK``
re-read in the window's last tick over the summed capacity of their gathers
— the buffer the re-read histories are sorted in, whatever it holds. The
rest is padding that every tick sorts. From
``dbsp_tpu.timeseries.counters.VALIDATED_TICKS`` (``topk_gathered_rows``,
``topk_gather_capacity_rows``). None where the program has no such counter
or the circuit has no ``CTopK``.
Layer: top-k (compiled/cnodes.py CTopK; compiler.py presize)."""

import time_counters as tc


def fill_pct(rows: int, capacity: int):
    return None if not capacity else 100.0 * rows / capacity


def read(ctx):
    ticks = tc.window_records(ctx)
    if ticks is None or "topk_gathered_rows" not in ticks[-1]:
        return None
    return fill_pct(ticks[-1]["topk_gathered_rows"],
                    ticks[-1]["topk_gather_capacity_rows"])
