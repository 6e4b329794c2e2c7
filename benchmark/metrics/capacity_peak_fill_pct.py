"""How near the window came to a grow and replay, %: the highest share of
its capacity that any checked capacity of the step program was required to
hold in any window tick, the capacities that size state carried across
ticks (trace levels, output traces) among them. At 100 the next larger tick
overflows: a grow, a replay and a new step program. From
``dbsp_tpu.timeseries.counters.VALIDATED_TICKS`` (``capacities``: per
capacity its device scope, key, class, validated requirement and
capacity). None where the program keeps no such record (the parent of the
PR that added it) or kept fewer records than the window has ticks: never a
partial number.

Before the reading it prints one fact line, ``{"phase": "capacity_fill"}``:
every capacity of the window's last tick, widest first, as ``[scope, kind,
class, required, capacity, peak_pct]`` (``peak_pct`` its highest share over
the window), so that a device trace's operations, named by lane count, can
be matched to the buffers they run over. Not a metric.
Layer: step program (compiled/compiler.py validate)."""

import json

import time_counters as tc

COLUMNS = ["scope", "kind", "class", "required", "capacity", "peak_pct"]


def peaks(ticks) -> dict:
    """(scope, kind) -> the highest requirement / capacity, %, over
    ``ticks``."""
    out: dict = {}
    for t in ticks:
        for scope, kind, _, required, capacity in t["capacities"]:
            pct = 100.0 * required / capacity
            if pct > out.get((scope, kind), -1.0):
                out[(scope, kind)] = pct
    return out


def fact_line(ticks, peak: dict) -> dict:
    last = sorted(ticks[-1]["capacities"],
                  key=lambda c: (-c[4], c[0], c[1]))
    return {"phase": "capacity_fill", "columns": COLUMNS,
            "window_ticks": len(ticks),
            "last_tick": [[*c, peak[(c[0], c[1])]] for c in last]}


def read(ctx):
    ticks = tc.window_records(ctx)
    if ticks is None or not all("capacities" in t for t in ticks):
        return None
    peak = peaks(ticks)
    if not peak:
        return None
    print(json.dumps(fact_line(ticks, peak)), flush=True)
    return max(peak.values())
