"""Process-wide counters of compiled circuits: of their time nodes, what
the windows slid, what the trace-bound GC truncated, where the watermarks
stand; of their top-K nodes, what each re-read and changed; of every
circuit, how full each checked capacity was.

Filled at validation from scalars that ride the requirement vector the
handle fetches anyway (``compiler._Ctx.observe``, ``_Ctx.require``): no
device sync of their own. Keyed by node index like
``parallel/exchange.py::EXCHANGE_SITE_ROWS``; under a worker mesh a row
count is the worst worker's and a capacity is one worker's. With a
validation cadence above one, "the last tick" is the largest tick of the
interval. Exported by ``obs/instrument.py::export_time_counters`` as
``dbsp_tpu_window_slide_rows_total{node,dir}``,
``dbsp_tpu_trace_gc_rows_total{node}``, ``dbsp_tpu_trace_gc_live_rows{node}``
and ``dbsp_tpu_watermark_ms{node}``; by ``export_topk_counters`` as
``dbsp_tpu_topk_gathered_rows_total{node}``, ``dbsp_tpu_topk_groups_total``
and ``dbsp_tpu_topk_changed_rows_total``; by ``export_capacities`` as
``dbsp_tpu_capacity_rows{node,kind}`` and
``dbsp_tpu_capacity_required_rows{node,kind}``. Empty before a circuit has
validated, and of the nodes a circuit lacks.
"""

from __future__ import annotations

import collections
from typing import Dict, Iterable, Tuple

# CWindow node -> {"out": rows, "in": rows} slid out of / into the window,
# summed over the levels of its trace: of the last validated tick, in total
WINDOW_SLIDE_LAST: Dict[int, Dict[str, int]] = {}
WINDOW_SLIDE_TOTAL: Dict[int, Dict[str, int]] = {}

# trace under a GC bound -> {"live": rows left after the last truncation,
# "truncated": rows it dropped, "truncated_total": since the circuit began,
# "capacity": of its levels}
TRACE_GC_ROWS: Dict[int, Dict[str, int]] = {}

# CWatermark node -> {"ms": the watermark, "advance": over the last tick}
WATERMARK_MS: Dict[int, Dict[str, int]] = {}

# CTopK node -> {"groups": touched, "gathered": rows re-read from its input
# trace, "capacity": of its gather, "inserted" / "retracted": rows it
# emitted} of the last validated tick, and the running "*_total" of each;
# with the node's shape as it ran that tick: "queries" (capacity), "k" and
# "values" (value columns)
TOPK_ROWS: Dict[int, Dict[str, int]] = {}

# one record per validated interval of every compiled circuit, oldest
# first, bounded (``CompiledHandle.validate`` appends it: sums over that
# circuit's nodes). Every record has ``capacities``: one
# ``(scope, kind, class, required, capacity)`` per checked capacity already
# sized, where ``scope`` is the node's device scope ``n<index>.<CNode
# class>`` (``compiler.node_scope``), ``kind`` the capacity's key, ``class``
# ``state`` (it sizes state carried across ticks, ``CNode.sizes_state``) or
# ``tick`` (a buffer the step program fills anew each tick), ``required``
# the validated requirement (under a worker mesh the worst worker's) and
# ``capacity`` the capacity it was checked against (one worker's); and their
# sums over the ``tick`` capacities, ``tick_live_rows`` and
# ``tick_capacity_rows``. A circuit with time nodes adds ``retired_rows`` /
# ``slid_in_rows`` (windows), ``gc_live_rows`` / ``gc_capacity_rows`` /
# ``gc_truncated_rows`` (traces under a GC bound), ``trace_live_rows``
# (every leveled trace of the windowed view, counted in the step program),
# ``watermark_ms``; one with top-K nodes ``topk_groups`` /
# ``topk_gathered_rows`` / ``topk_gather_capacity_rows`` /
# ``topk_inserted_rows`` / ``topk_retracted_rows``
VALIDATED_TICKS: collections.deque = collections.deque(maxlen=4096)

# node -> {kind: (required, capacity)} of each checked capacity at the last
# validated interval of the node's circuit (the largest requirement of a
# capacity checked more than once)
CAPACITY_ROWS: Dict[int, Dict[str, Tuple[int, int]]] = {}


def note_slide(node: int, out_rows: int, in_rows: int) -> None:
    WINDOW_SLIDE_LAST[node] = {"out": out_rows, "in": in_rows}
    total = WINDOW_SLIDE_TOTAL.setdefault(node, {"out": 0, "in": 0})
    total["out"] += out_rows
    total["in"] += in_rows


def note_gc(node: int, live: int, truncated: int, capacity: int) -> None:
    before = TRACE_GC_ROWS.get(node, {}).get("truncated_total", 0)
    TRACE_GC_ROWS[node] = {"live": live, "truncated": truncated,
                           "truncated_total": before + truncated,
                           "capacity": capacity}


def note_watermark(node: int, ms: int) -> None:
    before = WATERMARK_MS.get(node, {}).get("ms", 0)
    WATERMARK_MS[node] = {"ms": ms,
                          "advance": ms - before if before else 0}


def note_topk(node: int, groups: int, gathered: int, capacity: int,
              inserted: int, retracted: int, queries: int, k: int,
              values: int) -> None:
    ent = TOPK_ROWS.setdefault(node, {"groups_total": 0,
                                      "gathered_total": 0,
                                      "changed_total": 0})
    ent.update(groups=groups, gathered=gathered, capacity=capacity,
               inserted=inserted, retracted=retracted, queries=queries, k=k,
               values=values)
    ent["groups_total"] += groups
    ent["gathered_total"] += gathered
    ent["changed_total"] += inserted + retracted


def note_capacities(checked: Iterable[tuple]) -> None:
    """``(node, scope, kind, class, required, capacity)`` of each check of
    an interval. A capacity checked more than once (a window's slides, once
    a level) keeps its largest requirement."""
    last: Dict[Tuple[int, str], Tuple[int, int]] = {}
    for node, _, kind, _, required, capacity in checked:
        if (node, kind) not in last or required > last[(node, kind)][0]:
            last[(node, kind)] = (required, capacity)
    for (node, kind), rows in last.items():
        CAPACITY_ROWS.setdefault(node, {})[kind] = rows
