"""Process-wide counters of the time nodes of compiled circuits: what the
windows slid, what the trace-bound GC truncated, where the watermarks stand;
and of their top-K nodes: what each re-read and changed (since PR 38).

Filled at validation from scalars that ride the requirement vector the
handle fetches anyway (``compiler._Ctx.observe``): no device sync of their
own. Keyed by node index like ``parallel/exchange.py::EXCHANGE_SITE_ROWS``;
under a worker mesh a row count is the worst worker's. With a validation
cadence above one, "the last tick" is the largest tick of the interval.
Exported by ``obs/instrument.py::export_time_counters`` as
``dbsp_tpu_window_slide_rows_total{node,dir}``,
``dbsp_tpu_trace_gc_rows_total{node}``, ``dbsp_tpu_trace_gc_live_rows{node}``
and ``dbsp_tpu_watermark_ms{node}``; by ``export_topk_counters`` as
``dbsp_tpu_topk_gathered_rows_total{node}``, ``dbsp_tpu_topk_groups_total``,
``dbsp_tpu_topk_changed_rows_total`` and
``dbsp_tpu_topk_gather_capacity_rows``. Empty for a circuit without such
nodes.
"""

from __future__ import annotations

import collections
from typing import Dict

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

# one record per validated interval of a circuit with time nodes or top-K
# nodes, oldest first, bounded (``CompiledHandle.maintain`` appends it: the
# sums over that circuit's nodes): ``retired_rows`` / ``slid_in_rows``
# (windows), ``gc_live_rows`` / ``gc_capacity_rows`` / ``gc_truncated_rows``
# (traces under a GC bound), ``trace_live_rows`` (every leveled trace of the
# windowed view, counted in the step program), ``watermark_ms``;
# ``topk_groups`` / ``topk_gathered_rows`` / ``topk_gather_capacity_rows``
# / ``topk_inserted_rows`` / ``topk_retracted_rows`` (top-K nodes)
VALIDATED_TICKS: collections.deque = collections.deque(maxlen=4096)


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
