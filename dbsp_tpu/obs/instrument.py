"""Instrumentation: engine state -> registry metrics + trace spans.

Three attachment points, one per execution surface:

* :class:`CircuitInstrumentation` — host-driven circuits. Subscribes to the
  ``SchedulerEvent`` stream (the same stream ``CPUProfiler`` and
  ``TraceMonitor`` consume) for per-operator eval-latency histograms and
  step-latency summaries, and registers a scrape-time collector that walks
  the circuit graph for spine residency gauges, exchange counters, and
  watermark lag — state the operators already hold, read at scrape instead
  of copied per tick.
* :class:`CompiledInstrumentation` — compiled drivers. The whole tick is one
  XLA program, so per-operator timings do not exist; exports tick counters,
  tick-latency quantiles, overflow-replay counts, and per-trace
  device-resident capacity from the compiled states.
* :class:`ControllerInstrumentation` — the IO layer. Mirrors
  ``Controller.stats()`` endpoint counters into the registry at scrape.

:class:`PipelineObs` bundles one registry + one span recorder per deployed
pipeline (the unit the manager aggregates over).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, Optional, Tuple

from dbsp_tpu.obs.registry import MetricsRegistry
from dbsp_tpu.obs.tracing import SpanRecorder

# span categories for the trace viewer; exchange ops get their own so
# cross-worker data movement is visually separable from compute
_EXCHANGE_OPS = ("shard", "unshard")


def export_consolidate_paths(registry: MetricsRegistry) -> None:
    """Register a collector mirroring the consolidation-regime counters
    (``zset/kernels.py::CONSOLIDATE_COUNTS``) as
    ``dbsp_tpu_zset_consolidate_total{path=sort|rank|native|skipped|deferred}``.

    The counts are PROCESS-wide dispatch decisions (eager calls count per
    eval, traced calls once per trace, deferrals once per placement pass) —
    they attribute WHICH consolidation regimes fire, not per-tick volume."""
    if getattr(registry, "_consolidate_paths_exported", False):
        return  # one mirror per registry (both instrumentations may share)
    registry._consolidate_paths_exported = True
    counter = registry.counter(
        "dbsp_tpu_zset_consolidate_total",
        "Consolidation dispatch decisions by regime (process-wide; "
        "skipped = metadata no-op, rank = sorted-run merge fold, "
        "native = C++ argsort, sort = lax.sort, "
        "native_unsupported_dtype = native selected but demoted to sort "
        "by a non-int64-widenable column dtype, deferred = removed by "
        "the compiled placement pass)", labels=("path",))

    def _collect() -> None:
        from dbsp_tpu.zset import kernels as zkernels

        for path, n in zkernels.CONSOLIDATE_COUNTS.items():
            counter.labels(path=path).set_total(n)

    registry.register_collector(_collect)


def export_kernel_dispatch(registry: MetricsRegistry) -> None:
    """Register a collector mirroring the kernel dispatch decisions
    (``zset/kernels.py::KERNEL_DISPATCH_COUNTS``) as
    ``dbsp_tpu_zset_kernel_dispatch_total{kernel,backend}`` — which
    implementation (native C++ custom call / pure XLA) each Z-set kernel
    entry point selected. Same counting convention as the
    consolidation-path counter: dispatch DECISIONS (per eval eagerly, per
    trace under jit), not per-tick kernel volume — the metric answers "is
    this pipeline on the kernels I think it is", e.g. after a
    ``DBSP_TPU_NATIVE`` force-off or a dtype change knocked a path off the
    native set."""
    if getattr(registry, "_kernel_dispatch_exported", False):
        return
    registry._kernel_dispatch_exported = True
    counter = registry.counter(
        "dbsp_tpu_zset_kernel_dispatch_total",
        "Z-set kernel dispatch decisions by entry point and backend "
        "(native = C++ FFI custom call, xla = pure-XLA lowering; an "
        "accelerator's XLA formulation is named where it is not the "
        "CPU's: xla_bitonic = the "
        "bitonic merge network behind kernel=merge and kernel=sort_merge, "
        "a sort of more than SORT_CHUNK_ROWS rows; xla_shift = the shift "
        "compaction behind kernel=compact; xla_merge = a level of "
        "kernel=probe_ladder, or one side of a single-table kernel=probe, "
        "in which sorted queries were ranked by one merge, its xla rows "
        "the ones searched; xla_flat = a "
        "kernel=gather from the levels laid end to end, its xla rows one "
        "gather a level); the fused ladder-consumer "
        "megakernels report as kernel=join_ladder / gather_ladder / "
        "old_weights and the reduction offensive as kernel=segment_reduce "
        "/ agg_ladder / join_sorted, whose xla rows are the stitched-chain "
        "fallback (the DBSP_TPU_NATIVE force-off A/B control)",
        labels=("kernel", "backend"))

    def _collect() -> None:
        from dbsp_tpu.zset import kernels as zkernels

        for (kern, backend), n in list(
                zkernels.KERNEL_DISPATCH_COUNTS.items()):
            counter.labels(kernel=kern, backend=backend).set_total(n)

    registry.register_collector(_collect)


def export_exchange_overflows(registry: MetricsRegistry) -> None:
    """Register a collector mirroring the process-wide exchange
    bucket-overflow detections (``parallel/exchange.py::
    EXCHANGE_OVERFLOW_COUNTS``) as
    ``dbsp_tpu_exchange_overflow_total{kind}``: each count is one validated
    interval whose per-worker exchange (or sharded-input) bucket overflowed
    under skew and was re-run at grown capacity by the overflow-replay
    machinery — the replay saves the rows; the counter makes it visible.
    Beside it, per exchange site, the worst worker's live rows and the
    bucket capacity at the last validation (``EXCHANGE_SITE_ROWS``)."""
    if getattr(registry, "_exchange_overflows_exported", False):
        return
    registry._exchange_overflows_exported = True
    counter = registry.counter(
        "dbsp_tpu_exchange_overflow_total",
        "Exchange bucket overflows detected by the capacity-requirement "
        "check and repaired by overflow replay (kind = exchange | input)",
        labels=("kind",))

    live = registry.gauge(
        "dbsp_tpu_exchange_site_live_rows",
        "Worst-worker live rows at an exchange site (a compiled exchange "
        "or a sharded input) at the last validation",
        labels=("kind", "node"))
    capacity = registry.gauge(
        "dbsp_tpu_exchange_site_capacity_rows",
        "Static per-worker bucket capacity of an exchange site; capacity "
        "less live rows is padding every worker sorts and merges",
        labels=("kind", "node"))

    def _collect() -> None:
        from dbsp_tpu.parallel.exchange import (EXCHANGE_OVERFLOW_COUNTS,
                                                EXCHANGE_SITE_ROWS)

        for kind, n in list(EXCHANGE_OVERFLOW_COUNTS.items()):
            counter.labels(kind=kind).set_total(n)
        for (kind, node), (rows, cap) in list(EXCHANGE_SITE_ROWS.items()):
            live.labels(kind=kind, node=str(node)).set(rows)
            capacity.labels(kind=kind, node=str(node)).set(cap)

    registry.register_collector(_collect)


def export_time_counters(registry: MetricsRegistry) -> None:
    """Register a collector mirroring the time nodes' process-wide
    counters (``timeseries/counters.py``, filled at validation): per
    ``CWindow`` the rows slid out of and into the window
    (``dbsp_tpu_window_slide_rows_total{node,dir}``), per trace under a GC
    bound the rows truncated and the rows left
    (``dbsp_tpu_trace_gc_rows_total{node}``,
    ``dbsp_tpu_trace_gc_live_rows{node}``; its levels' capacity beside
    them), per ``CWatermark`` where it stands (``dbsp_tpu_watermark_ms``)."""
    if getattr(registry, "_time_counters_exported", False):
        return
    registry._time_counters_exported = True
    slide = registry.counter(
        "dbsp_tpu_window_slide_rows_total",
        "Rows a window retracted because its bounds moved past them "
        "(dir = out) or admitted (dir = in), summed over the levels of "
        "its trace", labels=("node", "dir"))
    gc_total = registry.counter(
        "dbsp_tpu_trace_gc_rows_total",
        "Rows the trace-bound GC truncated from a windowed trace inside "
        "the step program", labels=("node",))
    gc_live = registry.gauge(
        "dbsp_tpu_trace_gc_live_rows",
        "Live rows of a trace under a GC bound after the last validated "
        "tick's truncation", labels=("node",))
    gc_cap = registry.gauge(
        "dbsp_tpu_trace_gc_capacity_rows",
        "Capacity of the levels of a trace under a GC bound: what the "
        "truncation, the window's slices and the snapshot walk every tick",
        labels=("node",))
    watermark = registry.gauge(
        "dbsp_tpu_watermark_ms",
        "The watermark of a compiled watermark node at the last "
        "validated tick, ms of event time", labels=("node",))
    advance = registry.gauge(
        "dbsp_tpu_watermark_advance_ms",
        "How far the last validated tick moved the watermark",
        labels=("node",))

    def _collect() -> None:
        from dbsp_tpu.timeseries import counters

        for node, total in list(counters.WINDOW_SLIDE_TOTAL.items()):
            for direction, rows in total.items():
                slide.labels(node=str(node), dir=direction).set_total(rows)
        for node, ent in list(counters.TRACE_GC_ROWS.items()):
            gc_total.labels(node=str(node)).set_total(
                ent["truncated_total"])
            gc_live.labels(node=str(node)).set(ent["live"])
            gc_cap.labels(node=str(node)).set(ent["capacity"])
        for node, ent in list(counters.WATERMARK_MS.items()):
            watermark.labels(node=str(node)).set(ent["ms"])
            advance.labels(node=str(node)).set(ent["advance"])

    registry.register_collector(_collect)


def export_topk_counters(registry: MetricsRegistry) -> None:
    """Register a collector mirroring the top-K nodes' process-wide
    counters (``timeseries/counters.py`` ``TOPK_ROWS``, filled at
    validation): per ``CTopK`` the rows it re-read from its input trace
    (``dbsp_tpu_topk_gathered_rows_total{node}``), the groups it touched
    (``dbsp_tpu_topk_groups_total{node}``), the rows it inserted and
    retracted (``dbsp_tpu_topk_changed_rows_total{node}``). The capacity
    of its gather is ``export_capacities``' ``{node, kind="gather"}``."""
    if getattr(registry, "_topk_counters_exported", False):
        return
    registry._topk_counters_exported = True
    gathered = registry.counter(
        "dbsp_tpu_topk_gathered_rows_total",
        "Rows a top-K node re-read from its input trace: the whole "
        "histories of the groups each tick touched", labels=("node",))
    groups = registry.counter(
        "dbsp_tpu_topk_groups_total",
        "Groups whose top-K a top-K node recomputed", labels=("node",))
    changed = registry.counter(
        "dbsp_tpu_topk_changed_rows_total",
        "Rows a top-K node emitted, insertions and retractions",
        labels=("node",))

    def _collect() -> None:
        from dbsp_tpu.timeseries import counters

        for node, ent in list(counters.TOPK_ROWS.items()):
            gathered.labels(node=str(node)).set_total(ent["gathered_total"])
            groups.labels(node=str(node)).set_total(ent["groups_total"])
            changed.labels(node=str(node)).set_total(ent["changed_total"])

    registry.register_collector(_collect)


def export_capacities(registry: MetricsRegistry) -> None:
    """Register a collector mirroring how full each checked capacity of a
    compiled circuit was at its last validated interval
    (``timeseries/counters.py`` ``CAPACITY_ROWS``): per node and capacity
    key (``kind``, a closed set per node class) the static capacity
    (``dbsp_tpu_capacity_rows``) and the interval's requirement
    (``dbsp_tpu_capacity_required_rows``). A requirement near its capacity
    is the next grow and replay; one far below it is padding every tick
    carries."""
    if getattr(registry, "_capacities_exported", False):
        return
    registry._capacities_exported = True
    capacity = registry.gauge(
        "dbsp_tpu_capacity_rows",
        "Static capacity of a compiled node's buffer (kind = its capacity "
        "key), rows a worker, at the last validated interval",
        labels=("node", "kind"))
    required = registry.gauge(
        "dbsp_tpu_capacity_required_rows",
        "Rows the last validated interval required of a compiled node's "
        "capacity (the worst worker's); above the capacity it overflows "
        "and replays", labels=("node", "kind"))

    def _collect() -> None:
        from dbsp_tpu.timeseries import counters

        for node, kinds in list(counters.CAPACITY_ROWS.items()):
            for kind, (rows, cap) in list(kinds.items()):
                capacity.labels(node=str(node), kind=kind).set(cap)
                required.labels(node=str(node), kind=kind).set(rows)

    registry.register_collector(_collect)


def _gid_str(gid: Tuple[int, ...]) -> str:
    return ".".join(map(str, gid))


def _residency_tier_gauge(reg: MetricsRegistry, nid: str,
                          tiers: Dict[str, int]) -> None:
    """Per-tier resident rows of one trace (both engines share the
    family; tier names come from dbsp_tpu/residency.py)."""
    tier_gauge = reg.gauge(
        "dbsp_tpu_trace_tier_resident_rows",
        "Resident row capacity of one trace per residency tier (device = "
        "persistent HBM/device buffers, host = process-resident numpy, "
        "disk = memmap views over cold-store blobs; see "
        "dbsp_tpu/residency.py)", labels=("node", "tier"))
    for tier, rows in tiers.items():
        tier_gauge.labels(node=nid, tier=tier).set(rows)


def _residency_transitions(reg: MetricsRegistry,
                           agg: Dict[Tuple[str, str, str], int]) -> None:
    """Cumulative transition counts summed over every trace this
    instrumentation covers — the demotion/promotion evidence the growth
    acceptance reads. Called once per collect pass (set_total semantics:
    per-node stats must be pre-aggregated by the caller)."""
    if not agg:
        return
    trans = reg.counter(
        "dbsp_tpu_trace_residency_transitions_total",
        "Residency tier transitions by direction and cause (budget = "
        "enforcement demotion, maintain = drain-write promotion, probe = "
        "fault-on-probe promotion, lru = re-hot promotion, "
        "config/restore = applied at deploy/restore)",
        labels=("tier_from", "tier_to", "cause"))
    for (frm, to, cause), n in agg.items():
        trans.labels(tier_from=frm, tier_to=to, cause=cause).set_total(n)


class CircuitInstrumentation:
    """Host-path hooks: scheduler events -> histograms/spans, graph walk ->
    gauges. Attach once per circuit, after build."""

    def __init__(self, circuit, registry: MetricsRegistry,
                 spans: Optional[SpanRecorder] = None):
        self.circuit = circuit
        self.registry = registry
        self.spans = spans
        self._open: Dict[Tuple[int, ...], int] = {}
        self._step_t0: Optional[int] = None
        self._depth = 0
        self._names: Dict[Tuple[int, ...], str] = {}
        self.eval_hist = registry.histogram(
            "dbsp_tpu_circuit_operator_eval_seconds",
            "Host wall-clock of one operator eval (includes kernel "
            "dispatch; see profile.py for the async caveat)",
            labels=("operator", "node"))
        self.step_summary = registry.summary(
            "dbsp_tpu_circuit_step_seconds",
            "End-to-end latency of one root-circuit step")
        self.steps_total = registry.counter(
            "dbsp_tpu_circuit_steps_total", "Root-circuit steps evaluated")
        registry.register_collector(self._collect_graph)
        export_consolidate_paths(registry)
        export_kernel_dispatch(registry)
        export_exchange_overflows(registry)
        export_time_counters(registry)
        export_topk_counters(registry)
        export_capacities(registry)
        circuit.register_scheduler_event_handler(self._on_event)
        # mark exchange operators so they accumulate rows/bytes moved —
        # this costs one scalar device->host sync per exchange per tick
        # (shard_op._MovedRowsMixin), so it is env-gated for latency-
        # critical deploys: DBSP_TPU_OBS_EXCHANGE=0 keeps the counters off
        if os.environ.get("DBSP_TPU_OBS_EXCHANGE", "1") != "0":
            for node, _ in self._walk():
                if node.operator.name in _EXCHANGE_OPS:
                    node.operator.obs_enabled = True

    # -- event path ---------------------------------------------------------
    def _on_event(self, ev) -> None:
        if ev.kind == "eval_start":
            ts = ev.time_ns or time.perf_counter_ns()
            self._open[ev.node_id] = ts
            self._names[ev.node_id] = ev.name or "?"
            if self.spans is not None and self._depth:
                cat = "exchange" if ev.name in _EXCHANGE_OPS else "operator"
                self.spans.begin(f"{ev.name}[{_gid_str(ev.node_id)}]",
                                 cat=cat, ts_ns=ts)
        elif ev.kind == "eval_end":
            t0 = self._open.pop(ev.node_id, None)
            ts = ev.time_ns or time.perf_counter_ns()
            if t0 is not None:
                self.eval_hist.labels(
                    operator=ev.name or self._names.get(ev.node_id, "?"),
                    node=_gid_str(ev.node_id)).observe((ts - t0) / 1e9)
            if self.spans is not None and self._depth:
                self.spans.end(f"{ev.name}[{_gid_str(ev.node_id)}]",
                               ts_ns=ts)
        elif ev.kind == "step_start":
            ts = ev.time_ns or time.perf_counter_ns()
            if self._depth == 0:
                self._step_t0 = ts
            self._depth += 1
            if self.spans is not None:
                self.spans.begin("step" if self._depth == 1 else "substep",
                                 cat="step", ts_ns=ts)
        elif ev.kind == "step_end":
            ts = ev.time_ns or time.perf_counter_ns()
            if self._depth > 0:
                self._depth -= 1
                if self.spans is not None:
                    self.spans.end("step" if self._depth == 0 else "substep",
                                   ts_ns=ts)
                if self._depth == 0 and self._step_t0 is not None:
                    self.step_summary.observe((ts - self._step_t0) / 1e9)
                    self.steps_total.inc()
                    self._step_t0 = None

    # -- scrape-time graph walk ----------------------------------------------
    def _walk(self, circuit=None, prefix=()):
        c = circuit if circuit is not None else self.circuit
        for node in c.nodes:
            gid = (*prefix, node.index)
            yield node, gid
            if node.child is not None:
                yield from self._walk(node.child, gid)

    def _collect_graph(self) -> None:
        from dbsp_tpu.operators.trace_op import TraceOp
        from dbsp_tpu.timeseries.watermark import WatermarkMonotonic

        reg = self.registry
        res_trans: Dict[Tuple[str, str, str], int] = {}
        for node, gid in self._walk():
            op = node.operator
            nid = _gid_str(gid)
            try:
                if isinstance(op, TraceOp):
                    sp = op.spine
                    reg.gauge("dbsp_tpu_trace_device_resident_rows",
                              "Device (HBM) resident row capacity of one "
                              "spine (sharded batches count per-worker cap; "
                              "see trace/spine.py budget semantics)",
                              labels=("node",)).labels(node=nid).set(
                                  sp.device_resident_rows())
                    reg.gauge("dbsp_tpu_trace_host_offloaded_rows",
                              "Row capacity offloaded to host memory "
                              "(cold levels)",
                              labels=("node",)).labels(node=nid).set(
                                  sp.host_offloaded_rows())
                    _residency_tier_gauge(reg, nid, sp.tier_rows())
                    for k, n in sp.residency_stats.items():
                        res_trans[k] = res_trans.get(k, 0) + n
                    reg.gauge("dbsp_tpu_trace_level_count",
                              "Spine LSM levels currently held",
                              labels=("node",)).labels(node=nid).set(
                                  len(sp.batches))
                elif op.name in _EXCHANGE_OPS:
                    reg.counter("dbsp_tpu_exchange_rows_total",
                                "Live rows moved through shard/unshard "
                                "exchanges", labels=("node",)).labels(
                                    node=nid).set_total(
                                        getattr(op, "rows_moved", 0))
                    reg.counter("dbsp_tpu_exchange_bytes_total",
                                "Bytes moved through shard/unshard "
                                "exchanges", labels=("node",)).labels(
                                    node=nid).set_total(
                                        getattr(op, "bytes_moved", 0))
                    occ = getattr(op, "last_occupancy", None)
                    if occ and len(occ) > 1:
                        occ_gauge = reg.gauge(
                            "dbsp_tpu_exchange_worker_occupancy_rows",
                            "Live rows landed on each worker by the last "
                            "observed exchange eval (the skew input)",
                            labels=("node", "worker"))
                        for wi, n in enumerate(occ):
                            occ_gauge.labels(node=nid,
                                             worker=str(wi)).set(n)
                        reg.gauge(
                            "dbsp_tpu_exchange_skew_ratio",
                            "Max/mean worker occupancy of the last "
                            "observed exchange eval (1.0 = balanced, "
                            "W = one worker holds everything)",
                            labels=("node",)).labels(node=nid).set(
                                op.skew_ratio)
                elif isinstance(op, WatermarkMonotonic):
                    if op._wm is not None:
                        reg.gauge("dbsp_tpu_timeseries_watermark_timestamp",
                                  "Current watermark (event-time units)",
                                  labels=("node",)).labels(node=nid).set(
                                      op._wm)
                        # lag = how far the latest batch's events trail
                        # the event-time frontier (0 for in-order arrival,
                        # grows when a batch is older than the max seen).
                        # NOT frontier-minus-watermark: that is identically
                        # the configured lateness here and carries no
                        # signal. Both fields can be None (no batch yet /
                        # restored checkpoint) — skip the gauge then.
                        if op._max_ts is not None and \
                                op._last_batch_max is not None:
                            reg.gauge(
                                "dbsp_tpu_timeseries_watermark_lag_count",
                                "Event-time lag of the latest batch "
                                "behind the frontier (max seen minus "
                                "latest batch max, event-time units)",
                                labels=("node",)).labels(node=nid).set(
                                    op._max_ts - op._last_batch_max)
            except Exception:
                # scrape must not take the server down on a mid-step race;
                # the next scrape sees a consistent value
                continue
        try:
            _residency_transitions(reg, res_trans)
        except Exception:
            pass  # same scrape-safety posture as the walk above


class CompiledInstrumentation:
    """Compiled-path hooks: collector over the driver + compiled states."""

    def __init__(self, driver, registry: MetricsRegistry,
                 spans: Optional[SpanRecorder] = None):
        self.driver = driver
        self.registry = registry
        self._lat_seen = 0
        # the pipeline server and the manager's fleet aggregate can scrape
        # the same registry concurrently; the tail-consume below is a
        # read-modify-write that would double-observe without this
        self._lat_lock = threading.Lock()
        self.tick_summary = registry.summary(
            "dbsp_tpu_compiled_tick_seconds",
            "Whole-tick latency of the compiled step program")
        self.ticks_total = registry.counter(
            "dbsp_tpu_compiled_ticks_total", "Compiled ticks run")
        self.replays_total = registry.counter(
            "dbsp_tpu_compiled_overflow_replays_total",
            "Grow-and-replay cycles after a capacity overflow")
        # between-tick host phases (validate fetch / maintain drains /
        # snapshot copies) — the wall-clock the async tick pipeline exists
        # to bound; a spike tick's cause annotations are counted per cause
        self.host_overhead_hist = registry.histogram(
            "dbsp_tpu_compiled_tick_host_overhead_seconds",
            "Host wall-clock of one between-tick phase of the compiled "
            "step loop (validate = the per-interval device fetch, "
            "maintain = bounded LSM drain slice, snapshot = incremental "
            "state copy)", labels=("phase",))
        self.causes_total = registry.counter(
            "dbsp_tpu_compiled_tick_causes_total",
            "Latency-sample annotations by cause (maintain drain, "
            "snapshot copy, program retrace) — attributes tail ticks",
            labels=("cause",))
        self.maintain_rows_total = registry.counter(
            "dbsp_tpu_compiled_maintain_moved_rows_total",
            "Rows moved between trace levels by bounded maintenance")
        self._overhead_seen: Dict[str, int] = {}
        registry.register_collector(self._collect)
        export_consolidate_paths(registry)
        export_kernel_dispatch(registry)
        export_exchange_overflows(registry)
        export_time_counters(registry)
        export_topk_counters(registry)
        export_capacities(registry)
        if spans is not None:
            driver.spans = spans  # driver records tick/validate spans

    def _collect(self) -> None:
        from dbsp_tpu.compiled import cnodes

        d = self.driver
        self.ticks_total.set_total(getattr(d, "_tick", 0))
        # step_latencies_ns is the driver's live append-only list; slice
        # only the unseen tail (a full copy would be O(total ticks) per
        # scrape, unbounded on a serving pipeline)
        lat = getattr(d, "step_latencies_ns", ())
        with self._lat_lock:
            n = len(lat)
            tail = lat[self._lat_seen:n]
            self._lat_seen = n
        for ns in tail:
            self.tick_summary.observe(ns / 1e9)
        ch = getattr(d, "ch", None)
        if ch is None:
            return
        self.replays_total.set_total(getattr(ch, "overflow_replays", 0))
        # host-overhead phases: same unseen-tail protocol as latencies
        overhead = getattr(ch, "host_overhead_ns", None)
        if overhead:
            with self._lat_lock:
                for phase, samples in overhead.items():
                    n = len(samples)
                    tail = samples[self._overhead_seen.get(phase, 0):n]
                    self._overhead_seen[phase] = n
                    child = self.host_overhead_hist.labels(phase=phase)
                    for ns in tail:
                        child.observe(ns / 1e9)
        causes: Dict[str, int] = {}
        for _, cause in getattr(ch, "tick_causes", ()):
            causes[cause] = causes.get(cause, 0) + 1
        for cause, count in causes.items():
            self.causes_total.labels(cause=cause).set_total(count)
        stats = getattr(ch, "maintain_stats", None)
        if stats:
            self.maintain_rows_total.set_total(stats.get("rows_moved", 0))
        # ONE walk for all traces' tier partitions (per-key tier_rows
        # calls would re-walk every leveled node per node — O(N^2) per
        # scrape)
        tiers_by_node = (ch.tier_rows_by_node()
                         if hasattr(ch, "tier_rows_by_node") else {})
        for cn in ch.cnodes:
            if isinstance(cn, cnodes.CExchange):
                # compiled skew observable: worst-worker rows at the last
                # validation vs the static per-worker bucket (occupancy
                # near 1.0 = the next skewed tick overflows and replays)
                nid = str(cn.node.index)
                cap = cn.caps.get("exchange", 0)
                self.registry.gauge(
                    "dbsp_tpu_exchange_required_rows",
                    "Worst-worker live rows through this compiled "
                    "exchange at the last validation",
                    labels=("node",)).labels(node=nid).set(
                        cn.last_required)
                if cap:
                    self.registry.gauge(
                        "dbsp_tpu_exchange_bucket_occupancy_ratio",
                        "last_required / static per-worker exchange "
                        "capacity (>= 1.0 would overflow and replay)",
                        labels=("node",)).labels(node=nid).set(
                            cn.last_required / cap)
            if not isinstance(cn, cnodes._Leveled):
                continue
            nid = str(cn.node.index)
            # tiered residency (dbsp_tpu/residency.py): deep levels past
            # the budget live as host numpy / disk memmaps — the device
            # gauge reports the DEVICE tier only, the per-tier gauge
            # carries the full picture
            tiers = tiers_by_node.get(nid)
            if tiers is not None:
                self.registry.gauge(
                    "dbsp_tpu_trace_device_resident_rows",
                    "Device-resident row capacity of one compiled "
                    "leveled trace (device tier only — residency-"
                    "demoted levels are excluded)",
                    labels=("node",)).labels(node=nid).set(
                        tiers["device"])
                _residency_tier_gauge(self.registry, nid, tiers)
                self.registry.gauge(
                    "dbsp_tpu_trace_host_offloaded_rows",
                    "Row capacity offloaded to host memory "
                    "(cold levels)",
                    labels=("node",)).labels(node=nid).set(tiers["host"])
            self.registry.gauge(
                "dbsp_tpu_trace_level_count",
                "Levels of one compiled leveled trace",
                labels=("node",)).labels(node=nid).set(len(cn.level_keys))
        if hasattr(ch, "residency_stats"):
            try:
                _residency_transitions(
                    self.registry,
                    {k: n for k, n in list(ch.residency_stats.items())})
            except Exception:
                pass  # scrape-safety: never take the server down


class ControllerInstrumentation:
    """IO-layer mirror: Controller.stats() -> registry, at scrape time."""

    def __init__(self, controller, registry: MetricsRegistry):
        self.controller = controller
        self.registry = registry
        registry.register_collector(self._collect)

    def _collect(self) -> None:
        reg = self.registry
        s = self.controller.stats()
        reg.counter("dbsp_tpu_io_steps_total",
                    "Controller-driven circuit steps").set_total(s["steps"])
        reg.counter("dbsp_tpu_io_pushed_records_total",
                    "Rows pushed via the host API / HTTP endpoints"
                    ).set_total(s["pushed_records"])
        for path, n in s["parsed_records"].items():
            reg.counter("dbsp_tpu_io_parsed_records_total",
                        "Rows of HTTP pushes by the parser path that took "
                        "them: the columnar bulk path or the line parser "
                        "(fallback)", labels=("path",)).labels(
                            path=path).set_total(n)
        reg.counter("dbsp_tpu_io_checkpoints_total",
                    "Durable checkpoint generations written by this "
                    "controller").set_total(s.get("checkpoints", 0))
        # (the tick the last checkpoint covers is NOT a metric — it is an
        # index, not a count/unit; read it from /status or /stats)
        for name, ep in s["inputs"].items():
            reg.counter("dbsp_tpu_io_transport_retries_total",
                        "Transient transport failures retried with "
                        "backoff (connect/read), per input endpoint",
                        labels=("endpoint",)).labels(
                            endpoint=name).set_total(
                                ep.get("transport_retries", 0))
            reg.counter("dbsp_tpu_io_input_records_total",
                        "Rows ingested per input endpoint",
                        labels=("endpoint",)).labels(
                            endpoint=name).set_total(ep["total_records"])
            reg.counter("dbsp_tpu_io_input_bytes_total",
                        "Bytes ingested per input endpoint",
                        labels=("endpoint",)).labels(
                            endpoint=name).set_total(ep["total_bytes"])
            reg.gauge("dbsp_tpu_io_input_buffered_rows",
                      "Rows buffered awaiting a step",
                      labels=("endpoint",)).labels(
                          endpoint=name).set(ep["buffered_records"])
        for name, out in s["outputs"].items():
            reg.counter("dbsp_tpu_io_output_records_total",
                        "Rows emitted per output endpoint",
                        labels=("endpoint",)).labels(
                            endpoint=name).set_total(out["total_records"])
            reg.counter("dbsp_tpu_io_output_bytes_total",
                        "Bytes emitted per output endpoint",
                        labels=("endpoint",)).labels(
                            endpoint=name).set_total(out["total_bytes"])


class PipelineObs:
    """Per-pipeline observability bundle: one registry + one span window +
    one flight recorder + one SLO watchdog.

    Construction wires nothing; call the ``attach_*`` helpers for the
    surfaces the pipeline actually runs (host circuit, compiled driver,
    controller). The manager aggregates ``(labels, registry)`` pairs from
    every deployed pipeline into the fleet-wide exposition and the
    per-pipeline SLO states into fleet health.

    ``slo`` is the pipeline config's ``slo`` section (obs/slo.py config
    keys); the watchdog runs with every key disabled except the
    host-fallback one when omitted. :meth:`watch` — one poll of every
    flight source plus one SLO evaluation — is registered as a scrape-time
    collector and as a controller monitor, so SLO state is fresh on both
    paths without a dedicated thread."""

    def __init__(self, name: str = "",
                 max_trace_steps: Optional[int] = None,
                 flight_capacity: int = 2048, slo=None):
        from dbsp_tpu.obs.flight import FlightRecorder
        from dbsp_tpu.obs.slo import SLOConfig, SLOWatchdog
        from dbsp_tpu.obs.timeline import Timeline

        self.name = name
        self.registry = MetricsRegistry()
        # span-ring window: DBSP_TPU_TRACE_STEPS tunes the retained
        # top-level span count (the /trace window); evictions export as
        # dbsp_tpu_obs_trace_dropped_total{pipeline} via bind()
        if max_trace_steps is None:
            max_trace_steps = int(os.environ.get("DBSP_TPU_TRACE_STEPS",
                                                 "64"))
        self.spans = SpanRecorder(max_steps=max_trace_steps,
                                  process=name or "dbsp_tpu")
        self.spans.bind(self.registry, pipeline=name)
        self.flight = FlightRecorder(capacity=flight_capacity)
        self.slo = SLOWatchdog(self.flight, SLOConfig.from_dict(slo),
                               registry=self.registry, pipeline=name)
        # unified per-tick timeline: flight events + SLO incidents + tick
        # records + freshness stamps in one time-indexed ring (the spike
        # attribution and staleness surfaces read it)
        self.timeline = Timeline(registry=self.registry, pipeline=name)
        self._flight_sources = []
        self.registry.register_collector(self.watch)

    def watch(self):
        """One watchdog pass: poll flight sources, evaluate SLOs, and fold
        the fresh flight events + any newly opened incidents into the
        timeline. Returns the incidents opened by this pass."""
        for src in self._flight_sources:
            src.poll()
        incidents = self.slo.evaluate()
        self.timeline.ingest_flight(self.flight)
        for inc in incidents or ():
            self.timeline.note_incident(inc)
        return incidents

    def attach_circuit(self, circuit) -> CircuitInstrumentation:
        from dbsp_tpu.obs.flight import HostFlightSource

        self._flight_sources.append(HostFlightSource(circuit, self.flight))
        return CircuitInstrumentation(circuit, self.registry,
                                      spans=self.spans)

    def attach_compiled(self, driver) -> CompiledInstrumentation:
        from dbsp_tpu.obs.flight import CompiledFlightSource

        self._flight_sources.append(CompiledFlightSource(driver,
                                                         self.flight))
        return CompiledInstrumentation(driver, self.registry,
                                       spans=self.spans)

    def attach_controller(self, controller) -> ControllerInstrumentation:
        from dbsp_tpu.obs.flight import ControllerFlightSource

        add_monitor = getattr(controller, "add_monitor", None)
        if add_monitor is not None:
            add_monitor(self.watch)
        # checkpoint/restore events become SLO-visible: the controller
        # records them on this pipeline's ring, and the flight source
        # watches endpoint/transport failures the controller cannot
        # announce synchronously
        if hasattr(controller, "flight"):
            controller.flight = self.flight
        # tick latency + freshness stamps: the controller writes tick and
        # arrival/visibility records straight onto this pipeline's timeline
        if hasattr(controller, "timeline"):
            controller.timeline = self.timeline
        # the ``tick`` span and its phases: this pipeline's ring, the one
        # its /trace serves
        if hasattr(controller, "spans"):
            controller.spans = self.spans
        # read serving plane (dbsp_tpu/serving.py): read QPS/latency
        # metrics + a flight ring for staleness-breach attribution
        plane = getattr(controller, "read_plane", None)
        if plane is not None:
            plane.bind(registry=self.registry, flight=self.flight)
        # fleet-wide delta tracing (obs/tracing.py): the controller's
        # E2ETracer exports dbsp_tpu_e2e_stage_seconds{stage}, records
        # per-stage spans into this pipeline's ring, and feeds the
        # timeline's e2e_stage stream (EXPLAIN SPIKE stage attribution)
        e2e = getattr(controller, "e2e", None)
        if e2e is not None:
            e2e.bind(registry=self.registry, spans=self.spans,
                     timeline=self.timeline)
        self._flight_sources.append(
            ControllerFlightSource(controller, self.flight))
        return ControllerInstrumentation(controller, self.registry)
