"""Run a production (manager/controller) pipeline on the compiled path.

The reference keeps a JIT facade precisely so SQL-originated pipelines run
its compiled backend (``crates/dataflow-jit/src/facade.rs:48,105`` —
``DbspCircuit::new`` builds the jitted dataflow, ``step`` feeds it); without
it every deployed pipeline would fall back to the interpreted path. This is
that facade for the XLA backend: :class:`CompiledCircuitDriver` duck-types
the one method the IO controller calls (``step``) while running each tick
through :class:`~dbsp_tpu.compiled.compiler.CompiledHandle` — one XLA
program per tick instead of per-operator dispatches.

Feed/overflow protocol: inputs arrive through the normal host
``InputHandle`` buffers (the catalog's ``push_rows``: a POST's rows as one
column block, a transport's as row tuples); each ``step`` drains them via
``ZSetInput.eval`` (same canonicalization as the host path),
runs the tick, and validates capacity requirements at the validation
cadence. On overflow it grows, restores the interval-start snapshot, and
replays the retained feeds — deterministic, so the replay is exact.

Validation cadence (``DBSP_TPU_SERVE_VALIDATE_EVERY``, default 1): at 1,
every tick snapshots, validates, and delivers immediately — the bounded-
replay contract serving pipelines shipped with. At N > 1 the driver
PIPELINES: ticks dispatch asynchronously (JAX async dispatch lets the host
encode of tick t+1 — the input drain — overlap device compute of tick t),
feeds are retained for replay, and outputs buffer until the interval
validates, then deliver in order. One snapshot + one device fetch per N
ticks instead of per tick; output visibility lags up to N-1 ticks.

Outputs flow back through the host ``OutputOperator.eval`` so every
existing consumer (HTTP ``/read`` cursors, output transports, ``to_dict``
tests) sees compiled and host pipelines identically.
"""

from __future__ import annotations

import logging
import os
import time
from typing import Dict, List, Optional, Tuple

from dbsp_tpu.compiled.compiler import (CompiledHandle, CompiledOverflow,
                                        compile_circuit)
from dbsp_tpu.obs.tracing import default_recorder

logger = logging.getLogger(__name__)


#: where compiled programs persist when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: a fixed path inside the checkout (the path is part of the cache
#: key's environment, so a directory that moves never hits)
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_bench_cache")


def enable_compile_cache() -> str:
    """Wire JAX's persistent compilation cache for compiled pipelines.

    One rule: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    keeps its cache there and no directory is set in code; where it is
    not, the cache lives at :data:`DEFAULT_COMPILE_CACHE_DIR`. Every XLA
    program the engine traces (step programs, scan chunks, drain kernels)
    is serialized there and reused across process restarts — a cold served
    run is mostly compilation, and all of it is retrace/recompile that a
    warm cache eliminates. Thresholds are zeroed so every program is
    cached: engine programs are many and individually small. Returns the
    directory in use."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_COMPILE_CACHE_DIR
        if jax.config.jax_compilation_cache_dir != path:
            from jax.experimental.compilation_cache import compilation_cache

            jax.config.update("jax_compilation_cache_dir", path)
            # JAX decides once per process whether the cache is in use, at
            # its first compile: make it decide again
            compilation_cache.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompiledCircuitDriver:
    """Controller-facing driver over a compiled circuit (see module doc)."""

    mode = "compiled"

    def __init__(self, handle, compiled: Optional[CompiledHandle] = None,
                 validate_every: Optional[int] = None):
        from dbsp_tpu.operators.io_handles import OutputOperator, ZSetInput
        from dbsp_tpu.operators.upsert import UpsertInput

        self.host_handle = handle
        self.circuit = handle.circuit
        enable_compile_cache()
        self.ch = compiled or compile_circuit(handle)
        # the phase spans of a tick land here (obs.SpanRecorder; a
        # PipelineObs hands its own through CompiledInstrumentation)
        self.spans = default_recorder()
        self._tick = 0
        self.validate_every = max(1, validate_every if validate_every
                                  is not None else int(os.environ.get(
                                      "DBSP_TPU_SERVE_VALIDATE_EVERY", "1")))
        # (op, drain_fn): ZSetInput feeds its tick batch; UpsertInput feeds
        # the raw command batch its compiled node diffs against state
        self._inputs = []
        # op -> the ``table`` arg of its ``tick.build_inputs`` span; the
        # controller puts the catalog's names in
        self.input_labels: Dict = {}
        for cn in self.ch.cnodes:
            if isinstance(cn.op, ZSetInput):
                self._inputs.append((cn.op, cn.op.eval))
            elif isinstance(cn.op, UpsertInput):
                self._inputs.append((cn.op, cn.op.take_commands))
            else:
                continue
            self.input_labels[cn.op] = f"n{cn.node.index}"
        self._outputs = [(cn.node.index, cn.op) for cn in self.ch.cnodes
                         if isinstance(cn.op, OutputOperator)]
        # interval state: snapshot at interval start, retained (tick, feeds)
        # for exact replay, buffered per-tick outputs awaiting validation
        self._snap = None
        self._retained: List[Tuple[int, Dict]] = []
        self._out_buffer: List[Dict[int, object]] = []
        # wall-time the current deferred-validation interval opened (first
        # retained tick) — None when no interval is open. Drives the
        # /status ``open_interval_age_s`` freshness surface.
        self._interval_open_ts: Optional[float] = None

    @property
    def step_latencies_ns(self):
        return self.ch.step_times_ns

    @property
    def interval_open(self) -> bool:
        """True while ticks sit in an unvalidated interval — their outputs
        are not yet visible to readers (cadence > 1 only)."""
        return bool(self._retained)

    @property
    def open_interval_age_s(self) -> Optional[float]:
        """Seconds since the open deferred-validation interval started, or
        None when every delivered tick has validated (interval closed)."""
        ts = self._interval_open_ts
        return None if ts is None else max(0.0, time.time() - ts)

    def step(self) -> None:
        """One serving tick: drain input buffers -> compiled step ->
        (at the validation cadence) validate, grow + exact replay of the
        retained interval on overflow, maintain, and deliver the buffered
        outputs to the host output operators. Each phase is a ``tick.*``
        span, a child of the controller's ``tick`` when one drives it."""
        from dbsp_tpu.circuit.runtime import Runtime

        # the drain runs under the circuit's runtime like the host handle's
        # step (CircuitHandle.step): on a worker mesh ZSetInput.eval reads
        # it to key-hash-shard the tick's batch, and the serving thread has
        # no current runtime of its own
        spans = self.spans
        prev = Runtime._swap(self.host_handle.runtime)
        try:
            feeds: Dict = {}
            for op, drain in self._inputs:
                with spans.span("tick.build_inputs", "tick",
                                args={"table": self.input_labels[op]}) as sp:
                    feeds[op] = drain()
                    path, rows = getattr(  # (an UpsertInput has none)
                        op, "last_drain", ("device", feeds[op].cap))
                    sp.note(path=path, rows=rows)
        finally:
            Runtime._swap(prev)
        if not self._retained:
            # interval-start checkpoint; timed into host_overhead_ns like
            # run_ticks does, so serving pipelines feed the same phase
            # observability (obs histogram + flight recorder) as bench runs
            with spans.span("tick.snapshot", "tick") as sp:
                self._snap = self.ch.snapshot()
                sp.note(gc_levels=self.ch.snapshot_gc_levels)
            self.ch.host_overhead_ns["snapshot"].append(sp.elapsed_ns)
            # the previous interval's snapshot is gone: zero-reference
            # cold blobs can be swept without endangering any replay
            self.ch._sweep_cold()
            self._interval_open_ts = time.time()
        self._retained.append((self._tick, feeds))
        with spans.span("tick.dispatch", "tick",
                        args={"retraced": self.ch._step_jit is None}):
            self.ch.step(tick=self._tick, feeds=feeds)
        # feeds are host-built program INPUTS (never donated), so the
        # retained references replay the identical batches after a grow
        self._out_buffer.append(dict(self.ch.last_outputs))
        self._tick += 1
        if len(self._retained) >= self.validate_every:
            self._flush()

    def _flush(self) -> None:
        """Validate the open interval; on overflow grow + replay the
        retained feeds from the interval-start snapshot (exact); then run
        a bounded maintenance slice and deliver outputs in tick order."""
        spans = self.spans
        with spans.span("tick.validate", "tick") as sp:
            while True:
                try:
                    self.ch.validate(spans)
                    break
                except CompiledOverflow as e:
                    self.ch.overflow_replays += 1
                    with spans.span("tick.grow", "tick"):
                        self.ch.grow(e)
                    with spans.span("tick.replay", "tick",
                                    args={"ticks": len(self._retained)}):
                        self.ch.restore(self._snap)
                        self._out_buffer.clear()
                        for tick, feeds in self._retained:
                            self.ch.step(tick=tick, feeds=feeds)
                            self._out_buffer.append(
                                dict(self.ch.last_outputs))
            facts = self.ch.time_facts()  # of a circuit with time nodes
            if facts:
                sp.note(retired_rows=facts["retired_rows"],
                        watermark_ms=facts["watermark_ms"])
            tops = self.ch.topk_facts()  # of a circuit with top-K nodes
            if tops:
                sp.note(topk_gathered_rows=tops["topk_gathered_rows"])
        self.ch.host_overhead_ns["validate"].append(sp.elapsed_ns)
        stats = self.ch.maintain_stats
        drains0 = stats["drains"] + stats["partial_drains"]
        rows0 = stats["rows_moved"]
        with spans.span("tick.maintain", "tick") as sp:
            self.ch.maintain()  # spine drains; dispatch-free when nothing due
            sp.note(drains=stats["drains"] + stats["partial_drains"]
                    - drains0, rows_moved=stats["rows_moved"] - rows0)
        self.ch.host_overhead_ns["maintain"].append(sp.elapsed_ns)
        with spans.span("tick.deliver", "tick"):
            for outputs in self._out_buffer:
                for idx, out_op in self._outputs:
                    batch = outputs.get(idx)
                    if batch is not None:
                        # deferred-to-sink consolidation (placement pass):
                        # canonicalize at delivery so every host consumer
                        # (HTTP readers, transports, to_dict tests) sees
                        # the same batches as the eager-consolidate engine
                        # — the ONE policy shared with
                        # CompiledHandle.output()
                        canon = self.ch.canonicalize_sink(batch)
                        if canon is not batch and \
                                self.ch.last_outputs.get(idx) is batch:
                            # share the canonical batch with output()
                            # readers
                            self.ch.last_outputs[idx] = canon
                        out_op.eval(canon)
        self._out_buffer.clear()
        self._retained.clear()
        self._snap = None
        self._interval_open_ts = None

    def flush(self) -> None:
        """Force validation/delivery of a partially-filled interval (the
        controller calls this on pause/stop and before barrier reads so a
        cadence > 1 never leaves undelivered ticks behind)."""
        if self._retained:
            self._flush()

    def profile_ticks(self, n: int = 8, spans=None, registry=None) -> dict:
        """Measured operator attribution at the driver's current position:
        flush the open deferred-validation interval (so the snapshot sits
        at a validated tick boundary), then run the segmented protocol —
        per-node timing, bit-identity assert, rewind — via
        :meth:`CompiledHandle.profile_ticks`. The caller owns quiescence:
        the ``/profile`` route invokes this under the controller's step
        lock so no serving tick is in flight.

        Workload: the open interval's retained feeds (captured BEFORE the
        flush clears them) replay as the profiled ticks' inputs, so a
        cadence > 1 pipeline profiles real recent deltas. At the default
        serve cadence of 1 nothing is retained and the profile runs EMPTY
        ticks — on a delta-proportional engine that attributes fixed
        per-node overhead, not the serving workload, and the report says
        so (``measured["idle_inputs"]``)."""
        feeds_list = [dict(f) for _, f in self._retained] or None
        self.flush()
        return self.ch.profile_ticks(n, t0=self._tick,
                                     feeds_list=feeds_list,
                                     spans=spans if spans is not None
                                     else self.spans, registry=registry)

    def residency_summary(self):
        """Tiered-residency digest of the compiled engine (per-tier rows,
        budgets, transition count) for ``/status`` — None when residency
        is unconfigured and nothing ever demoted. See
        :func:`dbsp_tpu.residency.summary`."""
        from dbsp_tpu import residency

        return residency.summary(self)

    def restore_checkpoint(self, tick: int, retained) -> None:
        """Resume from a restored checkpoint (dbsp_tpu.checkpoint): the
        engine states were already applied to ``self.ch`` at the
        checkpoint's validated tick; this replays the checkpoint's
        retained-feed window — the inputs of the open (not yet validated)
        interval — so the driver lands exactly where the checkpointed one
        stood, with the same buffered outputs awaiting validation.
        Exactly-once: retained ticks were never delivered pre-crash
        (delivery happens at validation), so the replay re-delivers
        nothing and re-runs everything, deterministically."""
        self._snap = None
        self._retained = []
        self._out_buffer = []
        self._interval_open_ts = None
        self._tick = int(tick)
        for t, feeds_by_idx in retained:
            feeds = {self.ch.by_index[i].op: b
                     for i, b in feeds_by_idx.items()}
            if not self._retained:
                self._snap = self.ch.snapshot()
                self._interval_open_ts = time.time()
            self._retained.append((t, feeds))
            self.ch.step(tick=t, feeds=feeds)
            self._out_buffer.append(dict(self.ch.last_outputs))
            self._tick = t + 1
        if len(self._retained) >= self.validate_every:
            self._flush()


def try_compiled_driver(handle, registry=None, verified=False, flight=None):
    """Compile the circuit if every operator has a compiled equivalent;
    None when it must stay on the host-driven path (the caller records
    which mode the pipeline runs — facade.rs's feature gate).

    ANY compile-time failure falls back: ``NotImplementedError`` is the
    designed signal (operator without a compiled node), but init_state()
    can also raise (e.g. ``AssertionError`` from CZ1Input for non-Batch
    feedback) — with compiled mode defaulting on for every manager
    pipeline, an unexpected compile error must degrade to the host
    scheduler that previously ran the circuit, not kill the deploy. The
    failure is logged and, when ``registry`` (obs.MetricsRegistry) is
    given, counted as ``dbsp_tpu_compiled_fallback_total{reason=...}``.

    ``flight`` (obs.FlightRecorder) additionally records the fallback as a
    structured event carrying the reason AND its human-readable detail —
    the host fallback is an order-of-magnitude perf cliff, so it must be
    SLO-visible (the watchdog latches it into a degraded state and an
    incident), not just a counter a dashboard may or may not chart."""
    from dbsp_tpu.analysis import AnalysisError

    try:
        if verified:
            return CompiledCircuitDriver(
                handle, compiled=compile_circuit(handle, verified=True))
        return CompiledCircuitDriver(handle)
    except AnalysisError:
        # a circuit that FAILS STATIC ANALYSIS is broken on every path —
        # falling back would run it on the host scheduler and produce the
        # wrong answers the analyzer exists to prevent
        raise
    except Exception as e:  # noqa: BLE001 — deliberate: fallback, not crash
        reason = type(e).__name__
        if isinstance(e, NotImplementedError):
            logger.debug("compiled driver unavailable: %s", e)
        else:
            logger.warning("compiled driver failed (%s: %s); falling back "
                           "to the host scheduler", reason, e)
        if registry is not None:
            registry.counter(
                "dbsp_tpu_compiled_fallback_total",
                "Circuits that failed to compile and fell back to the "
                "host-driven path", labels=("reason",)).labels(
                    reason=reason).inc()
        if flight is not None:
            flight.record("fallback", reason=reason, detail=str(e)[:200])
        return None
