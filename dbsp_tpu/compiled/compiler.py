"""Compile a built circuit into ONE jitted step function.

Why this exists (the TPU-first argument): the host-driven scheduler evaluates
operators one kernel launch at a time and makes host-side decisions (grow-on-
demand capacities, spine merge scheduling, overflow checks) that each cost a
device->host round-trip. Each one stalls the accelerator's queue, and they
forbid XLA from fusing across operator boundaries. Compiled mode removes the host from
the per-tick path entirely:

  * the scheduler's toposort eval sequence is traced ONCE into a single
    ``step(states, tick, feeds) -> (states', outputs, required)`` function —
    XLA sees the whole tick and fuses/overlaps across operators;
  * every state (traces, accumulators) is a fixed-capacity device batch
    threaded through the function — no Python bookkeeping per tick;
  * all data-dependent capacity decisions become device-side "required
    capacity" scalars, reduced to a running max; the runner checks them at
    validation points (every N ticks / end of run), and on overflow grows the
    capacity, re-traces, and REPLAYS from the last validated snapshot —
    deterministic inputs (tick-indexed generators, retained feeds) make the
    replay exact. Optimistic execution + epoch validation, in place of the
    host path's per-eval synchronous checks.

The input side can be closed over too: pass ``gen_fn(tick) -> feeds`` (e.g.
:func:`dbsp_tpu.nexmark.device_gen.generate_tick`) and event generation joins
the same XLA program — a benchmark tick then transfers NOTHING between host
and device.

Between-tick discipline (the wall-clock side of the contract): ticks run
PIPELINED at depth 1 (``_run_pipelined`` — dispatch t, wait t-1), snapshots
are INCREMENTAL (deep trace levels are version-counted and only re-copied
after a drain touched them), and LSM maintenance is BUDGETED
(``DBSP_TPU_MAINTAIN_BUDGET_ROWS`` bounds rows moved per ``maintain`` call,
with a resumable prefix-slice cursor), so no single tick absorbs a drain
cascade and host work per interval is O(level 0 + budget), not O(state).
Each between-tick phase is timed into ``host_overhead_ns`` and annotated
onto the next latency sample (``tick_causes``) — tail ticks are attributable
to maintain / snapshot / retrace from the bench output alone.
``tools/check_hotpath.py`` (rule 3) keeps new syncs out of the step loop.

Reference analog: ``crates/dataflow-jit`` (compile the dataflow once,
schema-driven, no per-record interpretation) — here XLA is the codegen and
the circuit graph is the IR (SURVEY.md §2.4).

Supported operators: input/output handles, map/filter/flat_map/index, plus/
minus/neg/sum, trace, join, aggregate (general + linear), distinct,
watermark/apply/window (scalar streams become (valid, value) device pairs;
window GC feeds back into the trace state inside the program). Circuits
using other operators (nested/recursive children, async transports) stay on
the host-driven path — the two modes share kernels and state layouts, so
they compose (warm up host-side, then compile; or run host-side features
around a compiled core).
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dbsp_tpu import residency as res
from dbsp_tpu.circuit.scheduler import static_schedule
from dbsp_tpu.compiled import cnodes
from dbsp_tpu.compiled.cnodes import CNode
from dbsp_tpu.zset.batch import Batch, bucket_cap

# Maintenance budget (rows MOVED between trace levels per maintain() call).
# Bounding the per-call drain volume amortizes an LSM cascade over several
# validation intervals instead of letting one tick absorb l0->l1->...->tail
# in a single burst (the 8.3x p99/p50 tail measured in BENCH r05). The knob
# (DBSP_TPU_MAINTAIN_BUDGET_ROWS; <=0 = unbounded) is OWNED by the host
# spine and imported here so both engines share one amortization discipline.
from dbsp_tpu.trace.spine import MAINTAIN_BUDGET_ROWS  # noqa: E402


# Room an overflowed capacity of a windowed view gets while its window
# fills (see CompiledHandle.grow): NEXmark q5's capacities end 6 to 15 times
# their first reading above 0 (CPU rehearsal at the benchmark's size,
# PERF.md 6, PR 36), and each re-trace is a whole-program compile.
RAMP_ROOM = 8


class CompiledOverflow(RuntimeError):
    """A static capacity was exceeded since the last validation point.

    ``items`` is a list of (cnode, cap_key, required) — the runner's
    ``grow()`` consumes it; state since the last snapshot is invalid and must
    be replayed after growing.
    """

    def __init__(self, items):
        self.items = items
        msg = ", ".join(f"{c.op.name}.{k}: need {r} > cap {c.caps[k]}"
                        for c, k, r in items)
        super().__init__(f"compiled capacities exceeded: {msg}")


class _Ctx:
    """Per-trace context: feeds in, outputs + capacity requirements out."""

    def __init__(self, feeds):
        self.feeds = feeds
        self.outputs: Dict[int, Batch] = {}
        self.reqs: List[jnp.ndarray] = []
        self.req_index: List[Tuple[CNode, str]] = []
        self.obs: List[jnp.ndarray] = []
        self.obs_index: List[Tuple[CNode, str]] = []
        # trace-node index -> lower bound: window GC feeding back into the
        # trace state within the same program (TraceBound semantics)
        self.gc_bounds: Dict[int, jnp.ndarray] = {}

    def require(self, cnode: CNode, key: str, scalar) -> None:
        self.req_index.append((cnode, key))
        self.reqs.append(jnp.asarray(scalar, jnp.int64))

    def observe(self, cnode: CNode, key: str, scalar) -> None:
        """A non-negative device scalar that rides the requirement vector
        (behind the requirements) and is checked against no capacity:
        validation hands it to ``cnode.note_observations``. The time
        nodes' counters come this way, at no device fetch of their own."""
        self.obs_index.append((cnode, key))
        self.obs.append(jnp.asarray(scalar, jnp.int64))


def node_scope(cn: CNode) -> str:
    """The scope that names every operation of a node in the lowered step
    program and the device trace: ``n<index>.<CNode class>``."""
    return f"n{cn.node.index}.{type(cn).__name__}"


def _cnode_for(node) -> CNode:
    from dbsp_tpu.operators.aggregate import AggregateOp
    from dbsp_tpu.operators.aggregate_linear import LinearAggregateOp
    from dbsp_tpu.operators.basic import Minus, Neg, Plus, SumN
    from dbsp_tpu.operators.distinct import DistinctOp, StreamDistinct
    from dbsp_tpu.operators.filter_map import FilterOp, FlatMapOp, MapOp
    from dbsp_tpu.operators.io_handles import OutputOperator, ZSetInput
    from dbsp_tpu.operators.join import JoinOp
    from dbsp_tpu.operators.trace_op import TraceOp

    op = node.operator
    if isinstance(op, ZSetInput):
        return cnodes.CInput(node, op)
    if isinstance(op, (MapOp, FilterOp, FlatMapOp)):
        return cnodes.CPure(node, op)
    if isinstance(op, StreamDistinct):
        return cnodes.CStreamDistinct(node, op)
    if isinstance(op, TraceOp):
        return cnodes.CTrace(node, op)
    if isinstance(op, JoinOp):
        return cnodes.CJoin(node, op)
    if isinstance(op, AggregateOp):
        return cnodes.CAggregate(node, op)
    if isinstance(op, LinearAggregateOp):
        return cnodes.CLinearAggregate(node, op)
    if isinstance(op, DistinctOp):
        return cnodes.CDistinct(node, op)
    if isinstance(op, Plus):
        return cnodes.CPlus(node, op)
    if isinstance(op, Neg):
        return cnodes.CNeg(node, op)
    if isinstance(op, SumN):
        return cnodes.CSumN(node, op)
    if isinstance(op, OutputOperator):
        return cnodes.COutput(node, op)
    if isinstance(op, Minus):
        return cnodes.CMinus(node, op)
    from dbsp_tpu.operators.basic import Apply
    from dbsp_tpu.operators.shard_op import ExchangeOp, UnshardOp
    from dbsp_tpu.timeseries.watermark import WatermarkMonotonic
    from dbsp_tpu.timeseries.window import WindowOp

    if isinstance(op, ExchangeOp):
        return cnodes.CExchange(node, op)
    if isinstance(op, UnshardOp):
        return cnodes.CUnshard(node, op)
    from dbsp_tpu.operators.topk import TopKOp

    if isinstance(op, TopKOp):
        return cnodes.CTopK(node, op)
    if isinstance(op, WatermarkMonotonic):
        return cnodes.CWatermark(node, op)
    if isinstance(op, Apply):
        return cnodes.CApply(node, op)
    if isinstance(op, WindowOp):
        return cnodes.CWindow(node, op)
    from dbsp_tpu.operators.join_range import RangeJoinOp
    from dbsp_tpu.operators.upsert import UpsertInput
    from dbsp_tpu.timeseries.rolling import RollingAggregateOp

    if isinstance(op, RangeJoinOp):
        return cnodes.CRangeJoin(node, op)
    if isinstance(op, RollingAggregateOp):
        return cnodes.CRolling(node, op)
    if isinstance(op, UpsertInput):
        return cnodes.CUpsertIn(node, op)
    from dbsp_tpu.operators.z1 import Z1, _PlusNamed

    if isinstance(op, Z1):
        return cnodes.CZ1Output(node, op) if node.kind == "strict_output" \
            else cnodes.CZ1Input(node, op)
    if isinstance(op, _PlusNamed):
        return cnodes.CPlus(node, op)
    raise NotImplementedError(
        f"operator {op.name!r} ({type(op).__name__}) has no compiled "
        "equivalent yet — run this circuit on the host-driven path")


@jax.jit
def _copy_tree(tree):
    """Deep-copy a state pytree in ONE dispatch (eager per-leaf jnp.copy
    costs a dispatch per column, ~100 leaves per circuit)."""
    return jax.tree_util.tree_map(jnp.copy, tree)


def _on_workers(levels):
    """Pin what a drain returns to its levels' own placement: on a worker
    mesh, one slice a worker. An emptied level is all constants, and left
    to itself the TPU's compiler returns it REPLICATED on every chip
    (asked for a described v5e 2x2; the CPU's keeps it sharded): the next
    step then meets a state leaf under a new input sharding, which is a
    whole new SPMD step program — on four chips one after each level
    pair's first drain, 33 s from the cache and 97–109 s compiled, inside
    the serving window (CHANGES.md, PR 30)."""
    if not levels[0].sharded:
        return levels
    from dbsp_tpu.parallel.lift import current_mesh
    from dbsp_tpu.parallel.mesh import worker_sharding

    return jax.lax.with_sharding_constraint(
        levels, worker_sharding(current_mesh()))


@partial(jax.jit, static_argnums=(2,), donate_argnums=(0, 1))
def _drain_pair(receiver: Batch, source: Batch, cap: int):
    """One maintenance drain as a single jitted dispatch (eager Batch ops
    cost ~10 dispatches each; this runs every few validation intervals on
    every leveled trace, so dispatch overhead was measurable)."""
    with jax.named_scope("maintain.drain"):
        return _on_workers((receiver.merge_with(source).with_cap(cap),
                            source.masked(False)))


@partial(jax.jit, static_argnums=(3,), donate_argnums=(0, 1))
def _drain_slice(receiver: Batch, source: Batch, n, cap: int):
    """Drain only the FIRST ``n`` live rows of ``source`` into ``receiver``
    — the resumable merge cursor of budgeted maintenance. Live rows are
    packed at the front of a consolidated level, so the taken prefix is
    itself a consolidated batch and the remainder keeps every level
    invariant; the cursor is implicitly always 0. A key split across the
    slice boundary lands in two levels, which consumers already net
    (``_reduce_groups_impl(..., net=True)``). The remainder re-packs by a
    ROLL (the kept rows are already contiguous at [n, live)), not a
    compaction — ``kernels.compact`` assumes an unsharded row axis, while
    levels here may carry a worker axis ([W, cap]); roll + mask work on
    the last axis of either layout. On a sharded level ``n`` applies
    per-worker slice (lives are max-worker counts, the same convention
    capacity bucketing uses)."""
    with jax.named_scope("maintain.drain"):
        idx = jnp.arange(source.cap, dtype=jnp.int32)
        take = source.masked(idx < n)
        rolled = Batch(
            tuple(jnp.roll(k, -n, axis=-1) for k in source.keys),
            tuple(jnp.roll(v, -n, axis=-1) for v in source.vals),
            jnp.roll(source.weights, -n, axis=-1))
        # positions that wrapped around hold the taken prefix — dead
        # them; rolled live rows occupy [0, live - n), already packed at
        # the front. The remainder IS still one consolidated run (sorted
        # suffix, packed, sentinel tail) — tag it so the level's pytree aux
        # stays IDENTICAL across drains; an aux flip here would retrace the
        # whole step program on the next tick (run metadata is static
        # data).
        rest = rolled.masked(idx < source.cap - n).tagged((source.cap,))
        return _on_workers((receiver.merge_with(take).with_cap(cap), rest))


class CompiledHandle:
    """Drives a compiled circuit: step / validate / grow / snapshot-replay."""

    def __init__(self, circuit, gen_fn: Optional[Callable] = None,
                 runtime=None):
        self.circuit = circuit
        self.runtime = runtime  # needed for sharded host-side maintenance
        self.mesh = getattr(runtime, "mesh", None)
        self.workers = getattr(runtime, "workers", 1)
        self.order = static_schedule(circuit)
        self.cnodes: List[CNode] = [_cnode_for(n) for n in self.order]
        self.by_index = {cn.node.index: cn for cn in self.cnodes}
        # a GC'd trace is bounded by the window span, not the run length:
        # exclude it from linear presize projection (instance attr shadows
        # the class-level MONOTONE_CAPS)
        for cn in self.cnodes:
            # per-level consumers that were NOT fused over the expanded
            # slot ladder (range joins, windows, rolling aggregates) would
            # pay one launch per SLOT per tick — their input traces keep
            # the legacy merged l0 instead of slotting
            if isinstance(cn, (cnodes.CRangeJoin, cnodes.CWindow,
                               cnodes.CRolling)):
                for i in cn.node.inputs:
                    tgt = self.by_index.get(i)
                    if isinstance(tgt, cnodes.CTrace):
                        tgt._no_slots = True
            if isinstance(cn, cnodes.CWindow) and cn.op.gc:
                tgt = self.by_index.get(cn.node.inputs[0])
                if isinstance(tgt, cnodes.CTrace):
                    tgt.MONOTONE_CAPS = frozenset()
                    # in-program TraceBound truncation SHRINKS levels —
                    # maintain() must refetch exact live counts (its
                    # host cache only ever sees drains grow them) or the
                    # base_live requirement integrates upward forever
                    tgt._gc_refresh = True
        # A WINDOWED VIEW: the trace under a GC bound and every node
        # downstream of its window. Rows leave such a view as fast as they
        # arrive, so its state is bounded by the window's span and its
        # per-delta capacities ramp while the window fills and then stay
        # (see grow). Its traces count their levels' live rows in the step
        # program (``_run_nodes``): maintenance plans from exact counts,
        # where its host-side sums of drained rows only ever grow — under
        # steady retraction they drift above the rows held without bound
        # and end in a capacity grown for rows that cancelled long ago.
        self._windowed: set = set()
        for cn in self.cnodes:
            if isinstance(cn, cnodes.CWindow) and cn.op.gc:
                self._windowed.update((cn.node.index, cn.node.inputs[0]))
            elif any(i in self._windowed for i in cn.node.inputs) and \
                    cn.node.index not in self._windowed:
                self._windowed.add(cn.node.index)
        for cn in self.cnodes:
            if isinstance(cn, cnodes.CTrace) and \
                    cn.node.index in self._windowed:
                cn._counts_lives = True
        # A slotted level 0 pins its slot size at the FIRST trace. Behind a
        # producer whose output capacity is still its SEED then, and is
        # sized later by what validation reads (an aggregate's
        # ``queries``: a delta of 128; a top-K's or a window's ``out``),
        # the pin goes stale once presize or grow has raised it: no delta
        # matches it, every tick sorts level 0 on the fallback path and
        # consumers probe it as cap / 128 slot runs (q5's by_window trace:
        # 2,048 of them, and 262 s of a step program's compile for a v5e
        # in that trace alone; PERF.md 7, fault 12). Every trace downstream
        # of one keeps the merged level 0 — a windowed view's traces among
        # them, where half of a delta cancels rows level 0 already holds
        # and a merge nets them at once; NEXmark q6's top-10 input trace,
        # behind the top-1 per auction.
        seeded: set = set()
        for cn in self.cnodes:
            if isinstance(cn, (cnodes.CAggregate, cnodes.CLinearAggregate,
                               cnodes.CTopK, cnodes.CWindow)) or \
                    any(i in seeded for i in cn.node.inputs):
                seeded.add(cn.node.index)
        for cn in self.cnodes:
            if isinstance(cn, cnodes.CTrace) and cn.node.index in seeded:
                cn._no_slots = True
        # map host InputHandle ops -> node indices (for feeds dicts)
        self._op_to_index = {id(n.operator): n.index for n in self.order}
        self._gen_fn = gen_fn
        self.deferred_consolidations = self._place_consolidations()
        self.states: Dict[str, Any] = {}
        for cn in self.cnodes:
            cn.lead = (self.workers,) if self.workers > 1 else ()
            st = cn.init_state()
            if st is not None:
                if self.workers > 1:
                    from dbsp_tpu.parallel.mesh import worker_sharding

                    st = jax.device_put(st, worker_sharding(self.mesh))
                self.states[str(cn.node.index)] = st
        self._step_jit = None
        # device-resident tick cursor: the step program RETURNS tick+1 (and
        # the scan program t0+n), so the steady state never uploads the
        # tick scalar — the old per-tick jnp.asarray(tick) was an implicit
        # h2d transfer on every dispatch, the exact class
        # jax.transfer_guard("disallow") convicts (testing/retrace.py).
        # _tick_host mirrors the device value; a mismatch (restore, replay,
        # manual tick jump) re-uploads EXPLICITLY via jax.device_put.
        self._tick_dev = None
        self._tick_host: Optional[int] = None
        # armed by testing/retrace.py's sentinel session: a
        # jax.transfer_guard level ("disallow") wrapped around the jitted
        # step/scan calls so implicit device<->host transfers in the
        # steady tick raise with a stack
        self._steady_guard: Optional[str] = None
        self._checks: List[Tuple[CNode, str]] = []
        # what the nodes observe (``_Ctx.observe``): behind the
        # requirements in the same vector
        self._observed: List[Tuple[CNode, str]] = []
        # per check, what a tick's record names it by: (node index, device
        # scope, capacity key, class)
        self._check_names: List[Tuple[int, str, str, str]] = []
        self._req = None          # device running-max of requirements
        self._max_jit = jax.jit(jnp.maximum)
        self.last_outputs: Dict[int, Batch] = {}
        self.step_times_ns: List[int] = []
        # grow-and-replay cycles since construction (observability: the
        # obs registry exports this as
        # dbsp_tpu_compiled_overflow_replays_total)
        self.overflow_replays = 0
        # the subset caused by exchange/input bucket overflow (skew past a
        # static per-worker capacity) — exported as
        # dbsp_tpu_exchange_overflow_total and in bench detail
        self.exchange_overflows = 0
        # -- tail attribution + incremental-snapshot bookkeeping ------------
        # host_overhead_ns: wall time of each between-tick host phase (obs
        # exports dbsp_tpu_compiled_tick_host_overhead_seconds{phase});
        # tick_causes: (sample index, cause) annotations — a spike in
        # step_times_ns[i] is explained by the causes recorded against i
        # (bench.py turns these into per-cause spike counts)
        self.host_overhead_ns: Dict[str, List[int]] = {
            "validate": [], "maintain": [], "snapshot": []}
        self.tick_causes: List[Tuple[int, str]] = []
        self._pending_causes: set = set()
        # maintain amortization state (see maintain()): cumulative stats the
        # cascade test and obs read, plus the per-(state, level) version
        # counters the incremental snapshot uses to skip re-copying deep
        # levels that no drain has touched since the last snapshot
        self.maintain_stats: Dict[str, int] = {
            "calls": 0, "drains": 0, "partial_drains": 0, "rows_moved": 0,
            "max_slice_rows": 0, "max_budgeted_slice_rows": 0,
            "exempt_drains": 0}
        self.maintain_pending = False
        self._level_versions: Dict[str, List[int]] = {}
        self._snap_levels: Dict[str, List[Optional[Tuple[int, Batch]]]] = {}
        # hard-link scope marker for incremental checkpoints: assigned by
        # dbsp_tpu.checkpoint on first save, regenerated on restore (two
        # handles sharing a directory must never alias each other's blobs)
        self._ckpt_salt: Optional[str] = None
        # -- tiered trace residency (device <- host <- disk) -----------------
        # Residency bookkeeping lives OUTSIDE the jitted state pytree: the
        # step program is traced against a HOT pytree (donated, device) and
        # a COLD operand dict (numpy / memmap, device_put per call, buffers
        # die with it), so a demoted level never re-materializes as a
        # persistent program output. All transitions happen between
        # validated intervals (maintain / restore), never in the hot loop.
        self.residency_cfg: res.ResidencyConfig = res.ResidencyConfig.from_env()
        self._tiers: Dict[str, List[str]] = {}    # key -> tier per level
        self._cold_meta: Dict[str, Dict[int, dict]] = {}  # disk blob metas
        self._cold_store = None                   # residency.ColdStore
        self._lru: Dict[Tuple[str, int], int] = {}  # (key, lvl) -> interval
        self._interval = 0                        # maintain-call clock
        # transition observability: counts keyed (from, to, cause) +
        # bounded append-only log (CompiledFlightSource polls the tail into
        # `residency` flight events) + cold-blob corruption episodes
        # (polled into one-shot `restore` SLO incidents)
        self.residency_stats: Dict[Tuple[str, str, str], int] = {}
        self.residency_log: List[dict] = []
        self.cold_events: List[dict] = []

    # -- consolidate placement ----------------------------------------------
    def _place_consolidations(self) -> int:
        """Dedupe adjacent consolidations and defer them toward sinks.

        A consolidation is PURELY canonicalizing: it changes a batch's
        layout (sorted, netted, packed), never its Z-set value. When every
        consumer of a node re-canonicalizes anyway — a general map/flat_map
        (they consolidate after transforming, and row-wise transforms
        commute with netting), an n-ary sum (concat + consolidate), a
        key-hash exchange (consolidates after the all_to_all), or a host
        output sink (reads canonicalize lazily, see :meth:`output`) — the
        node's own trailing consolidation is dead work and is removed from
        the traced program (``defer_consolidate``). Order-preserving
        pass-throughs (filter, neg) inherit their consumers' requirement,
        so a join -> filter -> map chain defers the join's sort too.

        Everything stateful (traces, aggregates, distinct, plus/minus
        merges, windows, order-preserving maps) REQUIRES consolidated
        inputs and fences the deferral. Returns the number of deferred
        consolidations (each counted under ``path="deferred"`` in
        ``dbsp_tpu_zset_consolidate_total``).
        ``DBSP_TPU_DEFER_CONSOLIDATE=0`` disables the pass (bisect knob)."""
        import os

        from dbsp_tpu.operators.filter_map import FilterOp, FlatMapOp, MapOp
        from dbsp_tpu.zset import kernels as zkernels

        if os.environ.get("DBSP_TPU_DEFER_CONSOLIDATE", "1") == "0":
            return 0

        consumers: Dict[int, List[CNode]] = {}
        for cn in self.cnodes:
            for i in cn.node.inputs:
                consumers.setdefault(i, []).append(cn)

        def input_need(cn: CNode) -> bool:
            """Does ``cn`` require consolidated INPUT batches? (Consumers
            are resolved before producers — reversed toposort — so
            pass-through nodes may read their own ``_out_need``.)"""
            if isinstance(cn, cnodes.COutput):
                return False  # host reads canonicalize at the sink
            if isinstance(cn, cnodes.CExchange):
                return False  # consolidates after the all_to_all
            if isinstance(cn, cnodes.CSumN):
                # consolidates itself unless deferred — and deferral only
                # ever happens when its own consumers don't need
                # consolidated rows, so either way the inputs may arrive
                # unconsolidated
                return False
            if isinstance(cn, cnodes.CPure):
                op = cn.op
                if isinstance(op, FilterOp):
                    return getattr(cn, "_out_need", True)
                if isinstance(op, MapOp):
                    return op.preserves_order
                if isinstance(op, FlatMapOp):
                    return False
                return True
            if isinstance(cn, cnodes.CNeg):
                return getattr(cn, "_out_need", True)
            return True

        deferred = 0
        for cn in reversed(self.cnodes):
            cons = consumers.get(cn.node.index, [])
            cn._out_need = (not cons) or any(input_need(c) for c in cons)
            if cn._out_need:
                continue
            can_defer = isinstance(
                cn, (cnodes.CJoin, cnodes.CRangeJoin, cnodes.CSumN))
            if isinstance(cn, cnodes.CPure) and \
                    isinstance(cn.op, (MapOp, FlatMapOp)) and \
                    not getattr(cn.op, "preserves_order", False):
                can_defer = True
            if can_defer:
                cn.defer_consolidate = True
                deferred += 1
                zkernels.count_consolidate_path("deferred")
        return deferred

    # -- feeds ---------------------------------------------------------------
    def _feed_indices(self, feeds: Dict) -> Dict[int, Batch]:
        out = {}
        for h, b in feeds.items():
            op = getattr(h, "_op", h)  # InputHandle or raw operator
            out[self._op_to_index[id(op)]] = b
        return out

    # -- tiered trace residency ----------------------------------------------
    def set_residency(self, cfg: res.ResidencyConfig) -> None:
        """Apply one residency config (the pipeline-config / env merge) —
        the compiled half of the unified knob. Takes effect at the next
        maintain interval; sharded handles keep everything device-resident
        (cold operands cannot join the SPMD collectives, the same carve-out
        the host spine documents for sharded batches)."""
        if cfg == self.residency_cfg:
            return
        self.residency_cfg = cfg
        if self.mesh is not None:
            return
        if self._cold_store is not None and cfg.cold_dir and \
                self._cold_store.path != cfg.cold_dir:
            # the store is already materialized somewhere else (an env/
            # default temp dir from before this config arrived): keeping
            # it would silently strand all cold blobs outside the
            # configured directory — the accepted-but-ignored key again.
            # Fault the disk tier up (verified) so the old store owns
            # nothing, then let _store() lazily recreate at the new path;
            # enforcement re-demotes into it.
            for cn, key, (levels, base) in list(self._leveled_nodes()):
                tiers = list(self._tiers.get(key) or [])
                if res.TIER_DISK not in tiers:
                    continue
                levels = list(levels)
                for k, t in enumerate(tiers):
                    if t != res.TIER_DISK:
                        continue
                    ent = self._cold_meta.get(key, {}).get(k)
                    blob = ent["blob"] if ent is not None and \
                        ent.get("batch") is levels[k] \
                        else res.meta_from_batch(levels[k])
                    hot = res.fault_batch(blob, self._cold_store)
                    if ent is not None:
                        self._cold_meta[key].pop(k, None)
                        self._cold_store.release(ent["blob"])
                    levels[k] = hot
                    tiers[k] = res.TIER_HOST
                    self._log_transition(key, k, res.TIER_DISK,
                                         res.TIER_HOST, hot.cap, "config")
                self._tiers[key] = tiers
                cn.residency_tiers = tuple(tiers)
                self.states[key] = (tuple(levels), base)
            self._cold_store = None
        if cfg.active:
            # enforce immediately so a freshly deployed pipeline starts
            # within budget instead of waiting for the first drain
            self._enforce_residency(cause="config")
        elif self._tiers:
            # budgets DISABLED (explicit <= 0 config over an env knob):
            # promote everything back so the engine actually stops paying
            # the tiering, instead of stranding cold levels forever
            for cn, key, (levels, base) in list(self._leveled_nodes()):
                tiers = self._tiers.get(key)
                if not tiers:
                    continue
                levels = list(levels)
                for k, t in enumerate(tiers):
                    if t != res.TIER_DEVICE:
                        self._promote_level(cn, key, levels, tiers, k,
                                            "config")
                self._tiers.pop(key, None)
                cn.residency_tiers = tuple(tiers)
                self.states[key] = (tuple(levels), base)

    def _store(self) -> "res.ColdStore":
        if self._cold_store is None:
            path = self.residency_cfg.cold_dir
            if not path:
                # PER-HANDLE temp store, never the process-global default:
                # two handles sharing one store would cross-route their
                # corruption incidents (the observer is per store) and
                # cross-alias blob lifetimes
                import tempfile

                path = tempfile.mkdtemp(prefix="dbsp-tpu-cold-")
            self._cold_store = res.ColdStore(path,
                                             on_event=self._cold_event)
        return self._cold_store

    def _cold_event(self, ev: dict) -> None:
        if len(self.cold_events) < 512:
            self.cold_events.append(dict(ev))

    def _log_transition(self, key: str, lvl: int, tier_from: str,
                        tier_to: str, rows: int, cause: str) -> None:
        k = (tier_from, tier_to, cause)
        self.residency_stats[k] = self.residency_stats.get(k, 0) + 1
        if len(self.residency_log) < 4096:  # bounded; stats stay exact
            self.residency_log.append(
                {"node": key, "level": int(lvl), "tier_from": tier_from,
                 "tier_to": tier_to, "rows": int(rows), "cause": cause})

    def _leveled_nodes(self):
        for cn in self.cnodes:
            if isinstance(cn, cnodes._Leveled):
                st = self.states.get(str(cn.node.index))
                if st is not None and isinstance(st, tuple) and \
                        len(st) == 2 and isinstance(st[0], tuple):
                    yield cn, str(cn.node.index), st

    def tier_rows_by_node(self) -> Dict[str, Dict[str, int]]:
        """Per-trace resident row CAPACITY per tier, ONE walk over the
        leveled nodes (metrics scrapes and bench sampling index this
        instead of re-walking per key)."""
        out: Dict[str, Dict[str, int]] = {}
        for cn, k, (levels, _b) in self._leveled_nodes():
            row = {res.TIER_DEVICE: 0, res.TIER_HOST: 0, res.TIER_DISK: 0}
            tiers = self._tiers.get(k) or [res.TIER_DEVICE] * len(levels)
            for lvl, t in zip(levels, tiers):
                row[t] += lvl.cap
            out[k] = row
        return out

    def tier_rows(self, key: Optional[str] = None) -> Dict[str, int]:
        """Resident row CAPACITY per tier over the leveled traces (one
        trace when ``key`` given) — the compiled analog of
        ``Spine.tier_rows``; what the residency gauges and the growth
        bench sample."""
        out = {res.TIER_DEVICE: 0, res.TIER_HOST: 0, res.TIER_DISK: 0}
        for k, row in self.tier_rows_by_node().items():
            if key is not None and k != key:
                continue
            for t, rows in row.items():
                out[t] += rows
        return out

    def device_resident_rows(self, key: Optional[str] = None) -> int:
        """Device-resident leveled-trace capacity — what the device budget
        bounds (the residency hard-cap tests read this)."""
        return self.tier_rows(key)[res.TIER_DEVICE]

    def _split_states(self) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        """(hot pytree, cold operand dict) for one step dispatch. The hot
        dict rides the donated argument; cold levels ride separately so
        XLA device_puts them per call (transient buffers) and the program
        never returns them as persistent outputs."""
        if self.mesh is not None:
            # ONE input placement for the SPMD step program, whichever
            # host-side program last wrote a leaf (a drain, a re-pad after
            # a grow, a restore): to the jit a leaf under another sharding
            # is another program. A no-op for leaves already so placed; no
            # cold operand exists under a mesh (see set_residency).
            from dbsp_tpu.parallel.mesh import worker_sharding

            return jax.device_put(self.states,
                                  worker_sharding(self.mesh)), {}
        if not self._tiers:
            return self.states, {}
        hot = dict(self.states)
        cold: Dict[str, Any] = {}
        for key, tiers in self._tiers.items():
            if all(t == res.TIER_DEVICE for t in tiers):
                continue
            levels, base = hot[key]
            cold[key] = {str(i): levels[i]
                         for i, t in enumerate(tiers)
                         if t != res.TIER_DEVICE}
            hot[key] = (tuple(l for i, l in enumerate(levels)
                              if tiers[i] == res.TIER_DEVICE), base)
        return hot, cold

    @staticmethod
    def _interleave(hot_levels, lvmap) -> tuple:
        """THE one cold-level reinsertion rule (hot levels in order, cold
        levels at their recorded STR indices — pytree dict keys) — shared
        by the traced merge, the post-step rejoin, and the snapshot
        restore so the three paths can never reassemble different
        layouts."""
        K = len(hot_levels) + len(lvmap)
        it = iter(hot_levels)
        return tuple(lvmap[str(i)] if str(i) in lvmap else next(it)
                     for i in range(K))

    def _rejoin_states(self, states: Dict[str, Any],
                       cold: Dict[str, Any]) -> Dict[str, Any]:
        """Reassemble full level tuples after a step: program outputs for
        hot levels, the SAME host-side batch objects for cold ones (cold
        batches are immutable — the program never donates them)."""
        for key, lvmap in cold.items():
            if key not in states:
                continue
            hot_levels, base = states[key]
            states[key] = (self._interleave(hot_levels, lvmap), base)
        return states

    @staticmethod
    def _with_cold(states, cold):
        """(traced) merge cold operands back into full level tuples."""
        if not cold:
            return states
        out = dict(states)
        for key, lvmap in cold.items():
            hot, base = out[key]
            out[key] = (CompiledHandle._interleave(hot, lvmap), base)
        return out

    @staticmethod
    def _without_cold(new_states, cold):
        """(traced) strip cold levels from the returned states so they
        never become persistent program outputs."""
        for key, lvmap in (cold or {}).items():
            if key not in new_states:
                continue
            full, base = new_states[key]
            hot = tuple(l for i, l in enumerate(full)
                        if str(i) not in lvmap)
            new_states[key] = (hot, base)
        return new_states

    def _promote_level(self, cn, key: str, levels: list, tiers: list,
                       k: int, cause: str) -> None:
        """Promote one level to device for a WRITE (maintain drains merge
        into it). Disk levels take the VERIFIED read (the corruption
        detection point — recovery + incident via the cold store)."""
        t = tiers[k]
        if t == res.TIER_DEVICE:
            return
        if t == res.TIER_DISK:
            ent = self._cold_meta.get(key, {}).get(k)
            if ent is not None and ent.get("batch") is levels[k]:
                # meta dropped only AFTER the verified read succeeds — a
                # ColdError mid-promotion must leave the level tracked so
                # a retry still verifies instead of reading the memmap raw
                levels[k] = res.fault_batch(ent["blob"], self._store())
                self._cold_meta.get(key, {}).pop(k, None)
                self._store().release(ent["blob"])
            else:
                # IDENTITY mismatch (the save path's `batch is lvl` guard,
                # applied to the runtime promote): an overflow restore can
                # rewind a level to an OLDER disk batch than the recorded
                # meta describes — faulting through the stale meta would
                # merge the wrong content into the replay. Reconstruct the
                # meta from the memmap's content-addressed filenames and
                # VERIFY; the stale entry (if any) stays until its own
                # batch reappears or _sync_tiers drops it.
                levels[k] = res.fault_batch(
                    res.meta_from_batch(levels[k]), self._store())
        levels[k] = res.to_device(levels[k])
        tiers[k] = res.TIER_DEVICE
        self._lru[(key, k)] = self._interval
        self._log_transition(key, k, t, res.TIER_DEVICE, levels[k].cap,
                             cause)

    def _enforce_residency(self, cause: str = "budget") -> bool:
        """Demote/promote deep trace levels until every leveled trace fits
        the configured budgets. Called between validated intervals only
        (maintain / restore / config) — a tier change alters the hot
        pytree STRUCTURE, which the jitted step re-traces and caches per
        structure (an oscillating layout reuses its program; only
        capacity grows drop _step_jit). Policy: deepest-first demotion
        (deep levels are
        re-merged the least — one move buys the most headroom), level 0
        never demotes (the step program writes it every tick), and a host
        level only demotes to disk after ``lru_intervals`` maintain
        intervals without a write; promotion back to device happens for
        recently-written levels when headroom exists (the LRU clock —
        drain-writes promote eagerly in :meth:`maintain` itself)."""
        cfg = self.residency_cfg
        if cfg is None or not cfg.active or self.mesh is not None:
            return False
        changed = False
        for cn, key, (levels, base) in list(self._leveled_nodes()):
            K = len(levels)
            if K < 2 or getattr(cn, "_gc_refresh", False):
                continue
            tiers = list(self._tiers.get(key) or [res.TIER_DEVICE] * K)
            if len(tiers) != K:
                tiers = (tiers + [res.TIER_DEVICE] * K)[:K]
            levels = list(levels)

            def rows_in(tier):
                return sum(l.cap for l, t in zip(levels, tiers)
                           if t == tier)

            if cfg.device_rows is not None:
                for k in range(K - 1, 0, -1):
                    if rows_in(res.TIER_DEVICE) <= cfg.device_rows:
                        break
                    if tiers[k] != res.TIER_DEVICE:
                        continue
                    levels[k] = res.to_host(levels[k])
                    tiers[k] = res.TIER_HOST
                    self._log_transition(key, k, res.TIER_DEVICE,
                                         res.TIER_HOST, levels[k].cap,
                                         cause)
                    changed = True
            if cfg.host_rows is not None:
                for k in range(K - 1, 0, -1):
                    if rows_in(res.TIER_HOST) <= cfg.host_rows:
                        break
                    if tiers[k] != res.TIER_HOST:
                        continue
                    if self._interval - self._lru.get((key, k), -1 << 30) \
                            < cfg.lru_intervals:
                        continue  # recently written: not cold yet
                    lvl, meta = res.demote_batch_to_disk(levels[k],
                                                         self._store())
                    self._cold_meta.setdefault(key, {})[k] = {
                        "blob": meta, "batch": lvl}
                    levels[k] = lvl
                    tiers[k] = res.TIER_DISK
                    self._log_transition(key, k, res.TIER_HOST,
                                         res.TIER_DISK, lvl.cap, cause)
                    changed = True
            if cfg.device_rows is not None:
                # promotion under headroom, re-hot levels only (LRU)
                for k in range(1, K):
                    if tiers[k] != res.TIER_HOST:
                        continue
                    if self._interval - self._lru.get((key, k), -1 << 30) \
                            > cfg.lru_intervals:
                        continue  # cold: stays put
                    if rows_in(res.TIER_DEVICE) + levels[k].cap > \
                            cfg.device_rows:
                        continue
                    levels[k] = res.to_device(levels[k])
                    tiers[k] = res.TIER_DEVICE
                    self._log_transition(key, k, res.TIER_HOST,
                                         res.TIER_DEVICE, levels[k].cap,
                                         "lru")
                    changed = True
            if any(t != res.TIER_DEVICE for t in tiers):
                self._tiers[key] = tiers
            else:
                self._tiers.pop(key, None)
            cn.residency_tiers = tuple(tiers)
            self.states[key] = (tuple(levels), base)
        if changed:
            # a tier change alters the hot-pytree STRUCTURE only — the
            # jitted step re-traces and caches per input structure, so an
            # oscillating layout (drain promotes, budget demotes back)
            # re-uses its compiled program instead of recompiling; only
            # CAPACITY changes (grow) must drop _step_jit
            self._note_cause("residency")
        return changed

    def _sync_tiers(self, cause: str = "restore") -> None:
        """Reconcile the tier map with the ACTUAL leaf types after a path
        that may have materialized levels (restore re-padding after a
        grow) — the bookkeeping must never claim a tier the arrays left."""
        for cn, key, (levels, _b) in self._leveled_nodes():
            # DEFAULT to all-device rather than skipping untracked keys:
            # an overflow restore can reinsert a snapshot's cold level
            # under a tier map a later promotion emptied — skipping here
            # would leave the bookkeeping claiming "device" while the
            # leaf is a numpy/memmap batch, and the next dispatch would
            # ride it through the DONATED hot pytree (re-materializing
            # the whole level on device, unverified)
            tiers = self._tiers.get(key) or [res.TIER_DEVICE] * len(levels)
            tiers = (list(tiers) + [res.TIER_DEVICE] * len(levels)
                     )[:len(levels)]
            for k, lvl in enumerate(levels):
                actual = res.batch_tier(lvl)
                if actual != tiers[k]:
                    self._log_transition(key, k, tiers[k], actual,
                                         lvl.cap, cause)
                    tiers[k] = actual
                if actual != res.TIER_DISK:
                    ent = self._cold_meta.get(key, {}).pop(k, None)
                    if ent is not None:
                        self._store().release(ent["blob"])
            if any(t != res.TIER_DEVICE for t in tiers):
                self._tiers[key] = tiers
            else:
                self._tiers.pop(key, None)
            cn.residency_tiers = tuple(tiers)

    def _reconcile_cold_meta(self) -> None:
        """Re-key the disk blob bookkeeping to the ACTUAL batch objects
        after a rewind: an overflow restore can bring back an OLDER disk
        batch than the recorded meta describes (the meta followed a
        promote/re-demote cycle the snapshot predates). Stale entries are
        released; untracked disk levels get metas reconstructed from
        their content-addressed filenames (and re-retained, so the sweep
        cannot delete blobs the rewound state still needs)."""
        for cn, key, (levels, _b) in self._leveled_nodes():
            for k, lvl in enumerate(levels):
                ent = self._cold_meta.get(key, {}).get(k)
                is_disk = isinstance(lvl.weights, np.memmap)
                if ent is not None and ent.get("batch") is not lvl:
                    self._cold_meta[key].pop(k)
                    self._store().release(ent["blob"])
                    ent = None
                if is_disk and ent is None:
                    blob = res.meta_from_batch(lvl)
                    self._store().retain(blob)
                    self._cold_meta.setdefault(key, {})[k] = {
                        "blob": blob, "batch": lvl}

    def _sweep_cold(self) -> None:
        """Delete zero-reference cold blobs. Called ONLY when a new
        snapshot supersedes the old one — the one point where no overflow
        replay can ever fault content older than the live snapshot."""
        if self._cold_store is not None:
            self._cold_store.sweep()

    # -- tracing -------------------------------------------------------------
    def _run_nodes(self, states, tick, feeds, cold=None):
        """The scheduler's eval sequence as a pure traced function (shared
        by the single-worker and SPMD step builders)."""
        if self._gen_fn is not None:
            raw = self._gen_fn(tick)
            feeds = {self._op_to_index[id(getattr(h, "_op", h))]: b
                     for h, b in raw.items()}
        # cold (host/disk-tier) levels rejoin their traces here: they are
        # per-call operands, device_put by XLA for the duration of the
        # call, and stripped from the returned states below so they never
        # become persistent device buffers
        states = self._with_cold(states, cold)
        ctx = _Ctx(feeds)
        ctx.states = states  # strict-output halves read their partner's
        values: Dict[int, Any] = {}
        new_states = {}
        for cn in self.cnodes:
            ins = [values[i] for i in cn.node.inputs]
            st = states.get(str(cn.node.index))
            with jax.named_scope(node_scope(cn)):
                st2, out = cn.eval(ctx, st, ins)
            if st2 is not None:
                new_states[str(cn.node.index)] = st2
            values[cn.node.index] = out
        for idx, bound in ctx.gc_bounds.items():
            key = str(idx)
            if key in new_states:  # a leveled trace: truncate every level
                levels, base = new_states[key]
                # base_live goes stale-high until the next maintenance
                # recomputes it — conservative for capacity requirements
                new_states[key] = (tuple(
                    cnodes.truncate_below(lvl, bound)
                    for lvl in levels), base)
                ctx.observe(self.by_index[idx], "gc_truncated", sum(
                    a.live_count() - b.live_count()
                    for a, b in zip(levels, new_states[key][0])))
        for cn in self.cnodes:
            if getattr(cn, "_counts_lives", False):
                for k, lvl in zip(cn.level_keys,
                                  new_states[str(cn.node.index)][0]):
                    ctx.observe(cn, f"live.{k}", lvl.live_count())
        new_states = self._without_cold(new_states, cold)
        req = (jnp.stack(ctx.reqs + ctx.obs) if ctx.reqs or ctx.obs
               else jnp.zeros((0,), jnp.int64))
        self._checks = ctx.req_index  # same order every trace
        self._check_names = [
            (cn.node.index, node_scope(cn), key,
             "state" if cn.sizes_state(key) else "tick")
            for cn, key in ctx.req_index]
        self._observed = ctx.obs_index
        return new_states, ctx.outputs, req

    def _make_step(self):
        # states are DONATED: levels past 0 (and any untouched state) flow
        # through the program unmodified, and donation lets XLA alias them
        # input->output instead of copying — without it every tick paid a
        # full copy of all trace state (~tens of MB at q4 scale, measured
        # as the dominant steady-state cost). The flip side: snapshots
        # must be real copies (see snapshot()).
        if self.mesh is None:
            def step_fn(states, tick, feeds, cold):
                ns, outs, req = self._run_nodes(states, tick, feeds, cold)
                # tick+1 rides the program output so the next dispatch
                # reuses a device-resident cursor (no per-tick h2d upload)
                return ns, outs, req, tick + 1

            return jax.jit(step_fn, donate_argnums=(0,))

        # SPMD: ONE shard_map around the whole eval sequence. Inside, every
        # batch is its [cap_local] worker slice, operators run their plain
        # single-worker kernels, and exchange/unshard nodes are the only
        # cross-worker communication (all_to_all / all_gather over the mesh
        # axis) — the reference's worker/exchange architecture as a single
        # SPMD program (shard.rs:35-101, exchange.rs:586).
        from dbsp_tpu.parallel.mesh import shard_map
        from jax.sharding import PartitionSpec as P

        from dbsp_tpu.parallel.mesh import WORKER_AXIS

        W = P(WORKER_AXIS)

        def step_fn(states, tick, feeds, cold):
            # cold is always empty under a mesh (residency is single-
            # worker only — see set_residency); the arg keeps the call
            # signature uniform across both builders
            def body(states_l, tick_l, feeds_l):
                squeeze = lambda t: jax.tree_util.tree_map(  # noqa: E731
                    lambda a: a[0], t)
                expand = lambda t: jax.tree_util.tree_map(  # noqa: E731
                    lambda a: a[None], t)
                new_states, outputs, req = self._run_nodes(
                    squeeze(states_l), tick_l, squeeze(feeds_l))
                return expand(new_states), expand(outputs), req[None]

            ns, outs, reqw = shard_map(
                body, mesh=self.mesh, in_specs=(W, P(), W),
                out_specs=(W, W, W))(states, tick, feeds)
            # tick+1 computed OUTSIDE the shard_map: tick is replicated, so
            # the cursor output needs no worker axis
            return ns, outs, jnp.max(reqw, axis=0), tick + 1

        return jax.jit(step_fn, donate_argnums=(0,))

    def _make_scan(self, n: int):
        """A jitted program running ``n`` ticks of the eval sequence inside
        one ``lax.scan`` — ONE dispatch (and one host round-trip, if the
        caller blocks) per n ticks: per-dispatch overhead amortizes over
        the chunk. Requirements reduce to a running max across
        iterations; outputs are the LAST tick's (carried, not stacked — no
        n-times memory blowup). gen_fn mode only (feeds are host values).

        Sharded circuits scan INSIDE the shard_map: the whole n-tick loop is
        one SPMD program whose collectives (exchange/gather/pmax) run per
        iteration — N ticks per dispatch at any worker count."""
        assert self._gen_fn is not None, "scan mode needs a gen_fn"

        def _scan_body(states, t0, cold=None, varying=False):
            outs_shape = jax.eval_shape(
                lambda s, t: self._run_nodes(s, t, {}, cold)[1], states, t0)
            init_outs = jax.tree_util.tree_map(
                lambda sh: jnp.zeros(sh.shape, sh.dtype), outs_shape)
            if varying:
                # inside shard_map the per-tick outputs are worker-varying;
                # the zero init must carry the same vma type or the scan
                # carry types mismatch
                from dbsp_tpu.parallel.mesh import WORKER_AXIS

                init_outs = jax.tree_util.tree_map(
                    lambda a: jax.lax.pcast(a, (WORKER_AXIS,), to="varying"),
                    init_outs)

            def body(carry, i):
                st, _ = carry
                ns, outs, req = self._run_nodes(st, t0 + i, {}, cold)
                # states absent from ns (stateless ticks) carry through
                merged = {**st, **ns}
                return (merged, outs), req

            (ns, outs), reqs = jax.lax.scan(
                body, (states, init_outs), jnp.arange(n, dtype=jnp.int64))
            req = (jnp.max(reqs, axis=0) if reqs.shape[1]
                   else jnp.zeros((0,), jnp.int64))
            # t0+n: the device-resident tick cursor for the next chunk
            return ns, outs, req, t0 + n

        if self.mesh is None:
            return jax.jit(_scan_body, donate_argnums=(0,))

        from dbsp_tpu.parallel.mesh import shard_map
        from jax.sharding import PartitionSpec as P

        from dbsp_tpu.parallel.mesh import WORKER_AXIS

        W = P(WORKER_AXIS)

        def scan_fn(states, t0, cold):
            def body(states_l, t0_l):
                squeeze = lambda t: jax.tree_util.tree_map(  # noqa: E731
                    lambda a: a[0], t)
                expand = lambda t: jax.tree_util.tree_map(  # noqa: E731
                    lambda a: a[None], t)
                ns, outs, req, _ = _scan_body(squeeze(states_l), t0_l,
                                              varying=True)
                return expand(ns), expand(outs), req[None]

            ns, outs, reqw = shard_map(
                body, mesh=self.mesh, in_specs=(W, P()),
                out_specs=(W, W, W))(states, t0)
            # cursor computed outside the shard_map (t0 is replicated)
            return ns, outs, jnp.max(reqw, axis=0), t0 + n

        return jax.jit(scan_fn, donate_argnums=(0,))

    def step_scanned(self, t0: int, n: int, block: bool = False) -> None:
        """Run ticks [t0, t0+n) as one scanned dispatch (see _make_scan).
        Programs are cached per chunk length n."""
        cache = getattr(self, "_scan_jits", None)
        if cache is None:
            cache = self._scan_jits = {}
        fn = cache.get(n)
        if fn is None:
            fn = cache[n] = self._make_scan(n)
        t_start = time.perf_counter_ns()
        hot, cold = self._split_states()
        with self._guard():
            states, outputs, req, tick_next = fn(
                hot, self._tick_operand(t0), cold)
        self._tick_dev, self._tick_host = tick_next, t0 + n
        self.states = self._rejoin_states(states, cold)
        self.last_outputs = outputs
        self._req = req if self._req is None else self._max_jit(self._req, req)
        if block:
            self.block()
        self._append_sample(time.perf_counter_ns() - t_start)

    # -- stepping ------------------------------------------------------------
    def _tick_operand(self, tick: int):
        """The device-resident tick scalar for ``tick``. Steady state: the
        previous dispatch already returned it (tick+1 / t0+n is a program
        output) — zero transfers. Discontinuities (first tick, restore,
        overflow replay, manual jumps) upload EXPLICITLY via device_put,
        which jax.transfer_guard("disallow") permits; what the guard
        convicts is the implicit per-tick jnp.asarray(tick) this replaced."""
        if self._tick_dev is None or self._tick_host != tick:
            self._tick_dev = jax.device_put(np.int64(tick))
            self._tick_host = tick
        return self._tick_dev

    def _guard(self):
        """The transfer-guard context for the jitted step/scan call — a
        no-op unless testing/retrace.py's sentinel armed _steady_guard."""
        if self._steady_guard is None:
            return contextlib.nullcontext()
        return jax.transfer_guard(self._steady_guard)

    def _note_cause(self, cause: str) -> None:
        """Annotate the NEXT latency sample with a spike cause (maintain /
        snapshot / retrace) — consumed by :meth:`_append_sample`."""
        self._pending_causes.add(cause)

    def _append_sample(self, ns: int) -> None:
        idx = len(self.step_times_ns)
        self.step_times_ns.append(ns)
        if self._pending_causes:
            for c in sorted(self._pending_causes):
                self.tick_causes.append((idx, c))
            self._pending_causes.clear()

    def reset_timing(self) -> None:
        """Clear latency samples, cause annotations, host-overhead records,
        and maintain stats (harnesses call this between warmup and the
        measured run, so reported slices/rows describe the measured window,
        not warmup's presize-era cascades)."""
        self.step_times_ns.clear()
        self.tick_causes.clear()
        self._pending_causes.clear()
        for v in self.host_overhead_ns.values():
            v.clear()
        for k in self.maintain_stats:
            self.maintain_stats[k] = 0

    def _dispatch(self, tick: int, feeds: Optional[Dict] = None) -> None:
        """Dispatch one tick's program asynchronously (no timing, no sync)."""
        if self._step_jit is None:
            self._note_cause("retrace")  # first call compiles the program
            self._step_jit = self._make_step()
        f = self._feed_indices(feeds) if feeds else {}
        hot, cold = self._split_states()
        with self._guard():
            states, outputs, req, tick_next = self._step_jit(
                hot, self._tick_operand(tick), f, cold)
        self._tick_dev, self._tick_host = tick_next, tick + 1
        self.states = self._rejoin_states(states, cold)
        self.last_outputs = outputs
        self._req = req if self._req is None else self._max_jit(self._req, req)

    def step(self, tick: int = 0, feeds: Optional[Dict] = None,
             block: bool = False) -> None:
        """Dispatch one tick. No host sync unless ``block``; call
        :meth:`validate` (one sync) before trusting outputs/state."""
        t0 = time.perf_counter_ns()
        self._dispatch(tick, feeds)
        if block:
            self.block()
        self._append_sample(time.perf_counter_ns() - t0)

    def _run_pipelined(self, t0: int, upto: int) -> None:
        """Run ticks [t0, upto) with a depth-1 pipeline: dispatch tick t,
        then wait for tick t-1 — host-side work (feed indexing, pytree
        flattening, dispatch) of one tick overlaps device compute of the
        previous one, replacing the old block-per-tick protocol that
        serialized host and device. One latency sample per iteration
        (dispatch of t + completion wait of t-1): on a backend where the
        donating step call is effectively synchronous (measured on this
        CPU PJRT client: a donated dispatch blocks until its input
        buffers' producer finishes) the sample IS tick t's latency; on a
        truly async backend it is tick t-1's, shifted by one. The
        interval's LAST tick completes inside the caller's validate()
        fetch — the designated sync point — and its wall time lands in
        ``host_overhead_ns["validate"]``."""
        prev = None
        t_prev = time.perf_counter_ns()
        for tt in range(t0, upto):
            self._dispatch(tt)
            # completion marker for THIS tick: the requirement running-max
            # (outputs when the circuit has no capacity checks) — outputs
            # and req are program results, never donated, so a held marker
            # stays valid across the next dispatch
            marker = self._req if self._req is not None else self.last_outputs
            if prev is not None:
                jax.block_until_ready(prev)  # hotpath: ok pipeline barrier on tick t-1
            now = time.perf_counter_ns()
            self._append_sample(now - t_prev)
            t_prev = now
            prev = marker

    def block(self) -> None:
        """Wait for dispatched work (cheap sync, no data transfer)."""
        jax.block_until_ready(self.states)

    # -- validation / growth -------------------------------------------------
    def validate(self, spans=None) -> None:
        """ONE device->host fetch: check every capacity requirement recorded
        since the last validation. Raises :class:`CompiledOverflow`.
        ``spans`` (obs.SpanRecorder) gets the fetch — the wait for the step
        program plus the transfer — as ``tick.device_wait``."""
        if self._req is None or not self._checks:
            return
        with (spans.span("tick.device_wait", "tick") if spans is not None
              else contextlib.nullcontext()):
            req = np.asarray(jax.device_get(self._req))
        items = []
        for (cn, key), r in zip(self._checks, req):
            cn.note_requirement(key, int(r))
            if int(r) > cn.caps[key]:
                items.append((cn, key, int(r)))
        # validated requirement levels (for presize)
        self.last_req = req[:len(self._checks)]
        self._req = jnp.zeros_like(self._req)
        if items:
            raise CompiledOverflow(items)
        checked = self._checked_caps(self.last_req.tolist())
        # what the nodes observed, a node at a time: only of an interval
        # that stands (an overflowed one is replayed and validated again)
        seen: Dict[CNode, Dict[str, int]] = {}
        for (cn, key), v in zip(self._observed, req[len(self._checks):]):
            seen.setdefault(cn, {})[key] = int(v)
        for cn, values in seen.items():
            cn.note_observations(values)
        self._record_tick(checked)
        if any([cn.settle() for cn in self.cnodes]):
            self._step_jit = None  # provisional capacities were set
            self._scan_jits = {}

    def _checked_caps(self, reqs: List[int]) -> List[Tuple]:
        """``(node, scope, key, class, required, capacity)`` of every sized
        check (``class``: ``state`` where the capacity sizes state carried
        across ticks, ``CNode.sizes_state``, else ``tick``), as the
        interval that stood was validated against it."""
        return [(*name, r, cn.caps[key]) for (cn, key), name, r
                in zip(self._checks, self._check_names, reqs)
                if cn.caps[key]]

    def _req_value(self, cn: CNode, key: str) -> Optional[int]:
        """The last validated requirement for (cn, key), if any."""
        if getattr(self, "last_req", None) is None:
            return None
        for (c, k), r in zip(self._checks, self.last_req):
            if c is cn and k == key:
                return int(r)
        return None

    def maintain(self, budget_rows: Optional[int] = None) -> bool:
        """Host-side spine maintenance: drain half-full trace levels into
        the next level, between validated intervals (the compiled-mode
        analog of the reference's background spine merger,
        spine_fueled.rs:1-81 — there fuel amortizes merges across steps;
        here the step program never touches levels past 0 at all, and this
        method runs the actual merges outside the hot program, one native
        two-pointer merge each).

        State stays VALID throughout (rows only move between levels whose
        union is the trace), so no replay is needed — but a receiving
        level's capacity may grow, which invalidates the compiled programs
        (next step re-traces). Returns True when that happened.

        Drain policy (the LSM discipline): a level is due when half-full;
        draining into a receiver that would itself become due cascades the
        receiver onward FIRST, so chains terminate at the tail — the only
        level whose capacity this method normally grows. Growing middle
        levels instead would quietly absorb every cascade: the tail would
        never compact and the middle of the ladder would balloon toward
        the tail's size.

        ``budget_rows`` (default: module :data:`MAINTAIN_BUDGET_ROWS`, env
        ``DBSP_TPU_MAINTAIN_BUDGET_ROWS``; None/<=0 = unbounded) bounds the
        rows MOVED between levels per call — the fuel. A level whose live
        rows exceed the remaining budget drains a prefix slice
        (:func:`_drain_slice`, the resumable cursor) and the rest stays
        due, resuming on the next call, so a full cascade amortizes over
        several intervals instead of landing in one tick. Deferral is
        always safe: the trace is the union of its levels at every point,
        so consumers see identical content (proven bit-identical by
        tests/test_maintenance.py); only compaction, not correctness, is
        deferred. Two carve-outs keep deferral from regressing into worse
        failure modes: level 0's drain is budget-EXEMPT (deferring it
        risks an overflow replay + retrace, and its slice is bounded by
        l0's capacity — one interval's inflow), and a budgeted drain whose
        receiver lacks room FILLS the receiver to its existing capacity
        instead of growing it (a mid-run middle-level grow would retrace
        the step program)."""
        from dbsp_tpu.circuit.runtime import Runtime

        if budget_rows is None:
            budget_rows = MAINTAIN_BUDGET_ROWS
        left = budget_rows if budget_rows and budget_rows > 0 else None
        stats = self.maintain_stats
        stats["calls"] += 1
        rows_before = stats["rows_moved"]
        self.maintain_pending = False
        self._interval += 1  # the residency LRU clock ticks per maintain
        changed = False
        prev_rt = Runtime._swap(self.runtime) if self.mesh is not None \
            else None
        try:
            for cn in self.cnodes:
                if not isinstance(cn, cnodes._Leveled):
                    continue
                key = str(cn.node.index)
                st = self.states.get(key)
                if st is None:
                    continue
                levels, base = st
                K = len(levels)
                if K == 1:
                    continue
                levels = list(levels)
                tiers = list(self._tiers.get(key)
                             or [res.TIER_DEVICE] * K)
                # Host-cached live counts: fetching them from the device
                # would dispatch one eager O(cap) reduction per level per
                # trace per interval (measured as a double-digit share of
                # steady-state time at q4 scale). Level 0 is the only
                # level the step program writes, and its validated
                # REQUIREMENT is exactly its live count at validation —
                # already fetched. Deeper levels only change in this
                # method, which maintains the cache (drain sums are upper
                # bounds — netting may shrink the real count; an over-
                # estimate only triggers an early drain, never an error).
                cache = getattr(cn, "_live_cache", None)
                observed = getattr(cn, "observed_lives", None)
                counted = observed is not None and len(observed) == K
                if counted:
                    # a windowed view's trace: the step program counted
                    # every level (exact where the sums below only grow),
                    # once per validation
                    cache, cn.observed_lives = list(observed), None
                elif cache is None or len(cache) != K or \
                        getattr(cn, "_gc_refresh", False):
                    cache = [int(b.max_worker_live()) for b in levels]
                lives = cache
                req = self._req_value(cn, cn.level_keys[0])
                due0 = lives[0]
                if req is not None:
                    due0 = req
                    if counted:
                        pass  # level 0's rows were counted with the rest
                    elif getattr(cn, "_slot_cap", None):
                        # SLOTTED l0: the l0 requirement is slot CAPACITY
                        # consumed, not rows — using it as a row count
                        # would inflate every downstream lives[] (sparse
                        # deltas occupy whole slots) and burn the drain
                        # budget on phantom rows. The ROW count comes from
                        # the TAIL requirement (base + l0 live rows) minus
                        # the known deep lives; capacity still drives the
                        # drain-due check (full slots must fold even when
                        # sparsely filled).
                        tail_req = self._req_value(cn, cn.TAIL_KEY)
                        if tail_req is not None:
                            lives[0] = max(0, tail_req - sum(lives[1:]))
                        else:
                            lives[0] = req
                    else:
                        lives[0] = req
                # dispatch-free fast path: with cached lives the drain-due
                # check is host arithmetic — most intervals touch nothing
                # (l0's due check uses its consumed CAPACITY, see above)
                dues = [due0] + lives[1:]
                if not any(dues[k] and dues[k] * 2 >= levels[k].cap
                           for k in range(K - 1)):
                    cn._live_cache = lives
                    continue
                vers = self._level_versions.setdefault(key, [0] * K)

                def drain(k, exempt=False):
                    nonlocal changed, left
                    # l0 is budget-exempt: deferring IT is not a deferred
                    # compaction but an overflow REPLAY + step-program
                    # retrace (measured: a 17s p99 tick), and its slice is
                    # bounded by l0's capacity — one interval's inflow
                    budgeted = left is not None and not exempt and k > 0
                    if not budgeted and left is None and k + 1 < K - 1 and \
                            (lives[k] + lives[k + 1]) * 2 > levels[k + 1].cap:
                        # unbounded mode: make room downstream first (the
                        # budgeted path instead fills receivers to capacity
                        # and lets the shallow-first sweep drain them)
                        drain(k + 1)
                    n = min(lives[k], left) if budgeted else lives[k]
                    if n <= 0:
                        self.maintain_pending = True  # fuel ran out
                        return
                    # a drain WRITES both sides: cold operands promote to
                    # device first (disk reads verified — the compiled
                    # engine's corruption-detection point); the budget
                    # re-demotes after the sweep. A structure-only change
                    # — the jitted step re-traces per input structure, so
                    # no program invalidation is needed here.
                    if tiers[k] != res.TIER_DEVICE or \
                            tiers[k + 1] != res.TIER_DEVICE:
                        self._promote_level(cn, key, levels, tiers, k,
                                            "maintain")
                        self._promote_level(cn, key, levels, tiers, k + 1,
                                            "maintain")
                    rk1 = cn.level_keys[k + 1]
                    need = lives[k + 1] + n
                    if need > cn.caps[rk1]:
                        if k + 1 == K - 1:
                            # tail growth: unavoidable — the tail holds the
                            # whole trace (presize projects it to end-of-run
                            # size precisely to keep this out of the run)
                            cn.caps[rk1] = bucket_cap(need)
                            changed = True
                        elif left is None:
                            # unbounded mode: legacy headroom growth (an
                            # inverted ladder after l0 outgrew a middle
                            # level) — receivers absorb further drains
                            cn.caps[rk1] = bucket_cap(need * 2)
                            changed = True
                        else:
                            # budgeted: growing a middle level invalidates
                            # the step program (measured: a ~10-20s q4
                            # recompile landing in ONE tick). Fill the
                            # receiver to its existing capacity instead —
                            # the shallow-first sweep (or the next call)
                            # drains it onward; the remainder stays here.
                            n = cn.caps[rk1] - lives[k + 1]
                            if k == 0 and n < lives[k]:
                                # last resort: l0 MUST drain FULLY — a
                                # residue plus the next interval's inflow
                                # overflows l0 (replay + retrace). Force
                                # room below regardless of budget (rare;
                                # beats the overflow replay it prevents).
                                stats["exempt_drains"] += 1
                                drain(k + 1, exempt=True)
                                n = cn.caps[rk1] - lives[k + 1]
                            if n <= 0:
                                self.maintain_pending = True
                                return
                            n = min(n, lives[k])
                            need = lives[k + 1] + n
                    if k == 0 and getattr(cn, "_slot_cap", None):
                        # slotted l0: fold the per-slot sorted runs into
                        # one consolidated batch (rank-merge regime) so
                        # the drain merge sees its sorted-input contract;
                        # the step program's l0 aux stays untagged, so
                        # re-tag the emptied level after the drain
                        slot = cn._slot_cap
                        levels[0] = levels[0].tagged(
                            (slot,) * (levels[0].cap // slot)).consolidate()
                    if n >= lives[k]:
                        levels[k + 1], levels[k] = _drain_pair(
                            levels[k + 1], levels[k], cn.caps[rk1])
                        if k == 0:
                            # the step program's l0 aux is always None
                            levels[0] = levels[0].tagged(None)
                        stats["drains"] += 1
                    else:
                        levels[k + 1], levels[k] = _drain_slice(
                            levels[k + 1], levels[k],
                            jnp.asarray(n, jnp.int32), cn.caps[rk1])
                        if k == 0:
                            levels[0] = levels[0].tagged(None)
                        stats["partial_drains"] += 1
                        self.maintain_pending = True  # remainder stays due
                    vers[k] += 1
                    vers[k + 1] += 1
                    self._lru[(key, k)] = self._interval
                    self._lru[(key, k + 1)] = self._interval
                    lives[k + 1] += n  # upper bound (netting may shrink)
                    lives[k] -= n
                    stats["rows_moved"] += n
                    stats["max_slice_rows"] = max(stats["max_slice_rows"], n)
                    if budgeted:
                        stats["max_budgeted_slice_rows"] = max(
                            stats["max_budgeted_slice_rows"], n)
                        left -= n

                # Order: unbounded keeps the legacy deep-first cascade
                # (receivers make room before their feeders). Budgeted
                # runs SHALLOW-first — fill-to-cap makes draining into a
                # full receiver safe, and the sweep reaches that receiver
                # next, so the inflow path (l0 -> l1) can never starve
                # behind a multi-interval tail compaction; the deep,
                # state-sized drains get whatever fuel remains and defer
                # across calls.
                order = range(K - 1) if left is not None \
                    else range(K - 2, -1, -1)
                for k in order:
                    due = dues[0] if k == 0 else lives[k]
                    if due and due * 2 >= levels[k].cap:
                        if k > 0 and left is not None and left <= 0:
                            self.maintain_pending = True
                            continue  # deep compaction defers; l0 may not
                        drain(k)
                cn._live_cache = lives
                if any(t != res.TIER_DEVICE for t in tiers):
                    self._tiers[key] = tiers
                else:
                    self._tiers.pop(key, None)
                cn.residency_tiers = tuple(tiers)
                base_val = sum(lives[1:])
                self.states[key] = (tuple(levels),
                                    jnp.full_like(base, base_val))
        finally:
            if self.mesh is not None:
                Runtime._swap(prev_rt)
        # budget enforcement between intervals: demote what the drains
        # re-heated (and anything newly over budget), promote re-hot
        # levels under headroom — every transition logged with its cause
        changed |= self._enforce_residency(cause="budget")
        if stats["rows_moved"] > rows_before:
            self._note_cause("maintain")
        if changed:
            self._note_cause("retrace")
            self._step_jit = None
            self._scan_jits = {}
        return changed

    def time_facts(self) -> Dict[str, int]:
        """What this circuit's time nodes observed in the last validated
        interval (``timeseries/counters.py`` has them per node): the args
        of the driver's ``tick.validate`` span and, with the traces' rows,
        a record of ``VALIDATED_TICKS``. Empty without time nodes."""
        nodes = {cn for cn, _ in self._observed}
        wins = [cn for cn in nodes if isinstance(cn, cnodes.CWindow)]
        marks = [cn.watermark_ms for cn in nodes
                 if isinstance(cn, cnodes.CWatermark)]
        if not wins and not marks:
            return {}
        return {"retired_rows": sum(cn.slid_last["out"] for cn in wins),
                "slid_in_rows": sum(cn.slid_last["in"] for cn in wins),
                "watermark_ms": max(marks, default=0)}

    def topk_facts(self) -> Dict[str, int]:
        """What this circuit's top-K nodes observed in the last validated
        interval, summed over them (``timeseries/counters.py`` has them
        per node): groups touched, rows re-read from their input traces
        against the gathers' capacity, rows inserted and retracted. Empty
        without a ``CTopK``."""
        tops = [cn for cn in self.cnodes
                if isinstance(cn, cnodes.CTopK) and cn.observed]
        if not tops:
            return {}
        return {
            "topk_groups": sum(cn.observed["groups"] for cn in tops),
            "topk_gathered_rows": sum(cn.observed["gathered"]
                                      for cn in tops),
            "topk_gather_capacity_rows": sum(cn.caps["gather"]
                                             for cn in tops),
            "topk_inserted_rows": sum(cn.observed["inserted"]
                                      for cn in tops),
            "topk_retracted_rows": sum(cn.observed["retracted"]
                                       for cn in tops)}

    def _record_tick(self, caps: List[Tuple]) -> None:
        """One record of ``VALIDATED_TICKS`` per interval that stood (none
        for an interval that overflowed: its replay validates and records),
        for every circuit: each checked capacity against what the interval
        required of it (``caps``, from :meth:`_checked_caps`), and the facts
        of the time and top-K nodes it has. Host bookkeeping over the
        requirement vector validation fetched."""
        from dbsp_tpu.timeseries import counters

        record = self.time_facts()
        if record:
            traces = [cn for cn in self.cnodes
                      if getattr(cn, "_counts_lives", False)]
            gcd = [counters.TRACE_GC_ROWS.get(cn.node.index, {})
                   for cn in traces if getattr(cn, "_gc_refresh", False)]
            record.update({
                "gc_live_rows": sum(g.get("live", 0) for g in gcd),
                "gc_capacity_rows": sum(g.get("capacity", 0) for g in gcd),
                "gc_truncated_rows": sum(g.get("truncated", 0)
                                         for g in gcd),
                "trace_live_rows": sum(cn.live_rows for cn in traces)})
        record.update(self.topk_facts())
        tick = [c for c in caps if c[3] == "tick"]
        record.update(
            capacities=[c[1:] for c in caps],
            tick_live_rows=sum(c[4] for c in tick),
            tick_capacity_rows=sum(c[5] for c in tick))
        counters.VALIDATED_TICKS.append(record)
        counters.note_capacities(caps)

    def _windows_filling(self) -> bool:
        """True while a window of this circuit's windowed view has yet to
        slide a row out: the view is still filling."""
        wins = [cn for cn in self.cnodes if isinstance(cn, cnodes.CWindow)
                and cn.node.index in self._windowed]
        return any(cn.slid_total["out"] == 0 for cn in wins)

    def _enforce_ladders(self) -> bool:
        """Re-establish geometric level capacities between l0 and the tail.

        Requirement-driven growth sizes l0 (per-interval inflow) and the
        tail (whole-trace projection) but says nothing about the middle
        levels; without this they collapse toward l0's size and every
        drain cascades straight into the tail (observed: an all-32768
        ladder under a 1M tail merging the tail every ~4 ticks)."""
        changed = False
        for cn in self.cnodes:
            if not isinstance(cn, cnodes._Leveled):
                continue
            keys = cn.level_keys
            if len(keys) < 3:
                continue
            lo, hi = cn.caps[keys[0]], cn.caps[keys[-1]]
            if hi <= lo:
                continue
            g = (hi / lo) ** (1.0 / (len(keys) - 1))
            for k in range(1, len(keys) - 1):
                target = bucket_cap(int(lo * g ** k))
                if target > cn.caps[keys[k]]:
                    cn.caps[keys[k]] = target
                    changed = True
        return changed

    def presize(self, ratio: float, safety: float = 1.3,
                interval: int = 1) -> None:
        """Scale capacities for a run ~``ratio``x longer than what produced
        the last validated requirements: monotone capacities (traces, group
        gathers — they integrate the stream) are projected linearly; stable
        ones (join fan-outs — per-delta) just get doubled headroom. One
        re-trace now instead of a grow/replay ladder mid-measurement.

        ``interval`` is the validation cadence of the RUN being presized
        for: a leveled trace's level 0 only drains at validation points
        (maintain), so it must hold ``interval`` ticks of inflow — warmup
        validates every tick, making its observed l0 requirement a
        per-tick figure that would otherwise overflow (and grow/replay)
        on the first measured interval."""
        if getattr(self, "last_req", None) is None:
            return
        changed = False
        for (cn, key), r in zip(self._checks, self.last_req):
            r = int(r)
            if r <= 0:
                continue
            is_l0 = isinstance(cn, cnodes._Leveled) and \
                len(cn.level_keys) > 1 and key == cn.level_keys[0]
            if is_l0:
                target = int(r * max(1, interval) * safety)
            elif key in cn.MONOTONE_CAPS:
                target = int(r * ratio * safety)
            else:
                target = 2 * r
            if bucket_cap(target) > cn.caps[key]:
                cn.caps[key] = bucket_cap(target)
                changed = True
        changed |= self._enforce_ladders()
        if changed:
            snap = self.snapshot()
            self._step_jit = None
            self._scan_jits = {}
            self._req = None
            self.restore(snap)  # re-pad states to the new capacities
        self.prewarm_maintenance()

    def prewarm_maintenance(self) -> None:
        """Compile the maintenance drain kernels for the CURRENT ladder
        shapes, on warmup's clock instead of the measured run's.

        Each (receiver cap, source cap, out cap, schema) combination of
        :func:`_drain_pair` / :func:`_drain_slice` compiles on first use;
        left to happen lazily, those compiles land inside the measured
        window the first time each level pair drains (measured: ~5s of
        q4's mini-run maintain overhead was drain-kernel compiles, dwarfing
        the drains themselves). Presize fixes the ladder for the planned
        run, so every pair can be compiled here by running one throwaway
        drain over COPIES of the live levels (donation consumes the
        copies, never the state; results are discarded)."""
        from dbsp_tpu.circuit.runtime import Runtime

        prev_rt = Runtime._swap(self.runtime) if self.mesh is not None \
            else None
        try:
            for cn in self.cnodes:
                if not isinstance(cn, cnodes._Leveled):
                    continue
                st = self.states.get(str(cn.node.index))
                if st is None or len(st[0]) < 2:
                    continue
                levels = st[0]
                for k in range(len(levels) - 1):
                    recv, src = levels[k + 1], levels[k]
                    if isinstance(recv.weights, np.ndarray) or \
                            isinstance(src.weights, np.ndarray):
                        # cold (demoted) pair: a real drain promotes it
                        # first — prewarming here would transfer the whole
                        # level just to warm a kernel cache
                        continue
                    cap = cn.caps[cn.level_keys[k + 1]]
                    if recv.cap != cap:
                        continue  # growth pending; shapes would not match
                    if k == 0 and getattr(cn, "_slot_cap", None):
                        # slotted l0 drains consolidate the slot runs
                        # first — warm that fold program (and the drain
                        # over its tagged result) too
                        slot = cn._slot_cap
                        src = _copy_tree(src).tagged(
                            (slot,) * (src.cap // slot)).consolidate()
                    _drain_pair(_copy_tree(recv), _copy_tree(src), cap)
                    if MAINTAIN_BUDGET_ROWS:
                        _drain_slice(_copy_tree(recv), _copy_tree(src),
                                     jnp.asarray(0, jnp.int32), cap)
        finally:
            if self.mesh is not None:
                Runtime._swap(prev_rt)

    def grow(self, overflow: CompiledOverflow, headroom: int = 2,
             project_ratio: float = 1.0) -> None:
        """Grow the overflowed capacities (with headroom, so a growing state
        doesn't re-overflow next interval) and force a re-trace.

        ``project_ratio`` > 1 folds the presize projection into the grow:
        monotone capacities (traces — they integrate the stream) jump
        straight to their projected end-of-run size. On an
        accelerator each re-trace costs a full program compile (minutes),
        so one projected grow beats a doubling ladder by several compiles.

        A third kind of capacity, beside the monotone and the per-delta:
        in a WINDOWED VIEW (``__init__``) every capacity ramps while the
        window fills — the deltas carry ever more retractions, the groups
        ever more rows — and then stays. Many read 0 in the first tick, so
        no presize can size them, and they ramp together. While a window
        of the view has yet to slide a row out, an overflowed capacity of
        the view gets :data:`RAMP_ROOM` times its requirement, and those
        past half their capacity are raised in the same re-trace. (A
        requirement read downstream of a capacity that overflowed is of a
        truncated delta and reads low: room is what keeps the replay from
        uncovering the next overflow.)

        State since the last validated snapshot is invalid — callers MUST
        follow with :meth:`restore` of a validated snapshot (which re-pads
        it to the new capacities)."""
        exchange_hit = False
        ramping = self._windows_filling()
        for cn, key, required in overflow.items:
            # exchange-bucket overflow: a skewed tick routed more rows to a
            # worker than the static per-worker capacity — the replay that
            # follows is the data-loss save; count it (obs + bench export).
            # Per-KIND detection counts each overflowed site; the handle's
            # exchange_overflows counts REPLAYS (once per grow, matching
            # overflow_replays' unit even when one interval overflows
            # several exchange buckets).
            if isinstance(cn, cnodes.CExchange) or \
                    (isinstance(cn, cnodes.CInput) and key == "input"):
                from dbsp_tpu.parallel.exchange import count_exchange_overflow

                count_exchange_overflow(
                    "exchange" if isinstance(cn, cnodes.CExchange)
                    else "input")
                exchange_hit = True
            factor = max(headroom, project_ratio * 1.3) \
                if key in cn.MONOTONE_CAPS else headroom
            floor = cn.caps[key]
            if ramping and cn.node.index in self._windowed:
                factor = RAMP_ROOM
                if isinstance(cn, cnodes.CJoin):
                    # a join's sides ramp together, and the reading of one
                    # that overflows downstream of another overflow is of
                    # a truncated delta: no less than its sibling
                    floor = max(cn.caps["left"], cn.caps["right"])
            # max: a capacity key can overflow at several sites in one
            # interval (e.g. one requirement per trace level) — never let a
            # later, smaller item shrink the grown cap
            cn.caps[key] = max(floor, bucket_cap(int(required * factor)))
        if ramping:
            # the neighbours that ramp with them (a requirement past half
            # its capacity, in a view still filling, is on its way past
            # it): raised in this re-trace, not each in one of its own
            for (cn, key), r in zip(self._checks, self.last_req):
                if cn.node.index in self._windowed and \
                        2 * int(r) > cn.caps[key]:
                    cn.caps[key] = bucket_cap(int(r) * 2 * headroom)
        if exchange_hit:
            self.exchange_overflows += 1
        self._enforce_ladders()
        self._step_jit = None
        self._scan_jits = {}
        self._req = None

    def _snap_cacheable(self, key: str):
        """The leveled cnode for ``key`` if its deep levels are
        copy-skippable (untouched between maintain calls), else None.
        Window-GC'd traces are excluded: the step program truncates EVERY
        level in-program each tick, so their deep levels are never clean."""
        cn = self.by_index.get(int(key))
        if isinstance(cn, cnodes._Leveled) and \
                not getattr(cn, "_gc_refresh", False):
            st = self.states.get(key)
            if isinstance(st, tuple) and len(st) == 2 and \
                    isinstance(st[0], tuple) and len(st[0]) > 1:
                return cn
        return None

    def snapshot(self) -> Dict[str, Any]:
        """A restorable DEEP copy of the current (validated) states.

        Step programs donate their state buffers (input->output aliasing
        is what keeps untouched trace levels copy-free per tick), so a
        reference snapshot would be invalidated by the very next step —
        the copy here is the price of in-place stepping, paid per
        snapshot interval instead of per tick.

        INCREMENTAL: the step program only ever writes level 0 of a
        leveled trace — deeper levels change solely in :meth:`maintain`
        (version-counted there). A deep level whose version matches the
        cached copy from a previous snapshot reuses that copy instead of
        being copied again, so steady-state snapshot cost is O(level 0 +
        small states), not O(whole trace). Cached copies are plain result
        buffers (never donated anywhere — :meth:`restore` copies before
        use), so sharing them across snapshots is safe."""
        to_copy: Dict[str, Any] = {}
        reuse: Dict[str, Dict[int, Batch]] = {}
        # levels copied because a trace under a GC bound is never clean
        # (the driver's ``tick.snapshot`` span carries it)
        self.snapshot_gc_levels = 0
        for key, st in self.states.items():
            cn = self._snap_cacheable(key)
            if cn is None:
                to_copy[key] = st
                if getattr(self.by_index.get(int(key)), "_gc_refresh",
                           False):
                    self.snapshot_gc_levels += len(st[0])
                continue
            levels, b = st
            vers = self._level_versions.setdefault(key, [0] * len(levels))
            cache = self._snap_levels.get(key) or [None] * len(levels)
            kept: Dict[int, Batch] = {}
            fresh: Dict[int, Batch] = {}
            for i, lvl in enumerate(levels):
                if i > 0 and isinstance(lvl.weights, np.ndarray):
                    # cold (host/disk) level: immutable host-side buffers
                    # the program never donates — share by reference
                    # instead of copying through the device
                    kept[i] = lvl
                    continue
                ent = cache[i] if i > 0 else None
                if ent is not None and ent[0] == vers[i]:
                    kept[i] = ent[1]
                else:
                    fresh[i] = lvl
            to_copy[key] = (fresh, b)
            reuse[key] = kept
        copied = _copy_tree(to_copy)  # ONE dispatch for every fresh leaf
        snap: Dict[str, Any] = {}
        for key, st in self.states.items():
            if key not in reuse:
                snap[key] = copied[key]
                continue
            levels, _ = st
            fresh_c, base_c = copied[key]
            vers = self._level_versions[key]
            cache = self._snap_levels.setdefault(
                key, [None] * len(levels))
            merged = []
            for i in range(len(levels)):
                if i in reuse[key]:
                    merged.append(reuse[key][i])
                else:
                    merged.append(fresh_c[i])
                    if i > 0:
                        cache[i] = (vers[i], fresh_c[i])
            snap[key] = (tuple(merged), base_c)
        return snap

    def restore(self, snap: Dict[str, Any]) -> None:
        """Restore a snapshot (copying again — the snapshot must survive
        the restored states being donated), re-padding trace states to the
        current capacities (no-op when capacities haven't changed)."""
        from dbsp_tpu.circuit.runtime import Runtime

        # cold (numpy/memmap) levels in the snapshot are immutable host
        # buffers: reinsert them by reference instead of device-copying
        # them through _copy_tree (which would re-materialize every
        # demoted level on device during an overflow replay)
        snap2: Dict[str, Any] = {}
        cold_ref: Dict[str, Dict[str, Batch]] = {}
        for key, st in snap.items():
            if isinstance(st, tuple) and len(st) == 2 and \
                    isinstance(st[0], tuple):
                levels, base = st
                holds = {str(i): l for i, l in enumerate(levels)
                         if isinstance(l.weights, np.ndarray)}
                if holds:
                    cold_ref[key] = holds
                    snap2[key] = (tuple(l for i, l in enumerate(levels)
                                        if str(i) not in holds), base)
                    continue
            snap2[key] = st
        states = _copy_tree(snap2)
        for key, holds in cold_ref.items():
            hot, base = states[key]
            states[key] = (self._interleave(hot, holds), base)
        # the restored buffers are new objects at possibly new capacities;
        # drop the deep-level copy cache and advance every version so a
        # later snapshot never pairs a stale copy with the rewound state
        self._snap_levels.clear()
        for vers in self._level_versions.values():
            for i in range(len(vers)):
                vers[i] += 1
        # repad may consolidate a slotted l0 (slot geometry can change with
        # the grown capacities) — on sharded states that is an SPMD program
        # needing this handle's runtime
        prev_rt = Runtime._swap(self.runtime) if self.mesh is not None \
            else None
        try:
            for cn in self.cnodes:
                key = str(cn.node.index)
                if key in states:
                    states[key] = cn.repad_state(states[key])
                # cached live counts may UNDER-estimate the rewound state
                # (drains moved rows since the snapshot) — maintain() must
                # refetch exact counts or its drain could slice live rows
                cn._live_cache = None
        finally:
            if self.mesh is not None:
                Runtime._swap(prev_rt)
        self.states = states
        # re-padding after a grow may have materialized cold levels on
        # device (with_cap is a jnp op): reconcile the tier map with the
        # actual leaf types AND the blob bookkeeping with the actual
        # batch objects, then re-demote anything over budget
        self._sync_tiers(cause="restore")
        self._reconcile_cold_meta()
        self._enforce_residency(cause="restore")

    # -- checkpointed run -----------------------------------------------------
    def run_ticks(self, t0: int, n: int, validate_every: int = 16,
                  on_validated: Optional[Callable] = None,
                  block_each: bool = False, scan: bool = False,
                  project_ratio: float = 1.0,
                  snapshot_every: int = 1,
                  maintain_budget_rows: Optional[int] = None) -> None:
        """Run ticks [t0, t0+n) under a ``gen_fn`` with periodic validation
        and snapshot/replay on overflow (exact: inputs are functions of the
        tick index). ``on_validated(next_tick)`` fires after each validated
        interval with EXACTLY-ONCE delivery per reported tick: a high-water
        mark suppresses re-fires while an overflow replay re-runs intervals
        since the last snapshot (``snapshot_every > 1``), so accumulating
        callbacks (throughput counters) stay correct across replays.

        ``block_each`` runs each interval PIPELINED at depth 1 (see
        :meth:`_run_pipelined`): tick t+1's host work overlaps tick t's
        device compute, and ``step_times_ns`` records the wall time between
        consecutive tick completions — a real per-tick latency distribution
        without the old sync-per-tick serialization. Without it, ticks
        dispatch fully async and the only syncs are the validation
        fetches at interval boundaries.

        ``scan=True`` runs each validation interval as ONE scanned dispatch
        (see :meth:`step_scanned`) — per-tick latency is then the chunk time
        / chunk length. ``project_ratio`` is handed to :meth:`grow` so an
        overflow mid-run jumps monotone capacities to end-of-run size.
        ``maintain_budget_rows`` bounds each interval's maintenance slice
        (see :meth:`maintain`); between-tick host phases are timed into
        ``host_overhead_ns`` and annotated onto the next latency sample."""
        assert self._gen_fn is not None, "run_ticks needs a gen_fn"
        overhead = self.host_overhead_ns
        h0 = time.perf_counter_ns()
        snap, snap_t = self.snapshot(), t0
        overhead["snapshot"].append(time.perf_counter_ns() - h0)
        t = t0
        iv = 0
        reported = t0  # high-water tick already delivered to on_validated
        while t < t0 + n:
            upto = min(t + validate_every, t0 + n)
            if scan:
                self.step_scanned(t, upto - t, block=block_each)
            elif block_each:
                self._run_pipelined(t, upto)
            else:
                for tt in range(t, upto):
                    self.step(tick=tt)
            h0 = time.perf_counter_ns()
            try:
                self.validate()
            except CompiledOverflow as e:
                overhead["validate"].append(time.perf_counter_ns() - h0)
                self.overflow_replays += 1
                if any(isinstance(cn, cnodes.CExchange) or
                       (isinstance(cn, cnodes.CInput) and k == "input")
                       for cn, k, _ in e.items):
                    # skew past a static per-worker bucket: the replay IS
                    # the no-data-loss path; attribute it distinctly so
                    # flight/incident evidence separates exchange growth
                    # from ordinary trace-capacity growth
                    self._note_cause("exchange_overflow")
                self.grow(e, project_ratio=project_ratio)
                self.restore(snap)
                self._note_cause("retrace")
                t = snap_t
                continue  # replay from the snapshot at the new capacities
            overhead["validate"].append(time.perf_counter_ns() - h0)
            h0 = time.perf_counter_ns()
            # state stays valid; may re-trace next step
            self.maintain(budget_rows=maintain_budget_rows)
            overhead["maintain"].append(time.perf_counter_ns() - h0)
            iv += 1
            t = upto
            if iv % max(1, snapshot_every) == 0:
                # snapshots copy level 0 + the small states (deep levels
                # reuse version-matched cached copies, see snapshot()) —
                # coarser cadence amortizes them further; the replay window
                # on a rare overflow widens accordingly, which determinism
                # makes exact either way
                h0 = time.perf_counter_ns()
                snap, snap_t = self.snapshot(), t
                overhead["snapshot"].append(time.perf_counter_ns() - h0)
                self._sweep_cold()  # old snapshot superseded: safe point
                self._note_cause("snapshot")
            if on_validated is not None and t > reported:
                # replayed intervals (t <= reported after an overflow
                # rewind) were already delivered — suppress the duplicate
                on_validated(t)
                reported = t

    # -- host views -----------------------------------------------------------
    def canonicalize_sink(self, b):
        """Canonical form of a (possibly deferred) sink batch: the ONE
        deferred-to-sink consolidation policy shared by :meth:`output` and
        the serving driver's flush. No-op for non-batches and for batches
        already known-canonical (1 sorted run); sharded batches
        canonicalize per worker under this handle's runtime."""
        if not isinstance(b, Batch) or b.sorted_runs == 1:
            return b
        if b.sharded:
            from dbsp_tpu.circuit.runtime import Runtime

            prev = Runtime._swap(self.runtime)
            try:
                return b.consolidate()
            finally:
                Runtime._swap(prev)
        return b.consolidate()

    # -- operator attribution (EXPLAIN ANALYZE) -------------------------------
    def profile_ticks(self, n: int = 8, t0: int = 0,
                      feeds_list=None, spans=None,
                      registry=None) -> dict:
        """Measured per-node attribution: run ``n`` ticks with the step
        split into per-node jit segments (wall time + rows per node),
        assert the segmented run bit-identical to the fused program, and
        REWIND — production state and counters are untouched (see
        :mod:`dbsp_tpu.obs.opprofile` for the protocol and its caveats).
        ``t0`` is the tick index to profile from (matters under a
        ``gen_fn``: inputs are functions of the tick). Returns the shared
        ``/profile`` report (``opprofile.PROFILE_SCHEMA``)."""
        from dbsp_tpu.obs.opprofile import ProfileError, measured_profile

        if any(t != res.TIER_DEVICE for ts in self._tiers.values()
               for t in ts):
            raise ProfileError(
                "segmented profiling requires fully device-resident "
                "states: residency-demoted levels would be re-transferred "
                "per segment and the attribution would time the tiering, "
                "not the operators — raise DBSP_TPU_DEVICE_ROWS or "
                "profile an unbudgeted twin")

        return measured_profile(self, n=n, t0=t0, feeds_list=feeds_list,
                                spans=spans, registry=registry)

    def profile_static(self, feeds: Optional[Dict] = None) -> dict:
        """Compile-time attribution: per-node XLA cost analysis (flops /
        analytic bytes — the ROOFLINE §1 accounting applied per node)
        joined with graph metadata. No timing, no state mutation."""
        from dbsp_tpu.obs.opprofile import static_profile

        return static_profile(self, feeds=feeds)

    def output(self, handle_or_op) -> Optional[Batch]:
        """Latest output batch for an output handle (device; un-fetched).

        Deferred-to-sink canonicalization: when the placement pass removed
        a consolidation from the program, the sink batch arrives as a known
        multi-run or raw batch — canonicalize it HERE, lazily, on actual
        read (the hot loop never reads outputs, so the work only happens
        when a consumer exists). Already-canonical batches (1 sorted run)
        pass through untouched, so non-deferred pipelines see the identical
        object."""
        op = getattr(handle_or_op, "_op", handle_or_op)
        idx = self._op_to_index[id(op)]
        b = self.last_outputs.get(idx)
        canon = self.canonicalize_sink(b)
        if canon is not b:
            # cache the canonical batch so repeat reads of the same tick's
            # output (polling HTTP clients) don't re-consolidate
            self.last_outputs[idx] = canon
        return canon


def compile_circuit(handle, gen_fn: Optional[Callable] = None,
                    verified: bool = False) -> CompiledHandle:
    """Compile a host :class:`~dbsp_tpu.circuit.runtime.CircuitHandle`'s
    circuit. Existing operator state (spines warmed by host-path steps)
    migrates into the compiled states — warm up host-side, then compile.

    Multi-worker circuits (built with ``Runtime.init_circuit(N, ...)``)
    compile to a single SPMD program over the runtime's mesh; in that case a
    ``gen_fn`` runs per-worker inside the program and may use
    ``jax.lax.axis_index("workers")`` to generate its slice."""
    from dbsp_tpu.analysis import verify_circuit
    from dbsp_tpu.circuit.runtime import Runtime

    rt = getattr(handle, "runtime", None)
    # static analysis before tracing: an ERROR circuit (dangling feedback,
    # mismatched join keys, missing shard) would compile fine and produce
    # wrong answers; refusing here costs one graph walk. ``verified=True``
    # skips it for callers (the manager) that already ran verify_circuit —
    # avoids double-logging every WARN at deploy.
    if not verified:
        verify_circuit(handle.circuit,
                       workers=rt.workers if rt is not None else 1)
    prev = Runtime._swap(rt)
    try:
        ch = CompiledHandle(handle.circuit, gen_fn=gen_fn, runtime=rt)
    finally:
        Runtime._swap(prev)
    # retrace-sentinel construction hook (one flag check when disabled):
    # under DBSP_TPU_RETRACE_SENTINEL=1 / retrace.session() the handle's
    # program builders are ledgered and its transfer guard armed
    from dbsp_tpu.testing import retrace as _retrace_sentinel

    _retrace_sentinel.maybe_watch(ch)
    return ch
