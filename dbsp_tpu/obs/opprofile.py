"""Operator-level attribution for the COMPILED engine (EXPLAIN ANALYZE).

The host engine has had per-operator profiling since PR 1 (``profile.py``'s
``CPUProfiler`` over the scheduler-event stream — the reference's
``profile/cpu.rs`` shape); the compiled engine, the path every production
pipeline actually runs, was a black box: ONE fused XLA step program whose
tick latency we export but cannot decompose. ROOFLINE §3b attributes the
remaining kernel-side gap to "XLA step-program glue" *in aggregate*; this
module makes that attribution a per-node measurement.

Two attribution modes, one shared report schema (:data:`PROFILE_SCHEMA` —
the same rows the host profiler emits, so ``/profile`` answers one question
the same way on both engines):

* **static** — each compiled node's eval is lowered and compiled as its own
  XLA program and XLA's ``cost_analysis`` (flops / bytes accessed — the
  ROOFLINE §1 methodology) is read per node, joined with graph metadata
  (operator name, capacities, trace-ladder depth, sharding). No timing; one
  probe tick threads concrete operands through the segment chain without
  touching engine state (segments never donate).
* **measured** — :func:`measured_profile` runs N ticks with the step split
  into per-node jit segments, ``block_until_ready`` wall timing per
  segment, plus rows-in/out counters, then re-runs the SAME N ticks through
  the production fused program from the same snapshot and asserts the
  outputs and final states are bit-identical — the segmented numbers
  describe the real computation, not a divergent replica. The engine is
  rewound afterwards (snapshot/restore), so production ticks never pay for
  profiling; it runs on demand (``CompiledHandle.profile_ticks(n)``, the
  ``/profile?ticks=N`` route, ``bench.py --profile``,
  ``tools/roofline.py --per-node``) or by default when
  ``DBSP_TPU_PROFILE=segment`` is set.

Methodology caveats, stated once: segments do NOT donate their state
operands (the fused program does) and lost cross-operator fusion inflates
the absolute numbers — the report carries ``segmentation_overhead``
(segmented / fused ms per tick) so readers can see the distortion, and
relative attribution (which node dominates) is the quantity the mode
exists for. One distortion IS corrected exactly: a value a node returns
UNTOUCHED (a leveled trace's deep levels flowing through its state, a
trace view handing consumers the very level tracers it was given, a
sink echoing its input batch) is elided from the segment's program
outputs and substituted from the caller's own operands after the call —
identity, not approximation — so a node is charged for what it computes,
not for round-tripping state the fused program would alias in place
(pre-elision, the two q4 CTrace nodes' pass-through copies dominated the
whole attribution table). Sharded (``workers > 1``) circuits run the
whole step inside one ``shard_map`` and are not segmentable; profiling them
raises :class:`ProfileError` (the ``/profile`` route degrades to the static
metadata it can still serve).

Per-node metric families (``dbsp_tpu_compiled_node_seconds{node,kind}`` /
``dbsp_tpu_compiled_node_rows_total{node,kind}``) register ONLY through
:func:`export_node_metrics` — the cardinality gate ``tools/check_metrics.py``
(rule 4) pins to this module — and only after a profile actually ran, so a
pipeline that never profiles exports no per-node series. Families are
top-N capped (``DBSP_TPU_PROFILE_TOP_N``, default 16; the tail aggregates
under ``node="other"``).
"""

from __future__ import annotations

import os
import re
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "PROFILE_SCHEMA", "ProfileError", "ProfileDivergence", "check_report",
    "report_dot",
    "static_profile", "measured_profile", "graph_profile",
    "export_node_metrics", "summarize_for_bench", "env_default_ticks",
    "SegmentedStep", "dryrun",
]

PROFILE_SCHEMA = "dbsp_tpu.profile/v1"

# row keys every operator entry must carry in BOTH engine modes — the
# shared /profile contract (tests/test_opprofile.py round-trips it)
ROW_KEYS = ("node", "name", "kind", "total_ms", "evals", "share", "meta")
REPORT_KEYS = ("schema", "mode", "steps", "operators")


class ProfileError(RuntimeError):
    pass


class ProfileDivergence(ProfileError):
    """Segmented run disagreed with the fused program — a real engine bug
    (or donation hazard), never a 'profiling unsupported here' condition;
    surfaces instead of degrading to the graph report."""


def env_default_ticks() -> Optional[int]:
    """``DBSP_TPU_PROFILE=segment`` arms measured profiling by default on
    the ``/profile`` surfaces; ``DBSP_TPU_PROFILE_TICKS`` sets N."""
    if os.environ.get("DBSP_TPU_PROFILE", "") == "segment":
        return int(os.environ.get("DBSP_TPU_PROFILE_TICKS", "8"))
    return None


def check_report(report: dict) -> dict:
    """Validate the shared report schema (raises :class:`ProfileError`);
    returns the report so callers can chain."""
    missing = [k for k in REPORT_KEYS if k not in report]
    if missing:
        raise ProfileError(f"profile report missing keys {missing}")
    if report["schema"] != PROFILE_SCHEMA:
        raise ProfileError(
            f"unknown profile schema {report['schema']!r} "
            f"(expected {PROFILE_SCHEMA!r})")
    if report["mode"] not in ("host", "compiled"):
        raise ProfileError(f"unknown profile mode {report['mode']!r}")
    for row in report["operators"]:
        miss = [k for k in ROW_KEYS if k not in row]
        if miss:
            raise ProfileError(
                f"operator row {row.get('name')!r} missing keys {miss}")
    return report


# ---------------------------------------------------------------------------
# segmented execution
# ---------------------------------------------------------------------------


class _SegCtx:
    """Per-segment stand-in for ``compiler._Ctx``: one node's requirements,
    sink outputs, and window-GC bounds, captured inside that node's own
    traced program instead of the shared whole-step trace."""

    def __init__(self, feeds: Dict[int, Any], states: Dict[str, Any]):
        self.feeds = feeds
        self.states = states  # CZ1Output reads its partner's INPUT state
        self.outputs: Dict[int, Any] = {}
        self.reqs: List[jnp.ndarray] = []
        self.req_index: List[Tuple[Any, str]] = []
        self.gc_bounds: Dict[int, jnp.ndarray] = {}

    def require(self, cnode, key: str, scalar) -> None:
        self.req_index.append((cnode, key))
        self.reqs.append(jnp.asarray(scalar, jnp.int64))

    def observe(self, cnode, key: str, scalar) -> None:
        """Counters are the serving step's; a probe tick fills none."""


def _cost_of(executable) -> Dict[str, float]:
    """XLA cost analysis of one compiled segment (flops / bytes accessed —
    the ROOFLINE §1 accounting); zeros when the backend can't answer."""
    try:
        c = executable.cost_analysis()
        if isinstance(c, (list, tuple)):
            c = c[0] if c else {}
        return {"flops": float(c.get("flops", 0.0)),
                "bytes": float(c.get("bytes accessed", 0.0))}
    except Exception:  # noqa: BLE001 — cost analysis is best-effort
        return {"flops": 0.0, "bytes": 0.0}


def _live_rows(v) -> int:
    """Live-row count of one inter-node value (device reduction + fetch —
    profiling-mode only, never on the hot path)."""
    from dbsp_tpu.compiled import cnodes as cnmod
    from dbsp_tpu.zset.batch import Batch

    if v is None:
        return 0
    if isinstance(v, Batch):
        return int(jnp.sum(v.weights != 0))
    if isinstance(v, cnmod.CView):
        return int(jnp.sum(v.delta.weights != 0))
    return 0


class SegmentedStep:
    """The compiled eval sequence as per-node AOT-compiled jit segments.

    Mirrors ``CompiledHandle._run_nodes`` exactly — same eval order, same
    ``ctx`` protocol (feeds / partner states / sink outputs / window-GC
    truncation applied after the node loop) — but each node's eval is its
    own compiled program, so wall time, rows, and XLA cost analysis exist
    PER NODE. Segments never donate: the caller's state dict leaves are
    read-only inputs, which is what makes probe ticks side-effect-free.
    """

    def __init__(self, ch):
        from dbsp_tpu.compiled import cnodes as cnmod

        if ch.mesh is not None:
            raise ProfileError(
                "segmented profiling supports single-worker circuits only: "
                "a sharded step runs as one shard_map program whose "
                "collectives cannot be split per node")
        self.ch = ch
        self._cn = cnmod
        self._segments: Dict[int, Tuple[Any, Optional[str]]] = {}
        self._gc_segments: Dict[int, Any] = {}
        self._gen_exec = None
        self.costs: Dict[Any, Dict[str, float]] = {}

    # -- per-node programs --------------------------------------------------
    def _partner_key(self, cn) -> Optional[str]:
        if isinstance(cn, self._cn.CZ1Output):
            return str(cn.node.partner)
        return None

    def _segment(self, cn, args):
        # keyed on the FULL argument signature (tree structure + leaf
        # shape/dtype): a compiled executable only accepts exactly what
        # it was lowered with, and inter-node values legitimately vary
        # across ticks — feed present/absent on input nodes, sorted-run
        # aux tags and CAPACITIES downstream of an empty vs fed tick (an
        # unfed input emits its default-cap empty batch, not the feed
        # bucket's). The warmup dry pass replays the exact measured
        # sequence, so every signature compiles outside the timed walls.
        idx = cn.node.index
        leaves, treedef = jax.tree_util.tree_flatten(args)
        sig = tuple((getattr(x, "shape", ()),
                     str(getattr(x, "dtype", type(x).__name__)))
                    for x in leaves)
        key = (idx, treedef, sig)
        ent = self._segments.get(key)
        if ent is not None:
            return ent
        pkey = self._partner_key(cn)
        meta: Dict[str, Any] = {}

        def fn(state, ins, feed, partner_state):
            ctx = _SegCtx({idx: feed} if feed is not None else {},
                          {pkey: partner_state} if pkey is not None else {})
            st2, out = cn.eval(ctx, state, list(ins))
            # identity pass-through elision (module doc): any returned
            # leaf that IS one of the operand tracers (state levels
            # flowing through, views handing back their inputs, sinks
            # echoing batches) — or a repeat of an already-emitted output
            # leaf — leaves the program and is reconstructed from the
            # caller's operands after the call. Exact by construction:
            # the tracer identity proves the value is the operand.
            arg_leaves = jax.tree_util.tree_flatten(
                (state, ins, feed, partner_state))[0]
            env = {}
            for i, leaf in enumerate(arg_leaves):
                if isinstance(leaf, jax.core.Tracer):
                    env.setdefault(id(leaf), i)
            ret_leaves, ret_def = jax.tree_util.tree_flatten(
                (st2, out, dict(ctx.outputs)))
            plan: List[Tuple[str, int]] = []
            kept: List[Any] = []
            emitted: Dict[int, int] = {}
            for leaf in ret_leaves:
                lid = id(leaf)
                if isinstance(leaf, jax.core.Tracer) and lid in env:
                    plan.append(("arg", env[lid]))
                    continue
                if isinstance(leaf, jax.core.Tracer) and lid in emitted:
                    plan.append(("out", emitted[lid]))
                    continue
                if isinstance(leaf, jax.core.Tracer):
                    emitted[lid] = len(kept)
                plan.append(("out", len(kept)))
                kept.append(leaf)
            meta["plan"], meta["ret_def"] = plan, ret_def
            return tuple(kept), tuple(ctx.reqs), dict(ctx.gc_bounds)

        compiled = jax.jit(fn).lower(*args).compile()
        self.costs[idx] = _cost_of(compiled)
        plan, ret_def = meta["plan"], meta["ret_def"]

        def executable(state, ins, feed, partner_state):
            kept, reqs, gc = compiled(state, ins, feed, partner_state)
            arg_leaves = jax.tree_util.tree_flatten(
                (state, ins, feed, partner_state))[0]
            leaves = [arg_leaves[i] if kind == "arg" else kept[i]
                      for kind, i in plan]
            st2, out, outs = jax.tree_util.tree_unflatten(ret_def, leaves)
            return st2, out, reqs, gc, outs

        ent = self._segments[key] = (executable, pkey)
        return ent

    def _gc_segment(self, gidx: int, st, bound):
        ex = self._gc_segments.get(gidx)
        if ex is not None:
            return ex
        cnmod = self._cn

        def fn(st, bound):
            levels, base = st
            return (tuple(cnmod.truncate_below(lvl, bound)
                          for lvl in levels), base)

        ex = self._gc_segments[gidx] = jax.jit(fn).lower(st, bound).compile()
        return ex

    def _run_gen(self, tick):
        ch = self.ch
        targ = jnp.asarray(tick, jnp.int64)
        if self._gen_exec is None:
            def fn(t):
                raw = ch._gen_fn(t)
                return {ch._op_to_index[id(getattr(h, "_op", h))]: b
                        for h, b in raw.items()}

            self._gen_exec = jax.jit(fn).lower(targ).compile()
            self.costs["gen"] = _cost_of(self._gen_exec)
        return self._gen_exec(targ)

    # -- one tick -----------------------------------------------------------
    def run_tick(self, states: Dict[str, Any], feeds_by_idx: Dict[int, Any],
                 tick: int, rec: Optional["_Recorder"] = None,
                 spans=None, plan: Optional[list] = None,
                 plan_out: Optional[list] = None):
        """One tick of the eval sequence, node by node. Returns
        ``(new_states, outputs, refs)`` where ``refs`` carries the
        per-node (inputs, output) references the caller may count rows
        over AFTER its wall timer stopped (row counting is device work
        that must not pollute the attribution).

        ``plan_out`` (warmup) records each node's resolved executable in
        eval order; ``plan`` (measured ticks) replays that recording —
        the sequence is deterministic, so the measured loop skips the
        per-node signature computation entirely and its tick walls carry
        only dispatch + device time."""
        ch = self.ch
        values: Dict[int, Any] = {}
        new_states: Dict[str, Any] = {}
        outputs: Dict[int, Any] = {}
        gc_all: Dict[int, Any] = {}
        refs: List[Tuple[int, tuple, Any, Any]] = []
        if ch._gen_fn is not None:
            t0 = time.perf_counter_ns()
            feeds_by_idx = self._run_gen(tick)
            jax.block_until_ready(feeds_by_idx)
            if rec is not None:
                rec.note("gen", time.perf_counter_ns() - t0)
        for pos, cn in enumerate(ch.cnodes):
            idx = cn.node.index
            ins = tuple(values[i] for i in cn.node.inputs)
            st = states.get(str(idx))
            feed = feeds_by_idx.get(idx)
            pkey = self._partner_key(cn)
            pstate = states.get(pkey) if pkey is not None else None
            args = (st, ins, feed, pstate)
            if plan is not None:
                executable = plan[pos]
            else:
                executable, _ = self._segment(cn, args)
                if plan_out is not None:
                    plan_out.append(executable)
            label = f"{cn.op.name}[{idx}]"
            if spans is not None:
                spans.begin(label, cat="operator")
            t0 = time.perf_counter_ns()
            st2, out, _reqs, gc, outs = executable(*args)
            jax.block_until_ready((st2, out, outs))
            dt = time.perf_counter_ns() - t0
            if spans is not None:
                spans.end(label)
            if st2 is not None:
                new_states[str(idx)] = st2
            values[idx] = out
            outputs.update(outs)
            gc_all.update(gc)
            if rec is not None:
                rec.note(idx, dt)
                refs.append((idx, ins, out, feed))
        # window-GC truncation: mirrors the post-loop of _run_nodes; the
        # time is attributed to the truncated TRACE node (kind "gc")
        for gidx, bound in gc_all.items():
            key = str(gidx)
            st = new_states.get(key)
            if st is None:
                continue
            ex = self._gc_segment(int(gidx), st, bound)
            t0 = time.perf_counter_ns()
            st2 = ex(st, bound)
            jax.block_until_ready(st2)
            if rec is not None:
                rec.note_gc(int(gidx), time.perf_counter_ns() - t0)
            new_states[key] = st2
        return new_states, outputs, refs


class _Recorder:
    """Per-node accumulators over a measured run."""

    def __init__(self):
        self.ns: Dict[Any, int] = {}
        self.gc_ns: Dict[int, int] = {}
        self.rows_in: Dict[int, int] = {}
        self.rows_out: Dict[int, int] = {}
        self.tick_walls: List[int] = []

    def note(self, key, dt: int) -> None:
        self.ns[key] = self.ns.get(key, 0) + dt

    def note_gc(self, idx: int, dt: int) -> None:
        self.gc_ns[idx] = self.gc_ns.get(idx, 0) + dt

    def count_rows(self, refs) -> None:
        for idx, ins, out, feed in refs:
            rin = sum(_live_rows(v) for v in ins) + _live_rows(feed)
            self.rows_in[idx] = self.rows_in.get(idx, 0) + rin
            self.rows_out[idx] = self.rows_out.get(idx, 0) + _live_rows(out)


# ---------------------------------------------------------------------------
# comparison plumbing
# ---------------------------------------------------------------------------


def _np_tree(tree):
    """Materialize a device pytree to host numpy — taken BEFORE the fused
    comparison run so no compared buffer can be donated away under us."""
    return jax.device_get(tree)


def _tree_mismatches(label: str, a, b) -> List[str]:
    fa, ta = jax.tree_util.tree_flatten(a)
    fb, tb = jax.tree_util.tree_flatten(b)
    if ta != tb:
        return [f"{label}: tree structure differs ({ta} != {tb})"]
    out = []
    for i, (x, y) in enumerate(zip(fa, fb)):
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape or x.dtype != y.dtype:
            out.append(f"{label}: leaf {i} shape/dtype differs "
                       f"({x.shape}/{x.dtype} != {y.shape}/{y.dtype})")
            continue
        eq = (np.array_equal(x, y, equal_nan=True) if x.dtype.kind == "f"
              else np.array_equal(x, y))
        if not eq:
            out.append(f"{label}: leaf {i} differs")
    return out


def _save_handle_counters(ch) -> dict:
    """The handle bookkeeping a profile run must not leak into: latency
    samples, cause annotations, the requirement running-max, and the
    outputs dict production readers poll."""
    return {"req": ch._req,
            "lat": len(ch.step_times_ns),
            "causes": len(ch.tick_causes),
            "pending": set(ch._pending_causes),
            "outputs": ch.last_outputs,
            "overhead": {k: len(v) for k, v in ch.host_overhead_ns.items()}}


def _restore_handle_counters(ch, saved: dict) -> None:
    ch._req = saved["req"]
    del ch.step_times_ns[saved["lat"]:]
    del ch.tick_causes[saved["causes"]:]
    ch._pending_causes = set(saved["pending"])
    ch.last_outputs = saved["outputs"]
    for k, v in ch.host_overhead_ns.items():
        del v[saved["overhead"].get(k, 0):]


# ---------------------------------------------------------------------------
# reports
# ---------------------------------------------------------------------------


def _node_rows(ch, seg: SegmentedStep, rec: Optional[_Recorder],
               wall_ns: int, evals: int) -> List[dict]:
    rows = []
    for cn in ch.cnodes:
        idx = cn.node.index
        ns = 0
        if rec is not None:
            ns = rec.ns.get(idx, 0) + rec.gc_ns.get(idx, 0)
        row = {"node": idx, "name": cn.op.name,
               "kind": type(cn).__name__,
               "total_ms": round(ns / 1e6, 3), "evals": evals,
               "share": round(ns / max(wall_ns, 1), 4),
               "meta": cn.profile_meta()}
        if rec is not None:
            row["rows_in"] = rec.rows_in.get(idx, 0)
            row["rows_out"] = rec.rows_out.get(idx, 0)
            if idx in rec.gc_ns:
                row["gc_ms"] = round(rec.gc_ns[idx] / 1e6, 3)
        cost = seg.costs.get(idx)
        if cost is not None:
            row["flops"] = cost["flops"]
            row["bytes"] = cost["bytes"]
        rows.append(row)
    if rec is not None and "gen" in rec.ns:
        ns = rec.ns["gen"]
        rows.append({"node": -1, "name": "generate", "kind": "Generator",
                     "total_ms": round(ns / 1e6, 3), "evals": evals,
                     "share": round(ns / max(wall_ns, 1), 4),
                     "rows_in": 0, "rows_out": 0,
                     "meta": {"caps": {}, "inputs": [], "sharded": False},
                     **(seg.costs.get("gen") or {})})
    rows.sort(key=lambda r: -r["total_ms"])
    return rows


def static_profile(ch, feeds: Optional[dict] = None) -> dict:
    """Compile-time attribution: per-node XLA ``cost_analysis`` joined with
    graph metadata. Executes ONE probe tick over (a local view of) the
    live states purely to thread concrete operands through the segment
    chain — segments never donate, so engine state is untouched and no
    snapshot is needed. Raises :class:`ProfileError` on sharded circuits.
    """
    seg = SegmentedStep(ch)
    feeds_idx = ch._feed_indices(feeds) if feeds else {}
    states = dict(ch.states)
    seg.run_tick(states, feeds_idx, tick=0)
    rows = _node_rows(ch, seg, rec=None, wall_ns=1, evals=0)
    total_bytes = sum(r.get("bytes", 0.0) for r in rows) or 1.0
    for r in rows:
        if "bytes" in r:
            r["bytes_share"] = round(r["bytes"] / total_bytes, 4)
    rows.sort(key=lambda r: -r.get("bytes", 0.0))
    return {"schema": PROFILE_SCHEMA, "mode": "compiled", "steps": 0,
            "attribution": "static", "operators": rows, "measured": None}


def graph_profile(ch) -> dict:
    """Degraded attribution for circuits the segmented profiler cannot
    split (sharded circuits: the whole step is one ``shard_map`` program):
    graph metadata only — node names, kinds, capacities, edges — no costs,
    no timing. The ``/profile`` route serves this instead of erroring, so
    a sharded pipeline still answers with its operator inventory."""
    rows = [{"node": cn.node.index, "name": cn.op.name,
             "kind": type(cn).__name__, "total_ms": 0.0, "evals": 0,
             "share": 0.0, "meta": cn.profile_meta()} for cn in ch.cnodes]
    return {"schema": PROFILE_SCHEMA, "mode": "compiled", "steps": 0,
            "attribution": "graph", "operators": rows, "measured": None}


def measured_profile(ch, n: Optional[int] = None, t0: int = 0,
                     feeds_list: Optional[Sequence[dict]] = None,
                     spans=None, check: bool = True,
                     registry=None) -> dict:
    """Measured attribution: run ``n`` ticks segmented (per-node timing),
    re-run them through the production fused program from the same
    snapshot, assert bit-identity, and REWIND — the engine resumes exactly
    where it stood (see module doc for the full protocol).

    ``feeds_list`` supplies per-tick feeds for circuits without a
    ``gen_fn`` ({handle-or-op: Batch} dicts; capacities must be stable
    across the ticks — the engine's bucketed feed caps already are).
    ``registry`` exports the gated per-node metric families from the
    result. ``check=False`` reports mismatches instead of raising."""
    n = int(n or env_default_ticks() or 8)
    if ch.mesh is not None:
        raise ProfileError(
            "segmented profiling supports single-worker circuits only")
    if ch._gen_fn is None and feeds_list is None:
        feeds_list = [{} for _ in range(n)]
    if feeds_list is not None:
        feeds_list = list(feeds_list)[:n]
        feeds_list += [{}] * (n - len(feeds_list))
    # per-tick cost is delta-proportional, so attribution over EMPTY
    # ticks describes fixed per-node overhead, not a workload — flagged
    # in the report so readers (and the /profile route on an idle served
    # pipeline) can tell the two apart
    idle_inputs = ch._gen_fn is None and all(not f for f in feeds_list)

    # canonical start point: snapshot, then restore — both runs read the
    # POST-restore (repadded) state, so their input bits are identical
    snap = ch.snapshot()
    saved = _save_handle_counters(ch)
    ch.restore(snap)
    start = ch.states

    seg = SegmentedStep(ch)
    rec = _Recorder()

    def tick_feeds(i):
        if feeds_list is None:
            return {}
        return ch._feed_indices(feeds_list[i]) if feeds_list[i] else {}

    # warmup: one full DRY PASS of the exact measured sequence on a
    # throwaway state view — every segment (and the gen program) compiles
    # HERE, outside the measured walls. A single tick-0 pass is not
    # enough: segments are keyed on argument signature, which varies with
    # each tick's feed pattern AND with upstream emptiness (run-tag aux,
    # empty-vs-fed capacities), so only replaying the real sequence
    # (states threaded, same feeds) covers every signature the measured
    # loop will hit. The pass also RECORDS each tick's executable plan,
    # so the measured loop skips signature computation entirely (at mini
    # scales that per-node host work measurably diluted attribution).
    warm_states = dict(start)
    plans: List[list] = []
    for i in range(n):
        plan_i: list = []
        warm_states, _, _ = seg.run_tick(warm_states, tick_feeds(i),
                                         t0 + i, plan_out=plan_i)
        plans.append(plan_i)

    states = dict(start)
    seg_out_np = []
    for i in range(n):
        feeds_idx = tick_feeds(i)
        if spans is not None:
            # tick -> operator nesting in the /trace window (and one
            # TOP-LEVEL span per tick, so the recorder's bounded step ring
            # evicts whole ticks, not individual operator slices)
            spans.begin(f"profile_tick[{t0 + i}]", cat="step")
        w0 = time.perf_counter_ns()
        states, outputs, refs = seg.run_tick(states, feeds_idx, t0 + i,
                                             rec=rec, spans=spans,
                                             plan=plans[i])
        rec.tick_walls.append(time.perf_counter_ns() - w0)
        if spans is not None:
            spans.end(f"profile_tick[{t0 + i}]")
        rec.count_rows(refs)  # device reductions — outside the wall
        seg_out_np.append(_np_tree(outputs))
    seg_final_np = _np_tree(states)

    # fused comparison run: the production step program, same start bits
    # (ch.states is still `start`; the first fused step donates it, which
    # is why the segmented results were materialized to numpy above)
    fused_ns = []
    fused_out_np = []
    for i in range(n):
        f = feeds_list[i] if feeds_list is not None else None
        w0 = time.perf_counter_ns()
        ch.step(tick=t0 + i, feeds=f, block=True)
        fused_ns.append(time.perf_counter_ns() - w0)
        fused_out_np.append(_np_tree(ch.last_outputs))
    fused_final_np = _np_tree(ch.states)

    mism: List[str] = []
    for i in range(n):
        mism += _tree_mismatches(f"tick[{t0 + i}].outputs",
                                 seg_out_np[i], fused_out_np[i])
    mism += _tree_mismatches("final_states", seg_final_np, fused_final_np)

    # rewind: the profiled ticks were hypothetical — production resumes
    # from the pre-profile snapshot with its counters intact
    ch.restore(snap)
    _restore_handle_counters(ch, saved)

    if check and mism:
        raise ProfileDivergence(
            f"segmented step diverged from the fused program "
            f"({len(mism)} mismatches): {mism[:4]}")

    wall_ns = sum(rec.tick_walls)
    node_ns = (sum(v for k, v in rec.ns.items()) +
               sum(rec.gc_ns.values()))
    fused_sorted = sorted(fused_ns)
    fused_med = fused_sorted[len(fused_sorted) // 2]
    seg_ms = wall_ns / n / 1e6
    fused_ms = fused_med / 1e6
    rows = _node_rows(ch, seg, rec, wall_ns, evals=n)
    report = {
        "schema": PROFILE_SCHEMA, "mode": "compiled", "steps": n,
        "t0": t0, "attribution": "measured", "operators": rows,
        "measured": {
            "ticks": n,
            "idle_inputs": idle_inputs,
            "segmented_ms_per_tick": round(seg_ms, 3),
            "fused_ms_per_tick": round(fused_ms, 3),
            "segmentation_overhead": round(seg_ms / max(fused_ms, 1e-9), 3),
            "attributed_fraction": round(node_ns / max(wall_ns, 1), 4),
            "bit_identical": not mism,
            "mismatches": mism[:8],
        },
    }
    if registry is not None:
        export_node_metrics(registry, report)
    return report


# ---------------------------------------------------------------------------
# surfaces: metrics gate, graphviz, bench summary
# ---------------------------------------------------------------------------


def export_node_metrics(registry, report: dict,
                        top_n: Optional[int] = None) -> None:
    """The ONLY registration site for the per-node metric families — the
    cardinality gate ``tools/check_metrics.py`` rule 4 enforces. Gated
    twice: the families do not exist until a MEASURED profile ran (a
    pipeline that never profiles exports no per-node series), and only the
    top-N nodes by time get their own label children (``node="other"``
    aggregates the tail), bounding series count regardless of circuit
    size."""
    top_n = top_n if top_n is not None else int(
        os.environ.get("DBSP_TPU_PROFILE_TOP_N", "16"))
    ops = [r for r in report.get("operators", ()) if r.get("total_ms")]
    if not ops:
        return
    sec = registry.gauge(
        "dbsp_tpu_compiled_node_seconds",
        "Per-node seconds over the last segmented profile run "
        "(obs/opprofile.py; top-N nodes, tail aggregates as node=other)",
        labels=("node", "kind"))
    rows_total = registry.counter(
        "dbsp_tpu_compiled_node_rows_total",
        "Output rows attributed per node across segmented profile runs "
        "(top-N capped like _node_seconds)",
        labels=("node", "kind"))
    # the gauge family is "the LAST profile run": drop the previous run's
    # children or nodes that fell out of this run's top-N would keep
    # serving stale seconds next to the fresh series (the counter is
    # cumulative across runs by contract and must NOT be cleared)
    sec.clear_children()
    other_s, other_r = 0.0, 0
    for i, r in enumerate(sorted(ops, key=lambda r: -r["total_ms"])):
        if i < top_n:
            sec.labels(node=str(r["node"]), kind=r["kind"]).set(
                r["total_ms"] / 1e3)
            rows_total.labels(node=str(r["node"]), kind=r["kind"]).inc(
                r.get("rows_out", 0))
        else:
            other_s += r["total_ms"] / 1e3
            other_r += r.get("rows_out", 0)
    if other_s or other_r:
        sec.labels(node="other", kind="other").set(other_s)
        rows_total.labels(node="other", kind="other").inc(other_r)


def report_dot(report: dict) -> str:
    """Graphviz rendering of a profile report (the reference's
    ``dump_profile`` .dot shape): nodes shaded by time share, edges from
    the rows' graph metadata."""
    rows = report.get("operators", [])
    total = sum(r.get("total_ms", 0.0) for r in rows) or 1.0

    def nid(n):
        return "n" + re.sub(r"[^0-9A-Za-z]+", "_", str(n))

    lines = ["digraph profile {", '  rankdir="LR";']
    present = {str(r["node"]) for r in rows}
    for r in rows:
        ms = r.get("total_ms", 0.0)
        pct = 100.0 * ms / total
        label = f"{r['name']}\\n{ms:.1f}ms ({pct:.0f}%)"
        if not ms and r.get("bytes"):
            label = f"{r['name']}\\n{r['bytes'] / 1e6:.2f}MB"
        shade = min(9, 1 + int(pct / 12))
        lines.append(f'  {nid(r["node"])} [label="{label}", style=filled, '
                     f'colorscheme=reds9, fillcolor={shade}];')
    for r in rows:
        for i in (r.get("meta") or {}).get("inputs", ()):
            if str(i) in present:
                lines.append(f"  {nid(i)} -> {nid(r['node'])};")
    lines.append("}")
    return "\n".join(lines)


def summarize_for_bench(report: dict, top: int = 8) -> dict:
    """The compact embedding ``bench.py --profile`` puts in its JSON."""
    m = report.get("measured") or {}
    return {
        "attributed_fraction": m.get("attributed_fraction"),
        "bit_identical": m.get("bit_identical"),
        "segmented_ms_per_tick": m.get("segmented_ms_per_tick"),
        "fused_ms_per_tick": m.get("fused_ms_per_tick"),
        "segmentation_overhead": m.get("segmentation_overhead"),
        "top_operators": [
            {k: r.get(k) for k in ("node", "name", "kind", "total_ms",
                                   "share", "rows_out")}
            for r in report.get("operators", [])[:top]],
    }


# ---------------------------------------------------------------------------
# lint dryrun
# ---------------------------------------------------------------------------


def dryrun(query: str = "q4", ticks: int = 2, events_per_tick: int = 400,
           warm: int = 2) -> dict:
    """Build a mini compiled Nexmark circuit and run one measured profile
    end to end — the ``tools/lint_all.py`` front that keeps the profiler
    from silently rotting. Raises on schema drift, segmented/fused
    divergence, or attribution below 90%."""
    jax.config.update("jax_platforms", "cpu")
    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.compiled import compile_circuit
    from dbsp_tpu.nexmark import (GeneratorConfig, build_inputs, device_gen,
                                  queries)

    cfg = GeneratorConfig(seed=1)
    ept = max(events_per_tick // 50, 1)
    q = getattr(queries, query)

    def build(c):
        streams, handles = build_inputs(c)
        return handles, q(*streams).output()

    handle, (handles, _out) = Runtime.init_circuit(1, build)
    hp, ha, hb = handles

    def gen_fn(tick):
        p, a, b = device_gen.generate_tick(cfg, tick * ept, ept)
        return {hp: p, ha: a, hb: b}

    ch = compile_circuit(handle, gen_fn=gen_fn)
    ch.run_ticks(0, warm, validate_every=1)
    report = measured_profile(ch, n=ticks, t0=warm)
    check_report(report)
    m = report["measured"]
    if not m["bit_identical"]:
        raise ProfileError(
            f"{query}: segmented != fused: {m['mismatches']}")
    # attribution floor: real rot (a compile or fetch landing inside a
    # tick wall un-attributed) collapses this far below the floor; 0.85
    # leaves headroom for host-noise on tiny mini-protocol segments (the
    # committed PROFILE_q4.json artifact is separately gated >= 0.90 by
    # tests/test_opprofile.py)
    if m["attributed_fraction"] < 0.85:
        raise ProfileError(
            f"{query}: only {m['attributed_fraction']:.0%} of segmented "
            "tick time attributed to named nodes (floor: 85%)")
    return report
