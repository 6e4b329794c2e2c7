"""Incremental group-by aggregation.

Reference: ``operator/aggregate/mod.rs`` — the ``Aggregator`` trait (:75),
``stream_aggregate`` (:172), incremental ``aggregate`` (:204) whose
``AggregateIncremental::eval`` (:600) recomputes aggregates ONLY for keys
touched by the delta, reading the full group from the input trace, and emits
retract/insert pairs against the previous output.

TPU shape of the same algorithm, per tick:
  1. unique touched keys Q  = distinct live keys of the delta (one compact);
  2. group gather           = probe every input-spine level for Q's ranges,
                              expand (grow-on-demand caps), gather rows;
  3. net weights            = consolidate gathered rows on (q, vals) so a
                              (key,val) split across levels nets out;
  4. reduce                 = aggregator's segment reduction per q;
  5. diff                   = probe the operator's own output spine for Q's
                              previous values; emit -1 old / +1 new where
                              changed (skip unchanged; empty group retracts).
All steps are static-shape kernels; per-step cost scales with the delta and
the touched groups, not the accumulated state.

Weights semantics: a (key, val) with net weight w > 0 is present (w copies);
non-positive net weights mean absent. Inputs whose groups net to negative
multiplicities are ill-formed for aggregation (same contract as the
reference's aggregates over indexed Z-sets).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dbsp_tpu.circuit.builder import Stream
from dbsp_tpu.parallel.lift import lifted
from dbsp_tpu.circuit.operator import UnaryOperator
from dbsp_tpu.operators.registry import stream_method
from dbsp_tpu.operators.trace_op import TraceView
from dbsp_tpu.trace.spine import Spine
from dbsp_tpu.zset import kernels
from dbsp_tpu.zset.batch import Batch, bucket_cap, concat_batches

# ---------------------------------------------------------------------------
# Aggregators (reference: Fold/Min/Max/Avg, operator/aggregate/{fold,...}.rs)
# ---------------------------------------------------------------------------


class Aggregator:
    """Segment-reduction spec: vals+weights grouped by segment id -> outputs.

    ``reduce`` sees every gathered row (including absent ones, net w <= 0) and
    must ignore non-present rows itself; identity segments are reported
    through the separate nonempty mask, so identity values never escape.

    The built-ins declare their reduction DECLARATIVELY via
    :meth:`reduce_spec` — a tuple of ``(op, source column)`` pairs from the
    shared five-op vocabulary (count / sum / min / max / avg) — and inherit
    ``reduce`` from the spec through :func:`segment_reduce`, which
    dispatches the whole spec as ONE native custom call on CPU
    (``ZsetSegmentReduceFfi``) instead of 2-4 XLA dispatches per output.
    The spec is also what lets the compiled engine's fused aggregate
    megakernel (``cursor.agg_ladder``) run the reduction inside the trace
    walk; spec-less aggregators (``Fold``) keep their hand-written
    ``reduce`` and the stitched path.
    """

    out_dtypes: Tuple = ()
    name = "agg"
    #: semigroup aggregates (Min/Max) set this: when a group's delta holds
    #: ONLY insertions, the new output is combine(old output, reduce(delta))
    #: — no re-gather of the group's history from the input trace. The
    #: compiled path uses it to make append-mostly streams (e.g. Nexmark
    #: bids) cost O(delta) instead of O(touched history) per tick.
    insert_combinable = False

    def reduce_spec(self) -> Optional[Tuple[Tuple[str, int], ...]]:
        """``((op, src_col), ...)`` per output — ``None`` for opaque
        (hand-written) reductions, which the fused paths skip."""
        return None

    def reduce(self, val_cols: Tuple[jnp.ndarray, ...], weights: jnp.ndarray,
               seg: jnp.ndarray, num_segments: int
               ) -> Tuple[jnp.ndarray, ...]:
        spec = self.reduce_spec()
        if spec is None:
            raise NotImplementedError
        return segment_reduce(spec, val_cols, weights, seg, num_segments)

    def combine(self, a_vals: Tuple[jnp.ndarray, ...], a_present,
                b_vals: Tuple[jnp.ndarray, ...], b_present
                ) -> Tuple[jnp.ndarray, ...]:
        """Semigroup combine of two per-segment partial outputs (only
        required when ``insert_combinable``); absent sides must not leak
        their identity values into the result."""
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class Count(Aggregator):
    out_dtypes = (jnp.int64,)
    name = "count"

    def reduce_spec(self):
        return (("count", 0),)


@dataclasses.dataclass(frozen=True)
class Sum(Aggregator):
    col: int = 0
    out_dtypes = (jnp.int64,)
    name = "sum"

    def reduce_spec(self):
        return (("sum", self.col),)


@dataclasses.dataclass(frozen=True)
class Max(Aggregator):
    col: int = 0
    out_dtypes = (jnp.int64,)
    name = "max"
    insert_combinable = True

    def reduce_spec(self):
        return (("max", self.col),)

    def combine(self, a_vals, a_present, b_vals, b_present):
        a, b = a_vals[0], b_vals[0].astype(a_vals[0].dtype)
        return (jnp.where(a_present & b_present, jnp.maximum(a, b),
                          jnp.where(a_present, a, b)),)


@dataclasses.dataclass(frozen=True)
class Min(Aggregator):
    col: int = 0
    out_dtypes = (jnp.int64,)
    name = "min"
    insert_combinable = True

    def reduce_spec(self):
        return (("min", self.col),)

    def combine(self, a_vals, a_present, b_vals, b_present):
        a, b = a_vals[0], b_vals[0].astype(a_vals[0].dtype)
        return (jnp.where(a_present & b_present, jnp.minimum(a, b),
                          jnp.where(a_present, a, b)),)


@dataclasses.dataclass(frozen=True)
class Average(Aggregator):
    """Integer average sum//count (deterministic across worker counts, unlike
    float accumulation order). Truncating division (SQL/Rust semantics),
    not Python floor: -7 / 2 == -3, matching the reference engine on
    negative sums — the shared "avg" op implements exactly that."""

    col: int = 0
    out_dtypes = (jnp.int64,)
    name = "avg"

    def reduce_spec(self):
        return (("avg", self.col),)


@dataclasses.dataclass(frozen=True)
class Fold(Aggregator):
    """General user-defined aggregation (reference: ``aggregate/fold.rs:25``).

    ``reduce_fn(val_cols, weights, seg, num_segments) -> out_cols`` is any
    segment reduction over the gathered group rows (rows with net weight
    <= 0 must be ignored by masking on ``weights > 0``, exactly like the
    built-ins). Example — sum of squares:

        Fold(lambda v, w, s, n: (segment_sum(v[0]**2 * maximum(w, 0), s, n),),
             out_dtypes=(jnp.int64,))
    """

    reduce_fn: Callable = None
    out_dtypes: Tuple = (jnp.int64,)
    name: str = "fold"

    def reduce(self, val_cols, weights, seg, num_segments):
        return tuple(self.reduce_fn(val_cols, weights, seg, num_segments))


# ---------------------------------------------------------------------------
# Shared segment-reduction dispatch (the five-op Aggregator vocabulary)
# ---------------------------------------------------------------------------


def _seg_out_dtype(op: str, col: int, val_cols, weights):
    """Result dtype of one reduction op under the XLA formulation — what
    the native kernel's int64 accumulators re-narrow to (two's-complement
    truncation == wrapping narrow-dtype accumulation, so int32-weight
    paths stay bit-identical)."""
    if op == "count":
        return weights.dtype
    if op == "present":
        return jnp.int64  # jnp.where(w > 0, 1, 0) under x64
    v = val_cols[col]
    if op in ("min", "max"):
        return v.dtype
    return jnp.promote_types(v.dtype, weights.dtype)  # sum / avg


def segment_reduce(spec, val_cols, weights: jnp.ndarray, seg: jnp.ndarray,
                   num_segments: int) -> Tuple[jnp.ndarray, ...]:
    """Run a whole reduce spec — ``((op, src_col), ...)`` over the shared
    count/sum/min/max/avg(/present) vocabulary — per segment id, as ONE
    native custom call on CPU (``ZsetSegmentReduceFfi``; the
    ``DBSP_TPU_NATIVE=segment_reduce`` force-off and non-int dtypes fall
    back to the ``jax.ops.segment_*`` formulation below). Semantics per op
    (bit-identical on every backend): count = Σ max(w, 0); sum =
    Σ v·max(w, 0); min/max over rows with w > 0 (empty segments fill with
    the source dtype's identity); avg = truncating sum/count division;
    present = any w > 0 (as the 0/1 int the XLA formulation produces).
    Out-of-range seg ids are dropped (the trash-segment contract)."""
    out_dtypes = tuple(_seg_out_dtype(op, col, val_cols, weights)
                       for op, col in spec)
    # avg DIVIDES: the fused backends accumulate in int64 and narrow the
    # quotient, which equals the XLA formulation only when the result
    # dtype IS int64 (for sums, truncating an int64 accumulation equals a
    # wrapping narrow accumulation — division breaks that congruence).
    # Narrower promotions (int32 weights x int32 vals — no engine path,
    # weights are int64 everywhere) keep the XLA chain.
    fused_ok = all(op != "avg" or jnp.dtype(dt) == jnp.int64
                   for (op, _), dt in zip(spec, out_dtypes))
    if fused_ok and weights.ndim == 1 and num_segments >= 1:
        if kernels.native_kernel("segment_reduce"):
            from dbsp_tpu.zset import native_merge

            if native_merge.supports((*(c.dtype for c in val_cols),
                                      weights.dtype)):
                kernels.count_kernel_dispatch("segment_reduce", "native")
                return native_merge.segment_reduce_native(
                    spec, val_cols, weights, seg, num_segments, out_dtypes)
    kernels.count_kernel_dispatch("segment_reduce", "xla")
    wpos = jnp.maximum(weights, 0)
    outs: List[jnp.ndarray] = []
    for op, col in spec:
        if op == "count":
            outs.append(jax.ops.segment_sum(wpos, seg,
                                            num_segments=num_segments))
        elif op == "sum":
            outs.append(jax.ops.segment_sum(val_cols[col] * wpos, seg,
                                            num_segments=num_segments))
        elif op == "min":
            v = val_cols[col]
            hi = jnp.iinfo(v.dtype).max \
                if jnp.issubdtype(v.dtype, jnp.integer) else jnp.inf
            outs.append(jax.ops.segment_min(
                jnp.where(weights > 0, v, hi), seg,
                num_segments=num_segments))
        elif op == "max":
            v = val_cols[col]
            lo = jnp.iinfo(v.dtype).min \
                if jnp.issubdtype(v.dtype, jnp.integer) else -jnp.inf
            outs.append(jax.ops.segment_max(
                jnp.where(weights > 0, v, lo), seg,
                num_segments=num_segments))
        elif op == "avg":
            s = jax.ops.segment_sum(val_cols[col] * wpos, seg,
                                    num_segments=num_segments)
            c = jnp.maximum(jax.ops.segment_sum(
                wpos, seg, num_segments=num_segments), 1)
            outs.append(jnp.where(s >= 0, s // c, -((-s) // c)))
        elif op == "present":
            outs.append(jax.ops.segment_max(
                jnp.where(weights > 0, 1, 0), seg,
                num_segments=num_segments))
        else:
            raise ValueError(f"unknown segment-reduce op {op!r}")
    return tuple(outs)


def reduce_with_present(agg: "Aggregator", val_cols, weights, seg,
                        num_segments: int):
    """(outputs, presence) in as few dispatches as the aggregator allows:
    spec'd aggregators append a ``present`` op to their own spec, so the
    whole thing is ONE fused ``segment_reduce`` call; opaque ones pay
    their hand-written reduce plus the separate presence reduction."""
    spec = agg.reduce_spec()
    if spec is not None:
        res = segment_reduce((*spec, ("present", 0)), val_cols, weights,
                             seg, num_segments)
        return tuple(res[:-1]), res[-1]
    outs = tuple(agg.reduce(val_cols, weights, seg, num_segments))
    present = jax.ops.segment_max(
        jnp.where(weights > 0, 1, 0), seg, num_segments=num_segments)
    return outs, present


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------


def _delta_groups_impl(delta: Batch, nk: int):
    """Group structure of a consolidated delta in ONE run-boundary scan:
    ``(unique key cols, unique live mask, row live mask, segment id per
    row)``. The delta's sorted-run contract (``sorted_runs == 1`` — live
    rows packed, equal keys adjacent) is what makes the single
    prev-row comparison exact; the same ``first``-of-group mask feeds both
    the unique-key compaction and the fast path's per-row segment ids, so
    the boundaries are never scanned twice (they previously were —
    ``_unique_keys_impl`` then a second ``rows_equal_prev`` in
    CAggregate's fast path)."""
    keys = delta.keys[:nk]
    first = ~kernels.rows_equal_prev(keys, n=delta.cap)
    anylive = delta.weights != 0
    live = anylive & first
    cols, w = kernels.compact(keys, jnp.where(live, 1, 0).astype(jnp.int32),
                              live)
    seg = jnp.cumsum(jnp.where(live, 1, 0)) - 1
    return cols, w != 0, anylive, seg


def _unique_keys_impl(delta: Batch, nk: int
                      ) -> Tuple[Tuple[jnp.ndarray, ...], jnp.ndarray]:
    """Distinct live keys of a consolidated batch, compacted to the front.

    Returns (key_cols, live_mask) at the delta's capacity. The one
    run-boundary scan lives in :func:`_delta_groups_impl`; the segment
    ids computed there are dead code under jit for callers that only
    need the keys."""
    cols, qlive, _, _ = _delta_groups_impl(delta, nk)
    return cols, qlive


_unique_keys_jit = jax.jit(_unique_keys_impl, static_argnames=("nk",))


def _unique_keys_factory(nk: int):
    return lambda d: _unique_keys_impl(d, nk)


def _unique_keys(delta: Batch, nk: int):
    """Distinct live keys + live mask, re-bucketed to the distinct-key count.

    The trim (one scalar sync) is what keeps aggregation cost proportional
    to TOUCHED KEYS, not delta capacity: a 64k-cap delta over 16 groups
    would otherwise drag 64k-sized gathers/diffs through the whole eval.
    """
    if delta.sharded:
        qkeys, qlive = lifted(_unique_keys_factory, nk)(delta)
        nq = int(jnp.max(jnp.sum(qlive, axis=-1)))
    else:
        qkeys, qlive = _unique_keys_jit(delta, nk)
        nq = int(jnp.sum(qlive))
    cap = bucket_cap(max(nq, 1))
    if cap < qlive.shape[-1]:
        qkeys = tuple(k[..., :cap] for k in qkeys)
        qlive = qlive[..., :cap]
    return qkeys, qlive


def _gather_level_impl(qkeys: Tuple[jnp.ndarray, ...], qlive: jnp.ndarray,
                       level: Batch, out_cap: int,
                       sorted_queries: bool = False):
    """Expand one spine level's matching rows for the query keys.

    Returns (qrow ids, gathered val cols, weights, total). The output is
    SORTED by (qrow, vals): expansion follows query order and each group's
    rows keep the level's (key, vals) order; dead slots carry qrow ==
    q_cap (the trash segment) + sentinel vals, so they sort last. That
    ordering is what lets cross-level results combine with a rank-merge
    instead of a sort. ``sorted_queries`` states that ``qkeys`` are sorted
    (the front-packed unique keys of a consolidated delta): both probes
    may then rank by merge (:func:`kernels.lex_probe`)."""
    nk = len(qkeys)
    q_cap = qkeys[0].shape[0]
    lo = kernels.lex_probe(level.keys[:nk], qkeys, side="left",
                           sorted_queries=sorted_queries)
    hi = kernels.lex_probe(level.keys[:nk], qkeys, side="right",
                           sorted_queries=sorted_queries)
    lo = jnp.where(qlive, lo, 0)
    hi = jnp.where(qlive, hi, lo)
    row, src, valid, total = kernels.expand_ranges(lo, hi, out_cap)
    w = jnp.where(valid, level.weights[src], 0)
    vals = tuple(jnp.where(valid, c[src], kernels.sentinel_for(c.dtype))
                 for c in level.vals)
    qrow = jnp.where(valid, row, jnp.int32(q_cap))
    return qrow, vals, w, total


def _gather_ladder_factory(out_cap: int):
    from dbsp_tpu.zset import cursor

    return lambda qk, ql, levels: cursor.gather_ladder(qk, ql, levels,
                                                       out_cap)


@partial(jax.jit, static_argnames=("out_cap",))
def _gather_ladder(qkeys, qlive, levels, out_cap: int):
    from dbsp_tpu.zset import cursor

    return cursor.gather_ladder(qkeys, qlive, levels, out_cap)


class GroupGather:
    """Host driver: gather the full groups of the query keys across ALL
    spine levels in ONE fused launch (zset/cursor.py: one probe pair over
    the ladder, one cross-level expansion, one shared buffer with one
    monotone capacity — the per-level loop paid K probe kernels and K
    grow-on-demand buffers). One batched overflow sync per eval.

    With several levels the fused part may hold cross-level insert/retract
    rows for one (qrow, vals) — reducers net them
    (``_reduce_groups(..., net=len(levels) > 1)``)."""

    def __init__(self):
        self.out_cap = 0  # fused ladder output capacity (monotone)

    @staticmethod
    def _launch(qkeys, qlive, levels, cap):
        if qlive.ndim > 1:  # sharded query set
            return lifted(_gather_ladder_factory, cap)(qkeys, qlive, levels)
        return _gather_ladder(qkeys, qlive, levels, cap)

    def __call__(self, qkeys, qlive, levels: Sequence[Batch], q_cap: int):
        """Returns a 1-element list holding the fused (qrow, val_cols, w)
        part, or None for an empty ladder."""
        if not levels:
            return None
        levels = tuple(levels)
        if not self.out_cap:
            self.out_cap = bucket_cap(max(64, q_cap))
        part, total = self._launch(qkeys, qlive, levels, self.out_cap)
        t = int(np.max(jax.device_get(total)))  # ONE sync; worst worker
        if t > self.out_cap:
            self.out_cap = bucket_cap(t)
            part, _ = self._launch(qkeys, qlive, levels, self.out_cap)
        return [part]


def concat_parts(parts):
    """Flatten per-level gather parts to one (qrow, val_cols, w) triple —
    for consumers that net rows themselves (topk, upsert)."""
    qrow = jnp.concatenate([p[0] for p in parts], axis=-1)
    nvals = len(parts[0][1])
    vals = tuple(jnp.concatenate([p[1][i] for p in parts], axis=-1)
                 for i in range(nvals))
    w = jnp.concatenate([p[2] for p in parts], axis=-1)
    return qrow, vals, w


def _reduce_groups_impl(parts, agg: Aggregator, q_cap: int,
                        net: bool | None = None):
    """Net out cross-level duplicates (each part is sorted by (qrow, vals)
    — see :func:`_gather_level_impl`), then run the aggregator per q segment.

    One gathered level needs no netting (its rows are unique); multiple
    levels combine with one sort-consolidation on CPU or a fold of
    sorted merges on TPU (kernels.merge_strategy). ``net=True`` forces the
    consolidation for a SINGLE part that was itself combined from several
    levels (compiled ``gather_levels``) and so may carry cross-level
    insert/retract rows for one (qrow, vals)."""
    (qrow, val_cols, w), *rest = parts
    cols = (qrow, *val_cols)
    if not rest and net:
        cols, w = kernels.consolidate_cols(cols, w)
        qrow, val_cols = cols[0], cols[1:]
        cols = (qrow, *val_cols)
    if rest and kernels.merge_strategy() == "sort":
        all_cols = tuple(
            jnp.concatenate([p[i] if i == 0 else p[1][i - 1]
                             for p in parts])
            for i in range(1 + len(val_cols)))
        all_w = jnp.concatenate([p[2] for p in parts])
        cols, w = kernels.consolidate_cols(all_cols, all_w)
    else:
        for (qrow2, vals2, w2) in rest:
            cols, w = kernels.merge_sorted_cols(cols, w, (qrow2, *vals2), w2)
    qrow, val_cols = cols[0], cols[1:]
    # dead rows carry qrow >= q_cap (q_cap marker, or int32 sentinel after
    # a merge compaction) — clamp everything dead into the trash segment
    seg = jnp.minimum(qrow, q_cap).astype(jnp.int32)
    outs, present = reduce_with_present(agg, val_cols, w, seg, q_cap + 1)
    return tuple(o[:q_cap] for o in outs), present[:q_cap] > 0


_reduce_groups_jit = jax.jit(_reduce_groups_impl,
                             static_argnames=("agg", "q_cap", "net"))


def _reduce_groups_factory(agg: Aggregator, q_cap: int, net=None):
    return lambda parts: _reduce_groups_impl(parts, agg, q_cap, net)


def _reduce_groups(parts, agg: Aggregator, q_cap: int, net=None):
    if parts[0][2].ndim > 1:  # sharded gather parts
        return lifted(_reduce_groups_factory, agg, q_cap, net)(parts)
    return _reduce_groups_jit(parts, agg, q_cap, net)


def _diff_outputs_impl(qkeys, qlive, new_vals, new_present, old_vals,
                       old_present):
    """Build the retract/insert output delta (2*q_cap capacity)."""
    changed = jnp.zeros(qlive.shape, jnp.bool_)
    for nv, ov in zip(new_vals, old_vals):
        changed = changed | ~kernels._col_eq(nv.astype(ov.dtype), ov)
    changed = changed | (new_present != old_present)
    insert_w = jnp.where(qlive & new_present & changed, 1, 0)
    retract_w = jnp.where(qlive & old_present & changed, -1, 0)
    keys = tuple(jnp.concatenate([c, c]) for c in qkeys)
    vals = tuple(jnp.concatenate([nv.astype(ov.dtype), ov])
                 for nv, ov in zip(new_vals, old_vals))
    w = jnp.concatenate([insert_w, retract_w]).astype(jnp.int64)
    cols, w = kernels.consolidate_cols((*keys, *vals), w)
    return cols, w


_diff_outputs_jit = jax.jit(_diff_outputs_impl)


def _diff_outputs_factory():
    return _diff_outputs_impl


def _diff_outputs(qkeys, qlive, new_vals, new_present, old_vals, old_present):
    if qlive.ndim > 1:  # sharded
        return lifted(_diff_outputs_factory)(
            qkeys, qlive, new_vals, new_present, old_vals, old_present)
    return _diff_outputs_jit(qkeys, qlive, new_vals, new_present, old_vals,
                             old_present)


class AggregateOp(UnaryOperator):
    """Incremental aggregate over a traced indexed Z-set (aggregate/mod.rs:410)."""

    def __init__(self, agg: Aggregator, key_dtypes, name=None):
        self.agg = agg
        self.name = name or f"aggregate<{agg.name}>"
        self.key_dtypes = tuple(key_dtypes)
        self.out_schema = (self.key_dtypes, tuple(agg.out_dtypes))
        self.out_spine = Spine(self.key_dtypes, tuple(agg.out_dtypes))
        self._group_gather = GroupGather()
        self._old_gather = GroupGather()

    def clock_start(self, scope: int) -> None:
        if scope > 0:  # nested clock: reset per parent tick (nested.py)
            self.out_spine = Spine(self.key_dtypes, tuple(self.agg.out_dtypes))

    def eval(self, view: TraceView) -> Batch:
        from dbsp_tpu.circuit.runtime import Runtime

        delta = view.delta
        nk = len(self.key_dtypes)
        if int(delta.live_count()) == 0:
            w = Runtime.worker_count()
            return Batch.empty(*self.out_schema, lead=(w,) if w > 1 else ())
        qkeys, qlive = _unique_keys(delta, nk)
        q_cap = qlive.shape[-1]  # trimmed to distinct-key bucket

        gathered = self._group_gather(qkeys, qlive, view.spine.batches, q_cap)
        if gathered is None:
            new_vals = tuple(
                jnp.zeros(qlive.shape, d) for d in self.agg.out_dtypes)
            new_present = jnp.zeros(qlive.shape, jnp.bool_)
        else:
            # the fused part holds cross-level rows when the spine has
            # several levels — net them before reducing
            new_vals, new_present = _reduce_groups(
                tuple(gathered), self.agg, q_cap,
                net=len(view.spine.batches) > 1)

        old = self._old_gather(qkeys, qlive, self.out_spine.batches, q_cap)
        if old is None:
            old_vals = tuple(kernels.sentinel_fill(qlive.shape, d)
                             for d in self.agg.out_dtypes)
            old_present = jnp.zeros(qlive.shape, jnp.bool_)
        else:
            # previous outputs are single rows per key; Max over net-positive
            # rows reconstructs the value, presence from net weight
            old_vals, old_present = _reduce_groups(
                tuple(old), _TupleMax(len(self.agg.out_dtypes)), q_cap,
                net=len(self.out_spine.batches) > 1)

        cols, w = _diff_outputs(qkeys, qlive, new_vals, new_present,
                                old_vals, old_present)
        # re-bucket to live rows: the diff has 2*q_cap capacity but few live
        # rows, and downstream operators inherit whatever cap we emit
        out = Batch(cols[:nk], cols[nk:], w,
                    runs=(int(w.shape[-1]),)).shrink_to_fit()
        self.out_spine.insert(out)
        return out

    def fixedpoint(self, scope: int) -> bool:
        return True

    def state_dict(self):
        return {"out_spine": self.out_spine}

    def load_state_dict(self, state):
        self.out_spine = state["out_spine"]


@dataclasses.dataclass(frozen=True)
class _TupleMax(Aggregator):
    """Internal: recover the (unique) previous output row per key — a
    per-column "max over net-positive rows", i.e. one shared-vocabulary
    max op per column."""

    ncols: int = 1

    def reduce_spec(self):
        return tuple(("max", i) for i in range(self.ncols))


@stream_method
def aggregate(self: Stream, agg, name=None) -> Stream:
    """Incremental aggregate by the stream's key columns; output is an
    indexed Z-set (key -> aggregate value) maintained under retractions.

    A :class:`~dbsp_tpu.operators.aggregate_linear.LinearAggregator`
    (Count/Sum/Average) dispatches to the linear fast path, which consumes
    the raw delta stream — no input trace, delta-sized work only
    (aggregate/mod.rs:253). Other aggregators (Min/Max/Fold) use the
    general trace-gather path (aggregate/mod.rs:204,600)."""
    from dbsp_tpu.operators.aggregate_linear import (LinearAggregateOp,
                                                     LinearAggregator)
    from dbsp_tpu.operators.registry import require_schema

    schema = require_schema(self, "aggregate")
    if getattr(self.circuit, "nested_incremental", False):
        # inside a recursive() child: aggregate over the (epoch, iteration)
        # product lattice (reference: aggregate/mod.rs:204,410 is generic
        # over Timestamp incl. NestedTimestamp32). All aggregator kinds go
        # through the four-corner path — the linear fast path's
        # delta-only accumulators are not 2-d-incremental.
        from dbsp_tpu.operators.nested_ops import NestedAggregateOp

        # shard-lifted: group keys co-locate by first-key hash so each
        # worker aggregates complete groups; no-op on one worker
        src = self.shard()
        out = src.circuit.add_unary_operator(
            NestedAggregateOp(agg, schema, src.circuit, name), src)
        out.schema = (tuple(schema[0]), tuple(agg.out_dtypes))
        out.key_sharded = getattr(src, "key_sharded", False)
        return out
    if isinstance(agg, LinearAggregator):
        src = self.shard()  # co-locate keys (no-op on one worker)
        out = src.circuit.add_unary_operator(
            LinearAggregateOp(agg, schema[0], name), src)
        out.schema = (tuple(schema[0]), tuple(agg.out_dtypes))
        out.key_sharded = getattr(src, "key_sharded", False)
        return out
    t = self.trace()
    out = self.circuit.add_unary_operator(
        AggregateOp(agg, schema[0], name), t)
    out.schema = (tuple(schema[0]), tuple(agg.out_dtypes))
    out.key_sharded = getattr(t, "key_sharded", False)
    return out


@stream_method
def stream_aggregate(self: Stream, agg: Aggregator, name=None) -> Stream:
    """Non-incremental variant: aggregates each tick's batch alone
    (aggregate/mod.rs:172) — the differential-testing oracle for
    :func:`aggregate` via ``integrate().stream_aggregate()``."""
    from dbsp_tpu.operators.registry import require_schema

    schema = require_schema(self, "stream_aggregate")
    nk = len(schema[0])
    op_name = name or f"stream_aggregate<{agg.name}>"

    def eval_fn(batch: Batch) -> Batch:
        if batch.sharded:  # oracle path runs host-side; collapse first
            from dbsp_tpu.parallel.exchange import unshard_batch

            batch = unshard_batch(batch)
        qkeys, qlive = _unique_keys(batch, nk)
        q_cap = qlive.shape[-1]
        gg = GroupGather()
        gathered = gg(qkeys, qlive, [batch], q_cap)
        new_vals, new_present = _reduce_groups(tuple(gathered), agg, q_cap)
        w = jnp.where(qlive & new_present, 1, 0).astype(jnp.int64)
        cols, w = kernels.consolidate_cols(
            (*qkeys, *(v for v in new_vals)), w)
        return Batch(cols[:nk], cols[nk:], w, runs=(int(w.shape[-1]),))

    from dbsp_tpu.operators.basic import Apply

    out = self.circuit.add_unary_operator(Apply(eval_fn, op_name), self)
    out.schema = (tuple(schema[0]), tuple(agg.out_dtypes))
    return out
