"""Fused trace cursors: probe EVERY level of a trace ladder in one kernel.

A trace (host ``Spine`` or the compiled leveled state) is a small set of
consolidated batches in geometric capacity classes. Every traced operator
used to probe it one level at a time: K probe launches, K expansion buffers
with K grow-on-demand capacities, then a concat (+ full sort on the host
path) to combine — per-tick kernel count proportional to delta x
spine-depth, where the DBSP cost model (VLDB'23) wants it proportional to
the delta alone.

This module collapses that fan-out (the engine's answer to the reference's
``CursorList`` k-way merge cursor, ``trace/cursor/cursor_list.rs``):

* :func:`lex_probe_ladder` — the insertion points of the query rows in
  EVERY level, ``[K, m]``. On CPU with the native library ONE ladder-wide
  C++ probe call. Else one of two formulations PER LEVEL, same lanes bit
  for bit: the binary search (``kernels._probe_search``: one gather of
  ``m`` elements a key column at each of the level's ``cap.bit_length()``
  steps), or — on an accelerator, for a caller that states its queries
  are SORTED — one merge (``kernels.rank_sorted``: both operands are
  sorted, so a query's insertion point is the count of table rows ahead
  of it in the merged order; the merge network and the shift compaction
  stream whole columns and gather nothing). ``kernels.rank_by_merge``
  chooses from the two shapes alone, by two rates read on a TPU v5 lite:
  ``PROBE_GATHER_NS`` (a gathered element) and ``PROBE_PASS_NS`` (a row
  of one column through one stage). A wide delta against levels of its
  own order is merged; a few thousand lanes against millions of rows keep
  the search. ``join_ladder``, ``old_weights_ladder`` (a delta tagged as
  one consolidated run) and the aggregate's equality ``gather_ladder``
  (the front-packed unique keys of such a delta) make the statement; the
  range form and every other caller search.
* :func:`expand_ladder` — ONE ``expand_ranges``-style prefix-sum allocation
  whose [K*m] counts span levels: each output slot resolves to (level,
  query row, source row) through a single searchsorted over the cross-level
  prefix sums. Level-major order, so the output layout matches the old
  offset-scatter scheme exactly.
* :func:`_select_gather` — each slot's cell of every gathered column, the
  weights with the values in one call. On an accelerator one of two
  formulations, the same cells bit for bit: one clamped gather per level
  per column and a select, or — where ``kernels.gather_flat`` prices it
  cheaper from the shapes (a wide gather from a deep ladder) — one gather
  a column from the levels laid end to end.
* :func:`join_ladder` / :func:`gather_ladder` / :func:`old_weights_ladder`
  — the three hot consumers (incremental join, aggregate group gather,
  distinct old-weight lookup) as single fused kernels over the ladder.
  On CPU with the native library each consumer is ONE megakernel custom
  call (probe + expand + gather + weight-combine —
  ``native_merge.join_ladder_native`` & co.); the stitched
  probe-ladder/expand/gather chain below is the pure-XLA formulation —
  what an accelerator runs — and the force-off A/B control
  (``DBSP_TPU_NATIVE=join_ladder`` etc. — see
  ``native_merge.kernel_enabled``).

All functions are pure/traceable over 1-D row axes; sharded callers lift
them per worker exactly like the per-level kernels they replace
(``parallel/lift.py``). Outputs are bit-identical to the per-level loops:
the same (row, weight) multiset in the same level-major order, with dead
padding packed at the tail instead of scattered per level
(tests/test_cursor.py proves both).
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

from dbsp_tpu.zset import kernels
from dbsp_tpu.zset.batch import Batch

Cols = Tuple[jnp.ndarray, ...]


# ---------------------------------------------------------------------------
# Fused probe
# ---------------------------------------------------------------------------


@kernels._scoped
def lex_probe_ladder(tables: Sequence[Cols], query_cols: Cols,
                     side: str = "left", sorted_queries: bool = False
                     ) -> jnp.ndarray:
    """Insertion points of ``query`` rows into EVERY sorted table at once.

    ``tables`` is one tuple of key columns per trace level (heterogeneous
    capacities are fine — each level's lanes clamp to its own row count);
    returns ``[K, m]`` int32. Lane (k, i) equals
    ``lex_probe(tables[k], query_cols, side)[i]`` exactly.

    ``sorted_queries`` is the caller's statement that ``query_cols`` are
    sorted (a consolidated delta's keys, dead sentinel rows at the tail).
    On an accelerator each level then takes whichever formulation is
    cheaper for its two shapes (:func:`kernels.rank_by_merge`): the merge
    of :func:`kernels.rank_sorted`, or the binary search with that level's
    own number of steps.
    """
    assert tables, "lex_probe_ladder: empty ladder"
    flat = query_cols[0].ndim == 1
    if flat:
        dts = [c.dtype for t in tables for c in t]
        if kernels.native_kernel("probe_ladder"):
            from dbsp_tpu.zset import native_merge

            if native_merge.supports(
                    (*dts, *(c.dtype for c in query_cols))):
                kernels.count_kernel_dispatch("probe_ladder", "native")
                return native_merge.lex_probe_ladder_native(
                    tables, query_cols, side)
    return jnp.stack([kernels.rank_or_search(
        t, query_cols, side, sorted_queries and flat, "probe_ladder")
        for t in tables])


# ---------------------------------------------------------------------------
# Fused expansion
# ---------------------------------------------------------------------------


@kernels._scoped
def expand_ladder(lo: jnp.ndarray, hi: jnp.ndarray, out_cap: int):
    """Flatten ``[K, m]`` per-(level, query) ranges into ONE static buffer.

    Level-major: slot order is level 0's matches (query-major within the
    level), then level 1's, ... — the same layout the per-level
    offset-scatter produced. Returns ``(level, qrow, src, valid, total)``
    each of shape [out_cap] (total is the unclamped device scalar; the
    standard overflow contract of :func:`kernels.expand_ranges` applies).
    """
    K, m = lo.shape
    if kernels.native_kernel("expand"):
        kernels.count_kernel_dispatch("expand", "native")
        from dbsp_tpu.zset import native_merge

        flat, src, valid, total = native_merge.expand_ranges_native(
            lo.reshape(K * m), hi.reshape(K * m), out_cap)
        level = flat // m
        qrow = flat - level * m
        return level, qrow, src, valid, total
    kernels.count_kernel_dispatch("expand", "xla")
    counts = jnp.maximum(hi - lo, 0).reshape(K * m)
    starts = jnp.cumsum(counts) - counts
    # the OVERFLOW total accumulates in 64-bit: a ladder-wide match count
    # past 2^31 would wrap an int32 sum negative and defeat the runner's
    # requirement check. Slot resolution below stays int32: a wrapped
    # prefix-sum WOULD corrupt even valid slots, but any such launch has
    # total > out_cap by orders of magnitude, so the int64 total forces a
    # grow/replay (host) or overflow replay (compiled) and the garbage
    # buffer is discarded unread.
    total = jnp.sum(counts, dtype=jnp.int64)
    j = jnp.arange(out_cap, dtype=jnp.int32)
    flat = kernels.searchsorted1(starts, jnp.minimum(j, total - 1),
                                 side="right") - 1
    flat = jnp.clip(flat, 0, K * m - 1)
    offset = j - starts[flat]
    src = lo.reshape(K * m)[flat] + offset
    valid = j < total
    level = flat // m
    qrow = flat - level * m
    return level, qrow, src.astype(jnp.int32), valid, total


@kernels._scoped
def _select_gather(cols_per_level: Sequence[Cols], level: jnp.ndarray,
                   src: jnp.ndarray) -> Cols:
    """Gather column values from the level each output slot resolved to:
    the cell ``(level, clip(src, 0, cap[level] - 1))`` of every column, so
    dead slots read a clamped cell too. Three formulations, bit-identical:
    on CPU with the native library ONE C++ pass (ZsetGatherImpl); on an
    accelerator where :func:`kernels.gather_flat` prices it cheaper, one
    gather a column from the levels laid end to end
    (:func:`_flat_gather`); else one clamped gather per level per column,
    combined by level-id select (no scatters, no per-level buffers)."""
    if level.ndim == 1 and kernels.native_kernel("gather"):
        from dbsp_tpu.zset import native_merge

        if native_merge.supports(c.dtype for cols in cols_per_level
                                 for c in cols):
            kernels.count_kernel_dispatch("gather", "native")
            return native_merge.gather_levels_native(cols_per_level, level,
                                                     src)
    caps = [cols[0].shape[0] for cols in cols_per_level]
    if kernels.accelerator() and kernels.gather_flat(
            level.shape[0], caps, len(cols_per_level[0])):
        kernels.count_kernel_dispatch("gather", "xla_flat")
        return _flat_gather(cols_per_level, level, src)
    kernels.count_kernel_dispatch("gather", "xla")
    return _level_gather(cols_per_level, level, src)


def _level_gather(cols_per_level: Sequence[Cols], level: jnp.ndarray,
                  src: jnp.ndarray) -> Cols:
    """One clamped gather per level per column, combined by level-id
    select: a slot whose level is out of range keeps level 0's cell."""
    outs: List[jnp.ndarray] = []
    for ci in range(len(cols_per_level[0])):
        acc = None
        for k, cols in enumerate(cols_per_level):
            c = cols[ci]
            v = c[jnp.clip(src, 0, c.shape[0] - 1)]
            acc = v if acc is None else jnp.where(level == k, v, acc)
        outs.append(acc)
    return tuple(outs)


def _flat_gather(cols_per_level: Sequence[Cols], level: jnp.ndarray,
                 src: jnp.ndarray) -> Cols:
    """The accelerator's flat gather: each column's levels concatenated
    along the row axis, then ONE gather at ``base[level] + clip(src, 0,
    cap[level] - 1)``, where ``base`` are the static offsets of the levels
    in the concatenation. ``base`` and ``cap`` are picked per slot by an
    int32 select over the levels, in the per-level form's order (a slot
    whose level is out of range reads level 0), so each slot reads the
    cell that form selects. The concatenation is a materialized buffer,
    which the gather reads once a slot."""
    caps = [cols[0].shape[0] for cols in cols_per_level]
    assert sum(caps) < 2 ** 31, "flat gather: offsets overflow int32"
    offsets = [0]
    for cap in caps[:-1]:
        offsets.append(offsets[-1] + cap)
    base = jnp.full(level.shape, offsets[0], jnp.int32)
    hi = jnp.full(level.shape, caps[0] - 1, jnp.int32)
    for k in range(1, len(caps)):
        base = jnp.where(level == k, offsets[k], base)
        hi = jnp.where(level == k, caps[k] - 1, hi)
    g = base + jnp.clip(src.astype(jnp.int32), 0, hi)
    return tuple(
        jnp.concatenate([cols[ci] for cols in cols_per_level]).at[g].get(
            mode="promise_in_bounds", wrap_negative_indices=False)
        for ci in range(len(cols_per_level[0])))


# ---------------------------------------------------------------------------
# Fused consumers
# ---------------------------------------------------------------------------


def _finish_join(fn, key_cols, lvals, rvals, w, valid, total
                 ) -> Tuple[Batch, jnp.ndarray]:
    """Apply the pair function + dead-slot sentinel mask — the (cheap,
    elementwise) tail every join_ladder backend shares, so the fused
    megakernels and the stitched chain produce bit-identical batches."""
    out_keys, out_vals = fn(key_cols, lvals, rvals)
    # dead slots must carry sentinels so they sort to the tail later
    out_keys = tuple(jnp.where(valid, c, kernels.sentinel_for(c.dtype))
                     for c in out_keys)
    out_vals = tuple(jnp.where(valid, c, kernels.sentinel_for(c.dtype))
                     for c in out_vals)
    return Batch(out_keys, out_vals, w), total


def _ladder_dtypes(delta: Batch, levels: Sequence[Batch]):
    return (*(c.dtype for c in delta.cols), delta.weights.dtype,
            *(c.dtype for lvl in levels for c in (*lvl.cols, lvl.weights)))


def join_ladder(delta: Batch, levels: Sequence[Batch], nk: int, fn,
                out_cap: int, sorted_emit=None) -> Tuple[Batch, jnp.ndarray]:
    """Join a delta against ALL trace levels: one probe pair, one expansion,
    one output buffer. Replaces the per-level ``_join_level_impl`` loop
    (operators/join.py) and the compiled offset-scatter (cnodes).

    Output is RAW (callers consolidate once); the returned total is the
    UNCLAMPED cross-level requirement — when it exceeds ``out_cap`` the
    tail matches drop off the end and the caller grows + relaunches
    (host) or the runner's validation replays (compiled).

    ``sorted_emit`` — ``(n_out_keys, perm, out_dtypes)`` when the pair
    function is a pure column PERMUTATION of the raw (probed keys, delta
    vals, level vals) columns (``operators.join.fn_permutation`` probes the
    fn to find out) — selects the SORTED-EMIT megakernel on the native CPU
    path: the projection is applied in-call and the side's buffer comes
    back as ONE consolidated run (``runs=(out_cap,)``), so the caller's
    post-join ``concat().consolidate()`` rank-folds two runs with a single
    linear merge instead of full-sorting the doubled buffer, and the
    pair-fn + dead-slot-mask XLA passes disappear. The emitted Z-set is
    identical (netting only canonicalizes), so the post-consolidation
    batch is bit-identical to every other backend; the
    ``DBSP_TPU_NATIVE=join_sorted`` force-off is the A/B control.

    Backend dispatch (1-D operands, int64-widenable columns): ONE native
    megakernel custom call on CPU (probe + expand + both-side gathers +
    weight product — ``native_merge.join_ladder_native``); else the
    stitched probe-ladder/expand/gather chain below (also the
    ``DBSP_TPU_NATIVE=join_ladder`` force-off control).
    """
    assert levels, "join_ladder: trace has no levels"
    dk = delta.keys[:nk]
    if nk >= 1 and delta.weights.ndim == 1 and out_cap >= 1:
        if sorted_emit is not None and kernels.native_kernel("join_sorted"):
            from dbsp_tpu.zset import native_merge

            n_out_keys, perm, out_dts = sorted_emit
            if native_merge.supports((*_ladder_dtypes(delta, levels),
                                      *out_dts)):
                kernels.count_kernel_dispatch("join_sorted", "native")
                return native_merge.join_ladder_sorted_native(
                    delta, levels, nk, perm, n_out_keys, out_dts, out_cap)
        if kernels.native_kernel("join_ladder"):
            from dbsp_tpu.zset import native_merge

            if native_merge.supports(_ladder_dtypes(delta, levels)):
                kernels.count_kernel_dispatch("join_ladder", "native")
                key_cols, lvals, rvals, w, valid, total = \
                    native_merge.join_ladder_native(delta, levels, nk,
                                                    out_cap)
                return _finish_join(fn, key_cols, lvals, rvals, w, valid,
                                    total)
    kernels.count_kernel_dispatch("join_ladder", "xla")
    tables = [lvl.keys[:nk] for lvl in levels]
    # a consolidated delta's keys are sorted: the probes may rank by merge
    claim = delta.sorted_runs == 1
    lo = lex_probe_ladder(tables, dk, "left", sorted_queries=claim)
    hi = lex_probe_ladder(tables, dk, "right", sorted_queries=claim)
    # dead delta rows carry sentinel keys, which match every level's dead
    # tail — zero their ranges instead of emitting weight-0 garbage
    live = delta.weights != 0
    lo = jnp.where(live[None, :], lo, 0)
    hi = jnp.where(live[None, :], hi, lo)
    level, qrow, src, valid, total = expand_ladder(lo, hi, out_cap)
    lw, *rvals = _select_gather([(lvl.weights, *lvl.vals) for lvl in levels],
                                level, src)
    w = jnp.where(valid, delta.weights[qrow] * lw, 0)
    key_cols = tuple(c[qrow] for c in dk)
    lvals = tuple(c[qrow] for c in delta.vals)
    return _finish_join(fn, key_cols, lvals, tuple(rvals), w, valid, total)


def gather_ladder(qkeys: Cols, qlive: jnp.ndarray, levels: Sequence[Batch],
                  out_cap: int, qhi_keys: Cols = None,
                  gather_keys: int = 0, sorted_queries: bool = False):
    """Gather the query keys' rows from ALL trace levels into one
    (qrow, val_cols, w) part of capacity ``out_cap``. Dead slots carry
    qrow == q_cap (the trash segment) and sentinel vals — the same contract
    as the per-level gather + offset scatter it replaces. Returns
    ``(part, unclamped total)``.

    The ONE leveled-gather entry point, shared by equality and range
    consumers (the aggregate family, rolling aggregates, the radix time
    index): ``qhi_keys`` optionally gives DISTINCT upper-bound query
    columns for the right-side probe — each query then matches the key
    range [qkeys[i], qhi_keys[i]] instead of the exact group (empty
    ranges, qhi < qlo, gather nothing); ``gather_keys`` returns that many
    trailing PROBED KEY columns ahead of the vals (range gathers need the
    time column back; equality gathers already hold their keys).
    ``sorted_queries`` states that ``qkeys`` are sorted (the front-packed
    unique keys of a consolidated delta): the equality form's probes may
    then rank by merge (:func:`lex_probe_ladder`); the range form always
    searches.

    NOTE: with K > 1 the part may hold cross-level insert/retract rows for
    one (qrow, vals) — reducers must net them
    (``_reduce_groups_impl(..., net=True)``), exactly as with the old
    combined buffer.

    Backend dispatch mirrors :func:`join_ladder`: ONE native megakernel
    custom call on CPU (``native_merge.gather_ladder_native`` — the part
    comes back final, dead slots canonical), else the stitched chain (the
    ``DBSP_TPU_NATIVE=gather_ladder`` force-off control)."""
    assert levels, "gather_ladder: trace has no levels"
    nk = len(qkeys)
    q_cap = qlive.shape[-1]
    if nk >= 1 and qlive.ndim == 1 and out_cap >= 1:
        _all_cols = (*qkeys, *(qhi_keys or ()),
                     *(c for lvl in levels
                       for c in (*lvl.cols, lvl.weights)))
        if kernels.native_kernel("gather_ladder"):
            from dbsp_tpu.zset import native_merge

            if native_merge.supports(c.dtype for c in _all_cols):
                kernels.count_kernel_dispatch("gather_ladder", "native")
                return native_merge.gather_ladder_native(
                    qkeys, qlive, levels, out_cap, qhi_keys=qhi_keys,
                    gather_keys=gather_keys)
    kernels.count_kernel_dispatch("gather_ladder", "xla")
    tables = [lvl.keys[:nk] for lvl in levels]
    claim = sorted_queries and qhi_keys is None
    lo = lex_probe_ladder(tables, qkeys, "left", sorted_queries=claim)
    hi = lex_probe_ladder(tables, qkeys if qhi_keys is None else qhi_keys,
                          "right", sorted_queries=claim)
    lo = jnp.where(qlive[None, :], lo, 0)
    # probes are monotone, so with distinct bounds an empty query range
    # (qhi < qlo) lands hi <= lo — the clamp makes it gather nothing;
    # with qhi_keys=None hi >= lo always holds and the clamp is a no-op
    hi = jnp.where(qlive[None, :], jnp.maximum(hi, lo), lo)
    level, qrow, src, valid, total = expand_ladder(lo, hi, out_cap)
    lw, *gathered = _select_gather(
        [(lvl.weights, *lvl.keys[nk - gather_keys:nk], *lvl.vals)
         for lvl in levels], level, src)
    w = jnp.where(valid, lw, 0)
    vals = tuple(jnp.where(valid, v, kernels.sentinel_for(v.dtype))
                 for v in gathered)
    qrow = jnp.where(valid, qrow, jnp.int32(q_cap)).astype(jnp.int32)
    return (qrow, vals, w), total


def agg_ladder(delta: Batch, nk: int, out_trace: Batch,
               levels: Sequence[Batch], agg, q_cap: int, gather_cap: int,
               fast: bool, flag: jnp.ndarray):
    """The WHOLE general-aggregate reduce chain over a trace ladder in one
    entry point — unique touched keys (run-boundary scan of the
    consolidated delta), the previous outputs from the operator's own
    out-trace (exact-match probe + per-column ``_TupleMax``), the touched
    groups' ladder histories netted across levels and reduced by the
    aggregator's :func:`~dbsp_tpu.operators.aggregate.segment_reduce`
    spec, and (``fast`` mode) the delta's own reduction from the same run
    scan. ``flag`` is the RUNTIME ladder gate: ``ever_negative`` on the
    insert-combinable fast path (the slow re-gather engages only once a
    retraction has entered the stream), constant true on the general path.

    Returns ``(qkeys, qlive, nq, old_vals, old_present, lad_vals,
    lad_present, d_vals, d_present, gather_total)`` — ``nq`` and
    ``gather_total`` are the UNCLAMPED ``queries``/``gather`` capacity
    requirements (the standard grow/replay contract; on overflow the
    clamped buffers match the stitched chain bit for bit and are discarded
    by the replay either way).

    Backend dispatch mirrors :func:`join_ladder`: ONE native megakernel
    custom call on CPU for spec'd aggregators
    (``native_merge.agg_ladder_native`` — the gathered history never
    materializes at all); else the stitched unique-keys/gather/net/reduce
    chain below (also the ``DBSP_TPU_NATIVE=agg_ladder`` force-off
    control)."""
    from dbsp_tpu.operators import aggregate as A

    assert levels, "agg_ladder: trace has no levels"
    spec = agg.reduce_spec()
    # the fused backends assume the CAggregate state shape: the out trace
    # carries exactly one value column per aggregate output, and the
    # ladder levels share the delta's value schema (they are its integral)
    fusable = (spec is not None and nk >= 1 and delta.weights.ndim == 1
               and q_cap >= 1 and gather_cap >= 1
               and len(out_trace.vals) == len(spec)
               and len(levels[0].vals) == len(delta.vals)
               # avg divides — fused int64 accumulation equals the XLA
               # wrap only for int64 results (see segment_reduce)
               and all(op != "avg" or jnp.promote_types(
                           levels[0].vals[col].dtype,
                           levels[0].weights.dtype) == jnp.int64
                       for op, col in spec))
    if fusable:
        lad_dts = tuple(
            A._seg_out_dtype(op, col, levels[0].vals, levels[0].weights)
            for op, col in spec)
        d_dts = tuple(
            A._seg_out_dtype(op, col, delta.vals, delta.weights)
            for op, col in spec)
        _all_cols = (*delta.cols, delta.weights, *out_trace.cols,
                     out_trace.weights,
                     *(c for lvl in levels for c in (*lvl.cols,
                                                     lvl.weights)))
        if kernels.native_kernel("agg_ladder"):
            from dbsp_tpu.zset import native_merge

            if native_merge.supports(c.dtype for c in _all_cols):
                kernels.count_kernel_dispatch("agg_ladder", "native")
                return native_merge.agg_ladder_native(
                    delta, nk, out_trace, levels, spec, q_cap, gather_cap,
                    fast, flag, lad_dts, d_dts)
    kernels.count_kernel_dispatch("agg_ladder", "xla")
    return _agg_ladder_stitched(delta, nk, out_trace, levels, agg, q_cap,
                                gather_cap, fast, flag)


def _agg_ladder_stitched(delta: Batch, nk: int, out_trace: Batch, levels,
                         agg, q_cap: int, gather_cap: int, fast: bool,
                         flag):
    """The pure-XLA formulation and force-off A/B control: the chain
    CAggregate.eval used to stitch inline, with the run-boundary scan done
    ONCE (``_delta_groups_impl`` feeds both the unique-key compaction and
    the fast path's segment ids — the boundaries were previously scanned
    twice)."""
    from dbsp_tpu.operators import aggregate as A

    qkeys_full, qlive_full, anylive, seg_full = A._delta_groups_impl(
        delta, nk)
    nq = jnp.sum(qlive_full)
    qkeys = tuple(c[..., :q_cap] for c in qkeys_full)
    qlive = qlive_full[..., :q_cap]

    # previous outputs: the out trace holds one live row per present key,
    # so a q_cap expansion is exact; the unique keys of a consolidated
    # delta, front-packed, are sorted
    claim = delta.sorted_runs == 1
    oqrow, ovals, ow, _ = A._gather_level_impl(qkeys, qlive, out_trace,
                                               q_cap, sorted_queries=claim)
    old_vals, old_present = A._reduce_groups_impl(
        ((oqrow, ovals, ow),), A._TupleMax(len(agg.out_dtypes)), q_cap)

    if fast:
        seg = jnp.where(anylive, seg_full, q_cap).astype(jnp.int32)
        d_vals = tuple(o[:q_cap] for o in agg.reduce(
            delta.vals, delta.weights, seg, q_cap + 1))
        one = jnp.where(delta.weights > 0, 1, 0)
        d_present = jax.ops.segment_max(
            one, seg, num_segments=q_cap + 1)[:q_cap] > 0
    else:
        d_vals, d_present = None, None  # general path never reads them
    mask = qlive & jnp.broadcast_to(flag, qlive.shape)
    part, gtot = gather_ladder(qkeys, mask, levels, gather_cap,
                               sorted_queries=claim)
    lad_vals, lad_present = A._reduce_groups_impl(
        (part,), agg, q_cap, net=len(levels) > 1)
    return (qkeys, qlive, nq, old_vals, old_present, lad_vals, lad_present,
            d_vals, d_present, gtot.astype(jnp.int64))


def old_weights_ladder(delta: Batch, levels: Sequence[Batch]) -> jnp.ndarray:
    """Accumulated weight of each delta ROW (keys+vals) across ALL levels —
    the fused form of distinct's per-level probe-and-sum. Rows are unique
    within a consolidated level, so each (level, row) range is 0 or 1 wide;
    present weights sum across levels. ONE native custom call on CPU
    (``native_merge.old_weights_ladder_native``); the stitched probe pair
    below is the fallback and the ``DBSP_TPU_NATIVE=old_weights`` control."""
    assert levels, "old_weights_ladder: trace has no levels"
    if len(delta.cols) >= 1 and delta.weights.ndim == 1 and \
            kernels.native_kernel("old_weights"):
        from dbsp_tpu.zset import native_merge

        if native_merge.supports(_ladder_dtypes(delta, levels)):
            kernels.count_kernel_dispatch("old_weights", "native")
            return native_merge.old_weights_ladder_native(delta, levels)
    kernels.count_kernel_dispatch("old_weights", "xla")
    cols = delta.cols
    tables = [lvl.cols for lvl in levels]
    claim = delta.sorted_runs == 1  # whole rows of a consolidated delta
    lo = lex_probe_ladder(tables, cols, "left", sorted_queries=claim)
    hi = lex_probe_ladder(tables, cols, "right", sorted_queries=claim)
    live = delta.weights != 0
    found = (hi > lo) & live[None, :]
    old = jnp.zeros_like(delta.weights)
    for k, lvl in enumerate(levels):
        w = lvl.weights[jnp.minimum(lo[k], lvl.cap - 1)]
        old = old + jnp.where(found[k], w, 0)
    return old
