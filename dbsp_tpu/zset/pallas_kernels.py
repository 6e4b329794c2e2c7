"""Pallas TPU prototypes of the engine's irregular-access inner loops.

XLA:TPU's lowering of the probe/gather loops is the engine's biggest
unknown: dependent gathers lower to while loops at XLA's discretion, which
is exactly the fusion guess the DBSP delta-proportional cost model cannot
afford to lose. These kernels take
that lowering into our own hands (the MegaBlocks move: stop trusting the
compiler on irregular gather/scatter and hand-write the hot loop):

* :func:`lex_probe_ladder_pallas` — the ladder-wide lexicographic binary
  search (``cursor.lex_probe_ladder``) as ONE Pallas program, grid over
  trace levels, each program resolving every query against its level's
  sorted key columns with static block shapes ([K, maxcap] stacked tables,
  [1, m] query lanes).
* :func:`rank_merge_scatter` — the rank-merge inner loop of
  ``kernels.merge_sorted_cols`` (cross-rank binary search + position
  scatter) as a single program; the netting/compaction tail stays shared
  with the XLA path.
* :func:`join_ladder_pallas` / :func:`gather_ladder_pallas` — the FUSED
  trace-ladder consumers (``cursor.join_ladder`` / ``cursor.gather_ladder``)
  as megakernels: grid over the K trace levels with static [K, maxcap]
  stacked blocks, each program probing its level, resolving its window of
  the shared output buffer through in-kernel prefix sums, and gathering its
  level's values — probe + expand + gather + weight-combine in ONE
  ``pallas_call``, with the running cross-level offset carried in the total
  output block across the (sequential) grid.

Selection: :func:`use_pallas`, a static decision per backend. On the CPU
backend (which keeps its native C++ custom calls) the kernels are off
unless ``DBSP_TPU_PALLAS`` is ``1``/``on``/``interpret``, and then run
under the Pallas INTERPRETER — how the tier-1 suite bit-identity-tests
them with no TPU attached. Off the CPU the dispatch selects exactly the
programs the TPU's compiler accepts (``kernels.PALLAS_TPU_COMPILED`` — none
today, see there; ``tests/test_tpu_compile.py`` holds the list to the
compiler's verdict), compiled by Mosaic, never interpreted;
``DBSP_TPU_PALLAS=0``/``off`` forces even those off.

Integer/bool columns only (widened to int64 like the native C++ path —
sign-extension preserves lexicographic order); float columns stay on the
XLA formulation. All outputs are bit-identical to the XLA reference
(tests/test_pallas_kernels.py proves it on adversarial ladders).
"""

from __future__ import annotations

import os
from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl

Cols = Tuple[jnp.ndarray, ...]


def _mode() -> str:
    return os.environ.get("DBSP_TPU_PALLAS", "").strip().lower()


def enabled() -> bool:
    """Is the Pallas tier in play on this backend at all (see module doc)?
    The force-on spellings are shared with the dispatch pre-checks
    (``kernels.PALLAS_FORCE_ON``) so the grammar cannot drift."""
    from dbsp_tpu.zset.kernels import PALLAS_FORCE_ON, PALLAS_TPU_COMPILED

    m = _mode()
    if jax.default_backend() == "cpu":
        return m in PALLAS_FORCE_ON
    if m == "interpret":
        raise RuntimeError(
            "DBSP_TPU_PALLAS=interpret selects the Pallas interpreter, "
            "which is for the CPU backend only; this process runs on "
            f"{jax.default_backend()!r}")
    return m not in ("0", "off", "false") and bool(PALLAS_TPU_COMPILED)


def interpret_mode() -> bool:
    """Run under the Pallas interpreter instead of Mosaic: on the CPU
    backend, always (there is no Mosaic target there; this is what lets the
    tier-1 suite execute these kernels), and nowhere else."""
    return jax.default_backend() == "cpu"


def _supported_dtype(d) -> bool:
    d = jnp.dtype(d)
    return jnp.issubdtype(d, jnp.integer) or d == jnp.bool_


def use_pallas(kernel: str, cols) -> bool:
    """Dispatch gate for one call site: the tier enabled, ``kernel`` (the
    dispatch-counter name: ``probe_ladder`` / ``join_ladder`` /
    ``gather_ladder`` / ``agg_ladder`` / ``segment_reduce`` /
    ``rank_merge``) one the backend's compiler accepts, AND every operand
    column int64-widenable."""
    from dbsp_tpu.zset.kernels import PALLAS_TPU_COMPILED

    if not enabled():
        return False
    if jax.default_backend() != "cpu" and kernel not in PALLAS_TPU_COMPILED:
        return False
    return all(_supported_dtype(c.dtype) for c in cols)


# ---------------------------------------------------------------------------
# Shared in-kernel primitive: vectorized lexicographic binary search
# ---------------------------------------------------------------------------


def _lex_search(table_cols, query_cols, n, steps: int, strict: bool,
                hi_init=None):
    """Insertion points of ``query`` lanes into ``table`` lanes ([1, m]
    int32) — the same mid-split recurrence as ``kernels.lex_probe``, so the
    converged result is bit-identical. ``n`` may be a traced per-level
    cap; ``steps`` must statically cover ceil(log2(n + 1))."""
    m = query_cols[0].shape[-1]
    lo = jnp.zeros((1, m), jnp.int32)
    hi = jnp.full((1, m), n, jnp.int32) if hi_init is None else hi_init

    def step(_, lohi):
        lo, hi = lohi
        active = lo < hi
        mid = (lo + hi) >> 1
        lt = jnp.zeros((1, m), jnp.bool_)
        eq = jnp.ones((1, m), jnp.bool_)
        for t, q in zip(table_cols, query_cols):
            tv = jnp.take_along_axis(t, mid, axis=1)
            lt = lt | (eq & (tv < q))
            eq = eq & (tv == q)
        go_right = lt if strict else (lt | eq)
        lo = jnp.where(active & go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, steps, step, (lo, hi))
    return lo


# ---------------------------------------------------------------------------
# Ladder-wide probe
# ---------------------------------------------------------------------------


def _probe_ladder_kernel(caps_ref, *refs, ncols: int, steps: int,
                         strict: bool):
    tabs = [refs[i][:] for i in range(ncols)]            # [1, maxcap]
    qs = [refs[ncols + i][:] for i in range(ncols)]      # [1, m]
    out_ref = refs[2 * ncols]
    cap = caps_ref[0, 0]
    out_ref[:] = _lex_search(tabs, qs, cap, steps, strict)


def lex_probe_ladder_pallas(tables: Sequence[Cols], query_cols: Cols,
                            side: str = "left") -> jnp.ndarray:
    """Drop-in for the accelerator branch of ``cursor.lex_probe_ladder``:
    grid over the K trace levels, one program per level, each resolving
    all m queries with an in-VMEM binary search over its level's stacked
    (sentinel-padded) key columns. Returns [K, m] int32, lane (k, i) ==
    ``lex_probe(tables[k], query_cols, side)[i]`` bit-for-bit."""
    assert tables and query_cols
    K = len(tables)
    ncols = len(query_cols)
    m = query_cols[0].shape[-1]
    caps = [t[0].shape[-1] for t in tables]
    maxcap = max(caps)
    steps = max(c.bit_length() for c in caps)
    # stack heterogeneous levels into [K, maxcap] per column; the pad value
    # is never read (the search clamps hi to the level's own cap)
    stacked = []
    for ci in range(ncols):
        rows = []
        for t in tables:
            c = t[ci].astype(jnp.int64)
            if c.shape[-1] < maxcap:
                c = jnp.concatenate(
                    [c, jnp.full((maxcap - c.shape[-1],), jnp.iinfo(
                        jnp.int64).max, jnp.int64)])
            rows.append(c)
        stacked.append(jnp.stack(rows))
    qcols = [q.astype(jnp.int64).reshape(1, m) for q in query_cols]
    caps_arr = jnp.asarray(caps, jnp.int32).reshape(K, 1)

    grid = (K,)
    in_specs = [pl.BlockSpec((1, 1), lambda k: (k, 0))]
    in_specs += [pl.BlockSpec((1, maxcap), lambda k: (k, 0))
                 for _ in range(ncols)]
    in_specs += [pl.BlockSpec((1, m), lambda k: (0, 0))
                 for _ in range(ncols)]
    out = pl.pallas_call(
        partial(_probe_ladder_kernel, ncols=ncols, steps=steps,
                strict=side == "left"),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, m), lambda k: (k, 0)),
        out_shape=jax.ShapeDtypeStruct((K, m), jnp.int32),
        interpret=interpret_mode(),
    )(caps_arr, *stacked, *qcols)
    return out


# ---------------------------------------------------------------------------
# Fused ladder-consumer megakernels (probe + expand + gather, one call)
# ---------------------------------------------------------------------------


def _ladder_consumer_kernel(caps_ref, *refs, nk: int, ng: int,
                            steps_tab: int, steps_q: int, join: bool):
    """One grid step = one trace level: probe it, compute this level's
    window of the shared [1, out_cap] output (the running cross-level
    offset rides in the total block — TPU grids are sequential, so program
    k reads the sum of programs 0..k-1's totals), resolve each window slot
    to its (query, source) pair through the level-local prefix sums, and
    gather the level's values + weights into the shared buffers."""
    idx = 0
    tabs = [refs[idx + i][:] for i in range(nk)]            # [1, maxcap]
    idx += nk
    gcols = [refs[idx + i][:] for i in range(ng)]           # [1, maxcap]
    idx += ng
    lw = refs[idx][:]                                       # [1, maxcap]
    idx += 1
    qlo = [refs[idx + i][:] for i in range(nk)]             # [1, m]
    idx += nk
    qhi = [refs[idx + i][:] for i in range(nk)]             # [1, m]
    idx += nk
    qm = refs[idx][:]                                       # [1, m] int64:
    idx += 1                       # delta weights (join) / live 0|1 (gather)
    qrow_ref = refs[idx]
    out_refs = refs[idx + 1: idx + 1 + ng]
    w_ref = refs[idx + 1 + ng]
    tot_ref = refs[idx + 2 + ng]
    k = pl.program_id(0)
    m = qlo[0].shape[-1]
    out_cap = w_ref.shape[-1]

    @pl.when(k == 0)
    def _init():
        qrow_ref[:] = jnp.zeros((1, out_cap), jnp.int32)
        for r in out_refs:
            r[:] = jnp.zeros((1, out_cap), jnp.int64)
        w_ref[:] = jnp.zeros((1, out_cap), jnp.int64)
        tot_ref[:] = jnp.zeros((1, 1), jnp.int64)

    cap = caps_ref[0, 0]
    lo = _lex_search(tabs, qlo, cap, steps_tab, strict=True)
    hi = _lex_search(tabs, qhi, cap, steps_tab, strict=False)
    live = qm != 0
    lo = jnp.where(live, lo, 0)
    # distinct bounds may give an empty range (qhi < qlo): clamp gathers
    # nothing — a no-op for the equality/join form where hi >= lo always
    hi = jnp.where(live, jnp.maximum(hi, lo), lo)
    counts = (hi - lo).astype(jnp.int64)
    csum = jnp.cumsum(counts, axis=-1)
    starts = csum - counts
    tot_k = csum[0, m - 1]
    base = tot_ref[0, 0]
    j = jax.lax.broadcasted_iota(jnp.int64, (1, out_cap), 1)
    local = j - base
    sel = (local >= 0) & (local < tot_k)
    q = jnp.clip(local, 0, jnp.maximum(tot_k - 1, 0))
    # searchsorted-right over the level-local prefix sums == the stitched
    # expand_ladder's slot resolution restricted to this level's window
    flat = _lex_search([starts], [q], m, steps_q, strict=False) - 1
    flat = jnp.clip(flat, 0, m - 1)
    src = (jnp.take_along_axis(lo, flat, axis=1).astype(jnp.int64) + q
           - jnp.take_along_axis(starts, flat, axis=1))
    srci = jnp.clip(src, 0, jnp.maximum(cap - 1, 0)).astype(jnp.int32)
    lw_slot = jnp.take_along_axis(lw, srci, axis=1)
    if join:
        w_slot = jnp.take_along_axis(qm, flat, axis=1) * lw_slot
    else:
        w_slot = lw_slot
    qrow_ref[:] = jnp.where(sel, flat.astype(jnp.int32), qrow_ref[:])
    for r, g in zip(out_refs, gcols):
        r[:] = jnp.where(sel, jnp.take_along_axis(g, srci, axis=1), r[:])
    w_ref[:] = jnp.where(sel, w_slot, w_ref[:])
    tot_ref[:] = jnp.full((1, 1), base + tot_k, jnp.int64)


def _stack_levels(cols_per_level, maxcap: int, pad: int):
    """[K, maxcap] int64 stack of one column across heterogeneous levels
    (the pad value is never read: sources clamp to the level's own cap)."""
    rows = []
    for c in cols_per_level:
        c = c.astype(jnp.int64)
        if c.shape[-1] < maxcap:
            c = jnp.concatenate(
                [c, jnp.full((maxcap - c.shape[-1],), pad, jnp.int64)])
        rows.append(c)
    return jnp.stack(rows)


def _ladder_consumer_call(key_tabs, gather_tabs, weight_tab, qlo_cols,
                          qhi_cols, qmask, out_cap: int, join: bool):
    """Shared pallas_call builder for both megakernels. Returns raw
    ``(qrow, gathered int64 cols, w int64, total)`` — callers mask dead
    slots into their consumer-facing form."""
    K = len(weight_tab)
    nk = len(qlo_cols)
    ng = len(gather_tabs[0]) if gather_tabs else 0
    m = qlo_cols[0].shape[-1]
    caps = [w.shape[-1] for w in weight_tab]
    maxcap = max(caps)
    steps_tab = max(c.bit_length() for c in caps)
    steps_q = m.bit_length()
    pad = int(np.iinfo(np.int64).max)
    stacked = [_stack_levels([t[ci] for t in key_tabs], maxcap, pad)
               for ci in range(nk)]
    stacked += [_stack_levels([t[ci] for t in gather_tabs], maxcap, 0)
                for ci in range(ng)]
    stacked.append(_stack_levels(weight_tab, maxcap, 0))
    qs = [c.astype(jnp.int64).reshape(1, m) for c in qlo_cols]
    qs += [c.astype(jnp.int64).reshape(1, m) for c in qhi_cols]
    qs.append(qmask.astype(jnp.int64).reshape(1, m))
    caps_arr = jnp.asarray(caps, jnp.int32).reshape(K, 1)

    in_specs = [pl.BlockSpec((1, 1), lambda k: (k, 0))]
    in_specs += [pl.BlockSpec((1, maxcap), lambda k: (k, 0))
                 for _ in range(nk + ng + 1)]
    in_specs += [pl.BlockSpec((1, m), lambda k: (0, 0))
                 for _ in range(2 * nk + 1)]
    # every program revisits the SAME output block (index 0): the buffers
    # stay resident across the sequential grid and accumulate level windows
    out_specs = [pl.BlockSpec((1, out_cap), lambda k: (0, 0))
                 for _ in range(ng + 2)]
    out_specs.append(pl.BlockSpec((1, 1), lambda k: (0, 0)))
    out_shape = [jax.ShapeDtypeStruct((1, out_cap), jnp.int32)]
    out_shape += [jax.ShapeDtypeStruct((1, out_cap), jnp.int64)
                  for _ in range(ng + 1)]
    out_shape.append(jax.ShapeDtypeStruct((1, 1), jnp.int64))
    out = pl.pallas_call(
        partial(_ladder_consumer_kernel, nk=nk, ng=ng, steps_tab=steps_tab,
                steps_q=steps_q, join=join),
        grid=(K,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret_mode(),
    )(caps_arr, *stacked, *qs)
    qrow = out[0].reshape(out_cap)
    gathered = tuple(c.reshape(out_cap) for c in out[1:1 + ng])
    w = out[1 + ng].reshape(out_cap)
    total = out[2 + ng].reshape(())
    return qrow, gathered, w, total


def join_ladder_pallas(delta_keys, delta_w, levels, nk: int, out_cap: int):
    """The fused incremental-join core (``cursor.join_ladder`` minus the
    pair function) as ONE Pallas megakernel: both ladder probes, dead-row
    zeroing, cross-level expansion and the level-side value/weight gather.
    Returns ``(qrow, level_val_cols, w, valid, total)``; the caller applies
    the delta-side gathers, the pair function and the sentinel mask."""
    lval_dts = tuple(c.dtype for c in levels[0].vals)
    qrow, gathered, w, total = _ladder_consumer_call(
        [lvl.keys[:nk] for lvl in levels],
        [lvl.vals for lvl in levels],
        [lvl.weights for lvl in levels],
        delta_keys, delta_keys, delta_w, out_cap, join=True)
    j = jnp.arange(out_cap, dtype=jnp.int64)
    valid = j < total
    lvals = tuple(c.astype(d) for c, d in zip(gathered, lval_dts))
    return qrow, lvals, jnp.where(valid, w, 0).astype(delta_w.dtype), \
        valid, total


def gather_ladder_pallas(qkeys, qlive, levels, out_cap: int,
                         qhi_keys=None, gather_keys: int = 0):
    """The fused group gather (``cursor.gather_ladder``) as ONE Pallas
    megakernel, ``qhi_keys``/``gather_keys`` included. Returns the final
    consumer-facing ``((qrow, vals, w), total)`` with dead slots already
    canonical (qrow == q_cap, sentinel vals, weight 0)."""
    from dbsp_tpu.zset import kernels

    nk = len(qkeys)
    q_cap = qlive.shape[-1]
    gtabs = [(*lvl.keys[nk - gather_keys:nk], *lvl.vals) if gather_keys
             else tuple(lvl.vals) for lvl in levels]
    g_dts = tuple(c.dtype for c in gtabs[0])
    qrow, gathered, w, total = _ladder_consumer_call(
        [lvl.keys[:nk] for lvl in levels], gtabs,
        [lvl.weights for lvl in levels],
        qkeys, qkeys if qhi_keys is None else qhi_keys,
        qlive, out_cap, join=False)
    j = jnp.arange(out_cap, dtype=jnp.int64)
    valid = j < total
    vals = tuple(jnp.where(valid, c.astype(d), kernels.sentinel_for(d))
                 for c, d in zip(gathered, g_dts))
    qrow = jnp.where(valid, qrow, jnp.int32(q_cap)).astype(jnp.int32)
    w = jnp.where(valid, w, 0).astype(levels[0].weights.dtype)
    return (qrow, vals, w), total


# ---------------------------------------------------------------------------
# Segment reduction (the Aggregator zoo's five-op vocabulary)
# ---------------------------------------------------------------------------


_SEG_BLOCK = 128  # segments per program — one lane-width output block


def _segment_reduce_kernel(*refs, nv: int, ops):
    """One program = one block of segment ids: broadcast-compare the whole
    (vals, weights, seg) row set against the block's ids and reduce along
    the row axis — a scatter-free formulation (TPU segment scatters are
    exactly the lowering the engine does not trust), bit-identical to the
    ``jax.ops.segment_*`` semantics including identity fills for empty
    segments and dropped out-of-range ids."""
    vals = [refs[i][:] for i in range(nv)]            # [1, n] int64
    wv = refs[nv][:]                                  # [1, n]
    segv = refs[nv + 1][:]                            # [1, n]
    out_refs = refs[nv + 2:]
    sb = out_refs[0].shape[-1]
    s0 = pl.program_id(0) * sb
    sid = s0 + jax.lax.broadcasted_iota(jnp.int64, (sb, 1), 0)
    mask = segv == sid                                # [sb, n]
    wpos = jnp.maximum(wv, 0)
    live = mask & (wv > 0)
    for r, (op, col, ident) in zip(out_refs, ops):
        if op == "count":
            out = jnp.sum(jnp.where(mask, wpos, 0), axis=1)
        elif op == "sum":
            out = jnp.sum(jnp.where(mask, wpos * vals[col], 0), axis=1)
        elif op == "min":
            out = jnp.min(jnp.where(live, vals[col], ident), axis=1)
        elif op == "max":
            out = jnp.max(jnp.where(live, vals[col], ident), axis=1)
        elif op == "avg":
            s = jnp.sum(jnp.where(mask, wpos * vals[col], 0), axis=1)
            c = jnp.maximum(jnp.sum(jnp.where(mask, wpos, 0), axis=1), 1)
            out = jnp.where(s >= 0, s // c, -((-s) // c))
        else:  # present: exact segment_max(where(w>0,1,0)) — EVERY row of
            # the segment participates (retraction-only segments max to 0);
            # only truly empty segments keep the int64-min identity fill
            out = jnp.max(
                jnp.where(mask, (wv > 0).astype(jnp.int64), ident), axis=1)
        r[:] = out[None, :].astype(jnp.int64)


def segment_reduce_pallas(spec, val_cols, weights: jnp.ndarray,
                          seg: jnp.ndarray, num_segments: int, out_dtypes):
    """Drop-in for the accelerator branch of
    ``operators.aggregate.segment_reduce``: ONE Pallas program per
    :data:`_SEG_BLOCK` segment ids runs the WHOLE reduce spec (count / sum
    / min / max / avg / present) over the row set — where the XLA
    formulation paid 2-4 masked segment ops per output."""
    n = weights.shape[-1]
    nv = len(val_cols)
    nseg_pad = -(-num_segments // _SEG_BLOCK) * _SEG_BLOCK
    # int-only columns by the use_pallas gate, so the int64-widened
    # identities are exact
    ops = tuple((op, col, _seg_ident(op, col, val_cols))
                for op, col in spec)
    operands = [c.astype(jnp.int64).reshape(1, n) for c in val_cols]
    operands.append(weights.astype(jnp.int64).reshape(1, n))
    operands.append(seg.astype(jnp.int64).reshape(1, n))
    in_specs = [pl.BlockSpec((1, n), lambda b: (0, 0))
                for _ in range(nv + 2)]
    out_specs = [pl.BlockSpec((1, _SEG_BLOCK), lambda b: (0, b))
                 for _ in spec]
    out_shape = [jax.ShapeDtypeStruct((1, nseg_pad), jnp.int64)
                 for _ in spec]
    out = pl.pallas_call(
        partial(_segment_reduce_kernel, nv=nv, ops=ops),
        grid=(nseg_pad // _SEG_BLOCK,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret_mode(),
    )(*operands)
    return tuple(c.reshape(nseg_pad)[:num_segments].astype(d)
                 for c, d in zip(out, out_dtypes))


def _seg_ident(op: str, col: int, val_cols) -> int:
    from dbsp_tpu.zset.native_merge import seg_op_identity

    src = val_cols[col].dtype if op in ("min", "max") else jnp.int64
    return seg_op_identity(op, src)


def agg_ladder_pallas(delta, nk: int, out_trace, levels, agg, q_cap: int,
                      gather_cap: int, fast: bool, flag):
    """The accelerator lowering of ``cursor.agg_ladder``: the same chain as
    the stitched control, with its two heavy phases on hand-written Pallas
    programs — the grid-over-levels GATHER megakernel
    (:func:`gather_ladder_pallas`, selected inside ``cursor.gather_ladder``
    when Pallas is on) and the spec'd segment reduction
    (:func:`segment_reduce_pallas`, selected inside
    ``operators.aggregate.segment_reduce``). The run-boundary compaction
    and the cross-level netting stay ``lax``-native (sort-free compaction;
    the netting sort is the rank-merge regime's problem on TPU) — by
    construction bit-identical to every other backend."""
    from dbsp_tpu.zset import cursor

    return cursor._agg_ladder_stitched(delta, nk, out_trace, levels, agg,
                                       q_cap, gather_cap, fast, flag)


# ---------------------------------------------------------------------------
# Rank-merge inner loop (cross-rank probe + position scatter)
# ---------------------------------------------------------------------------


def _rank_merge_kernel(*refs, ncols: int, na: int, nb: int, steps_a: int,
                       steps_b: int):
    acols = [refs[i][:] for i in range(ncols)]                   # [1, na]
    wa = refs[ncols][:]
    bcols = [refs[ncols + 1 + i][:] for i in range(ncols)]       # [1, nb]
    wb = refs[2 * ncols + 1][:]
    sent_ref = refs[2 * ncols + 2]                               # [1, ncols]
    out_refs = refs[2 * ncols + 3: 3 * ncols + 3]
    ow_ref = refs[3 * ncols + 3]
    # cross-ranks: b-rows strictly before a_i; a-rows at-or-before b_j —
    # the bijective position map of kernels.merge_sorted_cols' rank path
    ra = _lex_search(bcols, acols, nb, steps_b, strict=True)
    rb = _lex_search(acols, bcols, na, steps_a, strict=False)
    pos_a = (jax.lax.broadcasted_iota(jnp.int32, (1, na), 1) + ra)[0]
    pos_b = (jax.lax.broadcasted_iota(jnp.int32, (1, nb), 1) + rb)[0]
    for ci in range(ncols):
        buf = jnp.full((na + nb,), sent_ref[0, ci], jnp.int64)
        buf = buf.at[pos_a].set(acols[ci][0]).at[pos_b].set(bcols[ci][0])
        out_refs[ci][:] = buf[None, :]
    w = jnp.zeros((na + nb,), jnp.int64)
    w = w.at[pos_a].set(wa[0]).at[pos_b].set(wb[0])
    ow_ref[:] = w[None, :]


def rank_merge_scatter(cols_a: Cols, w_a: jnp.ndarray, cols_b: Cols,
                       w_b: jnp.ndarray):
    """The rank-merge inner loop as ONE Pallas program: both cross-rank
    binary searches plus the position scatters of every column and the
    weights. Returns the scattered (pre-netting) ``(cols, w)`` buffers of
    capacity na+nb — bit-identical to the ``.at[pos].set`` formulation in
    ``kernels.merge_sorted_cols``; the caller's netting + compaction tail
    is unchanged."""
    ncols = len(cols_a)
    assert ncols and w_a.ndim == 1 and w_b.ndim == 1
    na, nb = int(w_a.shape[0]), int(w_b.shape[0])
    dtypes = tuple(c.dtype for c in cols_a)
    sent = jnp.asarray(
        [1 if np.dtype(d) == np.bool_ else int(np.iinfo(np.dtype(d)).max)
         for d in dtypes], jnp.int64).reshape(1, ncols)
    a64 = [c.astype(jnp.int64).reshape(1, na) for c in cols_a]
    b64 = [c.astype(jnp.int64).reshape(1, nb) for c in cols_b]
    out_shapes = tuple(jax.ShapeDtypeStruct((1, na + nb), jnp.int64)
                       for _ in range(ncols + 1))
    out = pl.pallas_call(
        partial(_rank_merge_kernel, ncols=ncols, na=na, nb=nb,
                steps_a=na.bit_length(), steps_b=nb.bit_length()),
        out_shape=out_shapes,
        interpret=interpret_mode(),
    )(*a64, w_a.astype(jnp.int64).reshape(1, na),
      *b64, w_b.astype(jnp.int64).reshape(1, nb), sent)
    out_cols = tuple(c.reshape(na + nb).astype(d)
                     for c, d in zip(out[:ncols], dtypes))
    w = out[ncols].reshape(na + nb).astype(w_a.dtype)
    return out_cols, w
