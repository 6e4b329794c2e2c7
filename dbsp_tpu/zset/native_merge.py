"""Native (C++) sorted-merge bridge for the CPU backend.

``merge_sorted_cols`` (zset/kernels.py) combines two consolidated runs.  On
CPU its sort strategy pays for a comparator-based multi-operand ``lax.sort``
of the full combined capacity — measured ~1.2s for a 1.5M-row 7-column
merge, which made spine tail merges the dominant cost of state-heavy
queries (Nexmark q4).  Two already-sorted runs need no sort at all: this
module routes the merge through an **XLA FFI custom call**
(native/zset_merge.cpp) — a C++ two-pointer walk that nets equal rows,
drops zero weights, packs survivors and sentinel-fills the tail,
bit-identical to the XLA path.  The FFI route keeps the whole compiled
circuit program on the XLA executor with zero Python round-trips per merge
(a ``jax.pure_callback`` route was tried first and deadlocks XLA:CPU when
converting >=8MB operands on the callback thread).

Only integer/bool columns take this path (every column is widened to int64
for the call; sign-extension preserves lexicographic order).  Float columns
fall back to the XLA sort.  The TPU backend never loads this library — its
merge network is pure XLA and runs on-device (kernels.merge_strategy).

Reference analog: the pairwise batch merger the spine drives,
crates/dbsp/src/trace/ord/merge_batcher.rs (the same two-pointer walk,
generic over Rust ords instead of columns).
"""

from __future__ import annotations

import ctypes
import os
import sys
import threading
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


_FFI = jax.ffi


def _vma_of(x):
    """Varying-manual-axes tag of a traced value (empty outside
    shard_map)."""
    return jax.typeof(x).vma

_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "zset_merge.cpp")
_SO = os.path.join(_REPO_ROOT, "native", "libzset_merge.so")

_lib: Optional[ctypes.CDLL] = None
_registered = False
_build_error: Optional[str] = None
_lock = threading.Lock()

_PP = ctypes.POINTER(ctypes.c_int64)

FFI_TARGET = "dbsp_zset_merge"
PROBE_TARGET = "dbsp_zset_probe"
CONSOLIDATE_TARGET = "dbsp_zset_consolidate"
EXPAND_TARGET = "dbsp_zset_expand"
GATHER_TARGET = "dbsp_zset_gather"
COMPACT_TARGET = "dbsp_zset_compact"
PROBE_LADDER_TARGET = "dbsp_zset_probe_ladder"
RANK_FOLD_TARGET = "dbsp_zset_rank_fold"
JOIN_LADDER_TARGET = "dbsp_zset_join_ladder"
GATHER_LADDER_TARGET = "dbsp_zset_gather_ladder"
OLD_WEIGHTS_TARGET = "dbsp_zset_old_weights"
SEGMENT_REDUCE_TARGET = "dbsp_zset_segment_reduce"
AGG_LADDER_TARGET = "dbsp_zset_agg_ladder"
JOIN_SORTED_TARGET = "dbsp_zset_join_sorted"

# every native kernel the per-kernel force-off knob can address (the
# DBSP_TPU_NATIVE csv grammar — see :func:`kernel_enabled`). `join_ladder`
# / `gather_ladder` / `old_weights` are the FUSED ladder consumers (PR 12):
# forcing one off falls back to the stitched probe/expand/gather chain
# (which still dispatches the granular kernels above). `segment_reduce` /
# `agg_ladder` / `join_sorted` are the reduction offensive: the Aggregator
# zoo's opcode segment reduction, the whole-CAggregate megakernel, and the
# sorted-emit join mode whose per-side consolidated runs kill the
# post-join sort — forcing those off restores the previous round's code
# path exactly, so an A/B isolates just this fusion layer.
KERNELS = ("merge", "consolidate", "probe", "probe_ladder", "expand",
           "gather", "compact", "rank_fold", "join_ladder",
           "gather_ladder", "old_weights", "segment_reduce", "agg_ladder",
           "join_sorted")


def _build() -> str:
    global _build_error
    if _build_error is not None:
        raise RuntimeError(_build_error)
    if not os.path.exists(_SO) or (
            os.path.getmtime(_SO) < os.path.getmtime(_SRC)):
        # route through the stamped build chokepoint (tools/build_native)
        # so dev rebuilds embed the source SHA-256 exactly like the
        # recorded builds — the staleness lint depends on it
        if _REPO_ROOT not in sys.path:
            sys.path.insert(0, _REPO_ROOT)
        from tools.build_native import compile_so

        try:
            compile_so(_SRC, _SO,
                       ["-O3", "-march=native", "-std=c++17", "-shared",
                        "-fPIC"], [_FFI.include_dir()])
        except RuntimeError as e:
            _build_error = f"native merge: {e}"
            raise RuntimeError(_build_error) from None
    return _SO


def _load() -> ctypes.CDLL:
    """Build + load the library and register the FFI target (once)."""
    global _lib, _registered
    with _lock:
        if _lib is None:
            lib = ctypes.CDLL(_build())
            lib.zset_merge.restype = None
            lib.zset_merge.argtypes = [
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
                ctypes.POINTER(_PP), _PP,
                ctypes.POINTER(_PP), _PP,
                _PP,
                ctypes.POINTER(_PP), _PP,
            ]
            _lib = lib
        if not _registered:
            for target, symbol in (
                    (FFI_TARGET, "ZsetMergeFfi"),
                    (PROBE_TARGET, "ZsetProbeFfi"),
                    (CONSOLIDATE_TARGET, "ZsetConsolidateFfi"),
                    (EXPAND_TARGET, "ZsetExpandFfi"),
                    (GATHER_TARGET, "ZsetGatherFfi"),
                    (COMPACT_TARGET, "ZsetCompactFfi"),
                    (PROBE_LADDER_TARGET, "ZsetProbeLadderFfi"),
                    (RANK_FOLD_TARGET, "ZsetRankFoldFfi"),
                    (JOIN_LADDER_TARGET, "ZsetJoinLadderFfi"),
                    (GATHER_LADDER_TARGET, "ZsetGatherLadderFfi"),
                    (OLD_WEIGHTS_TARGET, "ZsetOldWeightsFfi"),
                    (SEGMENT_REDUCE_TARGET, "ZsetSegmentReduceFfi"),
                    (AGG_LADDER_TARGET, "ZsetAggLadderFfi"),
                    (JOIN_SORTED_TARGET, "ZsetJoinLadderSortedFfi")):
                _FFI.register_ffi_target(
                    target, _FFI.pycapsule(getattr(_lib, symbol)),
                    platform="cpu")
            _registered = True
    return _lib


def available() -> bool:
    """Library builds/loads on this machine (cached) and the knobs allow
    SOME native kernel (``DBSP_TPU_NATIVE=0`` / legacy
    ``DBSP_TPU_NATIVE_MERGE=0`` are the all-off switches)."""
    if os.environ.get("DBSP_TPU_NATIVE_MERGE", "1") == "0":
        return False
    if os.environ.get("DBSP_TPU_NATIVE", "1").strip() == "0":
        return False
    try:
        _load()
        return True
    except RuntimeError:
        return False


_warned_unknown_kernels: set = set()


def kernel_enabled(kernel: str) -> bool:
    """Per-kernel A/B switch: ``DBSP_TPU_NATIVE=<csv|0|1>``.

    Unset/``1`` — every native kernel enabled (the default). ``0`` — all
    disabled (same as the legacy ``DBSP_TPU_NATIVE_MERGE=0``). A csv of
    names from :data:`KERNELS` (e.g. ``expand,gather``) FORCES those
    kernels onto their XLA fallback while the rest stay native — so any
    single kernel can be A/B'd from bench.py without code edits. A csv
    entry that names no known kernel warns LOUDLY (once per value): a
    typo'd force-off would otherwise no-op silently and corrupt the very
    A/B evidence the knob exists to produce. Does not check library
    availability; pair with :func:`available`."""
    v = os.environ.get("DBSP_TPU_NATIVE", "1").strip()
    if v == "0":
        return False
    if v in ("", "1"):
        return True
    off = {s.strip() for s in v.split(",") if s.strip()}
    unknown = off - set(KERNELS)
    if unknown and v not in _warned_unknown_kernels:
        _warned_unknown_kernels.add(v)
        import warnings

        warnings.warn(
            f"DBSP_TPU_NATIVE names unknown kernel(s) {sorted(unknown)} — "
            f"they match nothing and force nothing off. Valid names: "
            f"{', '.join(KERNELS)}", stacklevel=2)
    return kernel not in off


def _supported_dtype(d) -> bool:
    d = jnp.dtype(d)
    if d == jnp.bool_:
        return True
    if not jnp.issubdtype(d, jnp.integer):
        return False
    # every column is widened via astype(int64) before the C++ kernels:
    # unsigned widths <= 32 zero-extend losslessly, but uint64 values
    # >= 2^63 wrap NEGATIVE and break the lexicographic order the
    # two-pointer merge/probe assumes — those columns take the XLA path
    if jnp.issubdtype(d, jnp.unsignedinteger) and d.itemsize >= 8:
        return False
    return True


def supports(dtypes) -> bool:
    return all(_supported_dtype(d) for d in dtypes)


def _ptr(a: np.ndarray) -> _PP:
    return a.ctypes.data_as(_PP)


def _ptr_array(arrays) -> "ctypes.Array":
    return (_PP * len(arrays))(*[_ptr(a) for a in arrays])


def merge_raw(a_cols, a_w, b_cols, b_w, sentinels) -> Tuple[list, np.ndarray]:
    """Host-side (numpy-in, numpy-out) entry via the plain C ABI — used by
    tests to exercise the kernel without the XLA runtime in the loop."""
    ncols = len(a_cols)
    a_cols = [np.ascontiguousarray(a, np.int64) for a in a_cols]
    b_cols = [np.ascontiguousarray(b, np.int64) for b in b_cols]
    a_w = np.ascontiguousarray(a_w, np.int64)
    b_w = np.ascontiguousarray(b_w, np.int64)
    na, nb = a_w.shape[0], b_w.shape[0]
    cap = na + nb
    out_cols = [np.empty(cap, np.int64) for _ in range(ncols)]
    out_w = np.empty(cap, np.int64)
    sent = np.asarray(sentinels, np.int64)
    _load().zset_merge(
        ncols, na, nb,
        _ptr_array(a_cols), _ptr(a_w),
        _ptr_array(b_cols), _ptr(b_w),
        _ptr(sent),
        _ptr_array(out_cols), _ptr(out_w))
    return out_cols, out_w


def merge_consolidated_cols(cols_a: Sequence[jnp.ndarray], w_a: jnp.ndarray,
                            cols_b: Sequence[jnp.ndarray], w_b: jnp.ndarray
                            ) -> Tuple[Tuple[jnp.ndarray, ...], jnp.ndarray]:
    """Drop-in for the CPU branch of ``kernels.merge_sorted_cols``.

    Caller guarantees: both inputs consolidated (sorted, netted, packed),
    integer/bool columns only (see :func:`supports`). Works eagerly and
    under an outer trace (it lowers to one XLA custom call).
    """
    _load()
    ncols = len(cols_a)
    dtypes = tuple(c.dtype for c in cols_a)
    # int64-widened per-dtype sentinel (host ints — this runs under trace)
    sentinels = tuple(
        1 if np.dtype(d) == np.bool_ else int(np.iinfo(np.dtype(d)).max)
        for d in dtypes)
    cap = w_a.shape[-1] + w_b.shape[-1]
    a64 = tuple(c.astype(jnp.int64) for c in cols_a)
    b64 = tuple(c.astype(jnp.int64) for c in cols_b)
    result = tuple(jax.ShapeDtypeStruct((cap,), jnp.int64)
                   for _ in range(ncols + 1))
    out = _FFI.ffi_call(FFI_TARGET, result, vmap_method="sequential")(
        *a64, w_a.astype(jnp.int64), *b64, w_b.astype(jnp.int64),
        jnp.asarray(sentinels, jnp.int64))
    # inside a shard_map the inputs carry varying-manual-axes (vma) types;
    # custom-call results come back untagged, which breaks scan carries —
    # re-tag them to match the inputs
    vma = _vma_of(w_a)
    if vma:
        out = tuple(jax.lax.pcast(o, tuple(vma), to="varying") for o in out)
    out_cols = tuple(c.astype(d) for c, d in zip(out[:ncols], dtypes))
    return out_cols, out[ncols].astype(w_a.dtype)


def consolidate_cols_native(cols: Sequence[jnp.ndarray], weights: jnp.ndarray
                            ) -> Tuple[Tuple[jnp.ndarray, ...], jnp.ndarray]:
    """Native consolidation of an unsorted run — drop-in for the CPU branch
    of ``kernels.consolidate_cols`` (argsort + net + pack in C++; the XLA
    comparator sort it replaces is the per-tick cost of every operator
    output consolidation)."""
    _load()
    ncols = len(cols)
    dtypes = tuple(c.dtype for c in cols)
    sentinels = tuple(
        1 if np.dtype(d) == np.bool_ else int(np.iinfo(np.dtype(d)).max)
        for d in dtypes)
    cap = weights.shape[-1]
    c64 = tuple(c.astype(jnp.int64) for c in cols)
    result = tuple(jax.ShapeDtypeStruct((cap,), jnp.int64)
                   for _ in range(ncols + 1))
    out = _FFI.ffi_call(CONSOLIDATE_TARGET, result,
                        vmap_method="sequential")(
        *c64, weights.astype(jnp.int64),
        jnp.asarray(sentinels, jnp.int64))
    vma = _vma_of(weights)
    if vma:
        out = tuple(jax.lax.pcast(o, tuple(vma), to="varying") for o in out)
    out_cols = tuple(c.astype(d) for c, d in zip(out[:ncols], dtypes))
    return out_cols, out[ncols].astype(weights.dtype)


def lex_probe_native(table_cols: Sequence[jnp.ndarray],
                     query_cols: Sequence[jnp.ndarray],
                     side: str = "left") -> jnp.ndarray:
    """Native lexicographic searchsorted: per-query C++ binary search over
    the sorted table (native/zset_merge.cpp::ZsetProbeImpl). Drop-in for
    the CPU branch of ``kernels.lex_probe`` — the XLA unrolled-search loop
    there pays log2(n) rounds of whole-query-vector gathers per column
    (~175ms for 16k queries x 1M rows; this call is ~1ms)."""
    _load()
    t64 = tuple(c.astype(jnp.int64) for c in table_cols)
    q64 = tuple(c.astype(jnp.int64) for c in query_cols)
    m = q64[0].shape[-1]
    result = (jax.ShapeDtypeStruct((m,), jnp.int32),)
    out = _FFI.ffi_call(PROBE_TARGET, result, vmap_method="sequential")(
        *t64, *q64,
        jnp.asarray([1 if side == "right" else 0], jnp.int64))
    pos = out[0]
    vma = _vma_of(q64[0])
    if vma:
        pos = jax.lax.pcast(pos, tuple(vma), to="varying")
    return pos


def _retag(out, ref):
    """Re-tag custom-call results with the reference value's vma (see
    merge_consolidated_cols — custom calls drop the tag under shard_map)."""
    vma = _vma_of(ref)
    if vma:
        return tuple(jax.lax.pcast(o, tuple(vma), to="varying") for o in out)
    return tuple(out)


def lex_probe_ladder_native(tables, query_cols, side: str = "left"
                            ) -> jnp.ndarray:
    """ONE custom call probing the query rows into EVERY level's sorted
    table (native/zset_merge.cpp::ZsetProbeLadderImpl) — drop-in for the
    CPU branch of ``cursor.lex_probe_ladder``, replacing K separate probe
    dispatches + a stack with a single [K, m] result."""
    _load()
    K = len(tables)
    ncols = len(tables[0])
    m = query_cols[0].shape[-1]
    t64 = [c.astype(jnp.int64) for t in tables for c in t]
    q64 = [c.astype(jnp.int64) for c in query_cols]
    meta = jnp.asarray([K, ncols, 1 if side == "right" else 0], jnp.int64)
    result = (jax.ShapeDtypeStruct((K, m), jnp.int32),)
    out = _FFI.ffi_call(PROBE_LADDER_TARGET, result,
                        vmap_method="sequential")(*t64, *q64, meta)
    return _retag(out, query_cols[0])[0]


def expand_ranges_native(lo: jnp.ndarray, hi: jnp.ndarray, out_cap: int):
    """Sequential range expansion (ZsetExpandImpl) — drop-in for the CPU
    branch of ``kernels.expand_ranges`` (and, over flattened [K*m] ranges,
    ``cursor.expand_ladder``). Returns ``(row, src, valid, total)`` with
    the same dtypes/tail contract as the searchsorted formulation."""
    _load()
    result = (jax.ShapeDtypeStruct((out_cap,), jnp.int32),
              jax.ShapeDtypeStruct((out_cap,), jnp.int32),
              jax.ShapeDtypeStruct((out_cap,), jnp.bool_),
              jax.ShapeDtypeStruct((1,), jnp.int64))
    out = _FFI.ffi_call(EXPAND_TARGET, result, vmap_method="sequential")(
        lo.astype(jnp.int64), hi.astype(jnp.int64))
    row, src, valid, total = _retag(out, lo)
    return row, src, valid, total.reshape(())


def gather_levels_native(cols_per_level, level: jnp.ndarray,
                         src: jnp.ndarray):
    """Grouped gather across trace levels (ZsetGatherImpl) — drop-in for
    ``cursor._select_gather``: out[ci][j] = level[j]'s column ci at the
    clamped src[j]. One pass instead of K clamped gathers + selects per
    column."""
    _load()
    ncols = len(cols_per_level[0])
    if not ncols:
        return ()
    dtypes = tuple(c.dtype for c in cols_per_level[0])
    n = level.shape[-1]
    tabs = [cols[ci].astype(jnp.int64)
            for ci in range(ncols) for cols in cols_per_level]
    result = tuple(jax.ShapeDtypeStruct((n,), jnp.int64)
                   for _ in range(ncols))
    out = _FFI.ffi_call(GATHER_TARGET, result, vmap_method="sequential")(
        level.astype(jnp.int32), src.astype(jnp.int32), *tabs)
    out = _retag(out, level)
    return tuple(c.astype(d) for c, d in zip(out, dtypes))


def compact_native(cols, weights: jnp.ndarray, keep: jnp.ndarray):
    """Single-pass compaction (ZsetCompactImpl) — drop-in for the CPU
    branch of ``kernels.compact``."""
    _load()
    ncols = len(cols)
    dtypes = tuple(c.dtype for c in cols)
    sentinels = tuple(
        1 if np.dtype(d) == np.bool_ else int(np.iinfo(np.dtype(d)).max)
        for d in dtypes)
    cap = weights.shape[-1]
    c64 = tuple(c.astype(jnp.int64) for c in cols)
    result = tuple(jax.ShapeDtypeStruct((cap,), jnp.int64)
                   for _ in range(ncols + 1))
    out = _FFI.ffi_call(COMPACT_TARGET, result, vmap_method="sequential")(
        *c64, weights.astype(jnp.int64), keep.astype(jnp.bool_),
        jnp.asarray(sentinels, jnp.int64))
    out = _retag(out, weights)
    out_cols = tuple(c.astype(d) for c, d in zip(out[:ncols], dtypes))
    return out_cols, out[ncols].astype(weights.dtype)


def _sentinel64(dtypes) -> tuple:
    """Per-dtype sentinel values widened to int64 (host ints — traceable),
    derived from the ONE dead-row sentinel definition
    (``kernels.sentinel_scalar``) so the native megakernels' dead slots
    can never drift from the stitched backend's bit-identity contract."""
    from dbsp_tpu.zset import kernels

    return tuple(int(kernels.sentinel_scalar(d)) for d in dtypes)


def join_ladder_native(delta, levels, nk: int, out_cap: int):
    """The WHOLE fused incremental join in one custom call
    (ZsetJoinLadderImpl): both ladder probes, dead-row zeroing, the
    cross-level expansion, the delta-side qrow gathers (keys + vals), the
    level-side value gather and the weight product — where even the native
    stitched path paid 4+ custom calls with XLA where-mask glue between
    them. Returns ``(key_cols, delta_val_cols, level_val_cols, w, valid,
    total)`` in the original dtypes; the caller applies the pair function
    and the dead-slot sentinel mask (cheap elementwise XLA) on top."""
    _load()
    K = len(levels)
    dk = delta.keys[:nk]
    ndv = len(delta.vals)
    nlv = len(levels[0].vals)
    key_dts = tuple(c.dtype for c in dk)
    dval_dts = tuple(c.dtype for c in delta.vals)
    lval_dts = tuple(c.dtype for c in levels[0].vals)
    ops = [c.astype(jnp.int64) for c in (*dk, *delta.vals)]
    ops.append(delta.weights.astype(jnp.int64))
    for lvl in levels:
        ops.extend(c.astype(jnp.int64)
                   for c in (*lvl.keys[:nk], *lvl.vals, lvl.weights))
    ops.append(jnp.asarray([K, nk, ndv, nlv], jnp.int64))
    n_out = nk + ndv + nlv
    result = (*(jax.ShapeDtypeStruct((out_cap,), jnp.int64)
                for _ in range(n_out + 1)),
              jax.ShapeDtypeStruct((out_cap,), jnp.bool_),
              jax.ShapeDtypeStruct((1,), jnp.int64))
    out = _FFI.ffi_call(JOIN_LADDER_TARGET, result,
                        vmap_method="sequential")(*ops)
    out = _retag(out, delta.weights)
    key_cols = tuple(c.astype(d) for c, d in zip(out[:nk], key_dts))
    dvals = tuple(c.astype(d)
                  for c, d in zip(out[nk:nk + ndv], dval_dts))
    lvals = tuple(c.astype(d)
                  for c, d in zip(out[nk + ndv:n_out], lval_dts))
    w = out[n_out].astype(delta.weights.dtype)
    valid = out[n_out + 1]
    total = out[n_out + 2].reshape(())
    return key_cols, dvals, lvals, w, valid, total


def gather_ladder_native(qkeys, qlive, levels, out_cap: int,
                         qhi_keys=None, gather_keys: int = 0):
    """The WHOLE fused group gather in one custom call
    (ZsetGatherLadderImpl): both ladder probes (equality or distinct
    [lo, hi] range bounds), the cross-level expansion, the leveled value
    gather and the dead-slot canonicalization (qrow == q_cap, sentinel
    cols, weight 0) — the consumer-facing ``((qrow, vals, w), total)``
    part comes back FINAL, no XLA post-pass. Shares the contract of
    ``cursor.gather_ladder`` exactly (``qhi_keys``/``gather_keys``
    included)."""
    _load()
    K = len(levels)
    nk = len(qkeys)
    gcols0 = (*levels[0].keys[nk - gather_keys:nk], *levels[0].vals) \
        if gather_keys else tuple(levels[0].vals)
    g_dts = tuple(c.dtype for c in gcols0)
    ng = len(gcols0)
    ops = [c.astype(jnp.int64) for c in qkeys]
    if qhi_keys is not None:
        ops.extend(c.astype(jnp.int64) for c in qhi_keys)
    ops.append(qlive.astype(jnp.bool_))
    for lvl in levels:
        gc = (*lvl.keys[nk - gather_keys:nk], *lvl.vals) if gather_keys \
            else tuple(lvl.vals)
        ops.extend(c.astype(jnp.int64)
                   for c in (*lvl.keys[:nk], *gc, lvl.weights))
    ops.append(jnp.asarray(_sentinel64(g_dts), jnp.int64))
    ops.append(jnp.asarray([K, nk, 1 if qhi_keys is not None else 0],
                           jnp.int64))
    result = (jax.ShapeDtypeStruct((out_cap,), jnp.int32),
              *(jax.ShapeDtypeStruct((out_cap,), jnp.int64)
                for _ in range(ng + 1)),
              jax.ShapeDtypeStruct((1,), jnp.int64))
    out = _FFI.ffi_call(GATHER_LADDER_TARGET, result,
                        vmap_method="sequential")(*ops)
    out = _retag(out, qlive)
    qrow = out[0]
    vals = tuple(c.astype(d) for c, d in zip(out[1:1 + ng], g_dts))
    w = out[1 + ng].astype(levels[0].weights.dtype)
    total = out[2 + ng].reshape(())
    return (qrow, vals, w), total


def old_weights_ladder_native(delta, levels) -> jnp.ndarray:
    """Distinct's old-weight lookup in one custom call
    (ZsetOldWeightsImpl): per delta row, one exact-match binary search per
    level with the present weights summed — drop-in for the CPU branch of
    ``cursor.old_weights_ladder``."""
    _load()
    K = len(levels)
    nc = len(delta.cols)
    ops = [c.astype(jnp.int64) for c in delta.cols]
    ops.append(delta.weights.astype(jnp.int64))
    for lvl in levels:
        ops.extend(c.astype(jnp.int64) for c in (*lvl.cols, lvl.weights))
    ops.append(jnp.asarray([K, nc], jnp.int64))
    m = delta.weights.shape[-1]
    result = (jax.ShapeDtypeStruct((m,), jnp.int64),)
    out = _FFI.ffi_call(OLD_WEIGHTS_TARGET, result,
                        vmap_method="sequential")(*ops)
    return _retag(out, delta.weights)[0].astype(delta.weights.dtype)


# Segment-reduction opcodes shared with the C++ SegAccum (zset_merge.cpp)
# — ONE vocabulary for every backend of the Aggregator zoo's five
# reductions (+ the presence mask).
SEG_OPS = {"count": 0, "sum": 1, "min": 2, "max": 3, "avg": 4, "present": 5}


def seg_op_identity(op: str, src_dtype) -> int:
    """The accumulator init / empty-segment fill of one reduction op, as a
    host int — EXACTLY what the ``jax.ops.segment_*`` formulation fills
    empty segments with (min fills with the SOURCE dtype's max, max — and
    present, which IS a segment_max over 0/1 — with its min, the additive
    ops with 0), so the native kernel's untouched segments can never drift
    from the XLA fills."""
    if op == "min":
        return int(jnp.iinfo(jnp.dtype(src_dtype)).max)
    if op in ("max", "present"):
        return int(jnp.iinfo(jnp.dtype(src_dtype)).min)
    return 0


def _ops_meta(spec, val_dtypes) -> list:
    """[opcode, src_col, identity] triples for a reduce spec (tuples of
    (op name, source column)) — the meta layout the C++ kernels consume."""
    out = []
    for op, col in spec:
        src = val_dtypes[col] if op in ("min", "max") else jnp.int64
        out.extend((SEG_OPS[op], col, seg_op_identity(op, src)))
    return out


def segment_reduce_native(spec, val_cols, weights: jnp.ndarray,
                          seg: jnp.ndarray, num_segments: int, out_dtypes):
    """ONE custom call running a whole reduce spec (ZsetSegmentReduceImpl)
    — drop-in for the CPU branch of ``operators.aggregate.segment_reduce``:
    every op's jax.ops.segment_* chain (mask + reduce, 2-4 dispatches per
    output) collapses into a single pass over (vals, weights, seg)."""
    _load()
    val_dtypes = tuple(c.dtype for c in val_cols)
    meta = jnp.asarray([len(val_cols), *_ops_meta(spec, val_dtypes)],
                       jnp.int64)
    result = tuple(jax.ShapeDtypeStruct((num_segments,), jnp.int64)
                   for _ in spec)
    out = _FFI.ffi_call(SEGMENT_REDUCE_TARGET, result,
                        vmap_method="sequential")(
        *(c.astype(jnp.int64) for c in val_cols),
        weights.astype(jnp.int64), seg.astype(jnp.int32), meta)
    out = _retag(out, weights)
    return tuple(c.astype(d) for c, d in zip(out, out_dtypes))


def agg_ladder_native(delta, nk: int, out_trace, levels, spec,
                      q_cap: int, gather_cap: int, fast: bool,
                      flag: jnp.ndarray, lad_dtypes, d_dtypes):
    """The WHOLE CAggregate reduce chain in one custom call
    (ZsetAggLadderImpl): run-boundary unique keys, the out-trace exact-match
    probe (per-column TupleMax of the previous outputs), the touched
    groups' ladder history walk — cross-level netting + the aggregator's
    segment reduction folded into the walk, nothing materialized — and, in
    fast (insert-combinable) mode, the delta's own reduction in the same
    run scan. ``flag`` is the RUNTIME ladder gate (ever_negative on the
    fast path; constant true on the general path). Returns
    ``(qkeys, qlive, nq, old_vals, old_present, lad_vals, lad_present,
    d_vals, d_present, gather_total)`` with the stitched chain's exact
    dtypes and clamping behavior."""
    _load()
    dk = delta.keys[:nk]
    key_dts = tuple(c.dtype for c in dk)
    old_dts = tuple(c.dtype for c in out_trace.vals)
    nov = len(spec)
    lval_dts = tuple(c.dtype for c in levels[0].vals)
    meta = [len(levels), nk, len(delta.vals), len(levels[0].vals), nov,
            1 if fast else 0, gather_cap]
    meta += _ops_meta(spec, lval_dts)
    meta += [seg_op_identity("max", d) for d in old_dts]  # TupleMax inits
    meta += [int(kernels_sentinel(d)) for d in key_dts]
    ops = [c.astype(jnp.int64) for c in (*dk, *delta.vals)]
    ops.append(delta.weights.astype(jnp.int64))
    ops.extend(c.astype(jnp.int64)
               for c in (*out_trace.keys[:nk], *out_trace.vals,
                         out_trace.weights))
    for lvl in levels:
        ops.extend(c.astype(jnp.int64)
                   for c in (*lvl.keys[:nk], *lvl.vals, lvl.weights))
    ops.append(flag.astype(jnp.int64).reshape(1))
    ops.append(jnp.asarray(meta, jnp.int64))
    result = (*(jax.ShapeDtypeStruct((q_cap,), jnp.int64)
                for _ in range(nk)),
              jax.ShapeDtypeStruct((q_cap,), jnp.bool_),
              jax.ShapeDtypeStruct((1,), jnp.int64),
              *(jax.ShapeDtypeStruct((q_cap,), jnp.int64)
                for _ in range(nov)),
              jax.ShapeDtypeStruct((q_cap,), jnp.bool_),
              *(jax.ShapeDtypeStruct((q_cap,), jnp.int64)
                for _ in range(nov)),
              jax.ShapeDtypeStruct((q_cap,), jnp.bool_),
              *(jax.ShapeDtypeStruct((q_cap,), jnp.int64)
                for _ in range(nov)),
              jax.ShapeDtypeStruct((q_cap,), jnp.bool_),
              jax.ShapeDtypeStruct((1,), jnp.int64))
    out = _FFI.ffi_call(AGG_LADDER_TARGET, result,
                        vmap_method="sequential")(*ops)
    out = _retag(out, delta.weights)
    qkeys = tuple(c.astype(d) for c, d in zip(out[:nk], key_dts))
    qlive = out[nk]
    nq = out[nk + 1].reshape(())
    i = nk + 2
    old_vals = tuple(c.astype(d) for c, d in zip(out[i:i + nov], old_dts))
    old_present = out[i + nov]
    i += nov + 1
    lad_vals = tuple(c.astype(d) for c, d in zip(out[i:i + nov],
                                                 lad_dtypes))
    lad_present = out[i + nov]
    i += nov + 1
    if fast:
        d_vals = tuple(c.astype(d)
                       for c, d in zip(out[i:i + nov], d_dtypes))
        d_present = out[i + nov]
    else:
        d_vals, d_present = None, None  # general path never reads them
    gtotal = out[i + nov + 1].reshape(())
    return (qkeys, qlive, nq, old_vals, old_present, lad_vals, lad_present,
            d_vals, d_present, gtotal)


def kernels_sentinel(dtype) -> int:
    from dbsp_tpu.zset import kernels

    return int(kernels.sentinel_scalar(dtype))


def join_ladder_sorted_native(delta, levels, nk: int, perm, n_out_keys: int,
                              out_dtypes, out_cap: int):
    """Sorted-emit join megakernel (ZsetJoinLadderSortedImpl): the whole
    fused join with a permutation pair-fn applied IN the call and the
    side's buffer emitted as ONE consolidated run (sorted by the projected
    columns, equal rows netted, packed, sentinel tail). Returns
    ``(Batch tagged runs=(out_cap,), unclamped total)`` — the caller's
    post-join ``concat().consolidate()`` then rank-folds two runs with one
    linear native merge instead of a full argsort."""
    _load()
    K = len(levels)
    dk = delta.keys[:nk]
    n_out = len(perm)
    sentinels = tuple(kernels_sentinel(d) for d in out_dtypes)
    ops = [c.astype(jnp.int64) for c in (*dk, *delta.vals)]
    ops.append(delta.weights.astype(jnp.int64))
    for lvl in levels:
        ops.extend(c.astype(jnp.int64)
                   for c in (*lvl.keys[:nk], *lvl.vals, lvl.weights))
    ops.append(jnp.asarray(sentinels, jnp.int64))
    ops.append(jnp.asarray(
        [K, nk, len(delta.vals), len(levels[0].vals), n_out, *perm],
        jnp.int64))
    result = (*(jax.ShapeDtypeStruct((out_cap,), jnp.int64)
                for _ in range(n_out + 1)),
              jax.ShapeDtypeStruct((1,), jnp.int64))
    out = _FFI.ffi_call(JOIN_SORTED_TARGET, result,
                        vmap_method="sequential")(*ops)
    out = _retag(out, delta.weights)
    cols = tuple(c.astype(d) for c, d in zip(out[:n_out], out_dtypes))
    w_dt = jnp.promote_types(delta.weights.dtype, levels[0].weights.dtype)
    w = out[n_out].astype(w_dt)
    total = out[n_out + 1].reshape(())
    from dbsp_tpu.zset.batch import Batch

    return Batch(cols[:n_out_keys], cols[n_out_keys:], w,
                 runs=(out_cap,)), total


def rank_fold_native(cols, weights: jnp.ndarray, runs):
    """K-way merge consolidation of an R-run batch (ZsetRankFoldImpl) —
    drop-in for the rank regime of ``batch.consolidate_regime``: one
    custom call instead of a fold of R-1 pairwise merges. ``runs`` is the
    STATIC sorted-run metadata (segment lengths summing to cap)."""
    _load()
    ncols = len(cols)
    dtypes = tuple(c.dtype for c in cols)
    sentinels = tuple(
        1 if np.dtype(d) == np.bool_ else int(np.iinfo(np.dtype(d)).max)
        for d in dtypes)
    cap = weights.shape[-1]
    c64 = tuple(c.astype(jnp.int64) for c in cols)
    result = tuple(jax.ShapeDtypeStruct((cap,), jnp.int64)
                   for _ in range(ncols + 1))
    out = _FFI.ffi_call(RANK_FOLD_TARGET, result, vmap_method="sequential")(
        *c64, weights.astype(jnp.int64),
        jnp.asarray(tuple(runs), jnp.int64),
        jnp.asarray(sentinels, jnp.int64))
    out = _retag(out, weights)
    out_cols = tuple(c.astype(d) for c, d in zip(out[:ncols], dtypes))
    return out_cols, out[ncols].astype(weights.dtype)
