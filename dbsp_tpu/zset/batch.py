"""Columnar Z-set batches — the TPU-native answer to the reference's ordered
batch family (``crates/dbsp/src/trace/ord/``: ``OrdZSet``, ``OrdIndexedZSet``)
and its trie layers (``trace/layers/column_layer/mod.rs:31`` — whose
struct-of-arrays ``keys``/``diffs`` vectors validate this representation).

A :class:`Batch` is a pytree of flat device columns with a *static capacity*:

    keys:    tuple of [cap] arrays — the indexing columns (lexicographic order)
    vals:    tuple of [cap] arrays — the value columns
    weights: [cap] signed integers — Z-set multiplicities (0 == dead row)

Invariants of a *consolidated* batch (the canonical form every operator
produces):
  * rows are sorted lexicographically by (keys, vals),
  * no two live rows are equal on (keys, vals),
  * live rows (weight != 0) are packed at the front; dead rows carry per-dtype
    sentinel keys (max value) so a plain ascending sort keeps them last.

Capacities are powers of two chosen by the host (see :func:`bucket_cap`);
growth recompiles the operator kernel for the next bucket only, so the set of
compiled shapes stays logarithmic in state size (XLA static-shape discipline).
"""

from __future__ import annotations

import dataclasses
import os
from functools import partial
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dbsp_tpu.zset import kernels

WEIGHT_DTYPE = jnp.int64

Row = Tuple  # host-side row: tuple of python scalars

# consolidate() folds rank/native merges over a batch's sorted runs instead
# of sorting when it carries at most this many runs (more runs than this and
# the fold's N-1 sequential merges lose to one O(n log n) sort; 12 covers a
# window delta's 1 + 2*K-level slide parts at the default K=4 ladder)
RANK_FOLD_MAX_RUNS = int(os.environ.get("DBSP_TPU_RANK_FOLD_MAX_RUNS", "12"))


def bucket_cap(n: int, minimum: int = 8) -> int:
    """Round ``n`` up to a power-of-two capacity bucket."""
    cap = minimum
    while cap < n:
        cap *= 2
    return cap


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Batch:
    """An immutable columnar Z-set batch (possibly un-consolidated).

    ``runs`` is STATIC sorted-run metadata: a tuple of segment lengths
    (summing to ``cap``, along the row axis) such that each segment is
    itself a consolidated batch slice — sorted lexicographically, no two
    equal live rows, live rows packed at the segment front, dead sentinel
    tail. ``None`` means unknown/unordered (the conservative default every
    bare constructor call keeps). The metadata is what lets
    :meth:`consolidate` dispatch by regime: a 1-run batch is already
    canonical (no-op), few runs fold with rank/native sorted merges, and
    only genuinely unordered data pays a full sort. It lives in the pytree
    AUX data, so it survives jit/shard_map boundaries and distinct run
    structures compile separately (their consolidation programs differ).
    """

    keys: Tuple[jnp.ndarray, ...]
    vals: Tuple[jnp.ndarray, ...]
    weights: jnp.ndarray
    runs: Optional[Tuple[int, ...]] = None

    # -- pytree plumbing ----------------------------------------------------
    def tree_flatten(self):
        return ((self.keys, self.vals, self.weights),
                (len(self.keys), len(self.vals), self.runs))

    @classmethod
    def tree_unflatten(cls, aux, children):
        keys, vals, weights = children
        runs = aux[2] if len(aux) > 2 else None
        return cls(tuple(keys), tuple(vals), weights, runs)

    # -- basic properties ---------------------------------------------------
    # Arrays are [cap] on a single worker, or [W, cap] for a batch sharded
    # over a worker mesh (parallel/): the row axis is always the LAST axis,
    # and per-worker row invariants hold along it independently.
    @property
    def cap(self) -> int:
        return int(self.weights.shape[-1])

    @property
    def sorted_runs(self) -> int:
        """Number of known sorted-consolidated runs (0 = unknown/unordered)."""
        return len(self.runs) if self.runs is not None else 0

    def tagged(self, runs: Optional[Tuple[int, ...]]) -> "Batch":
        """Same columns with different sorted-run metadata. Callers assert
        the invariant; :func:`check_runs` (tests) verifies it."""
        return Batch(self.keys, self.vals, self.weights, runs)

    @property
    def sharded(self) -> bool:
        return self.weights.ndim == 2

    @property
    def cols(self) -> Tuple[jnp.ndarray, ...]:
        return (*self.keys, *self.vals)

    def key_dtypes(self):
        return tuple(k.dtype for k in self.keys)

    def val_dtypes(self):
        return tuple(v.dtype for v in self.vals)

    def live_count(self) -> jnp.ndarray:
        """Total number of live rows (device scalar; all workers)."""
        return jnp.sum(self.weights != 0)

    def max_worker_live(self) -> jnp.ndarray:
        """Max live rows on any one worker — what capacity bucketing needs
        for sharded batches (each worker slice has the same static cap)."""
        if self.sharded:
            return jnp.max(jnp.sum(self.weights != 0, axis=-1))
        return self.live_count()

    # -- constructors -------------------------------------------------------
    @staticmethod
    def empty(key_dtypes: Sequence, val_dtypes: Sequence = (), cap: int = 8,
              weight_dtype=WEIGHT_DTYPE, lead: Tuple[int, ...] = ()) -> "Batch":
        """``lead=(W,)`` builds an empty sharded batch (worker axis first)."""
        keys = tuple(kernels.sentinel_fill((*lead, cap), d) for d in key_dtypes)
        vals = tuple(kernels.sentinel_fill((*lead, cap), d) for d in val_dtypes)
        return Batch(keys, vals, jnp.zeros((*lead, cap), weight_dtype),
                     runs=(cap,))

    @staticmethod
    def from_columns(keys: Sequence[jnp.ndarray], vals: Sequence[jnp.ndarray],
                     weights: jnp.ndarray, cap: int | None = None,
                     consolidated: bool = False) -> "Batch":
        """Build (and by default consolidate) a batch from raw device columns."""
        n = int(weights.shape[0])
        for c in (*keys, *vals):
            assert c.shape[0] == n, (
                f"column length {c.shape[0]} != weights length {n}")
        cap = cap or bucket_cap(n)
        keys = tuple(_pad_sentinel(jnp.asarray(k), cap) for k in keys)
        vals = tuple(_pad_sentinel(jnp.asarray(v), cap) for v in vals)
        w = jnp.zeros((cap,), WEIGHT_DTYPE).at[:n].set(
            jnp.asarray(weights, WEIGHT_DTYPE))
        b = Batch(keys, vals, w, runs=(cap,) if consolidated else None)
        return b if consolidated else b.consolidate()

    @staticmethod
    def from_block(block: "ColumnBlock", nk: int) -> "Batch":
        """The consolidated batch of a host :class:`ColumnBlock` whose first
        ``nk`` columns are the keys: what :meth:`from_columns` builds from
        the same columns, bit for bit, in ONE device program. numpy pads
        every column to ``bucket_cap(n)`` with its sentinel and the weights
        with 0, and the padded arrays cross to the device as the arguments
        of :func:`_consolidate_block`. The padding carries ``n``, so blocks
        of one capacity bucket share a program."""
        pad = (0, bucket_cap(len(block)) - len(block))
        return _consolidate_block(
            tuple(np.pad(c, pad,
                         constant_values=kernels.sentinel_scalar(c.dtype))
                  for c in block.cols),
            np.pad(block.weights.astype(WEIGHT_DTYPE, copy=False), pad), nk)

    @staticmethod
    def from_tuples(rows: Sequence[Tuple[Row, int]], key_dtypes: Sequence,
                    val_dtypes: Sequence = (), cap: int | None = None) -> "Batch":
        """Host-side constructor from ((key..., val...), weight) pairs.

        The analog of the reference's ``Batch::from_tuples``
        (``trace/mod.rs:237``); used by tests and input handles.
        """
        nk, nv = len(key_dtypes), len(val_dtypes)
        n = len(rows)
        cap = cap or bucket_cap(max(n, 1))
        kcols = [np.empty((n,), jnp.dtype(d)) for d in key_dtypes]
        vcols = [np.empty((n,), jnp.dtype(d)) for d in val_dtypes]
        ws = np.empty((n,), jnp.dtype(WEIGHT_DTYPE))
        for i, (row, w) in enumerate(rows):
            assert len(row) == nk + nv, f"row arity {len(row)} != {nk}+{nv}"
            for j in range(nk):
                kcols[j][i] = row[j]
            for j in range(nv):
                vcols[j][i] = row[nk + j]
            ws[i] = w
        for col in (*kcols, *vcols):
            _check_domain(col, col.dtype)
        return Batch.from_columns(kcols, vcols, ws, cap=cap)

    # -- canonicalization ---------------------------------------------------
    def consolidate(self) -> "Batch":
        """Canonicalize, dispatching by sorted-run regime (module doc of
        :mod:`dbsp_tpu.zset.kernels` for the path accounting):

        * 1 known run — the batch IS consolidated; free by construction.
        * few runs — fold rank/native sorted merges over the run slices
          (no sort of the combined rows); output capacity unchanged.
        * unknown/many runs — full sort (or native argsort) consolidation.

        Every path produces the identical canonical batch (sorted unique
        live rows packed front, netted weights, sentinel dead tail)."""
        if self.sorted_runs == 1:
            kernels.count_consolidate_path("skipped")
            return self
        if self.sharded:  # canonicalize each worker slice under the mesh
            from dbsp_tpu.parallel.lift import lifted_consolidate

            return lifted_consolidate(self)
        return consolidate_regime(self)

    def compacted(self, keep: jnp.ndarray) -> "Batch":
        """Rows where ``keep`` holds, packed to the front (dead-sentinel
        tail), same capacity; preserves sort order — so a consolidated
        (1-run) input stays consolidated. Multi-run inputs lose their
        boundaries (segments shift arbitrarily under global packing)."""
        cols, w = kernels.compact(self.cols, self.weights, keep)
        nk = len(self.keys)
        runs = (self.cap,) if self.sorted_runs == 1 else None
        return Batch(cols[:nk], cols[nk:], w, runs)

    def masked(self, cond) -> "Batch":
        """The whole batch where ``cond`` (broadcastable) holds, dead
        (sentinel cols, zero weight) where it doesn't — the traced analog of
        'empty until X' host logic. A SCALAR cond is row-uniform (identity
        or all-dead-sentinel), so run metadata survives; a per-row cond
        interleaves sentinel rows with live ones and breaks sortedness."""
        cols = tuple(jnp.where(cond, c, kernels.sentinel_for(c.dtype))
                     for c in self.cols)
        nk = len(self.keys)
        runs = self.runs if jnp.ndim(cond) == 0 else None
        return Batch(cols[:nk], cols[nk:], jnp.where(cond, self.weights, 0),
                     runs)

    def with_cap(self, cap: int) -> "Batch":
        """Grow or shrink row capacity (last axis). Shrinking assumes live
        rows fit (caller checked the live count); consolidated batches keep
        live rows first on every worker."""
        if cap == self.cap:
            return self
        if cap > self.cap:
            # the sentinel pad extends the LAST run (all-dead tail keeps the
            # segment consolidated)
            runs = (*self.runs[:-1], self.runs[-1] + cap - self.cap) \
                if self.runs else None
            keys = tuple(_pad_sentinel(k, cap) for k in self.keys)
            vals = tuple(_pad_sentinel(v, cap) for v in self.vals)
            w = jnp.zeros((*self.weights.shape[:-1], cap),
                          self.weights.dtype).at[..., : self.cap].set(self.weights)
            return Batch(keys, vals, w, runs)
        runs = (cap,) if self.sorted_runs == 1 else None
        return Batch(tuple(k[..., :cap] for k in self.keys),
                     tuple(v[..., :cap] for v in self.vals),
                     self.weights[..., :cap], runs)

    # -- algebra (reference: crates/dbsp/src/algebra) -----------------------
    def neg(self) -> "Batch":
        """Z-set group inverse: negate all weights (order and zero-ness are
        untouched, so run metadata survives)."""
        return Batch(self.keys, self.vals, -self.weights, self.runs)

    def scale(self, c) -> "Batch":
        # c == 0 zeroes weights of rows still carrying live keys, which
        # breaks the packed-live-prefix part of the run invariant for the
        # native merge walk — drop the metadata rather than special-case it
        return Batch(self.keys, self.vals, self.weights * c)

    def add(self, other: "Batch") -> "Batch":
        """Z-set group addition of two CONSOLIDATED batches (the invariant
        every stream value upholds) via the rank-based sorted merge — no
        re-sort.

        The shrink keeps capacities in power-of-two buckets proportional to
        live rows — without it, iterated adds (the integrator loop) would grow
        capacity by cap_other per tick and trigger a fresh XLA compile each
        step. Costs one scalar device->host sync; host-level callers only.
        """
        return self.merge_with(other).shrink_to_fit()

    def merge_with(self, other: "Batch") -> "Batch":
        """Sorted merge of two consolidated batches; output cap is the sum
        of the input caps (see :func:`kernels.merge_sorted_cols`)."""
        assert len(self.keys) == len(other.keys) and \
            len(self.vals) == len(other.vals), "schema mismatch in merge"
        assert self.weights.ndim == other.weights.ndim, (
            "cannot merge a sharded batch with an unsharded one — check "
            "that every source in the circuit produces the same placement")
        if self.sharded:
            from dbsp_tpu.parallel.lift import lifted_merge

            return lifted_merge(self, other)
        return _merge_kernel(self, other)

    def shrink_to_fit(self, minimum: int = 8) -> "Batch":
        """Re-bucket a consolidated batch to bucket_cap(max worker live)."""
        return self.with_cap(bucket_cap(int(self.max_worker_live()), minimum))

    # -- host-side views (tests / output handles) ---------------------------
    def to_dict(self) -> Dict[Row, int]:
        """Materialize as {(key..., val...): weight} — the test oracle format
        and the serving-path row view. A sharded batch materializes the
        union over all worker slices. Vectorized: one boolean-mask gather +
        ``tolist`` per column instead of a per-row Python loop (the
        host-side analog of compaction; NDJSON encoders and HTTP output
        endpoints sit on this path at rate)."""
        ws = np.asarray(self.weights).reshape(-1)
        live = ws != 0
        if not live.any():
            return {}
        ws = ws[live]
        if not self.cols:  # unit-keyed batch: all rows are ()
            total = int(ws.sum())
            return {(): total} if total else {}
        cols = [np.asarray(c).reshape(-1)[live].tolist() for c in self.cols]
        out: Dict[Row, int] = {}
        for row, w in zip(zip(*cols), ws.tolist()):
            nw = out.get(row, 0) + w
            if nw:
                out[row] = nw
            else:
                out.pop(row, None)
        return out


@jax.jit
def _merge_kernel(a: Batch, b: Batch) -> Batch:
    cols, w = kernels.merge_sorted_cols(a.cols, a.weights, b.cols, b.weights)
    nk = len(a.keys)
    return Batch(cols[:nk], cols[nk:], w, runs=(w.shape[-1],))


@partial(jax.jit, static_argnames=("nk",))
def _consolidate_block(cols, weights, nk: int) -> Batch:
    """:meth:`Batch.from_block`'s program, one per (dtypes, ``nk``, cap)."""
    return Batch(cols[:nk], cols[nk:], weights).consolidate()


def consolidate_regime(batch: Batch) -> Batch:
    """Single-worker regime dispatch behind :meth:`Batch.consolidate` (also
    the per-worker body of the lifted sharded consolidate — arrays are 1-D
    here). The 1-run no-op short-circuits in the caller."""
    nk = len(batch.keys)
    runs = batch.runs
    if runs is not None and 2 <= len(runs) <= RANK_FOLD_MAX_RUNS:
        kernels.count_consolidate_path("rank")
        # native fast path: ONE k-way C++ merge over the run slices
        # (ZsetRankFoldImpl) instead of a fold of R-1 pairwise merges —
        # same canonical output, R-1 fewer custom calls and no
        # intermediate accumulator buffers
        if batch.cols and batch.weights.ndim == 1 and \
                kernels.native_kernel("rank_fold"):
            from dbsp_tpu.zset import native_merge

            if native_merge.supports(c.dtype for c in batch.cols):
                kernels.count_kernel_dispatch("rank_fold", "native")
                cols, w = native_merge.rank_fold_native(
                    batch.cols, batch.weights, runs)
                return Batch(cols[:nk], cols[nk:], w, runs=(batch.cap,))
        kernels.count_kernel_dispatch("rank_fold", "xla")
        # fold sorted merges over the run slices, smallest runs first so
        # the accumulator stays as small as it can
        bounds = []
        off = 0
        for r in runs:
            bounds.append((off, off + r))
            off += r
        parts = sorted(bounds, key=lambda se: se[1] - se[0])
        cols = batch.cols
        acc = tuple(c[..., parts[0][0]:parts[0][1]] for c in cols)
        acc_w = batch.weights[..., parts[0][0]:parts[0][1]]
        for s, e in parts[1:]:
            acc, acc_w = kernels.merge_sorted_cols(
                acc, acc_w, tuple(c[..., s:e] for c in cols),
                batch.weights[..., s:e])
        return Batch(acc[:nk], acc[nk:], acc_w, runs=(batch.cap,))
    cols, w = kernels.consolidate_cols(batch.cols, batch.weights)
    return Batch(cols[:nk], cols[nk:], w, runs=(batch.cap,))


def _check_domain(wide: np.ndarray, dt) -> None:
    """DOMAIN CONTRACT of a host column headed for dtype ``dt``: the max
    representable value of each integer column dtype is the engine's
    dead-row sentinel; a live row carrying it would be conflated with
    padding in probes/window slices. Reject at the host boundary (a
    vectorised max here; device-batch pushers uphold it by contract — see
    push_batch). ``wide`` holds the values before any narrowing cast, so a
    value the dtype cannot hold is refused too, not wrapped."""
    if not np.issubdtype(dt, np.integer) or not wide.size:
        return
    info = np.iinfo(dt)
    hi, lo = int(wide.max()), int(wide.min())
    if hi == info.max:
        raise ValueError(
            f"value {info.max} ({dt}) is reserved as the dead-row sentinel; "
            "remap the input domain (e.g. use a wider dtype)")
    if hi > info.max or lo < info.min:
        raise ValueError(
            f"value {hi if hi > info.max else lo} is outside the range of "
            f"a {dt} column")


def transposed(rows: Sequence[Tuple[Row, int]]) -> tuple:
    """Non-empty weighted rows -> (per-column tuples, weights)."""
    recs, weights = zip(*rows)
    return tuple(zip(*recs)), weights


def _joined(pieces: Sequence, wide, what: str) -> np.ndarray:
    """Pieces in row order — numpy arrays or sequences of Python numbers —
    as one ``wide`` array; a number ``wide`` cannot hold is a
    ``ValueError``."""
    try:
        return np.concatenate([np.asarray(p, dtype=wide) for p in pieces]) \
            if pieces else np.empty(0, wide)
    except OverflowError as e:
        raise ValueError(f"value outside the range of {what}: {e}")


def host_column(pieces: Sequence, dtype) -> np.ndarray:
    """One numpy column of ``dtype`` from its pieces in row order (the JSON
    parser's bulk path hands arrays, its line parser Python numbers), held
    to the domain contract (:func:`_check_domain`): raises ``ValueError``
    for a value the dtype cannot hold or reserves. Floats pass through
    ``float64`` as ``float(v)`` does."""
    dt = np.dtype(dtype)
    wide = (np.float64 if not np.issubdtype(dt, np.integer)
            else np.uint64 if dt == np.uint64 else np.int64)
    col = _joined(pieces, wide, f"a {dt} column")
    _check_domain(col, dt)
    return col.astype(dt, copy=False)


class ColumnBlock:
    """Weighted rows on the host, column-wise: one numpy array per schema
    column (keys then values, in the schema's dtypes) and a weight vector.
    What a parsed POST is pushed as (io/format.py -> InputHandle.extend):
    the tick's batch is built from the buffered blocks by
    :meth:`Batch.from_block`, with no row tuple in between. Supports
    ``len()`` and slicing like the list of weighted rows it replaces."""

    __slots__ = ("cols", "weights")

    def __init__(self, cols: Sequence[np.ndarray], weights: np.ndarray):
        self.cols = tuple(cols)
        self.weights = weights
        for c in self.cols:
            assert c.shape == weights.shape, (
                f"column length {c.shape} != weights length {weights.shape}")

    def __len__(self) -> int:
        return int(self.weights.shape[0])

    def __getitem__(self, s: slice) -> "ColumnBlock":
        if not isinstance(s, slice):
            raise TypeError("a ColumnBlock is sliced, not indexed")
        return ColumnBlock(tuple(c[s] for c in self.cols), self.weights[s])

    def rows(self) -> list:
        """The block as ``[((col...), weight), ...]`` of Python scalars."""
        return list(zip(zip(*(c.tolist() for c in self.cols)),
                        self.weights.tolist()))

    @staticmethod
    def from_parts(parts: Sequence[tuple], dtypes: Sequence) -> "ColumnBlock":
        """``parts``: (per-column pieces, weights) pairs in row order, a
        piece a numpy array or a sequence of Python numbers -> one block
        of ``dtypes`` columns; raises ``ValueError`` where
        :func:`host_column` does."""
        return ColumnBlock(
            [host_column([cols[j] for cols, _ in parts], d)
             for j, d in enumerate(dtypes)],
            _joined([weights for _, weights in parts],
                    np.dtype(WEIGHT_DTYPE), "a weight"))

    @staticmethod
    def from_rows(rows: Sequence[Tuple[Row, int]],
                  dtypes: Sequence) -> "ColumnBlock":
        """Weighted row tuples -> a block of ``dtypes`` columns."""
        return ColumnBlock.from_parts(
            [transposed(rows)] if rows else [], dtypes)

    @staticmethod
    def concat(blocks: Sequence["ColumnBlock"]) -> "ColumnBlock":
        if len(blocks) == 1:
            return blocks[0]
        return ColumnBlock(
            tuple(np.concatenate(cs)
                  for cs in zip(*(b.cols for b in blocks))),
            np.concatenate([b.weights for b in blocks]))


def _pad_sentinel(col: jnp.ndarray, cap: int) -> jnp.ndarray:
    n = col.shape[-1]
    if n == cap:
        return col
    assert n < cap, f"column of {n} rows exceeds capacity {cap}"
    fill = kernels.sentinel_fill((*col.shape[:-1], cap - n), col.dtype)
    return jnp.concatenate([col, fill], axis=-1)


def concat_batches(batches: Sequence[Batch]) -> Batch:
    """Stack batches into one (un-consolidated) batch of summed capacity
    (row axis = last axis, so sharded batches concat per worker).

    Sorted-run metadata concatenates: stacking consolidated inputs yields a
    known multi-run batch, whose ``consolidate()`` folds sorted merges
    instead of re-sorting (unknown inputs poison the result to unknown)."""
    assert batches
    first = batches[0]
    keys = tuple(
        jnp.concatenate([b.keys[i] for b in batches], axis=-1)
        for i in range(len(first.keys)))
    vals = tuple(
        jnp.concatenate([b.vals[i] for b in batches], axis=-1)
        for i in range(len(first.vals)))
    w = jnp.concatenate([b.weights for b in batches], axis=-1)
    runs: Optional[Tuple[int, ...]] = ()
    for b in batches:
        if b.runs is None:
            runs = None
            break
        runs = (*runs, *b.runs)
    return Batch(keys, vals, w, runs)
