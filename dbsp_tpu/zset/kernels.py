"""Low-level device kernels shared by the Z-set batch layer.

These are the TPU-native replacements for the reference engine's
consolidation / trie-layer machinery (reference: ``crates/dbsp/src/trace/
consolidation/`` and ``trace/layers/advance.rs``): instead of in-place
quicksort + pairwise merges over growable vectors, everything is expressed as
static-shape ``lax.sort`` / segmented-scan programs that XLA can fuse and tile.

All kernels operate on flat ``[cap]`` columns. Row validity is carried by the
weight column (weight == 0 <=> dead row); dead rows hold per-dtype sentinel
keys (max value) so that a single ascending sort moves them to the end.
"""

from __future__ import annotations

from functools import partial, wraps
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax



def _scoped(fn):
    """Run a public kernel under ``jax.named_scope("k.<kernel>")``: every
    operation it lowers to carries the scope in its metadata, so a device
    trace and the lowered text name it in situ, inside whichever program
    and circuit-node scope called it. Nothing runs at run time."""
    scope = "k." + fn.__name__

    @wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope(scope):
            return fn(*args, **kwargs)

    return scoped


# ---------------------------------------------------------------------------
# Consolidation-path accounting
# ---------------------------------------------------------------------------

# Dispatch decisions per consolidation regime, exported by obs as
# ``dbsp_tpu_zset_consolidate_total{path=...}`` and reported in bench JSON.
#   skipped  — sorted-run metadata proved the batch already consolidated
#              (consolidate() was a no-op);
#   rank     — few sorted runs, folded with rank/native sorted merges
#              (no sort of the combined rows);
#   native   — full consolidation via the C++ argsort custom call;
#   sort     — full multi-operand ``lax.sort`` consolidation;
#   native_unsupported_dtype — native was SELECTED but a column dtype
#              (float) is not int64-widenable, so the call demoted to the
#              sort path. Counted separately so a schema change that
#              silently knocks a pipeline off the native kernels is
#              visible in /metrics instead of folding into plain "sort";
#   deferred — the compiled placement pass removed the consolidation from
#              the program entirely (its consumers canonicalize anyway).
# Eager host-path calls count once per eval; calls under an XLA trace count
# once per TRACE — the counter attributes which regimes fire where, not
# per-tick kernel volume.
CONSOLIDATE_COUNTS: Dict[str, int] = {
    "sort": 0, "rank": 0, "native": 0, "skipped": 0, "deferred": 0,
    "native_unsupported_dtype": 0}


def count_consolidate_path(path: str) -> None:
    CONSOLIDATE_COUNTS[path] = CONSOLIDATE_COUNTS.get(path, 0) + 1


# ---------------------------------------------------------------------------
# Kernel dispatch accounting + the per-kernel native gate
# ---------------------------------------------------------------------------

# Which implementation each kernel entry point dispatched to, keyed by
# (kernel, backend) with backend one of "native" (C++ FFI custom call),
# "xla" (pure-XLA lowering) or, where an accelerator's XLA formulation is
# not the CPU's, its name: "xla_bitonic" (the merge network behind "merge"
# and "sort_merge", a sort of more than SORT_CHUNK_ROWS rows) and
# "xla_shift" (the shift compaction of "compact"), "xla_merge" (a level of
# "probe_ladder" ranked by one merge, that kernel counting per LEVEL, or
# one side of a single-table "probe" ranked so), "xla_flat" (a "gather"
# from the levels laid end to end).
# Same counting convention as CONSOLIDATE_COUNTS (eager calls per eval,
# traced calls per trace); exported by obs as
# ``dbsp_tpu_zset_kernel_dispatch_total{kernel,backend}`` and embedded in
# bench JSON as ``kernel_paths`` — so which path a deployment's hot loop
# actually took is observable, not inferred from env vars.
KERNEL_DISPATCH_COUNTS: Dict[Tuple[str, str], int] = {}


def count_kernel_dispatch(kernel: str, backend: str) -> None:
    key = (kernel, backend)
    KERNEL_DISPATCH_COUNTS[key] = KERNEL_DISPATCH_COUNTS.get(key, 0) + 1


def accelerator() -> bool:
    """Does this process compute off the CPU? The ONE place that decides
    which formulation a kernel takes: its native C++ call on a CPU with the
    library (:func:`native_kernel`), the accelerator's formulation (merge
    network, shift compaction, chunked sort, doubling group sums) off the
    CPU, the plain XLA one else. Asked at every call — never cached: tests
    and the benchmark's rehearsals replace ``jax.default_backend``."""
    return jax.default_backend() != "cpu"


# The two rates behind :func:`rank_by_merge`, both read on a TPU v5 lite
# with each formulation alone and warm (``tools/probe_rates.py``; my chip
# run, PR 37, call 1; PERF.md 6). A binary search gathers single elements:
# 14.3-14.5 ns a gathered int64 for 4,096 to 65,536 lanes against tables of
# 4,096 to 2,097,152 rows (27-38 ns at 262,144 lanes; PR 29 read 16.5). The
# merge network, the count of table rows ahead and the shift compaction
# stream whole columns: 0.012-0.014 ns a row for each column a stage
# carries, from 262,144 padded rows up (65,536 queries in 262,144 rows:
# 0.62 ms against the search's 17.9 ms; below that a call's ~0.2 ms of loop
# overhead shows, as the search's ~20 us a step does).
PROBE_GATHER_NS = 14.4
PROBE_PASS_NS = 0.013


def rank_by_merge(m: int, cap: int, nk: int) -> bool:
    """Is ranking ``m`` SORTED queries in a sorted table of ``cap`` rows
    cheaper by one merge (:func:`rank_sorted`) than by a binary search, on
    an accelerator? A static cost of the two shapes and nothing else. The
    search gathers ``m`` elements a key column at each of its
    ``cap.bit_length()`` steps. The merge streams the padded union through
    one stage per binary digit of its length: the network carries the key
    columns and a row number, the count of table rows ahead and the shift
    of the queries back to the front three narrow columns more. So a wide
    delta against a level of its own order takes the merge, and a few
    thousand lanes against a level of millions keep the search."""
    search = cap.bit_length() * m * nk * PROBE_GATHER_NS
    total = 1 << (cap + m - 1).bit_length()
    merge = (total.bit_length() - 1) * total * (nk + 4) * PROBE_PASS_NS
    return merge < search


# The rate of the copy behind :func:`gather_flat`, read on a TPU v5 lite
# with each form of the ladder gather alone and warm
# (``tools/probe_rates.py``; PERF.md 6): laying levels of 5,570,560 rows in
# all end to end costs 0.029 ns a row a column inside the flat form (0.98
# ms for six int64 columns at 64 slots, the gather a few us of it; the
# concatenation timed alone, its outputs written to buffers of their own,
# 0.042). A gathered element costs either form about PROBE_GATHER_NS:
# 13.5-17 ns from 16,384 to 262,144 slots. Below that the per-level form
# also pays ~20 us a gather that the rule leaves unpriced, so from 2,048
# to 3,072 slots against those levels it keeps the per-level form where
# the flat one is up to 0.35 ms faster.
GATHER_COPY_NS = 0.029


def gather_flat(out_cap: int, caps: Sequence[int], ncols: int) -> bool:
    """Is gathering ``out_cap`` slots of ``ncols`` columns from a ladder of
    levels with capacities ``caps`` cheaper from the levels laid end to end
    (one gather a column from their concatenation) than by one clamped
    gather per level a column, on an accelerator? A static cost of the
    shapes and nothing else, priced by ``PROBE_GATHER_NS`` and
    ``GATHER_COPY_NS``: the per-level form gathers ``len(caps) * out_cap``
    elements a column; the flat form gathers ``out_cap`` and first copies
    every level's rows into the concatenation. So wide gathers from a deep
    ladder take the flat form, and a few hundred lanes against levels of
    millions of rows — or a single level — keep the per-level one."""
    per_level = len(caps) * out_cap * ncols * PROBE_GATHER_NS
    flat = (out_cap * ncols * PROBE_GATHER_NS
            + sum(caps) * ncols * GATHER_COPY_NS)
    return flat < per_level


def native_kernel(kernel: str) -> bool:
    """Should ``kernel`` dispatch to its native C++ implementation HERE?

    True only on the CPU backend, with the FFI library loadable, and with
    the kernel not forced off via ``DBSP_TPU_NATIVE`` (csv force-off list;
    ``0`` = all off — see ``native_merge.kernel_enabled``). Callers still
    check dtype support per call site."""
    if accelerator():
        return False
    from dbsp_tpu.zset import native_merge

    return native_merge.available() and native_merge.kernel_enabled(kernel)


# ---------------------------------------------------------------------------
# Sentinels
# ---------------------------------------------------------------------------


def sentinel_scalar(dtype):
    """Largest representable value of ``dtype`` as a HOST scalar — the ONE
    definition of the dead-row sentinel; callers that need the value
    outside a device array (the native FFI wrappers widening it to int64)
    read it here so it can never drift from :func:`sentinel_for`."""
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return float("inf")
    if jnp.issubdtype(dtype, jnp.integer):
        return int(jnp.iinfo(dtype).max)
    if dtype == jnp.bool_:
        return True
    raise TypeError(f"unsupported column dtype {dtype}")


def sentinel_for(dtype) -> jnp.ndarray:
    """Largest representable value of ``dtype`` — reserved to mark dead rows."""
    return jnp.array(sentinel_scalar(dtype), dtype=jnp.dtype(dtype))


def sentinel_fill(shape, dtype) -> jnp.ndarray:
    return jnp.full(shape, sentinel_for(dtype), dtype=dtype)


# ---------------------------------------------------------------------------
# Row-wise lexicographic sort
# ---------------------------------------------------------------------------


# Rows per ``lax.sort`` call on accelerators. XLA:TPU's compile time for a
# multi-operand int64 sort climbs steeply with the row count (a 5-column
# NEXmark row: 1.4 s at 2,048 rows, 7.3 s at 4,096, 26 s at 8,192, minutes at
# 65,536 — compiled for a described v5e), while the merge network over the
# sorted chunks is one small loop body per level at any size.
SORT_CHUNK_ROWS = 2048


@_scoped
def sort_rows(cols: Sequence[jnp.ndarray], payload: Sequence[jnp.ndarray]
              ) -> Tuple[Tuple[jnp.ndarray, ...], Tuple[jnp.ndarray, ...]]:
    """Stable ascending lexicographic sort by ``cols``; ``payload`` rides along.

    Zero-column rows (unit-keyed Z-sets, e.g. a global COUNT(*)) are a valid
    degenerate case: every row is equal, nothing to sort.

    On accelerators a sort of more than :data:`SORT_CHUNK_ROWS` rows runs as
    a merge sort (:func:`_sort_rows_chunked`) — same result bit for bit.
    """
    if not cols:
        return (), tuple(payload)
    ops = (*cols, *payload)
    if cols[0].ndim == 1 and cols[0].shape[0] > SORT_CHUNK_ROWS and \
            accelerator():
        count_kernel_dispatch("sort_merge", "xla_bitonic")
        out = _sort_rows_chunked(ops, len(cols), SORT_CHUNK_ROWS)
    else:
        out = lax.sort(ops, num_keys=len(cols), is_stable=True)
    return tuple(out[: len(cols)]), tuple(out[len(cols):])


def _pad_last(dtype):
    """A value that a stable ascending ``lax.sort`` leaves at the very end:
    the dtype's greatest under the sort's total order (NaN for floats)."""
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.nan, dtype)
    return sentinel_for(dtype)


# The accelerator formulations below are loops, and a loop dispatched
# eagerly is traced anew and asked of the compiler on every call (the
# host path consolidates each input batch eagerly): each is one jitted
# program, compiled once per shape.


@partial(jax.jit, static_argnames=("num_keys", "chunk"))
def _sort_rows_chunked(ops: Sequence[jnp.ndarray], num_keys: int,
                       chunk: int) -> Tuple[jnp.ndarray, ...]:
    """Stable lexicographic sort of 1-D ``ops`` by their first ``num_keys``
    operands: sorts of ``chunk`` rows (one ``lax.map`` body), then a
    bottom-up merge sort whose every level merges all neighbouring run
    pairs at once with :func:`_bitonic_merge` — elementwise passes over
    contiguous rows, no gather. The compiler sees one small sort whatever
    the row count. Bit-identical to ``lax.sort(..., is_stable=True)``."""
    n = ops[0].shape[0]
    total = 1 << (n - 1).bit_length()
    size = min(total, 1 << (chunk.bit_length() - 1))
    if total > n:
        # pad rows compare >= every real row and sit after them in the
        # input, so stability keeps them last: the first n rows are the
        # stable sort of the real rows
        ops = [jnp.concatenate([o, jnp.full((total - n,),
                                            _pad_last(o.dtype))])
               for o in ops]
    ops = lax.map(
        lambda row: tuple(lax.sort(row, num_keys=num_keys, is_stable=True)),
        tuple(o.reshape(total // size, size) for o in ops))
    ops = tuple(o.reshape(total) for o in ops)
    while size < total:
        # a pair's second run reversed after its first is one bitonic
        # sequence; the row number breaks ties as a stable merge does
        ops = _bitonic_merge(
            tuple(_rev_second_half(o, size)
                  for o in (*ops, jnp.arange(total, dtype=jnp.int32))),
            num_keys, 2 * size)[:-1]
        size *= 2
    return tuple(o[:n] for o in ops)


def _rev_second_half(x: jnp.ndarray, size: int) -> jnp.ndarray:
    """Reverse the second ``size`` rows of every aligned ``2 * size``."""
    pairs = x.reshape(-1, 2, size)
    return jnp.stack([pairs[:, 0], pairs[:, 1, ::-1]], axis=1).reshape(-1)


def _lex_lt_eq(xs: Sequence[jnp.ndarray], ys: Sequence[jnp.ndarray], shape
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Row-wise ``(x < y, x == y)``, lexicographic over column lists of
    matching dtypes that broadcast to ``shape``, under the total order
    lax.sort uses (NaN ranks greatest, NaN == NaN)."""
    lt = jnp.zeros(shape, jnp.bool_)
    all_eq = jnp.ones(shape, jnp.bool_)
    for x, y in zip(xs, ys):
        col_lt = x < y
        if jnp.issubdtype(x.dtype, jnp.floating):
            col_lt = col_lt | (jnp.isnan(y) & ~jnp.isnan(x))
        lt = lt | (all_eq & col_lt)
        all_eq = all_eq & _col_eq(x, y)
    return lt, all_eq


def _rolled(x: jnp.ndarray, k) -> jnp.ndarray:
    """``x[(i + k) % n]`` at i for a traced ``0 <= k <= n``: one contiguous
    slice of the doubled column, so a loop over shift distances is one
    program body."""
    return lax.dynamic_slice(jnp.concatenate([x, x]), (k,), x.shape)


def _bitonic_merge(ops: Sequence[jnp.ndarray], num_keys: int, span: int
                   ) -> Tuple[jnp.ndarray, ...]:
    """Sort every aligned ``span`` rows (a power of two) of 1-D ``ops``,
    each holding a BITONIC sequence — ascending then descending — under the
    order (first ``num_keys`` operands, then ``ops[-1]``, which must make
    it strict: a row number).

    The merge of sorted runs on accelerators: log2(span) compare-exchange
    stages, each an elementwise pass in which row i meets row i + j
    through shifted copies of the columns. Nothing is gathered, scattered
    or searched, whatever the data: the chip streams such passes where it
    gathers single int64 elements at 16.5 ns each (PERF.md 6, PR 29)."""
    n = ops[0].shape[0]
    keyed = (*range(num_keys), len(ops) - 1)
    i = jnp.arange(n, dtype=jnp.int32)
    # inside shard_map the rows vary per worker: the row numbers must enter
    # the loop with the varying type they leave with
    vma = tuple(jax.typeof(ops[0]).vma - jax.typeof(ops[-1]).vma)
    if vma:
        ops = (*ops[:-1], lax.pcast(ops[-1], vma, to="varying"))

    def stage(s, ops):
        j = jnp.int32(span // 2) >> s
        low = (i & j) == 0  # the lower row of a pair keeps the smaller
        up = [_rolled(o, j) for o in ops]
        less, _ = _lex_lt_eq([ops[k] for k in keyed], [up[k] for k in keyed],
                             (n,))
        return tuple(
            jnp.where(low, jnp.where(less, o, u),
                      _rolled(jnp.where(less, u, o), n - j))
            for o, u in zip(ops, up))

    return lax.fori_loop(0, span.bit_length() - 1, stage, tuple(ops))


def _bitonic_rows(ops_a: Sequence[jnp.ndarray], ops_b: Sequence[jnp.ndarray]
                  ) -> Tuple[jnp.ndarray, ...]:
    """Two sorted runs as ONE bitonic sequence of a power-of-two length:
    ``a``, pad rows that sort last and ``b`` reversed, with a last operand
    of row numbers in (a, b, pad) order — of equal rows ``a``'s come first
    and pads go last — for :func:`_bitonic_merge`."""
    na, nb = ops_a[0].shape[0], ops_b[0].shape[0]
    total = 1 << (na + nb - 1).bit_length()
    rows = [jnp.concatenate([a, jnp.full((total - na - nb,),
                                         _pad_last(a.dtype)),
                             b.astype(a.dtype)[::-1]])
            for a, b in zip(ops_a, ops_b)]
    t = jnp.arange(total, dtype=jnp.int32)
    return (*rows, jnp.concatenate([t[:na], t[na + nb:], t[na:na + nb][::-1]]))


@partial(jax.jit, static_argnames=("num_keys",))
def _merge_runs(ops_a: Sequence[jnp.ndarray], ops_b: Sequence[jnp.ndarray],
                num_keys: int) -> Tuple[jnp.ndarray, ...]:
    """Stable merge of two runs sorted by their first ``num_keys`` operands
    (of equal rows ``a``'s come first): :func:`_bitonic_rows` through
    :func:`_bitonic_merge`."""
    rows = _bitonic_rows(ops_a, ops_b)
    out = _bitonic_merge(rows, num_keys, rows[0].shape[0])
    return tuple(o[:ops_a[0].shape[0] + ops_b[0].shape[0]] for o in out[:-1])


def _col_eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Element equality under the total order lax.sort uses: NaN == NaN."""
    eq = a == b
    if jnp.issubdtype(a.dtype, jnp.floating):
        eq = eq | (jnp.isnan(a) & jnp.isnan(b))
    return eq


def rows_equal_prev(cols: Sequence[jnp.ndarray], n: int | None = None
                    ) -> jnp.ndarray:
    """For sorted columns: mask[i] = row i equals row i-1 (mask[0] = False).

    With zero columns all rows are the unit row, hence equal; ``n`` supplies
    the row count for that case.
    """
    if not cols:
        assert n is not None
        return jnp.arange(n) > 0
    n = cols[0].shape[0]
    eq = jnp.ones((n,), dtype=jnp.bool_)
    for c in cols:
        eq = eq & jnp.concatenate(
            [jnp.zeros((1,), jnp.bool_), _col_eq(c[1:], c[:-1])])
    return eq


# ---------------------------------------------------------------------------
# Compaction: scatter live rows to the front, sentinel-fill the rest
# ---------------------------------------------------------------------------


@_scoped
def compact(cols: Sequence[jnp.ndarray], weights: jnp.ndarray,
            keep: jnp.ndarray) -> Tuple[Tuple[jnp.ndarray, ...], jnp.ndarray]:
    """Move rows with ``keep`` to the front (order preserved); rest is dead.

    Equivalent of the reference's in-place ``retain`` on batch vectors.
    On CPU with the native library the whole pass is ONE sequential C++
    copy (ZsetCompactImpl); without it a GATHER formulation: output slot j
    reads the (j+1)-th kept row, found by one searchsorted over the
    inclusive keep-prefix-sums (a scatter measured ~40ns/element on
    XLA:CPU, where scatters lower to a sequential update loop; searchsorted
    + gathers vectorize). On accelerators neither: shifts
    (:func:`_compact_shift`). Bit-identical output on every path.
    """
    if cols and weights.ndim == 1 and native_kernel("compact"):
        from dbsp_tpu.zset import native_merge

        if native_merge.supports(c.dtype for c in cols):
            count_kernel_dispatch("compact", "native")
            return native_merge.compact_native(cols, weights, keep)
    if weights.ndim == 1 and accelerator():
        count_kernel_dispatch("compact", "xla_shift")
        *out_cols, w = _compact_shift((*cols, weights), keep)
        return tuple(out_cols), w
    count_kernel_dispatch("compact", "xla")
    cap = weights.shape[0]
    csum = jnp.cumsum(keep.astype(jnp.int32))
    total = csum[-1]
    j = jnp.arange(cap, dtype=jnp.int32)
    src = jnp.minimum(searchsorted1(csum, j + 1, side="left"), cap - 1)
    valid = j < total
    out_cols = tuple(
        jnp.where(valid, c[src], sentinel_for(c.dtype)) for c in cols)
    w = jnp.where(valid, weights[src], 0)
    return tuple(out_cols), w


def _dropped_before(keep: jnp.ndarray) -> jnp.ndarray:
    """Dropped rows at or before each row, by doubling (a cumsum of an odd
    length can take the TPU's compiler half a minute: 1,114,112 rows,
    30 s)."""
    n = keep.shape[0]
    i = jnp.arange(n, dtype=jnp.int32)

    def count(s, c):
        j = jnp.int32(1) << s
        return c + jnp.where(i >= j, _rolled(c, n - j), 0)

    return lax.fori_loop(0, (n - 1).bit_length(), count,
                         (~keep).astype(jnp.int32))


def _shift_front(ops: Sequence[jnp.ndarray], keep: jnp.ndarray,
                 dist: jnp.ndarray):
    """Move each kept row left by its ``dist`` — the dropped rows before it
    — one binary digit of that distance per elementwise pass, lowest digit
    first. After the digits below 2**k a kept row i sits at ``final_i +
    (dist_i >> k << k)``, which grows strictly with i, so kept rows never
    collide and never pass each other. As many passes as the longest
    distance has digits: none when the kept rows are a prefix already (an
    insert-only merge). Returns ``(keep, dist, ops)`` as moved: each
    arrived row still knows how far it came."""
    n = keep.shape[0]
    i = jnp.arange(n, dtype=jnp.int32)
    dist = jnp.where(keep, dist, 0)

    def stage(carry):
        j, keep, dist, ops = carry
        moves = keep & ((dist & j) != 0)
        arrives = _rolled(moves, j) & (i < n - j)
        return (j * 2, arrives | (keep & ~moves),
                jnp.where(arrives, _rolled(dist, j), dist),
                tuple(jnp.where(arrives, _rolled(o, j), o) for o in ops))

    top = jnp.max(dist)
    _, keep, dist, ops = lax.while_loop(
        lambda carry: carry[0] <= top, stage,
        (jnp.int32(1), keep, dist, tuple(ops)))
    return keep, dist, ops


@jax.jit
def _compact_shift(ops: Sequence[jnp.ndarray], keep: jnp.ndarray
                   ) -> Tuple[jnp.ndarray, ...]:
    """:func:`compact` without a gather: a kept row moves left by the
    number of dropped rows before it (:func:`_dropped_before`,
    :func:`_shift_front`). The last operand is the weight column; dropped
    slots come out dead."""
    keep, _, (*cols, w) = _shift_front(ops, keep, _dropped_before(keep))
    return (*(jnp.where(keep, c, sentinel_for(c.dtype)) for c in cols),
            jnp.where(keep, w, 0))


def _net_sorted(cols: Sequence[jnp.ndarray], w: jnp.ndarray
                ) -> jnp.ndarray:
    """Weights of SORTED rows netted per group of equal rows: the group's
    sum on ONE of its rows, 0 on the others (every column is a key, so the
    group's rows are interchangeable)."""
    n = w.shape[0]
    dup = rows_equal_prev(cols, n=n)
    if accelerator():
        return _group_sums(dup, w)
    seg = jnp.cumsum(~dup) - 1  # segment id per row
    sums = jax.ops.segment_sum(w, seg, num_segments=n)
    return jnp.where(dup, 0, sums[seg]).astype(w.dtype)


@jax.jit
def _group_sums(dup: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """:func:`_net_sorted` on accelerators: a segmented running sum by
    doubling — elementwise passes instead of a scatter-add and a gather,
    as many as the longest group of equal rows needs; a group's LAST row
    holds its sum. Rows i < j have met their group's start by the pass of
    distance j, so what :func:`_rolled` wraps around is never added."""
    n = w.shape[0]

    def stage(carry):
        j, met, w = carry
        return (j * 2, met | _rolled(met, n - j),
                jnp.where(met, w, w + _rolled(w, n - j)))

    _, _, w = lax.while_loop(lambda carry: ~jnp.all(carry[1]), stage,
                             (jnp.int32(1), ~dup, w))
    last = jnp.concatenate([~dup[1:], jnp.ones((1,), jnp.bool_)])
    return jnp.where(last, w, 0)


# ---------------------------------------------------------------------------
# Consolidation: sort + sum weights of identical rows + compact
# ---------------------------------------------------------------------------


@_scoped
def consolidate_cols(cols: Sequence[jnp.ndarray], weights: jnp.ndarray
                     ) -> Tuple[Tuple[jnp.ndarray, ...], jnp.ndarray]:
    """Canonicalize a weighted row set (reference: ``trace/consolidation``).

    Sorts rows lexicographically, sums weights of equal rows, drops rows whose
    net weight is zero, and packs survivors to the front. Output capacity ==
    input capacity; tail rows are dead (weight 0, sentinel keys).
    """
    if cols and weights.ndim == 1 and native_kernel("consolidate"):
        from dbsp_tpu.zset import native_merge

        if native_merge.supports(c.dtype for c in cols):
            count_consolidate_path("native")
            count_kernel_dispatch("consolidate", "native")
            return native_merge.consolidate_cols_native(cols, weights)
        # native was selected but a column dtype (float) is not
        # int64-widenable: the demotion is its own counter bucket so the
        # silent fallback is visible in /metrics
        count_consolidate_path("native_unsupported_dtype")
    else:
        count_consolidate_path("sort")
    count_kernel_dispatch("consolidate", "xla")
    cols, (weights,) = sort_rows(cols, (weights,))
    w_new = _net_sorted(cols, weights)
    return compact(cols, w_new, w_new != 0)


# ---------------------------------------------------------------------------
# Sorted merge of two consolidated row sets (no re-sort)
# ---------------------------------------------------------------------------


def merge_strategy() -> str:
    """Backend-dependent choice for combining sorted row sets.

    On accelerators ``bitonic``: the two runs, one reversed, are a bitonic
    sequence that log2(n) elementwise compare-exchange passes sort
    (:func:`_bitonic_merge`) — O(n log n) streamed bytes and no gather. The
    cross-rank merge it replaced did O(log n) *dependent* gather passes,
    and a v5e gathers single int64 elements at 16.5 ns each: 3.3 s to merge
    131,072 rows into 1,048,576 against 17 ms now (PERF.md 6, PR 29). On
    CPU: a ``jax.pure_callback`` into the native two-pointer merge
    (native/zset_merge.cpp) — already-sorted runs need no sort, and
    XLA:CPU's comparator-based multi-operand sort measured ~50x slower than
    the C++ walk at spine-tail shapes (1.2s vs ~25ms for 1.5M rows x 7
    cols). ``sort`` remains the fallback when the native library can't
    build, the ``merge`` kernel is forced off (``DBSP_TPU_NATIVE``), or a
    column dtype (float) isn't int64-widenable.
    """
    if accelerator():
        return "bitonic"
    return "native" if native_kernel("merge") else "sort"


@_scoped
def merge_sorted_cols(cols_a: Sequence[jnp.ndarray], w_a: jnp.ndarray,
                      cols_b: Sequence[jnp.ndarray], w_b: jnp.ndarray
                      ) -> Tuple[Tuple[jnp.ndarray, ...], jnp.ndarray]:
    """Merge two SORTED row sets into one consolidated set, capacity |a|+|b|.
    Strategy is backend-dependent (see :func:`merge_strategy`).

    The replacement for the reference's pairwise batch ``Merger``
    (``trace/ord/merge_batcher``): both inputs are sorted, so nothing is
    re-sorted — the CPU walks them with two pointers, an accelerator runs
    the merge network over them (:func:`_merge_runs`; of equal rows ``a``'s
    come first). Equal rows land adjacent; their weights are summed and
    zero-net rows dropped, so the result is consolidated. Dead sentinel
    rows merge into the dead tail and vanish in the compaction.
    """
    if not cols_a:  # zero-column (unit-row) sets: nothing to order
        return consolidate_cols((), jnp.concatenate([w_a, w_b]))
    strategy = merge_strategy()
    if strategy == "bitonic":
        count_kernel_dispatch("merge", "xla_bitonic")
        *out_cols, w = _merge_runs((*cols_a, w_a), (*cols_b, w_b),
                                   len(cols_a))
        w = _net_sorted(out_cols, w)
        return compact(out_cols, w, w != 0)
    if strategy == "native" and w_a.ndim == 1:
        from dbsp_tpu.zset import native_merge

        if native_merge.supports(c.dtype for c in cols_a):
            count_kernel_dispatch("merge", "native")
            return native_merge.merge_consolidated_cols(cols_a, w_a,
                                                        cols_b, w_b)
    count_kernel_dispatch("merge", "xla")
    cols = tuple(jnp.concatenate([a, b.astype(a.dtype)])
                 for a, b in zip(cols_a, cols_b))
    return consolidate_cols(cols, jnp.concatenate([w_a, w_b]))


# ---------------------------------------------------------------------------
# Lexicographic searchsorted over multi-column sorted tables
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("side",))
@_scoped
def lex_searchsorted(table_cols: Tuple[jnp.ndarray, ...],
                     query_cols: Tuple[jnp.ndarray, ...],
                     side: str = "left") -> jnp.ndarray:
    """Insertion points of ``query`` rows into lexicographically sorted ``table``.

    TPU-native replacement for the reference's exponential-search ``advance``
    (``trace/layers/advance.rs``): instead of data-dependent binary search we
    sort table and query rows together once; a query row's position in the
    merged order, minus the number of queries before it, is its insertion
    index. O((n+m) log(n+m)), fully static shapes, any number of key columns.
    """
    assert len(table_cols) == len(query_cols) and table_cols
    n = table_cols[0].shape[0]
    m = query_cols[0].shape[0]
    # Tie-break flag: for 'left' queries sort before equal table rows.
    tflag = 1 if side == "left" else 0
    flags = jnp.concatenate(
        [jnp.full((n,), tflag, jnp.int32), jnp.full((m,), 1 - tflag, jnp.int32)]
    )
    pos = jnp.concatenate(
        [jnp.zeros((n,), jnp.int32), jnp.arange(m, dtype=jnp.int32)]
    )
    cols = tuple(
        jnp.concatenate([t.astype(jnp.promote_types(t.dtype, q.dtype)),
                         q.astype(jnp.promote_types(t.dtype, q.dtype))])
        for t, q in zip(table_cols, query_cols)
    )
    *_, sflags, spos = lax.sort((*cols, flags, pos), num_keys=len(cols) + 1,
                                is_stable=True)
    is_query = sflags == (1 - tflag)
    q_before = jnp.cumsum(is_query) - jnp.where(is_query, 1, 0)
    insertion = jnp.arange(n + m, dtype=jnp.int32) - q_before.astype(jnp.int32)
    out = jnp.zeros((m,), jnp.int32)
    out = out.at[jnp.where(is_query, spos, m)].set(insertion, mode="drop")
    return out


def searchsorted1(table: jnp.ndarray, query: jnp.ndarray,
                  side: str = "left") -> jnp.ndarray:
    """Single-column searchsorted.

    Both operands widen to their COMMON dtype: casting the query down to the
    table dtype (the old behavior) silently truncates a wider query — an
    int64 query of 2^40 against an int32 table wrapped negative and probed
    the wrong end of the table.

    (A native-FFI dispatch was tried here and measured ~25% SLOWER at the
    q4 tick: the custom call breaks XLA fusion with the surrounding
    expansion arithmetic and pays an int64-widening copy per operand —
    the vectorized scan lowering stays.)"""
    dt = jnp.promote_types(table.dtype, query.dtype)
    return jnp.searchsorted(table.astype(dt), query.astype(dt), side=side
                            ).astype(jnp.int32)


def _lex_le_rows(table_cols, idx, query_cols, strict: bool):
    """Per-query compare: table[idx] < query (strict) or <= query, under the
    same total order lax.sort uses (NaN ranks greatest, NaN == NaN).

    Both sides widen to their COMMON dtype — casting the query down to the
    table dtype silently truncates a wider query (the same hazard class
    :func:`searchsorted1` fixes; a no-op when dtypes already match, which
    the schema-pinned engine paths guarantee)."""
    dts = [jnp.promote_types(t.dtype, q.dtype)
           for t, q in zip(table_cols, query_cols)]
    lt, all_eq = _lex_lt_eq(
        [t[idx].astype(dt) for t, dt in zip(table_cols, dts)],
        [q.astype(dt) for q, dt in zip(query_cols, dts)], idx.shape)
    return lt if strict else lt | all_eq


@_scoped
def lex_probe(table_cols: Tuple[jnp.ndarray, ...],
              query_cols: Tuple[jnp.ndarray, ...],
              side: str = "left", sorted_queries: bool = False
              ) -> jnp.ndarray:
    """Delta-proportional searchsorted: O(m log n) vectorized binary search.

    The hot-path probe used by incremental operators to look a delta's keys up
    in a large trace (the analog of the reference's exponential-search
    ``advance``, ``trace/layers/advance.rs``). Unlike :func:`lex_searchsorted`
    (which sorts table+query together, O(n+m)), cost here scales with the
    *delta*, preserving DBSP's per-step cost model; the trace is only gathered
    at log2(n) probe indices per query. Unrolled loop — n is static under jit.

    ``sorted_queries`` is the caller's statement that ``query_cols`` are
    sorted (dead sentinel lanes at the tail). On an accelerator the same
    insertion points then come by one merge (:func:`rank_sorted`) where
    :func:`rank_by_merge` prices it cheaper for the two shapes — the rule
    each level of ``cursor.lex_probe_ladder`` follows — counted as
    ``probe/xla_merge``.
    """
    assert table_cols, "lex_probe requires at least one key column"
    flat = table_cols[0].ndim == 1 and query_cols[0].ndim == 1
    if flat and native_kernel("probe"):
        from dbsp_tpu.zset import native_merge

        if native_merge.supports(c.dtype for c in (*table_cols,
                                                   *query_cols)):
            count_kernel_dispatch("probe", "native")
            return native_merge.lex_probe_native(table_cols, query_cols,
                                                 side)
    return rank_or_search(table_cols, query_cols, side,
                          sorted_queries and flat, "probe")


def rank_or_search(table_cols, query_cols, side: str, sorted_queries: bool,
                   kernel: str) -> jnp.ndarray:
    """The insertion points by whichever XLA formulation is cheaper: one
    merge (:func:`rank_sorted`) for queries the caller states are sorted,
    on an accelerator, where :func:`rank_by_merge` prices it below the
    search; the binary search (:func:`_probe_search`) else. Counted under
    ``kernel`` as ``xla_merge`` or ``xla``."""
    if sorted_queries and accelerator() and rank_by_merge(
            query_cols[0].shape[0], table_cols[0].shape[0],
            len(query_cols)):
        count_kernel_dispatch(kernel, "xla_merge")
        return rank_sorted(table_cols, query_cols, side)
    count_kernel_dispatch(kernel, "xla")
    return _probe_search(table_cols, query_cols, side)


def _probe_search(table_cols, query_cols, side: str) -> jnp.ndarray:
    """The binary search of :func:`lex_probe`, unrolled: one gather of the
    table per step per key column, as many steps as the table's row count
    has binary digits. Any leading axes ride along."""
    n = table_cols[0].shape[-1]
    shape = query_cols[0].shape
    lo = jnp.zeros(shape, jnp.int32)
    hi = jnp.full(shape, n, jnp.int32)
    # n+1 candidate insertion points [0, n] => ceil(log2(n+1)) halvings
    strict = side == "left"
    for _ in range(n.bit_length()):
        active = lo < hi
        mid = (lo + hi) >> 1  # < hi <= n on active lanes; clamped gather else
        go_right = _lex_le_rows(table_cols, mid, query_cols, strict=strict)
        lo = jnp.where(active & go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
    return lo


@_scoped
def rank_sorted(table_cols: Tuple[jnp.ndarray, ...],
                query_cols: Tuple[jnp.ndarray, ...],
                side: str = "left") -> jnp.ndarray:
    """:func:`lex_probe` for SORTED queries, by one merge instead of a
    gather per search step: lane for lane the same insertion points.

    Both operands are sorted, so a query's insertion point is the number
    of table rows ahead of it in the merged order — ties to the queries
    for ``side="left"``, to the table for ``"right"``. The merge network
    (:func:`_bitonic_merge`) carries the key columns and a row number that
    says which rows are queries; the table rows ahead of each
    (:func:`_dropped_before`) are also how far it moves when the shift
    compaction brings the queries back to the front in their own order
    (:func:`_shift_front`), so the ranks are that compaction's distances.
    Elementwise passes over contiguous rows only: nothing is gathered,
    scattered, sorted or searched. Dead lanes included: sentinel queries
    against a table's sentinel tail rank as the search ranks them, and the
    network's pad rows sort behind both. No ``jit`` of its own: its callers
    are traced, and its loops keep the caller's scope path in the lowered
    program (``n6.CJoin/k.lex_probe_ladder/k.rank_sorted/while/body``)."""
    assert len(table_cols) == len(query_cols) and table_cols
    n = table_cols[0].shape[0]
    m = query_cols[0].shape[0]
    dts = [jnp.promote_types(t.dtype, q.dtype)
           for t, q in zip(table_cols, query_cols)]
    table = [t.astype(dt) for t, dt in zip(table_cols, dts)]
    query = [q.astype(dt) for q, dt in zip(query_cols, dts)]
    # of equal rows the first run's come first in the merged order
    rows = _bitonic_rows(query, table) if side == "left" \
        else _bitonic_rows(table, query)
    q0 = 0 if side == "left" else n   # the queries' first row number
    t = _bitonic_merge(rows, len(dts), rows[0].shape[0])[-1]
    is_query = (t >= q0) & (t < q0 + m)
    _, rank, _ = _shift_front((), is_query, _dropped_before(is_query))
    return rank[:m]


# ---------------------------------------------------------------------------
# Range expansion: turn per-row [lo, hi) ranges into a flat gather index list
# ---------------------------------------------------------------------------


@_scoped
def expand_ranges(lo: jnp.ndarray, hi: jnp.ndarray, out_cap: int
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Flatten variable-length ranges into static-capacity index arrays.

    Given m ranges [lo_i, hi_i), produces for each output slot j < total:
      src_row[j]  — which input row the slot belongs to,
      src_idx[j]  — lo[src_row] + offset within the range,
      valid[j]    — j < total,
    plus the (device, scalar) total. This is the two-pass count/scan/scatter
    shape the reference's join fan-out uses, with the scatter replaced by a
    searchsorted over the prefix sums (static shapes; TPU-friendly gathers).

    OVERFLOW CONTRACT: when ``total > out_cap`` only the first ``out_cap``
    range elements are emitted. Callers MUST host-check ``total`` against
    ``out_cap`` and re-run with a grown capacity bucket — see
    ``operators/join.py``. ``total`` is returned (not clamped) precisely so
    that check is possible.

    On CPU with the native library the count/scan/search pass is ONE
    sequential C++ walk (ZsetExpandImpl) with the identical tail contract
    (invalid slots anchor at the last non-empty range).
    """
    if lo.ndim == 1 and native_kernel("expand"):
        count_kernel_dispatch("expand", "native")
        from dbsp_tpu.zset import native_merge

        return native_merge.expand_ranges_native(lo, hi, out_cap)
    count_kernel_dispatch("expand", "xla")
    counts = jnp.maximum(hi - lo, 0)
    starts = jnp.cumsum(counts) - counts  # exclusive prefix sum
    total = jnp.sum(counts, dtype=jnp.int64)  # 64-bit: see expand_ladder
    j = jnp.arange(out_cap, dtype=jnp.int32)
    row = searchsorted1(starts, jnp.minimum(j, total - 1), side="right") - 1
    row = jnp.clip(row, 0, lo.shape[0] - 1)
    offset = j - starts[row]
    src = lo[row] + offset
    valid = j < total
    return row, src.astype(jnp.int32), valid, total
