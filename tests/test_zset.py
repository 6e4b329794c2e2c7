"""Z-set batch layer tests against a host dict oracle.

Mirrors the reference's model-checked batch tests
(``crates/dbsp/src/trace/test_batch.rs``): every device kernel result is
compared with a naive {row: weight} dict computed in Python.
"""

import random

import numpy as np
import pytest
import jax.numpy as jnp

from dbsp_tpu.zset import Batch, concat_batches, kernels


def dict_add(a, b):
    out = dict(a)
    for r, w in b.items():
        out[r] = out.get(r, 0) + w
        if out[r] == 0:
            del out[r]
    return out


def random_rows(rng, n, key_range=10, val_range=5, nvals=1):
    rows = []
    for _ in range(n):
        key = rng.randrange(key_range)
        vals = tuple(rng.randrange(val_range) for _ in range(nvals))
        w = rng.choice([-2, -1, 1, 2, 3])
        rows.append(((key, *vals), w))
    return rows


def oracle(rows):
    d = {}
    for r, w in rows:
        d[r] = d.get(r, 0) + w
        if d[r] == 0:
            del d[r]
    return d


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("n", [0, 1, 7, 64])
def test_from_tuples_consolidates(seed, n):
    rng = random.Random(seed)
    rows = random_rows(rng, n)
    b = Batch.from_tuples(rows, key_dtypes=[jnp.int64], val_dtypes=[jnp.int32])
    assert b.to_dict() == oracle(rows)


def test_consolidated_invariants():
    rng = random.Random(0)
    rows = random_rows(rng, 50)
    b = Batch.from_tuples(rows, key_dtypes=[jnp.int64], val_dtypes=[jnp.int32])
    w = np.asarray(b.weights)
    n_live = int((w != 0).sum())
    # live rows packed at the front
    assert (w[:n_live] != 0).all() and (w[n_live:] == 0).all()
    # sorted lexicographically by (key, val) on the live prefix
    k = np.asarray(b.keys[0])[:n_live]
    v = np.asarray(b.vals[0])[:n_live]
    order = sorted(zip(k.tolist(), v.tolist()))
    assert list(zip(k.tolist(), v.tolist())) == order
    # no duplicate live rows
    assert len(set(zip(k.tolist(), v.tolist()))) == n_live
    # dead rows carry sentinel keys
    assert (np.asarray(b.keys[0])[n_live:] == np.iinfo(np.int64).max).all()
    assert int(b.live_count()) == n_live


@pytest.mark.parametrize("seed", range(2))
def test_add_neg(seed):
    rng = random.Random(seed)
    ra, rb = random_rows(rng, 40), random_rows(rng, 30)
    a = Batch.from_tuples(ra, key_dtypes=[jnp.int64], val_dtypes=[jnp.int32])
    b = Batch.from_tuples(rb, key_dtypes=[jnp.int64], val_dtypes=[jnp.int32])
    assert a.add(b).to_dict() == dict_add(oracle(ra), oracle(rb))
    # a + (-a) == 0
    assert a.add(a.neg()).to_dict() == {}


def test_concat_batches_then_consolidate():
    rng = random.Random(3)
    parts = [random_rows(rng, 20) for _ in range(4)]
    batches = [
        Batch.from_tuples(p, key_dtypes=[jnp.int64], val_dtypes=[jnp.int32])
        for p in parts
    ]
    merged = concat_batches(batches).consolidate()
    want = {}
    for p in parts:
        want = dict_add(want, oracle(p))
    assert merged.to_dict() == want


def test_with_cap_grow_shrink():
    rows = [((i, 0), 1) for i in range(10)]
    b = Batch.from_tuples(rows, key_dtypes=[jnp.int64], val_dtypes=[jnp.int32])
    big = b.with_cap(64)
    assert big.cap == 64 and big.to_dict() == b.to_dict()
    small = big.with_cap(16)
    assert small.cap == 16 and small.to_dict() == b.to_dict()


@pytest.mark.parametrize("side", ["left", "right"])
@pytest.mark.parametrize("seed", range(2))
def test_lex_searchsorted_matches_numpy_single_col(side, seed):
    rng = np.random.RandomState(seed)
    table = np.sort(rng.randint(0, 20, size=30).astype(np.int64))
    query = rng.randint(-2, 23, size=17).astype(np.int64)
    got = kernels.lex_searchsorted((jnp.asarray(table),), (jnp.asarray(query),),
                                   side=side)
    want = np.searchsorted(table, query, side=side)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("side", ["left", "right"])
def test_lex_searchsorted_two_cols(side):
    rows = sorted(
        [(1, 2), (1, 5), (2, 1), (2, 1), (2, 9), (5, 0), (5, 0), (7, 3)]
    )
    queries = [(0, 0), (1, 5), (2, 1), (2, 2), (5, 0), (9, 9), (2, 0)]
    t0 = jnp.asarray([r[0] for r in rows], jnp.int64)
    t1 = jnp.asarray([r[1] for r in rows], jnp.int64)
    q0 = jnp.asarray([q[0] for q in queries], jnp.int64)
    q1 = jnp.asarray([q[1] for q in queries], jnp.int64)
    got = kernels.lex_searchsorted((t0, t1), (q0, q1), side=side)
    import bisect

    fn = bisect.bisect_left if side == "left" else bisect.bisect_right
    want = [fn(rows, q) for q in queries]
    np.testing.assert_array_equal(np.asarray(got), want)


def test_expand_ranges():
    lo = jnp.asarray([0, 3, 3, 7], jnp.int32)
    hi = jnp.asarray([2, 3, 6, 9], jnp.int32)
    row, src, valid, total = kernels.expand_ranges(lo, hi, out_cap=16)
    assert int(total) == 7
    got = [(int(row[j]), int(src[j])) for j in range(7)]
    assert got == [(0, 0), (0, 1), (2, 3), (2, 4), (2, 5), (3, 7), (3, 8)]
    assert bool(valid[6]) and not bool(valid[7])


def test_expand_ranges_empty():
    lo = jnp.asarray([4, 4], jnp.int32)
    hi = jnp.asarray([4, 4], jnp.int32)
    row, src, valid, total = kernels.expand_ranges(lo, hi, out_cap=8)
    assert int(total) == 0
    assert not bool(valid.any())


def test_float_val_columns():
    rows = [((1, 2.5), 1), ((1, 2.5), 2), ((2, -1.0), 1)]
    b = Batch.from_tuples(rows, key_dtypes=[jnp.int64], val_dtypes=[jnp.float32])
    assert b.to_dict() == {(1, 2.5): 3, (2, -1.0): 1}


def test_nan_rows_consolidate_and_cancel():
    nan = float("nan")
    rows = [((1, nan), 1), ((1, nan), -1), ((2, nan), 2)]
    b = Batch.from_tuples(rows, key_dtypes=[jnp.int64], val_dtypes=[jnp.float32])
    d = b.to_dict()
    assert len(d) == 1
    ((k, v), w), = d.items()
    assert k == 2 and w == 2 and np.isnan(v)


def test_unit_keyed_batch():
    # zero key and value columns: a bare counter Z-set (e.g. global COUNT(*))
    b = Batch.from_columns([], [], jnp.asarray([3, -1, 4], jnp.int64), cap=8)
    assert b.to_dict() == {(): 6}
    assert b.add(b.neg()).to_dict() == {}


def test_from_columns_length_mismatch_raises():
    with pytest.raises(AssertionError):
        Batch.from_columns([jnp.arange(5)], [], jnp.ones((3,), jnp.int64))


@pytest.mark.parametrize("n,chunk", [(3, 2), (10, 4), (129, 128), (256, 128),
                                     (1000, 64), (4097, 128), (5000, 2048),
                                     (98304, 2048)])
def test_chunked_sort_bit_identical_to_lax_sort(n, chunk):
    """The accelerator formulation of sort_rows (chunk sorts folded by
    stable rank merges) equals the stable multi-operand lax.sort bit for
    bit: duplicate keys keep input order (payload proves it), dead
    sentinel rows and NaN/inf floats land where lax.sort puts them, and
    row counts that are no multiple of the chunk pad invisibly."""
    import jax
    from jax import lax

    rng = np.random.default_rng(n)
    a = rng.integers(0, 5, n)
    a[: n // 7] = np.iinfo(np.int64).max  # dead-row sentinels
    f = rng.standard_normal(n).round(0)
    f[rng.integers(0, n, max(1, n // 10))] = np.nan
    f[rng.integers(0, n, max(1, n // 10))] = np.inf
    ops = (jnp.asarray(a), jnp.asarray(f),
           jnp.asarray(rng.integers(0, 3, n).astype(np.int32)),
           jnp.asarray(rng.integers(-3, 4, n)), jnp.arange(n))
    want = lax.sort(ops, num_keys=3, is_stable=True)
    got = jax.jit(lambda *o: kernels._sort_rows_chunked(o, 3, chunk))(*ops)
    for x, y in zip(want, got):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_sort_rows_takes_the_chunked_path_off_cpu(request, monkeypatch):
    """sort_rows keys its formulation on the backend, statically: plain
    lax.sort on the CPU, the chunked merge sort elsewhere — and the
    consolidation built on it gives the same canonical batch."""
    import jax

    rng = np.random.default_rng(3)
    n = 3 * kernels.SORT_CHUNK_ROWS
    cols = (jnp.asarray(rng.integers(0, 50, n)),
            jnp.asarray(rng.integers(0, 4, n)))
    w = jnp.asarray(rng.integers(-2, 3, n))
    want = kernels.consolidate_cols(cols, w)
    calls = []
    real = kernels._sort_rows_chunked
    monkeypatch.setattr(kernels, "_sort_rows_chunked",
                        lambda *a: calls.append(1) or real(*a))
    request.getfixturevalue("accelerator_dispatch")
    got = kernels.consolidate_cols(cols, w)
    assert calls, "off the CPU a large sort must take the chunked path"
    for x, y in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_chunked_sort_under_shard_map():
    """Per-worker slices sort independently inside shard_map (the loop
    carries take the rows' varying-manual-axes type)."""
    import jax
    from jax import lax, shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("workers",))
    rng = np.random.default_rng(5)
    a = jnp.asarray(rng.integers(0, 9, (2, 300)))
    w = jnp.asarray(rng.integers(-2, 3, (2, 300)))

    def body(a, w):
        out = kernels._sort_rows_chunked((a[0], w[0]), 1, 64)
        return tuple(o[None] for o in out)

    got = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("workers"),) * 2,
                            out_specs=(P("workers"),) * 2))(a, w)
    for k in range(2):
        want = lax.sort((a[k], w[k]), num_keys=1, is_stable=True)
        for x, y in zip(want, got):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y[k]))


# ---------------------------------------------------------------------------
# The accelerator formulations (bitonic merge network, shift compaction,
# doubling segment sums), steered onto the CPU: bit-identical to the
# stable lax.sort + netting and to the CPU backend's own (native) kernels
# ---------------------------------------------------------------------------

I64_MAX = np.iinfo(np.int64).max
I32_MAX = np.iinfo(np.int32).max


def _sorted_run(rows, cap, dtypes):
    """A sorted row set of capacity ``cap``: ``rows`` = [(tuple, weight)]
    in any order, sorted here by Python's tuple order with NaN greatest;
    dead sentinel tail. Equal rows are kept apart (not netted)."""
    def key(rw):
        return tuple((1, 0.0) if isinstance(v, float) and np.isnan(v)
                     else (0, v) for v in rw[0])

    rows = sorted(rows, key=key)
    pad = cap - len(rows)
    assert pad >= 0
    cols = tuple(
        jnp.concatenate([jnp.asarray([r[0][i] for r in rows], dt).reshape(-1),
                         kernels.sentinel_fill((pad,), dt)])
        for i, dt in enumerate(dtypes))
    w = jnp.asarray([r[1] for r in rows] + [0] * pad, jnp.int64)
    return cols, w


def _sort_and_net(cols, w):
    """The reference: one stable lax.sort of all rows, equal neighbours
    summed on the host, survivors packed to the front."""
    from jax import lax

    n = w.shape[0]
    *cols, w = lax.sort((*cols, w), num_keys=len(cols), is_stable=True) \
        if cols else (w,)
    cols = [np.asarray(c) for c in cols]
    w = np.asarray(w)
    out = []  # (row index kept, net weight)
    for i in range(n):
        same = out and all(
            c[i] == c[out[-1][0]] or (c.dtype.kind == "f" and
                                      np.isnan(c[i]) and
                                      np.isnan(c[out[-1][0]]))
            for c in cols)
        if same:
            out[-1][1] += int(w[i])
        else:
            out.append([i, int(w[i])])
    live = [(i, x) for i, x in out if x != 0]
    idx = [i for i, _ in live]
    pad = n - len(live)
    return (tuple(np.concatenate([c[idx], np.full(pad, np.asarray(
        kernels.sentinel_for(c.dtype)), c.dtype)]) for c in cols),
        np.asarray([x for _, x in live] + [0] * pad, np.int64))


def _took(before):
    """The (kernel, backend) pairs counted since ``before`` was copied."""
    return {k for k, n in kernels.KERNEL_DISPATCH_COUNTS.items()
            if n > before.get(k, 0)}


def _assert_same(got, want):
    got = (*got[0], got[1])
    want = (*want[0], want[1])
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _merge_case(name):
    """(cols_a, w_a, cols_b, w_b) of one merge shape worth its own row."""
    rng = np.random.default_rng(sum(map(ord, name)))
    two = (jnp.int64, jnp.int32)

    def rand(live, hi=40):
        seen = {}
        for _ in range(live):
            seen[(int(rng.integers(0, hi)), int(rng.integers(0, 3)))] = \
                int(rng.choice([-2, -1, 1, 2]))
        return list(seen.items())

    if name == "odd_sizes":  # neither side nor the sum a power of two
        return (*_sorted_run(rand(400), 513, two),
                *_sorted_run(rand(90), 101, two))
    if name == "one_row_into_many":
        return (*_sorted_run(rand(1), 1, two),
                *_sorted_run(rand(250), 300, two))
    if name == "all_dead_side":
        return (*_sorted_run([], 8, two), *_sorted_run(rand(60), 64, two))
    if name == "zero_length_side":
        return (*_sorted_run(rand(60), 64, two), *_sorted_run([], 0, two))
    if name == "full_capacity":  # no dead tail on either side
        a = [((k, 0), 1) for k in range(0, 64)]
        b = [((k, 0), -1) for k in range(32, 96)]  # half of it cancels
        return (*_sorted_run(a, 64, two), *_sorted_run(b, 64, two))
    if name == "everything_cancels":
        a = rand(200)
        return (*_sorted_run(a, 256, two),
                *_sorted_run([(r, -w) for r, w in a], 256, two))
    if name == "long_equal_runs":
        # sorted but NOT consolidated: 300 equal rows on one side, 77 on
        # the other — one group across the a/b seam, many network stages
        # and nine doublings of the segment sum
        a = [((7, 1), 1)] * 300 + [((9, 0), 2)]
        b = [((7, 1), -1)] * 77 + [((7, 2), 5), ((9, 0), -2)]
        return (*_sorted_run(a, 320, two), *_sorted_run(b, 96, two))
    if name == "live_sentinel_row":
        # a LIVE row whose every key is the dead-row sentinel sorts among
        # the dead rows and the pad rows of the network, and must survive
        a = [((I64_MAX, I32_MAX), 3), ((1, 1), 1)]
        b = [((I64_MAX, I32_MAX), 4), ((I64_MAX, 0), 1)]
        return (*_sorted_run(a, 5, two), *_sorted_run(b, 9, two))
    if name == "nan_inf_floats":
        f = (jnp.int64, jnp.float32)
        nan, inf = float("nan"), float("inf")
        a = [((1, nan), 1), ((1, inf), 2), ((1, -inf), 1), ((2, 0.5), 1),
             ((2, nan), -1)]
        b = [((1, nan), -1), ((1, inf), 2), ((2, nan), -1), ((3, -inf), 1),
             ((0, nan), 7)]
        return (*_sorted_run(a, 8, f), *_sorted_run(b, 16, f))
    if name == "int32_into_int64":  # b's columns are cast to a's
        a = _sorted_run(rand(30), 32, (jnp.int64, jnp.int64))
        b = _sorted_run(rand(30), 32, (jnp.int32, jnp.int32))
        return (*a, *b)
    if name == "five_columns":  # the bids row
        five = (jnp.int64, jnp.int64, jnp.int64, jnp.int32, jnp.int64)
        rows = lambda n: list({  # noqa: E731
            tuple(int(rng.integers(0, 4)) for _ in five): 1
            for _ in range(n)}.items())
        return (*_sorted_run(rows(700), 1024, five),
                *_sorted_run(rows(100), 128, five))
    if name == "one_column_full_capacity":  # half of b cancels half of a
        one = (jnp.int64,)
        return (*_sorted_run([((k,), 1) for k in range(16)], 16, one),
                *_sorted_run([((k,), -1) for k in range(8, 24)], 16, one))
    if name.startswith("three_int64_"):
        # consolidated batches of two key columns and a value column, few
        # distinct values a column, weights of either sign, 64 slots into
        # 128: a random number of live rows by seed, or ("dense") 40 into
        # 70 over ten values a column, where many rows meet and some cancel
        dense = name == "three_int64_dense"
        rng = np.random.default_rng(20 if dense else int(name[-2:]))
        three = (jnp.int64,) * 3

        def netted(live):
            net = {}
            for _ in range(live):
                row = tuple(int(v) for v in
                            rng.integers(0, 10 if dense else 12, 3))
                net[row] = net.get(row, 0) + (int(rng.integers(-3, 4)) or 1)
            return [(r, w) for r, w in net.items() if w]

        la, lb = (40, 70) if dense else (int(rng.integers(0, 50)),
                                         int(rng.integers(0, 100)))
        return (*_sorted_run(netted(la), 64, three),
                *_sorted_run(netted(lb), 128, three))
    raise AssertionError(name)


MERGE_CASES = ["odd_sizes", "one_row_into_many", "all_dead_side",
               "zero_length_side", "full_capacity", "everything_cancels",
               "long_equal_runs", "live_sentinel_row", "nan_inf_floats",
               "int32_into_int64", "five_columns",
               "one_column_full_capacity", "three_int64_seed10",
               "three_int64_seed11", "three_int64_seed12",
               "three_int64_seed13", "three_int64_dense"]


@pytest.mark.parametrize("name", MERGE_CASES)
def test_accelerator_merge_bit_identical(name, request):
    import jax

    cols_a, w_a, cols_b, w_b = _merge_case(name)
    cols = tuple(jnp.concatenate([a, b.astype(a.dtype)])
                 for a, b in zip(cols_a, cols_b))
    want = _sort_and_net(cols, jnp.concatenate([w_a, w_b]))
    if name != "long_equal_runs":  # the native walk nets across sides only
        _assert_same(kernels.merge_sorted_cols(cols_a, w_a, cols_b, w_b), want)
    request.getfixturevalue("accelerator_dispatch")
    before = dict(kernels.KERNEL_DISPATCH_COUNTS)
    got = jax.jit(kernels.merge_sorted_cols)(cols_a, w_a, cols_b, w_b)
    assert _took(before) == {("merge", "xla_bitonic"),
                             ("compact", "xla_shift")}
    _assert_same(got, want)


@pytest.mark.parametrize("n", [1, 2, 777, 2048, 2049, 5000])
def test_accelerator_consolidate_bit_identical(n, accelerator_dispatch):
    """Below, at and above SORT_CHUNK_ROWS: groups of up to dozens of
    equal rows, rows that net to zero, dead rows scattered through the
    input, an int32 beside an int64 column."""
    rng = np.random.default_rng(n)
    cols = (jnp.asarray(rng.integers(0, 40, n)),
            jnp.asarray(rng.integers(0, 3, n).astype(np.int32)))
    w = jnp.asarray(rng.integers(-1, 2, n))
    dead = jnp.asarray(rng.random(n) < 0.2)
    cols = tuple(jnp.where(dead, kernels.sentinel_for(c.dtype), c)
                 for c in cols)
    w = jnp.where(dead, 0, w)
    before = dict(kernels.KERNEL_DISPATCH_COUNTS)
    got = kernels.consolidate_cols(cols, w)
    if n > kernels.SORT_CHUNK_ROWS:
        assert kernels.KERNEL_DISPATCH_COUNTS[("sort_merge", "xla_bitonic")] \
            > before.get(("sort_merge", "xla_bitonic"), 0)
    _assert_same(got, _sort_and_net(cols, w))


def test_accelerator_zero_column_rows(accelerator_dispatch):
    """Unit rows (no key column): every row is equal, the weights net."""
    w_a = jnp.asarray([3, -1, 0, 0], jnp.int64)
    w_b = jnp.asarray([4, 0], jnp.int64)
    cols, w = kernels.merge_sorted_cols((), w_a, (), w_b)
    assert cols == () and w.tolist() == [6, 0, 0, 0, 0, 0]
    cols, w = kernels.merge_sorted_cols((), w_a, (), -w_a)
    assert w.tolist() == [0] * 8


@pytest.mark.parametrize("pattern", ["all", "none", "prefix", "suffix",
                                     "alternate", "random", "one_at_end"])
def test_accelerator_compact_bit_identical(pattern, request, monkeypatch):
    import jax

    n = 333
    rng = np.random.default_rng(4)
    keep = {"all": np.ones(n, bool), "none": np.zeros(n, bool),
            "prefix": np.arange(n) < 100, "suffix": np.arange(n) >= 200,
            "alternate": np.arange(n) % 2 == 1,
            "random": rng.random(n) < 0.4,
            "one_at_end": np.arange(n) == n - 1}[pattern]
    cols = (jnp.asarray(rng.integers(0, 1 << 40, n)),
            jnp.asarray(rng.integers(0, 9, n).astype(np.int32)),
            jnp.asarray(rng.standard_normal(n).astype(np.float32)))
    w = jnp.asarray(rng.integers(1, 4, n))
    keep = jnp.asarray(keep)
    monkeypatch.setenv("DBSP_TPU_NATIVE", "0")  # floats: the XLA reference
    want = kernels.compact(cols, w, keep)
    request.getfixturevalue("accelerator_dispatch")
    _assert_same(jax.jit(kernels.compact)(cols, w, keep), want)


def test_accelerator_merge_under_shard_map(accelerator_dispatch):
    """Per-worker slices merge independently inside shard_map, as
    lifted_merge runs them."""
    import jax
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("workers",))
    parts = [_merge_case("odd_sizes"), _merge_case("odd_sizes")]
    # the second worker's b side is negated a: its slice cancels in part
    ca, wa, cb, wb = parts[1]
    parts[1] = (ca, wa, tuple(c[:101] for c in ca), -wa[:101])
    stacked = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *parts)

    def body(ca, wa, cb, wb):
        cols, w = kernels.merge_sorted_cols(
            tuple(c[0] for c in ca), wa[0], tuple(c[0] for c in cb), wb[0])
        return tuple(c[None] for c in cols), w[None]

    spec = P("workers")
    got = jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,) * 4,
                            out_specs=(spec, spec)))(*stacked)
    for k, (ca, wa, cb, wb) in enumerate(parts):
        cols = tuple(jnp.concatenate([a, b]) for a, b in zip(ca, cb))
        _assert_same((tuple(c[k] for c in got[0]), got[1][k]),
                     _sort_and_net(cols, jnp.concatenate([wa, wb])))


# ---------------------------------------------------------------------------
# Which formulation each public kernel takes off the CPU: the (kernel,
# backend) pairs it counts under the steer are the ones a run on the chip
# prints in its ``kernel_dispatch`` line (PERF.md 3)
# ---------------------------------------------------------------------------

# every pair a q4 run on the chip has printed (PERF.md 6)
CHIP_PAIRS = {
    ("agg_ladder", "xla"), ("compact", "xla_shift"), ("consolidate", "xla"),
    ("expand", "xla"), ("gather", "xla"), ("gather", "xla_flat"),
    ("gather_ladder", "xla"),
    ("join_ladder", "xla"), ("merge", "xla_bitonic"), ("probe", "xla"),
    ("probe", "xla_merge"), ("probe_ladder", "xla"),
    ("probe_ladder", "xla_merge"), ("rank_fold", "xla"),
    ("segment_reduce", "xla"), ("sort_merge", "xla_bitonic")}

_NET = {("consolidate", "xla"), ("compact", "xla_shift")}
# at these few rows 64 slots from two levels are cheaper gathered from the
# levels laid end to end (kernels.gather_flat)
_CHAIN = {("probe_ladder", "xla"), ("expand", "xla"), ("gather", "xla_flat")}
# a consumer that states its queries are sorted: at these few rows every
# level is cheaper by the merge (kernels.rank_by_merge)
_CHAIN_SORTED = {("probe_ladder", "xla_merge"), ("expand", "xla"),
                 ("gather", "xla_flat")}
ACCELERATOR_PAIRS = {
    "consolidate_cols": _NET | {("sort_merge", "xla_bitonic")},
    "consolidate_cols_one_chunk": _NET,  # SORT_CHUNK_ROWS rows: lax.sort
    "merge_sorted_cols": {("merge", "xla_bitonic"), ("compact", "xla_shift")},
    "compact": {("compact", "xla_shift")},
    "rank_fold": {("rank_fold", "xla"), ("merge", "xla_bitonic"),
                  ("compact", "xla_shift")},
    "lex_probe": {("probe", "xla")},
    "expand_ranges": {("expand", "xla")},
    "lex_probe_ladder": {("probe_ladder", "xla")},
    "join_ladder": _CHAIN_SORTED | {("join_ladder", "xla")},
    "gather_ladder": _CHAIN | {("gather_ladder", "xla")},
    # 4 slots against a level of 60,000 rows: one gather a level
    "gather_ladder_narrow": _CHAIN - {("gather", "xla_flat")} | {
        ("gather", "xla"), ("gather_ladder", "xla")},
    "old_weights_ladder": {("old_weights", "xla"),
                           ("probe_ladder", "xla_merge")},
    "segment_reduce": {("segment_reduce", "xla")},
    # the out trace's probe of the delta's sorted unique keys: the merge
    "agg_ladder": _CHAIN_SORTED | _NET | {
        ("agg_ladder", "xla"), ("gather_ladder", "xla"),
        ("probe", "xla_merge"), ("segment_reduce", "xla")},
}


def _dispatch_call(name):
    from dbsp_tpu.operators.aggregate import Max, segment_reduce
    from dbsp_tpu.zset import cursor

    def batch(keys, vals=(), consolidated=False):
        keys = np.sort(np.asarray(keys, np.int64))
        return Batch.from_columns(
            [keys, keys % 3], [keys + v for v in vals],
            np.ones(len(keys), np.int64), cap=2 * len(keys),
            consolidated=consolidated)

    delta, levels = batch(range(0, 20, 2), (1,)), [
        batch(range(40), (2,)), batch(range(5, 25), (3,))]
    n = kernels.SORT_CHUNK_ROWS
    col = jnp.arange(2 * n, dtype=jnp.int64) % 7
    fn = lambda k, lv, rv: (k, (*lv, *rv))  # noqa: E731
    return {
        "consolidate_cols": lambda: kernels.consolidate_cols(
            (col,), jnp.ones_like(col)),
        "consolidate_cols_one_chunk": lambda: kernels.consolidate_cols(
            (col[:n],), jnp.ones_like(col[:n])),
        "merge_sorted_cols": lambda: kernels.merge_sorted_cols(
            delta.cols, delta.weights, levels[0].cols, levels[0].weights),
        "compact": lambda: kernels.compact((col,), col, col > 3),
        "rank_fold": lambda: concat_batches([delta, levels[0]]).consolidate(),
        "lex_probe": lambda: kernels.lex_probe(levels[0].keys, delta.keys),
        "expand_ranges": lambda: kernels.expand_ranges(
            jnp.asarray([0, 3], jnp.int32), jnp.asarray([2, 7], jnp.int32), 8),
        "lex_probe_ladder": lambda: cursor.lex_probe_ladder(
            [lvl.keys for lvl in levels], delta.keys),
        "join_ladder": lambda: cursor.join_ladder(delta, levels, 2, fn, 64),
        "gather_ladder": lambda: cursor.gather_ladder(
            delta.keys, delta.weights != 0, levels, 64),
        "gather_ladder_narrow": lambda: cursor.gather_ladder(
            delta.keys, delta.weights != 0,
            [levels[0], batch(range(30_000), (2,), consolidated=True)], 4),
        "old_weights_ladder": lambda: cursor.old_weights_ladder(
            delta, levels),
        "segment_reduce": lambda: segment_reduce(
            (("max", 0),), (col,), jnp.ones_like(col),
            (col % 4).astype(jnp.int32), 4),
        "agg_ladder": lambda: cursor.agg_ladder(
            delta, 2, batch(range(0, 20, 4), (1,)), levels, Max(0), 16, 64,
            False, jnp.asarray(True)),
    }[name]


@pytest.mark.parametrize("name", ACCELERATOR_PAIRS)
def test_kernel_counts_the_pairs_a_chip_run_prints(name, accelerator_dispatch):
    call = _dispatch_call(name)
    before = dict(kernels.KERNEL_DISPATCH_COUNTS)
    call()
    took = _took(before)
    assert took == ACCELERATOR_PAIRS[name]
    assert "native" not in {backend for _, backend in took}


def test_every_pair_of_a_chip_run_has_a_case():
    covered = set().union(*ACCELERATOR_PAIRS.values())
    assert CHIP_PAIRS <= covered, CHIP_PAIRS - covered
