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


def test_sort_rows_takes_the_chunked_path_off_cpu(monkeypatch):
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
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
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
