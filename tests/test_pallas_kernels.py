"""Interpret-mode bit-identity of the Pallas kernel prototypes.

The Pallas programs (zset/pallas_kernels.py) are selected on accelerator
backends, where the tier-1 suite cannot run them compiled — so the suite
pins them through the Pallas INTERPRETER on CPU instead: same kernel
bodies, same traced control flow, executed without Mosaic. Every test
compares against the pure-XLA reference on the adversarial ladder shapes
from tests/test_cursor.py (duplicate keys across levels, empty levels,
full-capacity batches, cancelling weights, sentinel tails).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from dbsp_tpu.zset import cursor, kernels, pallas_kernels
from dbsp_tpu.zset.batch import Batch

pytestmark = pytest.mark.fast


@pytest.fixture
def pallas_interpret(monkeypatch):
    """Force the Pallas dispatch path (interpreter) regardless of backend."""
    monkeypatch.setenv("DBSP_TPU_PALLAS", "interpret")


def _consolidated(rng, n_live, cap, nk=2, nv=1, key_range=40,
                  allow_neg=True):
    lo = -3 if allow_neg else 1
    rows = []
    for _ in range(n_live):
        key = tuple(int(rng.integers(0, key_range)) for _ in range(nk + nv))
        w = int(rng.integers(lo, 4)) or 1
        rows.append((key, w))
    cols = [np.array([r[0][i] for r in rows], dtype=np.int64)
            for i in range(nk + nv)]
    ws = np.array([r[1] for r in rows], dtype=np.int64)
    return Batch.from_columns(cols[:nk], cols[nk:], ws, cap=cap)


def _adversarial_ladders(rng):
    """Ladder shapes that broke per-level loops before: duplicate keys
    across levels, an EMPTY level, a FULL-capacity level (no dead tail),
    heterogeneous caps."""
    # a FULL-capacity level: every slot live, no dead sentinel tail
    full = Batch.from_columns(
        [np.arange(64, dtype=np.int64), np.arange(64, dtype=np.int64) % 7],
        [np.zeros(64, np.int64)], np.ones(64, np.int64), cap=64)
    assert int(full.live_count()) == 64
    yield [_consolidated(rng, max(2, c // 3), c) for c in (256, 64, 32, 16)]
    yield [_consolidated(rng, 20, 64), Batch.empty((jnp.int64, jnp.int64),
                                                   (jnp.int64,), cap=32),
           _consolidated(rng, 10, 16)]
    yield [full, _consolidated(rng, 30, 64, key_range=8)]


# ---------------------------------------------------------------------------
# ladder-wide lex probe
# ---------------------------------------------------------------------------


def test_probe_ladder_interpret_bitidentical(pallas_interpret, monkeypatch):
    rng = np.random.default_rng(0)
    for ladder in _adversarial_ladders(rng):
        tables = [lvl.keys for lvl in ladder]
        delta = _consolidated(rng, 20, 32)
        for side in ("left", "right"):
            got = np.asarray(pallas_kernels.lex_probe_ladder_pallas(
                tables, delta.keys, side))
            monkeypatch.setenv("DBSP_TPU_PALLAS", "0")
            monkeypatch.setenv("DBSP_TPU_NATIVE", "0")
            want = np.asarray(cursor.lex_probe_ladder(tables, delta.keys,
                                                      side))
            monkeypatch.setenv("DBSP_TPU_PALLAS", "interpret")
            monkeypatch.setenv("DBSP_TPU_NATIVE", "1")
            np.testing.assert_array_equal(got, want, err_msg=side)


def test_probe_ladder_dispatches_pallas(pallas_interpret):
    """The cursor entry point routes to the Pallas kernel (and counts the
    dispatch) when the override is active."""
    rng = np.random.default_rng(1)
    levels = [_consolidated(rng, 10, 32), _consolidated(rng, 5, 16)]
    delta = _consolidated(rng, 8, 16)
    before = dict(kernels.KERNEL_DISPATCH_COUNTS)
    out = cursor.lex_probe_ladder([lvl.keys for lvl in levels], delta.keys)
    assert out.shape == (2, 16)
    assert kernels.KERNEL_DISPATCH_COUNTS.get(("probe_ladder", "pallas"), 0) \
        > before.get(("probe_ladder", "pallas"), 0)


def test_use_pallas_gates_float_columns(pallas_interpret):
    f = jnp.zeros((8,), jnp.float32)
    i = jnp.zeros((8,), jnp.int64)
    assert pallas_kernels.use_pallas("probe_ladder", (i, i))
    assert not pallas_kernels.use_pallas("probe_ladder", (i, f))


def test_pallas_disabled_by_default_on_cpu(monkeypatch):
    monkeypatch.delenv("DBSP_TPU_PALLAS", raising=False)
    assert not pallas_kernels.enabled()  # tier-1 runs JAX_PLATFORMS=cpu
    monkeypatch.setenv("DBSP_TPU_PALLAS", "0")
    assert not pallas_kernels.enabled()
    monkeypatch.setenv("DBSP_TPU_PALLAS", "interpret")
    assert pallas_kernels.enabled() and pallas_kernels.interpret_mode()


# ---------------------------------------------------------------------------
# fused ladder-consumer megakernels (join_ladder / gather_ladder)
# ---------------------------------------------------------------------------


def test_join_ladder_megakernel_interpret_bitidentical(pallas_interpret,
                                                       monkeypatch):
    """The grid-over-levels join megakernel vs the pure-XLA stitched chain
    on the adversarial ladders — whole-Batch output + unclamped total."""
    fn = lambda k, lv, rv: (k, (*lv, *rv))  # noqa: E731
    rng = np.random.default_rng(30)
    for ladder in _adversarial_ladders(rng):
        delta = _consolidated(rng, 20, 32)
        got, gt = cursor.join_ladder(delta, ladder, 2, fn, 1024)
        monkeypatch.setenv("DBSP_TPU_PALLAS", "0")
        monkeypatch.setenv("DBSP_TPU_NATIVE", "0")
        want, wt = cursor.join_ladder(delta, ladder, 2, fn, 1024)
        monkeypatch.setenv("DBSP_TPU_PALLAS", "interpret")
        monkeypatch.setenv("DBSP_TPU_NATIVE", "1")
        assert int(gt) == int(wt)
        for g, w in zip((*got.cols, got.weights), (*want.cols,
                                                   want.weights)):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_gather_ladder_megakernel_interpret_bitidentical(pallas_interpret,
                                                         monkeypatch):
    rng = np.random.default_rng(31)
    for ladder in _adversarial_ladders(rng):
        delta = _consolidated(rng, 24, 32)
        qlive = jnp.asarray(np.asarray(delta.weights) != 0)
        got = cursor.gather_ladder(delta.keys, qlive, ladder, 1024)
        monkeypatch.setenv("DBSP_TPU_PALLAS", "0")
        monkeypatch.setenv("DBSP_TPU_NATIVE", "0")
        want = cursor.gather_ladder(delta.keys, qlive, ladder, 1024)
        monkeypatch.setenv("DBSP_TPU_PALLAS", "interpret")
        monkeypatch.setenv("DBSP_TPU_NATIVE", "1")
        (gq, gv, gw), gt = got
        (wq, wv, ww), wt = want
        assert int(gt) == int(wt)
        for g, w in zip((gq, *gv, gw), (wq, *wv, ww)):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_ladder_megakernels_dispatch_pallas(pallas_interpret):
    """The cursor entry points route to the megakernels (and count the
    dispatch) when the override is active."""
    fn = lambda k, lv, rv: (k, (*lv, *rv))  # noqa: E731
    rng = np.random.default_rng(32)
    levels = [_consolidated(rng, 10, 32), _consolidated(rng, 5, 16)]
    delta = _consolidated(rng, 8, 16)
    before = dict(kernels.KERNEL_DISPATCH_COUNTS)
    cursor.join_ladder(delta, levels, 2, fn, 256)
    cursor.gather_ladder(delta.keys, delta.weights != 0, levels, 256)
    for kern in ("join_ladder", "gather_ladder"):
        assert kernels.KERNEL_DISPATCH_COUNTS.get((kern, "pallas"), 0) > \
            before.get((kern, "pallas"), 0), kern


# ---------------------------------------------------------------------------
# rank-merge inner loop
# ---------------------------------------------------------------------------


def _xla_rank_scatter(cols_a, w_a, cols_b, w_b):
    """The XLA formulation of the rank-merge inner loop (the reference the
    Pallas program must reproduce bit-for-bit)."""
    na, nb = w_a.shape[0], w_b.shape[0]
    ra = kernels.lex_probe(cols_b, cols_a, side="left")
    rb = kernels.lex_probe(cols_a, cols_b, side="right")
    pos_a = jnp.arange(na, dtype=jnp.int32) + ra
    pos_b = jnp.arange(nb, dtype=jnp.int32) + rb
    out = []
    for ca, cb in zip(cols_a, cols_b):
        buf = kernels.sentinel_fill((na + nb,), ca.dtype)
        out.append(buf.at[pos_a].set(ca).at[pos_b].set(cb.astype(ca.dtype)))
    w = jnp.zeros((na + nb,), w_a.dtype).at[pos_a].set(w_a) \
        .at[pos_b].set(w_b)
    return tuple(out), w


@pytest.mark.parametrize("seed", range(4))
def test_rank_merge_scatter_interpret_bitidentical(pallas_interpret,
                                                   monkeypatch, seed):
    rng = np.random.default_rng(10 + seed)
    a = _consolidated(rng, int(rng.integers(0, 50)), 64, key_range=12)
    b = _consolidated(rng, int(rng.integers(0, 100)), 128, key_range=12)
    got_cols, got_w = pallas_kernels.rank_merge_scatter(
        a.cols, a.weights, b.cols, b.weights)
    monkeypatch.setenv("DBSP_TPU_NATIVE", "0")
    want_cols, want_w = _xla_rank_scatter(a.cols, a.weights, b.cols,
                                          b.weights)
    for g, w in zip((*got_cols, got_w), (*want_cols, want_w)):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_merge_sorted_cols_rank_path_via_pallas(pallas_interpret,
                                                monkeypatch):
    """Force the accelerator strategy on CPU: merge_sorted_cols' rank
    branch must select the Pallas program and still produce the canonical
    merge (== the sort path)."""
    rng = np.random.default_rng(20)
    a = _consolidated(rng, 40, 64, key_range=10)
    b = _consolidated(rng, 70, 128, key_range=10)
    monkeypatch.setattr(kernels, "merge_strategy", lambda: "bitonic")
    before = dict(kernels.KERNEL_DISPATCH_COUNTS)
    got = kernels.merge_sorted_cols(a.cols, a.weights, b.cols, b.weights)
    assert kernels.KERNEL_DISPATCH_COUNTS.get(("merge", "pallas"), 0) > \
        before.get(("merge", "pallas"), 0)
    monkeypatch.setenv("DBSP_TPU_PALLAS", "0")
    xla_rank = kernels.merge_sorted_cols(a.cols, a.weights, b.cols,
                                         b.weights)
    monkeypatch.undo()
    cols = tuple(jnp.concatenate([x, y.astype(x.dtype)])
                 for x, y in zip(a.cols, b.cols))
    sort_ref = kernels.consolidate_cols(
        cols, jnp.concatenate([a.weights, b.weights]))
    for g, w, s in zip((*got[0], got[1]), (*xla_rank[0], xla_rank[1]),
                       (*sort_ref[0], sort_ref[1])):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        np.testing.assert_array_equal(np.asarray(g), np.asarray(s))


def test_rank_merge_full_capacity_no_dead_tail(pallas_interpret,
                                               monkeypatch):
    """Full-cap inputs (every slot live) — the overflow-adjacent shape:
    no sentinel tail to hide scatter mistakes behind."""
    a = Batch.from_columns([jnp.arange(0, 16, dtype=jnp.int64)], [],
                           jnp.ones((16,), jnp.int64), cap=16,
                           consolidated=True)
    b = Batch.from_columns([jnp.arange(8, 24, dtype=jnp.int64)], [],
                           -jnp.ones((16,), jnp.int64), cap=16,
                           consolidated=True)
    got_cols, got_w = pallas_kernels.rank_merge_scatter(
        a.cols, a.weights, b.cols, b.weights)
    monkeypatch.setenv("DBSP_TPU_NATIVE", "0")
    want_cols, want_w = _xla_rank_scatter(a.cols, a.weights, b.cols,
                                          b.weights)
    for g, w in zip((*got_cols, got_w), (*want_cols, want_w)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# segment reduce + composed aggregate megakernel (the reduction offensive)
# ---------------------------------------------------------------------------


def test_segment_reduce_interpret_bitidentical(pallas_interpret,
                                               monkeypatch):
    """The five-op segment reduction as one Pallas program per segment
    block — identical to the jax.ops.segment_* formulation, including
    identity fills for empty segments, retraction-only segments, and
    dropped out-of-range seg ids."""
    from dbsp_tpu.operators.aggregate import segment_reduce

    rng = np.random.default_rng(20)
    spec = (("count", 0), ("sum", 0), ("min", 0), ("max", 1), ("avg", 1),
            ("present", 0))
    for n, S in ((1, 1), (64, 7), (500, 130)):  # crosses the 128 block
        v1 = jnp.asarray(rng.integers(-1000, 1000, n))
        v2 = jnp.asarray(rng.integers(-9, 9, n).astype(np.int32))
        w = jnp.asarray(rng.integers(-3, 4, n))
        seg = jnp.asarray(rng.integers(0, S + 5, n).astype(np.int32))
        monkeypatch.setenv("DBSP_TPU_NATIVE", "0")
        got = segment_reduce(spec, (v1, v2), w, seg, S)
        monkeypatch.setenv("DBSP_TPU_PALLAS", "0")
        want = segment_reduce(spec, (v1, v2), w, seg, S)
        monkeypatch.setenv("DBSP_TPU_PALLAS", "interpret")
        for i, (g, ww) in enumerate(zip(got, want)):
            assert g.dtype == ww.dtype, (i, g.dtype, ww.dtype)
            np.testing.assert_array_equal(np.asarray(g), np.asarray(ww),
                                          err_msg=f"op {i} n={n}")


def test_agg_ladder_composed_interpret_bitidentical(pallas_interpret,
                                                    monkeypatch):
    """The composed accelerator lowering of cursor.agg_ladder (Pallas
    gather megakernel + Pallas segment reduce) equals the pure-XLA
    stitched chain on adversarial ladders, both fast-path flag values."""
    from dbsp_tpu.operators.aggregate import Average, Count, Max

    import jax

    rng = np.random.default_rng(21)
    for ladder in _adversarial_ladders(rng):
        delta = _consolidated(rng, 20, 32)
        out_trace = _consolidated(rng, 10, 16)
        for agg, fast in ((Max(0), True), (Count(), False),
                          (Average(0), False)):
            for flag in ((True, False) if fast else (True,)):
                monkeypatch.setenv("DBSP_TPU_NATIVE", "0")
                got = cursor.agg_ladder(delta, 2, out_trace, ladder, agg,
                                        16, 512, fast, jnp.asarray(flag))
                monkeypatch.setenv("DBSP_TPU_PALLAS", "0")
                want = cursor.agg_ladder(delta, 2, out_trace, ladder, agg,
                                         16, 512, fast, jnp.asarray(flag))
                monkeypatch.setenv("DBSP_TPU_PALLAS", "interpret")
                for i, (g, w) in enumerate(zip(
                        jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want))):
                    g, w = np.asarray(g), np.asarray(w)
                    assert g.dtype == w.dtype, (agg.name, i)
                    np.testing.assert_array_equal(
                        g, w, err_msg=f"{agg.name} flag={flag} leaf {i}")


def test_new_kernels_dispatch_pallas(pallas_interpret):
    """Non-vacuity: the interpret runs above actually ride the Pallas
    dispatch counters (segment_reduce + agg_ladder labels)."""
    from dbsp_tpu.operators.aggregate import Max, segment_reduce

    rng = np.random.default_rng(22)
    before = dict(kernels.KERNEL_DISPATCH_COUNTS)
    segment_reduce((("max", 0),), (jnp.asarray([1, 2]),),
                   jnp.asarray([1, 1]), jnp.asarray([0, 1], jnp.int32), 2)
    delta = _consolidated(rng, 8, 16)
    cursor.agg_ladder(delta, 2, _consolidated(rng, 4, 8),
                      [_consolidated(rng, 6, 8)], Max(0), 8, 64, True,
                      jnp.asarray(True))

    def delta_of(kern):
        return kernels.KERNEL_DISPATCH_COUNTS.get((kern, "pallas"), 0) - \
            before.get((kern, "pallas"), 0)

    assert delta_of("segment_reduce") >= 1
    assert delta_of("agg_ladder") == 1
