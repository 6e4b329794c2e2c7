"""Bit-identity of the REDUCTION-OFFENSIVE megakernels vs the stitched
chains they replaced, with ``DBSP_TPU_NATIVE`` per-kernel force-off as the
control.

The tentpole collapsed CAggregate's reduce chain — unique-keys, out-trace
probe + TupleMax, ladder gather, cross-level netting, aggregator segment
reduction, fast-path delta reduction — into ONE ``cursor.agg_ladder`` call
(native C++ megakernel on CPU; the stitched chain as fallback/control and,
over the accelerator's leaf formulations, what a chip runs), rewired every
built-in Aggregator through the shared five-op ``segment_reduce`` dispatch,
and made the join emit each side as ONE consolidated run (``join_sorted``)
so the post-join consolidate rank-folds instead of sorting. All of that is
only legal because every backend produces identical values:

* kernel level: ``segment_reduce`` / ``agg_ladder`` / sorted-emit join —
  each backend of ``BACKENDS`` (the accelerator's formulation, the
  stitched control ``join_sorted,agg_ladder,segment_reduce`` forced off —
  the PR-12 code path — and pure XLA) a case of its own against the native
  megakernel — on adversarial inputs (all-retraction groups, empty
  deltas, int32 weights, gather-cap overflow with exact unclamped totals,
  duplicate keys across levels, empty and full-capacity levels, runtime
  fast/slow flag both ways);
* engine level: q1–q8 accumulated outputs, host AND compiled, fused vs the
  reduction-off control, plus the fast→slow ``ever_negative`` transition
  bit-identical on BOTH sides of the flip;
* dispatch level: the new fused labels must actually fire (non-vacuous)
  and drop to zero under force-off.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dbsp_tpu.zset import cursor, kernels
from dbsp_tpu.zset.batch import Batch, concat_batches
from dbsp_tpu.operators.aggregate import (Average, Count, Max, Min, Sum,
                                          segment_reduce)
from dbsp_tpu.operators.join import fn_permutation

from test_fused_ladder import (REDUCE_OFF, _adversarial_ladders,
                               _consolidated, _run_compiled, _run_host,
                               _took, _with_backend)

pytestmark = pytest.mark.fast

# each a case of its own, compared with "native" (test_fused_ladder's
# NATIVE_ENV)
BACKENDS = ("accelerator", "stitched_control", "pure_xla")
# segment_reduce has one XLA formulation, on the CPU and off it
XLA_BACKENDS = ("stitched_control", "pure_xla")


def _assert_same(got, want, ctx=""):
    assert len(got) == len(want), ctx
    for i, (g, w) in enumerate(zip(got, want)):
        if g is None and w is None:
            continue
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype, f"{ctx}[{i}]: dtype {g.dtype}!={w.dtype}"
        np.testing.assert_array_equal(g, w, err_msg=f"{ctx}[{i}]")


# ---------------------------------------------------------------------------
# segment_reduce: the shared five-op vocabulary
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", XLA_BACKENDS)
@pytest.mark.parametrize("weight_dtype", [np.int64, np.int32])
def test_segment_reduce_backends_bitidentical(request, backend,
                                              weight_dtype):
    rng = np.random.default_rng(0)
    spec = (("count", 0), ("sum", 0), ("min", 0), ("max", 1), ("avg", 1),
            ("present", 0))
    cases = []
    for n, S in ((1, 1), (64, 7), (300, 41), (500, 130)):
        v1 = jnp.asarray(rng.integers(-1000, 1000, n))
        v2 = jnp.asarray(rng.integers(-9, 9, n).astype(np.int32))
        w = jnp.asarray(rng.integers(-3, 4, n).astype(weight_dtype))
        # seg ids PAST num_segments must be dropped on every backend
        seg = jnp.asarray(rng.integers(0, S + 3, n).astype(np.int32))
        cases.append(((v1, v2), w, seg, S))

    def reduce_all():
        return [o for vals, w, seg, S in cases
                for o in segment_reduce(spec, vals, w, seg, S)]

    want = _with_backend(request, "native", reduce_all)
    _assert_same(_with_backend(request, backend, reduce_all), want,
                 f"segment_reduce {backend}")


@pytest.mark.parametrize("backend", XLA_BACKENDS)
def test_segment_reduce_all_retractions(request, backend):
    """Groups whose every row is a retraction: the additive ops see zero
    positive mass, min/max stay at their identity, present stays 0."""
    v = jnp.asarray([5, 9, -2, 7])
    w = jnp.asarray([-1, -2, -1, 3])
    seg = jnp.asarray([0, 0, 1, 2], jnp.int32)
    spec = (("count", 0), ("sum", 0), ("max", 0), ("present", 0))

    def reduce():
        return segment_reduce(spec, (v,), w, seg, 3)

    want = _with_backend(request, "native", reduce)
    _assert_same(_with_backend(request, backend, reduce), want,
                 f"all-retraction {backend}")
    cnt, s, mx, pres = (np.asarray(x) for x in want)
    assert cnt[0] == 0 and s[0] == 0 and pres[0] == 0
    assert mx[0] == np.iinfo(np.int64).min  # identity never escapes raw
    assert cnt[2] == 3 and pres[2] == 1


# ---------------------------------------------------------------------------
# agg_ladder: the whole CAggregate chain
# ---------------------------------------------------------------------------

AGGS = [(Max(0), True), (Min(0), True), (Count(), False), (Sum(0), False),
        (Average(0), False)]


def _agg_case(rng, weight_dtype=np.int64, empty_delta=False,
              all_retract=False):
    delta = _consolidated(rng, 0 if empty_delta else 22, 32,
                          weight_dtype=weight_dtype)
    if all_retract and not empty_delta:
        delta = Batch(delta.keys, delta.vals,
                      -jnp.abs(delta.weights), delta.runs)
    levels = [_consolidated(rng, 40, 64, weight_dtype=weight_dtype),
              Batch.empty((jnp.int64, jnp.int64), (jnp.int64,), cap=16,
                          weight_dtype=jnp.dtype(weight_dtype)),
              _consolidated(rng, 10, 16, weight_dtype=weight_dtype)]
    out_trace = _consolidated(rng, 12, 16, weight_dtype=weight_dtype)
    return delta, levels, out_trace


def _agg_all(cases, aggs, gather_cap=512):
    """Every leaf of ``cursor.agg_ladder`` over ``cases`` x ``aggs`` x the
    runtime flag's values, as one flat list."""
    def run():
        return [leaf
                for delta, levels, out_trace in cases
                for agg, fast in aggs
                for flag in ((True, False) if fast else (True,))
                for leaf in jax.tree_util.tree_leaves(cursor.agg_ladder(
                    delta, 2, out_trace, levels, agg, 16, gather_cap, fast,
                    jnp.asarray(flag)))]
    return run


def _assert_agg_backend(request, backend, run, ctx):
    """``run`` under ``backend`` equals the native megakernels' leaves,
    which are returned."""
    want = _with_backend(request, "native", run)
    before = dict(kernels.KERNEL_DISPATCH_COUNTS)
    _assert_same(_with_backend(request, backend, run), want,
                 f"{ctx} {backend}")
    if backend == "accelerator":  # the chain a chip's kernel_dispatch shows
        assert _took(before) >= {
            ("agg_ladder", "xla"), ("gather_ladder", "xla"),
            ("segment_reduce", "xla"), ("compact", "xla_shift")}
    return want


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("weight_dtype", [np.int64, np.int32])
def test_agg_ladder_backends_bitidentical(request, backend, weight_dtype):
    rng = np.random.default_rng(1)
    cases = [_agg_case(rng, weight_dtype, **case)
             for case in ({}, {"empty_delta": True}, {"all_retract": True})]
    _assert_agg_backend(request, backend, _agg_all(cases, AGGS),
                        "agg_ladder")


@pytest.mark.parametrize("backend", BACKENDS)
def test_agg_ladder_adversarial_ladders(request, backend):
    """Duplicate keys across four levels, an EMPTY level, a FULL-capacity
    level without a dead tail: both values of the fast path's flag."""
    rng = np.random.default_rng(21)
    cases = [(_consolidated(rng, 20, 32), ladder, _consolidated(rng, 10, 16))
             for ladder in _adversarial_ladders(rng)]
    aggs = [(Max(0), True), (Count(), False), (Average(0), False)]
    _assert_agg_backend(request, backend, _agg_all(cases, aggs),
                        "agg_ladder adversarial")


@pytest.mark.parametrize("backend", BACKENDS)
def test_agg_ladder_gather_overflow_exact(request, backend):
    """gather-cap overflow: every backend must report the SAME unclamped
    total (the requirement the runner's grow/replay keys off) AND the same
    clamped buffers — the megakernel counts raw rows in the stitched
    level-major order, so even the discarded overflow launch matches."""
    rng = np.random.default_rng(2)
    case = (_consolidated(rng, 30, 32, key_range=5),
            [_consolidated(rng, 60, 128, key_range=5),
             _consolidated(rng, 40, 64, key_range=5)],
            _consolidated(rng, 8, 16, key_range=5))
    run = _agg_all([case], [(Sum(0), False)], gather_cap=8)
    want = _assert_agg_backend(request, backend, run, "agg overflow")
    assert int(want[-1]) > 8, "shape must actually overflow the cap"


def test_agg_ladder_counts_dispatch(monkeypatch):
    """Force-off non-vacuity at the cursor level: agg_ladder:native fires
    on the hot path and drops to zero (stitched fallback engaged) under
    DBSP_TPU_NATIVE force-off."""
    rng = np.random.default_rng(3)
    delta, levels, out_trace = _agg_case(rng)
    before = dict(kernels.KERNEL_DISPATCH_COUNTS)
    monkeypatch.setenv("DBSP_TPU_NATIVE", "1")
    cursor.agg_ladder(delta, 2, out_trace, levels, Max(0), 16, 256, True,
                      jnp.asarray(True))
    monkeypatch.setenv("DBSP_TPU_NATIVE", REDUCE_OFF)
    cursor.agg_ladder(delta, 2, out_trace, levels, Max(0), 16, 256, True,
                      jnp.asarray(True))

    def delta_of(kern, backend):
        return kernels.KERNEL_DISPATCH_COUNTS.get((kern, backend), 0) - \
            before.get((kern, backend), 0)

    assert delta_of("agg_ladder", "native") == 1
    assert delta_of("agg_ladder", "xla") == 1


# ---------------------------------------------------------------------------
# sorted-emit join: the post-join sort dies
# ---------------------------------------------------------------------------


def test_fn_permutation_probe():
    """A pure column selection yields its permutation; anything computing
    (arithmetic, astype, constants) is conservatively rejected."""
    fn = lambda k, lv, rv: ((k[0], rv[0]), (lv[0], lv[1], rv[1]))  # noqa
    assert fn_permutation(fn, 2, 2, 2) == (2, (0, 4, 2, 3, 5))
    ident = lambda k, lv, rv: (k, (*lv, *rv))  # noqa: E731
    assert fn_permutation(ident, 1, 1, 1) == (1, (0, 1, 2))
    arith = lambda k, lv, rv: (k, (-lv[0],))  # noqa: E731
    assert fn_permutation(arith, 1, 1, 1) is None
    cast = lambda k, lv, rv: (k, (rv[0].astype(jnp.int32),))  # noqa: E731
    assert fn_permutation(cast, 1, 1, 1) is None
    oob = lambda k, lv, rv: (k, (lv[5],))  # noqa: E731
    assert fn_permutation(oob, 1, 1, 1) is None


@pytest.mark.parametrize("weight_dtype", [np.int64, np.int32])
def test_join_sorted_emits_consolidated_run(monkeypatch, weight_dtype):
    """The sorted-emit buffer IS one canonical run (re-consolidating is a
    no-op) and its Z-set equals the unsorted control's consolidation."""
    fn = lambda k, lv, rv: ((k[0], rv[0]), (lv[0], k[1], rv[1]))  # noqa
    n_out_keys, perm = fn_permutation(fn, 2, 1, 2)
    se = (n_out_keys, perm, tuple(jnp.dtype(jnp.int64) for _ in range(5)))
    rng = np.random.default_rng(4)
    for ladder_seed in range(3):
        delta = _consolidated(rng, 20, 32, weight_dtype=weight_dtype)
        levels = [_consolidated(rng, 40, 64, nv=2,
                                weight_dtype=weight_dtype),
                  _consolidated(rng, 10, 16, nv=2,
                                weight_dtype=weight_dtype)]
        monkeypatch.setenv("DBSP_TPU_NATIVE", "1")
        sb, st = cursor.join_ladder(delta, levels, 2, fn, 512,
                                    sorted_emit=se)
        assert sb.runs == (512,), "sorted emit must tag ONE run"
        monkeypatch.setenv("DBSP_TPU_NATIVE", REDUCE_OFF)
        cb, ct = cursor.join_ladder(delta, levels, 2, fn, 512)
        monkeypatch.setenv("DBSP_TPU_NATIVE", "1")
        assert int(st) == int(ct)
        assert sb.to_dict() == cb.consolidate().to_dict()
        resorted = sb.tagged(None).consolidate()
        _assert_same((*resorted.cols, resorted.weights),
                     (*sb.cols, sb.weights), "sorted emit not canonical")


def test_join_sorted_post_consolidate_rank_folds(monkeypatch):
    """The acceptance shape: concat of two sorted-emit sides consolidates
    through the RANK regime (2 runs, one linear merge — no sort), and the
    result is bit-identical to the full-sort control."""
    fn = lambda k, lv, rv: (k, (*lv, *rv))  # noqa: E731
    rng = np.random.default_rng(5)
    delta = _consolidated(rng, 20, 32)
    levels = [_consolidated(rng, 40, 64)]
    se = (2, (0, 1, 2, 3), tuple(jnp.dtype(jnp.int64) for _ in range(4)))
    monkeypatch.setenv("DBSP_TPU_NATIVE", "1")
    lout, _ = cursor.join_ladder(delta, levels, 2, fn, 256, sorted_emit=se)
    rout, _ = cursor.join_ladder(delta, levels, 2, fn, 128, sorted_emit=se)
    cat = concat_batches([lout, rout])
    assert cat.runs == (256, 128)
    before = dict(kernels.CONSOLIDATE_COUNTS)
    got = cat.consolidate()
    assert kernels.CONSOLIDATE_COUNTS["rank"] == before["rank"] + 1
    monkeypatch.setenv("DBSP_TPU_NATIVE", REDUCE_OFF)
    lc, _ = cursor.join_ladder(delta, levels, 2, fn, 256)
    rc, _ = cursor.join_ladder(delta, levels, 2, fn, 128)
    want = concat_batches([lc, rc]).consolidate()
    monkeypatch.setenv("DBSP_TPU_NATIVE", "1")
    _assert_same((*got.cols, got.weights), (*want.cols, want.weights),
                 "rank-folded != sorted control")


def test_join_sorted_overflow_totals_exact(monkeypatch):
    fn = lambda k, lv, rv: (k, (*lv, *rv))  # noqa: E731
    se = (2, (0, 1, 2, 3), tuple(jnp.dtype(jnp.int64) for _ in range(4)))
    rng = np.random.default_rng(6)
    delta = _consolidated(rng, 40, 64, key_range=5)
    levels = [_consolidated(rng, 60, 128, key_range=5) for _ in range(2)]
    monkeypatch.setenv("DBSP_TPU_NATIVE", "1")
    _, st = cursor.join_ladder(delta, levels, 2, fn, 16, sorted_emit=se)
    monkeypatch.setenv("DBSP_TPU_NATIVE", REDUCE_OFF)
    _, ct = cursor.join_ladder(delta, levels, 2, fn, 16)
    monkeypatch.setenv("DBSP_TPU_NATIVE", "1")
    assert int(st) == int(ct) and int(st) > 16


# ---------------------------------------------------------------------------
# engine level: fused vs the reduction-off control
# ---------------------------------------------------------------------------

CONTROL_ENV = {"DBSP_TPU_NATIVE": REDUCE_OFF}

QUERIES_FAST = ("q4", "q8")
QUERIES_ALL = ("q1", "q2", "q3", "q4", "q5", "q6", "q7", "q8")


@pytest.mark.parametrize("qname", QUERIES_ALL)
def test_host_engine_fused_vs_reduction_off(monkeypatch, qname):
    want = _run_host(qname)
    for k, v in CONTROL_ENV.items():
        monkeypatch.setenv(k, v)
    assert _run_host(qname) == want


@pytest.mark.parametrize("qname", QUERIES_FAST)
def test_compiled_engine_fused_vs_reduction_off(monkeypatch, qname):
    want = _run_compiled(qname)
    assert want, f"{qname} produced no output — vacuous comparison"
    for k, v in CONTROL_ENV.items():
        monkeypatch.setenv(k, v)
    assert _run_compiled(qname) == want


@pytest.mark.slow
@pytest.mark.parametrize("qname", QUERIES_ALL)
def test_compiled_engine_fused_vs_reduction_off_full(monkeypatch, qname):
    want = _run_compiled(qname)
    for k, v in CONTROL_ENV.items():
        monkeypatch.setenv(k, v)
    assert _run_compiled(qname) == want


def _flip_feeds():
    """A feed schedule that crosses the ever_negative flip mid-run: pure
    inserts, then the FIRST retraction (tick 2 — the fast path's runtime
    ladder gate flips on, no retrace), then inserts again, then a
    retraction of the current maximum (only the slow re-gather can answer
    it), then a tick that fully retracts one group (present must drop)."""
    K, V = (jnp.int64,), (jnp.int64,)
    ticks = [
        [((7, 1), 1), ((7, 5), 1), ((9, 3), 1)],
        [((7, 7), 1), ((9, 6), 1)],
        [((7, 5), -1), ((11, 2), 1)],          # flip: first retraction
        [((7, 4), 1), ((9, 9), 1)],
        [((7, 7), -1)],                        # retract the current max
        [((11, 2), -1)],                       # all-retraction group
        [],                                    # empty delta after the flip
        [((7, 2), 1)],
    ]
    return K, V, ticks


def _run_flip_compiled():
    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.compiled import compile_circuit
    from dbsp_tpu.operators import add_input_zset

    jax.clear_caches()  # trace-time dispatch — see test_fused_ladder
    K, V, ticks = _flip_feeds()

    def build(c):
        s, h = add_input_zset(c, K, V)
        return h, s.aggregate(Max(0)).output()

    handle, (h, out) = Runtime.init_circuit(1, build)
    ch = compile_circuit(handle)
    outs = []
    for t, rows in enumerate(ticks):
        feeds = {h: Batch.from_tuples(rows, K, V)} if rows else {}
        ch.step(tick=t, feeds=feeds)
        ch.validate()
        b = ch.output(out)
        outs.append(b.to_dict() if b is not None else {})
    return outs


def _run_flip_host():
    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.operators import add_input_zset

    jax.clear_caches()
    K, V, ticks = _flip_feeds()

    def build(c):
        s, h = add_input_zset(c, K, V)
        return h, s.aggregate(Max(0)).output()

    handle, (h, out) = Runtime.init_circuit(1, build)
    outs = []
    for rows in ticks:
        if rows:
            h.push_batch(Batch.from_tuples(rows, K, V))
        handle.step()
        b = out.take()
        outs.append(b.to_dict() if b is not None else {})
    return outs


def test_fast_to_slow_flip_bitidentical(monkeypatch):
    """The insert-combinable fast path's ever_negative transition: per-tick
    output deltas are bit-identical to the reduction-off control AND to
    the host engine on BOTH sides of the flip — including the
    retract-the-maximum tick (slow re-gather), the all-retraction group
    (present drops), and an empty delta after the flip."""
    fused = _run_flip_compiled()
    host = _run_flip_host()
    for k, v in CONTROL_ENV.items():
        monkeypatch.setenv(k, v)
    control = _run_flip_compiled()
    host_control = _run_flip_host()
    assert fused == control, "compiled flip run diverged from control"
    assert host == host_control, "host flip run diverged from control"
    assert fused == host, "compiled flip run diverged from host engine"
    # ground truth spot checks: the retracted max falls back to 4, the
    # fully retracted group 11 disappears
    acc = {}
    for d in fused:
        for r, w in d.items():
            acc[r] = acc.get(r, 0) + w
            if not acc[r]:
                del acc[r]
    assert acc == {(7, 4): 1, (9, 9): 1}
