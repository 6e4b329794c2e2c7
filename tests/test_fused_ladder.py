"""Bit-identity of the FUSED ladder-consumer megakernels vs the stitched
chain they replaced, with the ``DBSP_TPU_NATIVE`` per-kernel force-off as
the control.

The trace-tax tentpole collapsed each trace consumer (incremental join,
aggregate group gather, distinct old-weight lookup) from a stitched
probe-ladder/expand/gather chain — 4+ dispatches with XLA where-mask glue —
into ONE native C++ megakernel call on the CPU, and made the compiled
CTrace post view LAZY (consumers probe the appended delta as its own ladder
level instead of re-reading the written slot). Off the CPU the stitched
chain IS the ladder kernel, composed over the accelerator's leaf
formulations. All of that is only legal because every backend produces
identical batches:

* kernel level: join_ladder / gather_ladder (equality AND range form) /
  old_weights_ladder — each backend of ``BACKENDS`` (the accelerator's
  formulation, stitched native, pure XLA) a case of its own against the
  native megakernel — on adversarial ladders (duplicate keys across
  levels, EMPTY levels, full-capacity levels, cancelling weights, dead
  query rows, int32 weights, out_cap overflow with exact unclamped totals);
* engine level: q1–q8 accumulated outputs, host AND compiled, fused vs the
  force-off + lazy-post-off control (the stitched pre-change code path);
* dispatch level: the compiled q4 hot loop must ACTUALLY select the fused
  kernels (non-vacuous — the lint front's import-based tier-1 twin).
"""

import os

import numpy as np
import pytest

import jax.numpy as jnp

from dbsp_tpu.zset import cursor, kernels
from dbsp_tpu.zset.batch import Batch

pytestmark = pytest.mark.fast

# the full stitched control: PR-12's fused ladder consumers AND the
# reduction-offensive layer on top of them (sorted-emit join, aggregate
# megakernel, opcode segment reduce) all forced off
FUSED_OFF = ("join_ladder,gather_ladder,old_weights,"
             "join_sorted,agg_ladder,segment_reduce")
# the reduction offensive alone forced off — the PR-12 code path, the A/B
# control BENCH_local_aggfuse_off.json uses
REDUCE_OFF = "join_sorted,agg_ladder,segment_reduce"


def _consolidated(rng, n_live, cap, nk=2, nv=1, key_range=40,
                  allow_neg=True, weight_dtype=np.int64):
    lo = -3 if allow_neg else 1
    rows = []
    for _ in range(n_live):
        key = tuple(int(rng.integers(0, key_range)) for _ in range(nk + nv))
        w = int(rng.integers(lo, 4)) or 1
        rows.append((key, w))
    cols = [np.array([r[0][i] for r in rows], dtype=np.int64)
            for i in range(nk + nv)]
    ws = np.array([r[1] for r in rows], dtype=weight_dtype)
    return Batch.from_columns(cols[:nk], cols[nk:], ws, cap=cap)


def _adversarial_ladders(rng, weight_dtype=np.int64):
    full = Batch.from_columns(
        [np.arange(64, dtype=np.int64), np.arange(64, dtype=np.int64) % 7],
        [np.zeros(64, np.int64)], np.ones(64, weight_dtype), cap=64)
    yield [_consolidated(rng, max(2, c // 3), c, weight_dtype=weight_dtype)
           for c in (256, 64, 32, 16)]
    yield [_consolidated(rng, 20, 64, weight_dtype=weight_dtype),
           Batch.empty((jnp.int64, jnp.int64), (jnp.int64,), cap=32,
                       weight_dtype=jnp.dtype(weight_dtype)),
           _consolidated(rng, 10, 16, weight_dtype=weight_dtype)]
    yield [full, _consolidated(rng, 30, 64, key_range=8,
                               weight_dtype=weight_dtype)]


# DBSP_TPU_NATIVE per backend. "accelerator" is what a chip runs: no
# native kernel, and the dispatch steered off the CPU (conftest's
# ``accelerator_dispatch``), so the ladder kernels' XLA chains compose over
# the shift compaction, the merge network and the doubling group sums.
# "stitched_control" is the committed A/B control (the PR-12 code path:
# fused ladder consumers still native, the reduction layer forced off);
# "pure_xla" strips the native kernels entirely.
NATIVE_ENV = {
    "native": "1",
    "accelerator": "0",
    "stitched_native": FUSED_OFF,
    "stitched_control": REDUCE_OFF,
    "pure_xla": "0",
}
# each a case of its own, compared with "native"
BACKENDS = ("accelerator", "stitched_native", "pure_xla")
# where the chain meets no leaf kernel that has an accelerator formulation
# of its own, the steer changes nothing over "pure_xla"
XLA_BACKENDS = ("stitched_native", "pure_xla")


def _with_backend(request, backend, fn):
    """``fn()`` under one entry of ``NATIVE_ENV``. The steer is one-way
    within a test: take the native reference first."""
    request.getfixturevalue("monkeypatch").setenv("DBSP_TPU_NATIVE",
                                                  NATIVE_ENV[backend])
    if backend == "accelerator":
        request.getfixturevalue("accelerator_dispatch")
    return fn()


def _took(before):
    """The (kernel, backend) pairs counted since ``before`` was copied."""
    return {k for k, n in kernels.KERNEL_DISPATCH_COUNTS.items()
            if n > before.get(k, 0)}


def _assert_same(got, want, ctx=""):
    assert len(got) == len(want), ctx
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype, f"{ctx}: dtype {g.dtype} != {w.dtype}"
        np.testing.assert_array_equal(g, w, err_msg=ctx)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("weight_dtype", [np.int64, np.int32])
def test_join_ladder_backends_bitidentical(request, backend, weight_dtype):
    """The raw join buffer, and what CJoin makes of it: its consolidation
    (4,096 slots, above SORT_CHUNK_ROWS — off the CPU the chunked sort,
    the doubling group sums and the shift compaction)."""
    fn = lambda k, lv, rv: (k, (*lv, *rv))  # noqa: E731
    rng = np.random.default_rng(0)
    cases = [(ladder, _consolidated(rng, 20, 32, weight_dtype=weight_dtype))
             for ladder in _adversarial_ladders(rng, weight_dtype)]

    def join_all():
        outs = []
        for ladder, delta in cases:
            out, total = cursor.join_ladder(delta, ladder, 2, fn, 4096)
            net = out.consolidate()
            outs += [*out.cols, out.weights, np.asarray(total),
                     *net.cols, net.weights]
        return outs

    want = _with_backend(request, "native", join_all)
    before = dict(kernels.KERNEL_DISPATCH_COUNTS)
    _assert_same(_with_backend(request, backend, join_all), want,
                 f"join_ladder {backend}")
    if backend == "accelerator":
        assert _took(before) >= {
            ("join_ladder", "xla"), ("sort_merge", "xla_bitonic"),
            ("compact", "xla_shift")}


@pytest.mark.parametrize("backend", BACKENDS)
def test_gather_ladder_backends_bitidentical(request, backend):
    """The gathered part, and what CAggregate makes of it: cross-level
    netting (a consolidation) and the per-group reduction."""
    from dbsp_tpu.operators.aggregate import Max, _reduce_groups_impl

    rng = np.random.default_rng(1)
    cases = []
    for ladder in _adversarial_ladders(rng):
        delta = _consolidated(rng, 24, 32)
        qlive = np.asarray(delta.weights) != 0
        qlive[-3:] = False
        cases.append((ladder, delta.keys, jnp.asarray(qlive)))

    def gather_all():
        outs = []
        for ladder, qkeys, qlive in cases:
            part, total = cursor.gather_ladder(qkeys, qlive, ladder, 1024)
            qrow, vals, w = part
            reduced, present = _reduce_groups_impl(
                (part,), Max(0), qlive.shape[0], net=True)
            outs += [qrow, *vals, w, np.asarray(total), *reduced, present]
        return outs

    want = _with_backend(request, "native", gather_all)
    before = dict(kernels.KERNEL_DISPATCH_COUNTS)
    _assert_same(_with_backend(request, backend, gather_all), want,
                 f"gather_ladder {backend}")
    if backend == "accelerator":
        assert _took(before) >= {
            ("gather_ladder", "xla"), ("compact", "xla_shift"),
            ("segment_reduce", "xla")}


@pytest.mark.parametrize("backend", XLA_BACKENDS)
def test_range_gather_ladder_backends_bitidentical(request, backend):
    """The range form (distinct qhi bounds + probed-key gather-back — the
    CRolling/radix consumers), including EMPTY ranges where qhi < qlo."""
    rng = np.random.default_rng(2)
    levels = tuple(_consolidated(rng, 30, 64, nk=2, nv=2) for _ in range(3))
    qp = jnp.asarray(rng.integers(0, 8, 16).astype(np.int64))
    qlo = jnp.asarray(rng.integers(0, 20, 16).astype(np.int64))
    qhi = qlo + jnp.asarray(rng.integers(-2, 10, 16).astype(np.int64))
    qlive = jnp.asarray(rng.integers(0, 2, 16).astype(bool))

    def gather():
        (qrow, vals, w), total = cursor.gather_ladder(
            (qp, qlo), qlive, levels, 512, qhi_keys=(qp, qhi), gather_keys=1)
        return (qrow, *vals, w, np.asarray(total))

    want = _with_backend(request, "native", gather)
    _assert_same(_with_backend(request, backend, gather), want,
                 f"range gather {backend}")


@pytest.mark.parametrize("backend", XLA_BACKENDS)
@pytest.mark.parametrize("weight_dtype", [np.int64, np.int32])
def test_old_weights_ladder_backends_bitidentical(request, backend,
                                                  weight_dtype):
    rng = np.random.default_rng(3)
    cases = [(ladder, _consolidated(rng, 16, 32, weight_dtype=weight_dtype))
             for ladder in _adversarial_ladders(rng, weight_dtype)]

    def old_weights():
        return [cursor.old_weights_ladder(delta, ladder)
                for ladder, delta in cases]

    want = _with_backend(request, "native", old_weights)
    _assert_same(_with_backend(request, backend, old_weights), want,
                 f"old_weights {backend}")


@pytest.mark.parametrize("backend", XLA_BACKENDS)
def test_overflow_totals_exact_on_every_backend(request, backend):
    """out_cap overflow: every backend must report the SAME unclamped
    total — it is the requirement the runner's grow/replay contract keys
    off (a clamped or drifted total silently loses rows)."""
    fn = lambda k, lv, rv: (k, (*lv, *rv))  # noqa: E731
    rng = np.random.default_rng(4)
    delta = _consolidated(rng, 40, 64, key_range=5)
    levels = [_consolidated(rng, 60, 128, key_range=5) for _ in range(2)]

    def totals():
        _, jt = cursor.join_ladder(delta, levels, 2, fn, 16)
        _, gt = cursor.gather_ladder(delta.keys, delta.weights != 0, levels,
                                     16)
        return int(jt), int(gt)

    want = _with_backend(request, "native", totals)
    assert _with_backend(request, backend, totals) == want, backend
    assert want[0] > 16, "shape must actually overflow"


def test_fused_kernels_count_dispatch(monkeypatch):
    """Force-off knob non-vacuity at the cursor level: the fused label is
    counted on the hot path and goes to ZERO (with the stitched fallback
    engaged) under DBSP_TPU_NATIVE force-off."""
    fn = lambda k, lv, rv: (k, (*lv, *rv))  # noqa: E731
    rng = np.random.default_rng(5)
    levels = [_consolidated(rng, 10, 32), _consolidated(rng, 5, 16)]
    delta = _consolidated(rng, 8, 16)
    before = dict(kernels.KERNEL_DISPATCH_COUNTS)
    monkeypatch.setenv("DBSP_TPU_NATIVE", "1")
    cursor.join_ladder(delta, levels, 2, fn, 256)
    monkeypatch.setenv("DBSP_TPU_NATIVE", FUSED_OFF)
    cursor.join_ladder(delta, levels, 2, fn, 256)

    def delta_of(kern, backend):
        return kernels.KERNEL_DISPATCH_COUNTS.get((kern, backend), 0) - \
            before.get((kern, backend), 0)

    assert delta_of("join_ladder", "native") == 1
    assert delta_of("join_ladder", "xla") == 1


# ---------------------------------------------------------------------------
# engine-level bit-identity: fused vs the stitched + materialized control
# ---------------------------------------------------------------------------

# the full legacy control: fused megakernels forced off AND the lazy
# CTrace post view disabled — the pre-tentpole code path
CONTROL_ENV = {"DBSP_TPU_NATIVE": FUSED_OFF, "DBSP_TPU_TRACE_LAZY_POST": "0"}

QUERIES_FAST = ("q4", "q8")          # join+aggregate / join+distinct
QUERIES_ALL = ("q1", "q2", "q3", "q4", "q5", "q6", "q7", "q8")


def _accumulate(out_batch, integral):
    if out_batch is None:
        return
    for r, w in out_batch.to_dict().items():
        integral[r] = integral.get(r, 0) + w
        if integral[r] == 0:
            del integral[r]


def _run_host(qname, workers=1, ticks=2, per_tick=800):
    import jax

    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.nexmark import (GeneratorConfig, NexmarkGenerator,
                                  build_inputs, queries)

    # backend dispatch happens at TRACE time: a cached jit from the prior
    # env setting would make the A/B comparison vacuous
    jax.clear_caches()
    gen = NexmarkGenerator(GeneratorConfig(seed=7))

    def build(c):
        streams, handles = build_inputs(c)
        return handles, getattr(queries, qname)(*streams).output()

    handle, (handles, out) = Runtime.init_circuit(workers, build)
    integral, n = {}, 0
    for _ in range(ticks):
        gen.feed(handles, n, n + per_tick)
        handle.step()
        _accumulate(out.take(), integral)
        n += per_tick
    return integral


def _run_compiled(qname, ticks=3, per_tick=40):
    import jax

    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.compiled import compile_circuit
    from dbsp_tpu.nexmark import (GeneratorConfig, build_inputs, device_gen,
                                  queries)

    jax.clear_caches()  # see _run_host — trace-time dispatch
    cfg = GeneratorConfig(seed=7)

    def build(c):
        streams, handles = build_inputs(c)
        return handles, getattr(queries, qname)(*streams).output()

    handle, (handles, out) = Runtime.init_circuit(1, build)
    hp, ha, hb = handles

    def gen_fn(tick):
        p, a, b = device_gen.generate_tick(cfg, tick * per_tick, per_tick)
        return {hp: p, ha: a, hb: b}

    ch = compile_circuit(handle, gen_fn=gen_fn)
    integral = {}

    def capture(next_tick):
        _accumulate(ch.output(out), integral)

    ch.run_ticks(0, ticks, validate_every=1, on_validated=capture)
    return integral


@pytest.mark.parametrize("qname", QUERIES_ALL)
def test_host_engine_fused_vs_stitched(monkeypatch, qname):
    """q1–q8, host engine: fused megakernels vs the force-off stitched
    control accumulate identical outputs."""
    want = _run_host(qname)
    for k, v in CONTROL_ENV.items():
        monkeypatch.setenv(k, v)
    assert _run_host(qname) == want


@pytest.mark.parametrize("qname", QUERIES_FAST)
def test_compiled_engine_fused_vs_stitched(monkeypatch, qname):
    """Compiled engine (fast tier: the join+aggregate and join+distinct
    shapes): fused megakernels + lazy post view vs the full legacy
    control. The remaining queries run in the slow-tier matrix below."""
    want = _run_compiled(qname)
    assert want, f"{qname} produced no output — vacuous comparison"
    for k, v in CONTROL_ENV.items():
        monkeypatch.setenv(k, v)
    assert _run_compiled(qname) == want


@pytest.mark.slow
@pytest.mark.parametrize("qname", QUERIES_ALL)
def test_compiled_engine_fused_vs_stitched_full(monkeypatch, qname):
    want = _run_compiled(qname)
    for k, v in CONTROL_ENV.items():
        monkeypatch.setenv(k, v)
    assert _run_compiled(qname) == want


def test_sharded_host_fused_vs_stitched(monkeypatch):
    """[W, cap] operands: the 2-worker host q4 (lifted fused cursors under
    shard_map) equals its own stitched control AND the 1-worker run."""
    want = _run_host("q4", workers=1)
    got_sharded = _run_host("q4", workers=2)
    assert got_sharded == want
    for k, v in CONTROL_ENV.items():
        monkeypatch.setenv(k, v)
    assert _run_host("q4", workers=2) == want


def test_compiled_q4_dispatches_fused_ladder_kernels(monkeypatch):
    """Non-vacuous hot path (the lint kernel front's tier-1 twin): the
    compiled q4 loop must actually SELECT the fused megakernels at every
    layer of the force-off ladder — the reduction offensive on top
    (sorted-emit join + aggregate megakernel), the PR-12 fused consumers
    when those are forced off, and the stitched XLA chain at full
    force-off — so every A/B control bench.py leans on is proven live."""
    from dbsp_tpu.zset import kernels as zk

    monkeypatch.setenv("DBSP_TPU_NATIVE", "1")
    before = dict(zk.KERNEL_DISPATCH_COUNTS)
    _run_compiled("q4", ticks=2)

    def delta_of(kern, backend):
        return zk.KERNEL_DISPATCH_COUNTS.get((kern, backend), 0) - \
            before.get((kern, backend), 0)

    # the reduction offensive owns the q4 hot loop: the join emits sorted
    # (join_sorted supersedes join_ladder) and CAggregate is ONE megakernel
    assert delta_of("join_sorted", "native") > 0
    assert delta_of("agg_ladder", "native") > 0

    # one layer down: the PR-12 fused consumers re-engage
    monkeypatch.setenv("DBSP_TPU_NATIVE", REDUCE_OFF)
    before = dict(zk.KERNEL_DISPATCH_COUNTS)
    _run_compiled("q4", ticks=2)
    assert delta_of("join_sorted", "native") == 0
    assert delta_of("agg_ladder", "native") == 0
    assert delta_of("join_ladder", "native") > 0
    assert delta_of("gather_ladder", "native") > 0
    assert delta_of("agg_ladder", "xla") > 0  # the stitched chain is live

    # full force-off: the stitched XLA fallbacks carry everything
    monkeypatch.setenv("DBSP_TPU_NATIVE", FUSED_OFF)
    before = dict(zk.KERNEL_DISPATCH_COUNTS)
    _run_compiled("q4", ticks=2)
    assert delta_of("join_ladder", "native") == 0
    assert delta_of("gather_ladder", "native") == 0
    assert delta_of("join_ladder", "xla") > 0
    assert delta_of("gather_ladder", "xla") > 0
