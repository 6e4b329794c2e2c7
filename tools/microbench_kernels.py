"""Per-kernel microbenchmark of the engine's hot Z-set kernels.

Times each kernel the roofline model reasons about (tools/roofline.py §1),
at the SAME q4-steady-state shapes, on the active backend — the measured
complement of the analytic cost table: when a bench regression appears,
this pins it to a kernel instead of a query.

Kernels & shapes (ROOFLINE §1):
  * consolidate      — full consolidation of an unsorted run, 16k x 6 cols
                       (dispatches native argsort / lax.sort per backend);
  * rank_fold        — consolidate() of 4 stacked sorted runs (the
                       sorted-run regime), 4 x 16k x 6 cols;
  * lex_probe        — 16k queries x 1M-row 2-col sorted table;
  * lex_probe_ladder — the same queries fused over a 4-level ladder
                       (1M/256k/64k/16k rows — zset/cursor.py);
  * merge_sorted_cols— spine tail-class merge, 1M + 64k rows x 7 cols;
  * expand_ranges    — 16k ranges expanded into a 64k slot buffer;
  * compact          — live-row packing of a half-dead 16k x 6-col run
                       (the filter/distinct/upsert output shape);
  * gather_ladder    — the fused group gather (probe + expand + leveled
                       gather) of 4096 query keys against a 4-level
                       ladder (262k..4k rows) into 8192 slots — ROOFLINE
                       §1's "group gather" row, end to end. Dispatches the
                       ONE-call megakernel (native, on the CPU) unless
                       forced off;
  * join_ladder      — the fused incremental-join consumer (both probes +
                       expansion + both-side gathers + weight product +
                       pair apply) of a 16k-row delta against the same
                       4-level ladder shape into 65536 slots — the
                       CJoin/JoinOp hot path end to end, megakernel
                       dispatch included;
  * join_sorted      — the SAME join through the sorted-emit megakernel
                       (permutation pair fn applied in-call, side emitted
                       as one consolidated run) PLUS the 2-run rank-fold
                       consolidate of the concat — the whole post-join
                       path the reduction offensive replaced, vs
                       join_ladder + full-sort consolidate on the control;
  * segment_reduce   — the Aggregator zoo's five-op segment reduction
                       (count/sum/min/max/avg + present) of 16k gathered
                       rows into 4096 groups, ONE dispatch per spec;
  * agg_ladder       — the whole CAggregate reduce chain (unique keys +
                       out-trace TupleMax probe + ladder gather + netting
                       + reduction) for a 4096-group delta over the
                       4-level gather ladder — the q4-max hot path end to
                       end, megakernel dispatch included.

Every entry dispatches through the engine's own backend switch, so the
measured path follows DBSP_TPU_NATIVE — A/B a single
kernel with e.g. ``DBSP_TPU_NATIVE=expand python tools/microbench_kernels.py``
(forces expand alone onto XLA; see zset/native_merge.py::kernel_enabled).

Run:  python tools/microbench_kernels.py            (JSON to stdout)
      python tools/microbench_kernels.py --reps 9   (more samples)

Output: one JSON object {kernel: {shape, ms, ...}, meta: {...}} — consumed
by tools/record_perf.py (which records the floors tests/test_perf.py
gates on) and by humans bisecting a bench regression (README §Performance).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _force_cpu() -> None:
    """CLI runs pin the CPU backend (recordings must match the backend the
    perf gate measures on). Import-time mutation would flip the platform
    under an already-initialized pytest session — main() only."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def _cols(n, k, sort_first=True, seed=0):
    rng = np.random.default_rng(seed)
    first = np.sort(rng.integers(0, 1 << 40, n)) if sort_first else \
        rng.integers(0, 1 << 40, n)
    cols = [jnp.asarray(first)]
    for _ in range(k - 1):
        cols.append(jnp.asarray(rng.integers(0, 1000, n)))
    return tuple(cols)


def _time(fn, *args, reps: int = 5) -> float:
    """Median wall ms of a jitted call (compile excluded by a warmup call)."""
    jitted = jax.jit(fn)
    jax.block_until_ready(jitted(*args))  # compile + warm
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(jitted(*args))
        samples.append((time.perf_counter() - t0) * 1e3)
    samples.sort()
    return samples[len(samples) // 2]


def run(reps: int = 5) -> dict:
    from dbsp_tpu.zset import cursor, kernels
    from dbsp_tpu.zset.batch import Batch, concat_batches

    out: dict = {}

    # 1) full consolidation of an unsorted run (every operator output)
    n, k6 = 16_384, 6
    cols = _cols(n, k6, sort_first=False, seed=4)
    w = jnp.ones((n,), jnp.int64)
    out["consolidate"] = {
        "shape": f"{n} rows x {k6} cols (unsorted)",
        "strategy": kernels.merge_strategy(),
        "ms": _time(kernels.consolidate_cols, cols, w, reps=reps)}

    # 2) sorted-run regime: consolidate() of 4 stacked consolidated runs
    def _consolidated(seed):
        c, ww = kernels.consolidate_cols(
            _cols(n, k6, sort_first=False, seed=seed),
            jnp.ones((n,), jnp.int64))
        return Batch(c[:1], c[1:], ww, runs=(n,))

    stacked = concat_batches([_consolidated(s) for s in range(4)])
    out["rank_fold"] = {
        "shape": f"4 runs x {n} rows x {k6} cols",
        "ms": _time(lambda b: b.consolidate(), stacked, reps=reps)}

    # 3) trace probe: delta keys into the tail (binary search)
    big = 1_048_576
    q = 16_384
    table2 = _cols(big, 2, seed=3)
    query2 = _cols(q, 2, seed=2)
    out["lex_probe"] = {
        "shape": f"{q} queries x {big} rows x 2 cols",
        "ms": _time(lambda t, qq: kernels.lex_probe(t, qq), table2, query2,
                    reps=reps)}

    # 4) the same probe fused over a 4-level ladder (K geometric levels)
    ladder = [table2] + [_cols(big >> (2 * i), 2, seed=6 + i)
                         for i in (1, 2, 3)]
    out["lex_probe_ladder"] = {
        "shape": f"{q} queries x 4 levels ({big}..{big >> 6} rows)",
        "ms": _time(lambda tabs, qq: cursor.lex_probe_ladder(tabs, qq),
                    tuple(ladder), query2, reps=reps)}

    # 5) spine tail-class sorted merge
    na, nb, k7 = 1_048_576, 65_536, 7
    a, b = _cols(na, k7), _cols(nb, k7, seed=1)
    wa = jnp.ones((na,), jnp.int64)
    wb = jnp.ones((nb,), jnp.int64)
    out["merge_sorted_cols"] = {
        "shape": f"{na}+{nb} rows x {k7} cols",
        "strategy": kernels.merge_strategy(),
        "ms": _time(kernels.merge_sorted_cols, a, wa, b, wb, reps=reps)}

    # 6) range expansion (join fan-out allocation)
    rng = np.random.default_rng(9)
    lo = jnp.asarray(np.sort(rng.integers(0, big - 8, q)).astype(np.int32))
    hi = lo + jnp.asarray(rng.integers(0, 4, q).astype(np.int32))
    out["expand_ranges"] = {
        "shape": f"{q} ranges -> 65536 slots",
        "ms": _time(lambda l, h: kernels.expand_ranges(l, h, 65_536),
                    lo, hi, reps=reps)}

    # 7) compaction: pack the live half of a 16k-row run (the shape every
    #    filter / distinct / upsert output pays per tick)
    ccols = _cols(n, k6, sort_first=True, seed=11)
    cw = jnp.asarray(np.random.default_rng(12).integers(-1, 2, n)
                     .astype(np.int64))
    out["compact"] = {
        "shape": f"{n} rows x {k6} cols (~half live)",
        "ms": _time(lambda c, w: kernels.compact(c, w, w != 0),
                    ccols, cw, reps=reps)}

    # 8) fused group gather: probe + cross-level expansion + leveled value
    #    gather for 4096 query keys over a 4-level ladder (ROOFLINE §1
    #    "group gather" at q4 aggregate shapes)
    glevels = []
    for i, cap in enumerate((262_144, 65_536, 16_384, 4_096)):
        kc = _cols(cap, 2, seed=20 + i)
        vc = _cols(cap, 4, sort_first=False, seed=30 + i)
        glevels.append(Batch(kc, vc, jnp.ones((cap,), jnp.int64),
                             runs=(cap,)))
    gq = 4_096
    qkeys = tuple(c[:gq] for c in _cols(gq, 2, seed=40))
    qlive = jnp.ones((gq,), bool)
    out["gather_ladder"] = {
        "shape": f"{gq} groups x 4 levels (262144..4096 rows) -> 8192 "
                 "slots",
        "ms": _time(lambda qk, ql: cursor.gather_ladder(
            qk, ql, glevels, 8_192)[0], qkeys, qlive, reps=reps)}

    # 8b) fused incremental-join consumer: the whole join_ladder megakernel
    #     (probe pair + expansion + both-side gathers + weight product +
    #     pair apply) for a 16k-row delta over a 4-level ladder — the
    #     CJoin/JoinOp hot path the trace-tax fusion collapsed to one call
    jlevels = []
    for i, cap in enumerate((1_048_576, 262_144, 65_536, 16_384)):
        kc = _cols(cap, 2, seed=50 + i)
        vc = _cols(cap, 2, sort_first=False, seed=60 + i)
        jlevels.append(Batch(kc, vc, jnp.ones((cap,), jnp.int64),
                             runs=(cap,)))
    jq = 16_384
    jdelta = Batch(tuple(c[:jq] for c in _cols(jq, 2, seed=70)),
                   tuple(c[:jq] for c in _cols(jq, 1, sort_first=False,
                                               seed=71)),
                   jnp.ones((jq,), jnp.int64), runs=(jq,))
    jfn = lambda k, lv, rv: (k, (*lv, *rv))  # noqa: E731
    out["join_ladder"] = {
        "shape": f"{jq}-row delta x 4 levels (1048576..16384 rows) -> "
                 "65536 slots",
        "ms": _time(lambda d: cursor.join_ladder(
            d, tuple(jlevels), 2, jfn, 65_536)[0], jdelta, reps=reps)}

    # 8c) sorted-emit join + the 2-run rank-fold consolidate it enables —
    #     the whole post-join path (the control pays join_ladder + a full
    #     argsort consolidate of the doubled buffer instead)
    from dbsp_tpu.operators.join import fn_permutation

    jperm = fn_permutation(jfn, 2, 1, 2)
    jse = (jperm[0], jperm[1],
           tuple(jnp.dtype(jnp.int64) for _ in range(5)))

    def _join_post(d):
        lout, _ = cursor.join_ladder(d, tuple(jlevels), 2, jfn, 65_536,
                                     sorted_emit=jse)
        rout, _ = cursor.join_ladder(d, tuple(jlevels[:2]), 2, jfn, 32_768,
                                     sorted_emit=jse)
        out = concat_batches([lout, rout]).consolidate()
        return (*out.cols, out.weights)

    out["join_sorted"] = {
        "shape": f"{jq}-row delta x 4 levels -> 2 sorted sides + rank-fold "
                 "consolidate",
        "ms": _time(_join_post, jdelta, reps=reps)}

    # 8d) the shared five-op segment reduction at the aggregate's gather
    #     shape: 16k netted rows -> 4096 groups, one dispatch for the spec
    from dbsp_tpu.operators.aggregate import segment_reduce

    sr_n, sr_g = 16_384, 4_096
    rngs = np.random.default_rng(80)
    sv = (jnp.asarray(rngs.integers(0, 1 << 30, sr_n)),
          jnp.asarray(rngs.integers(0, 1000, sr_n)))
    sw = jnp.asarray(rngs.integers(-2, 3, sr_n).astype(np.int64))
    sseg = jnp.asarray(np.sort(rngs.integers(0, sr_g, sr_n))
                       .astype(np.int32))
    sspec = (("max", 0), ("count", 0), ("sum", 1), ("present", 0))
    out["segment_reduce"] = {
        "shape": f"{sr_n} rows -> {sr_g} groups x 4 ops",
        "ms": _time(lambda v, w, s: segment_reduce(sspec, v, w, s,
                                                   sr_g + 1),
                    sv, sw, sseg, reps=reps)}

    # 8e) the whole CAggregate chain as ONE call: 4096-group delta over the
    #     gather ladder + a 4096-row out trace (q4-max shape, fast path
    #     with the ladder gate ON — the worst case, i.e. full re-gather)
    from dbsp_tpu.operators.aggregate import Max

    adelta_cols = _cols(gq, 2, seed=90)
    akeys = tuple(c[:gq] for c in adelta_cols)
    avals = tuple(c[:gq] for c in _cols(gq, 1, sort_first=False, seed=91))
    adelta = Batch(akeys, avals, jnp.ones((gq,), jnp.int64), runs=(gq,))
    ot_cols = _cols(gq, 2, seed=92)
    ot_vals = _cols(gq, 1, sort_first=False, seed=93)
    aot = Batch(ot_cols, (ot_vals[0],), jnp.ones((gq,), jnp.int64),
                runs=(gq,))
    out["agg_ladder"] = {
        "shape": f"{gq} groups x 4 levels (262144..4096 rows) + {gq}-row "
                 "out trace, Max fast path, gate on",
        "ms": _time(lambda d: cursor.agg_ladder(
            d, 2, aot, tuple(glevels), Max(0), gq, 16_384, True,
            jnp.asarray(True))[5], adelta, reps=reps)}

    # 9) flight-recorder steady-state overhead: one tick event recorded
    #    into the bounded ring (dbsp_tpu/obs/flight.py) — pure host work,
    #    no device dispatch. Reported as ms per 1000 events; the tier-1
    #    gate (tests/test_flight.py) bounds the per-event cost at < 2% of
    #    the recorded q3 p50 tick time.
    from dbsp_tpu.obs.flight import FlightRecorder

    rec = FlightRecorder(capacity=2048)
    n_ev = 10_000
    samples = []
    for _ in range(max(3, reps)):
        t0 = time.perf_counter()
        for i in range(n_ev):
            rec.record("tick", tick=i, latency_ns=1000, causes=())
        samples.append((time.perf_counter() - t0) * 1e3)
    samples.sort()
    out["flight_record"] = {
        "shape": f"{n_ev} tick events into a 2048-slot ring",
        "ms": samples[len(samples) // 2] / (n_ev / 1000)}

    out["meta"] = {"backend": jax.default_backend(),
                   "strategy": kernels.merge_strategy(), "reps": reps}
    return out


def main() -> None:
    _force_cpu()
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()
    print(json.dumps(run(reps=args.reps), indent=1, sort_keys=True))


if __name__ == "__main__":
    main()
