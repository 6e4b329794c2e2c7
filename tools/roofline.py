"""Roofline cost model for the engine's hot kernels at Nexmark shapes.

An analytic cost model, no substitute for a chip run (``chip_smoke.py``
is the proof the system starts on a TPU): for each hot kernel at the q3/q4/q8 steady-state shapes it reports

  * XLA's own cost analysis of the compiled HLO (flops, bytes accessed) —
    the TPU-path variants (rank-merge, XLA probe loop) are compiled for
    analysis even on the CPU backend, since the HLO and its memory
    traffic are backend-independent;
  * analytic HBM bytes (what the algorithm must touch, independent of
    XLA's accounting);
  * a v5e-class tick-time prediction: every kernel here is far below the
    ~1 flop/byte ridge, so time ~= bytes / HBM bandwidth.

Run:  python tools/roofline.py            (writes ROOFLINE.md)
      python tools/roofline.py --print    (stdout only)
      python tools/roofline.py --per-node (also RUNS a measured q4
          operator profile — dbsp_tpu.obs.opprofile, segmented per-node
          timing asserted bit-identical to the fused program — writes it
          to PROFILE_q4.json and regenerates §3c's per-operator table)

Without --per-node, §3c is regenerated from the committed
PROFILE_q4.json (or from --profile-json PATH, e.g. a
``bench.py --profile`` BENCH_PROFILE_OUT report), so a plain regenerate
never silently drops the attribution table.

The numbers feed ROOFLINE.md §3's per-tick roll-up;
tests/test_tpu_compile.py compiles the main path's kernels for a described
v5e chip.
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# v5e single-chip headline specs (public): 819 GB/s HBM, 394 bf16 GFLOP/s
# per MXU lane irrelevant here — every kernel below is memory-bound.
V5E_HBM_GBS = 819
# fallback CPU effective bandwidth when the fit-time measurement is
# unavailable: the PR-4 reference host's ~8 GB/s
CPU_EFF_GBS_FALLBACK = 8

# The PR-4 reference-host calibration (BENCH_local_fused_cursors.json on
# its container): measured q4 kernel-side ms/tick and the 8 GB/s model
# prediction it was fitted against. Containers differ round to round
# (core speed varies ~3x at similar memory bandwidth), so cross-host
# kernel-side changes are reported by scaling THIS fixed reference with a
# same-host A/B ratio (--bench vs --bench-off), never by comparing raw
# ms across hosts.
REF_KERNEL_MS = 8.2
REF_PRED_MS = 1.74  # 13.9 MB/tick at 8 GB/s
REF_GAP = REF_KERNEL_MS / REF_PRED_MS  # the "4.7x" ROADMAP item 5 names

# Gap-refit HISTORY: every same-host A/B ratio recorded by a prior round,
# each scaling the PR-4 reference calibration in sequence — the current
# round's --bench/--bench-off pair multiplies ON TOP of these, so the
# headline gap chains measured ratios instead of ever comparing raw ms
# across hosts. Entries are (label, bench-pair file prefix, ratio); the
# prefix lets :func:`refit_base_for` stop the chain when the LIVE pair is
# one already recorded here (re-calibrating against an old committed pair
# must not multiply its own ratio in twice).
RECORDED_REFITS = (
    ("PR-7 native kernel set", "BENCH_local_native_kernels", 0.87),
    ("PR-12 fused ladder megakernels + lazy post view",
     "BENCH_local_megakernels", 0.70),
)


def refit_base_for(source_off: str):
    """(base gap, applied refit entries) to chain UNDER a live A/B whose
    control file is ``source_off``: refits recorded from that same pair
    (or later) are excluded so the live ratio replaces — never
    double-counts — its own recorded entry."""
    gap, applied = REF_GAP, []
    for label, prefix, ratio in RECORDED_REFITS:
        if os.path.basename(source_off).startswith(prefix):
            break
        gap *= ratio  # 4.1x entering this round on the current pair
        applied.append((label, prefix, ratio))
    return gap, applied

# the current round's committed A/B pair (the reduction offensive: fused
# CAggregate megakernel + opcode segment reduce + sorted-emit join vs the
# PR-12 code path — DBSP_TPU_NATIVE=segment_reduce,agg_ladder,join_sorted
# — on the same host) — the default --bench / --bench-off targets so a
# plain regenerate reproduces the committed calibration
DEFAULT_BENCH = "BENCH_local_aggfuse.json"
DEFAULT_BENCH_OFF = "BENCH_local_aggfuse_off.json"


def _host_bandwidth_gbs() -> float:
    """Measured streaming (copy) bandwidth of THIS host, GB/s — the
    denominator the CPU-side roofline prediction must use for a same-host
    gap to mean anything. ~0.3 s, single-threaded numpy copy."""
    import time

    try:
        a = np.random.randint(0, 1000, 20_000_000).astype(np.int64)
        b = np.empty_like(a)
        np.copyto(b, a)  # warm pages
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            np.copyto(b, a)
            best = min(best, time.perf_counter() - t0)
        return (a.nbytes * 2 / 1e9) / best
    except Exception:  # noqa: BLE001 — fall back to the reference figure
        return float(CPU_EFF_GBS_FALLBACK)


def _cost(fn, *args):
    c = jax.jit(fn).lower(*args).compile().cost_analysis()
    if isinstance(c, list):
        c = c[0]
    return {"flops": c.get("flops", 0.0),
            "bytes": c.get("bytes accessed", 0.0)}


def _cols(n, k, sort_first=True, seed=0):
    rng = np.random.default_rng(seed)
    first = np.sort(rng.integers(0, 1 << 40, n)) if sort_first else \
        rng.integers(0, 1 << 40, n)
    cols = [jnp.asarray(first)]
    for i in range(k - 1):
        cols.append(jnp.asarray(rng.integers(0, 1000, n)))
    return tuple(cols)


def kernel_table():
    """(name, shape-desc, cost dict, analytic bytes) rows for the TPU-path
    variants of the engine's hot kernels at q4 bench shapes."""
    from dbsp_tpu.zset import kernels

    rows = []

    # 1) rank-merge (TPU spine drain): tail-class merge, 7 cols
    na, nb, k = 1_048_576, 65_536, 7
    a, b = _cols(na, k), _cols(nb, k, seed=1)
    wa = jnp.ones((na,), jnp.int64)
    wb = jnp.ones((nb,), jnp.int64)

    def rank_merge(a, wa, b, wb):
        ra = kernels.lex_probe(b, a, side="left")
        rb = kernels.lex_probe(a, b, side="right")
        # position scatter + netting as in merge_sorted_cols' rank path
        pos_a = jnp.arange(na, dtype=jnp.int32) + ra
        pos_b = jnp.arange(nb, dtype=jnp.int32) + rb
        out = []
        for ca, cb in zip(a, b):
            buf = kernels.sentinel_fill((na + nb,), ca.dtype)
            out.append(buf.at[pos_a].set(ca).at[pos_b].set(cb))
        w = jnp.zeros((na + nb,), wa.dtype).at[pos_a].set(wa) \
            .at[pos_b].set(wb)
        return tuple(out), w

    # force the pure-XLA path for analysis (native custom calls are
    # opaque to cost analysis; the XLA HLO is the backend-independent
    # traffic model)
    saved = {k: os.environ.get(k) for k in
             ("DBSP_TPU_NATIVE_MERGE", "DBSP_TPU_NATIVE")}
    os.environ["DBSP_TPU_NATIVE_MERGE"] = "0"
    os.environ["DBSP_TPU_NATIVE"] = "0"
    try:
        rows.append(("spine drain merge (rank)",
                     f"{na}+{nb} rows x {k} cols",
                     _cost(rank_merge, a, wa, b, wb),
                     (na + nb) * (k + 1) * 8 * 2))
        # 2) trace probe: delta keys into the tail (binary search)
        q = 16_384
        qc = _cols(q, 2, seed=2)
        t = _cols(na, 2, seed=3)
        rows.append(("trace probe (lex binary search)",
                     f"{q} queries x {na} rows x 2 cols",
                     _cost(lambda t, q: kernels.lex_probe(t, q), t, qc),
                     q * 21 * 2 * 8 * 2))
        # 3) delta consolidation (operator outputs): 16k x 6 cols
        n, k6 = 16_384, 6
        cols = _cols(n, k6, sort_first=False, seed=4)
        w = jnp.ones((n,), jnp.int64)
        rows.append(("delta consolidate (sort)",
                     f"{n} rows x {k6} cols",
                     _cost(lambda c, w: kernels.consolidate_cols(c, w),
                           cols, w),
                     int(n * np.log2(n)) * (k6 + 1) * 8))

        # 4) per-level gather expansion (aggregate history fetch)
        from dbsp_tpu.operators.aggregate import _gather_level_impl

        qk = tuple(c[:4096] for c in _cols(4096, 2, seed=5))
        qlive = jnp.ones((4096,), bool)
        from dbsp_tpu.zset.batch import Batch

        lvl = Batch(_cols(262_144, 2, seed=6),
                    _cols(262_144, 4, seed=7)[:4],
                    jnp.ones((262_144,), jnp.int64))
        rows.append(("group gather (probe+expand)",
                     "4096 groups x 262k-row level",
                     _cost(lambda q, l, lv: _gather_level_impl(
                         q, lv, l, 8192), qk, lvl, qlive),
                     8192 * 7 * 8 * 2))
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return rows


def per_tick_model(cpu_gbs: float = CPU_EFF_GBS_FALLBACK):
    """Amortized per-tick HBM bytes for q4 at the bench protocol
    (7,500 ev/tick CPU; 100,000 ev/tick TPU), from the LSM cost model:
    every row passes each of K=4 levels once; probes and operator-output
    consolidations are delta-proportional."""
    out = {}
    for proto, ev_tick in (("cpu", 7_500), ("tpu", 100_000)):
        delta = int(ev_tick * 0.92)  # bids fraction reaches the hot path
        row_bytes = 7 * 8
        K = 4
        # spine: delta merges into l0 every tick (touch 2x l0 ~ 4 deltas),
        # deeper drains amortize to one pass per level per row
        spine = delta * row_bytes * (4 * 2 + K)
        # two leveled traces (join input, aggregate input) + output trace
        spine *= 2.5
        # probes + gathers + consolidates ~ 6 delta-sized passes
        streaming = delta * row_bytes * 6
        total = spine + streaming
        out[proto] = {
            "events_per_tick": ev_tick,
            "bytes_per_tick": total,
            "pred_v5e_tick_ms": total / (V5E_HBM_GBS * 1e9) * 1e3,
            "pred_v5e_events_per_s":
                ev_tick / (total / (V5E_HBM_GBS * 1e9)),
            "pred_cpu_tick_ms": total / (cpu_gbs * 1e9) * 1e3,
        }
    return out


def _bench_measurement(path: str | None = None):
    """The measured q4 tick to calibrate against, from a bench JSON.

    Looks at ``--bench PATH`` or, by default, the newest ``BENCH_r*.json``
    in the repo root. Since the pipelined-tick rework, bench JSON carries
    ``host_overhead_ms`` (validate fetches / maintain drains / snapshot
    copies) — between-tick host time that is NOT kernel time and must be
    subtracted from elapsed before fitting the roofline discount (the old
    calibration silently folded it in; ROOFLINE §3b). Returns a dict with
    ``kernel_ms`` (host-overhead-subtracted per-tick time when available,
    else the p50 tick), ``p50_ms``, ``host_share`` and ``source``."""
    import glob
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # "_off" files are A/B control runs (native kernels forced off) —
    # never a default calibration target; the current round's committed
    # pair is tried first so a plain regenerate reproduces its refit
    cands = ([path] if path else
             [os.path.join(root, DEFAULT_BENCH)] +
             sorted((p for p in
                     glob.glob(os.path.join(root, "BENCH_local*.json"))
                     if "_off" not in os.path.basename(p)),
                    reverse=True) +
             sorted(glob.glob(os.path.join(root, "BENCH_r*.json")),
                    reverse=True))
    for p in cands:
        try:
            with open(p) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        parsed = doc.get("parsed", doc) or {}
        detail = parsed.get("detail", {})
        q4 = detail.get("queries", {}).get("q4", detail)
        p50 = q4.get("p50_tick_ms")
        if not p50 or q4.get("platform", detail.get("platform")) == "tpu":
            continue
        out = {"source": os.path.basename(p), "p50_ms": float(p50),
               "kernel_ms": float(p50), "host_share": None}
        overhead = q4.get("host_overhead_ms")
        elapsed = q4.get("elapsed_s")
        ticks = q4.get("ticks")
        if overhead and elapsed and ticks:
            host_total = sum(float(v) for v in overhead.values())
            kernel_ms = (float(elapsed) * 1e3 - host_total) / int(ticks)
            out["kernel_ms"] = max(kernel_ms, 1e-3)
            out["host_share"] = host_total / (float(elapsed) * 1e3)
        return out
    # no usable bench JSON: the historical r05 figure, un-adjusted
    return {"source": "fallback (BENCH r05 p50)", "p50_ms": 12.0,
            "kernel_ms": 12.0, "host_share": None}


def _run_per_node_profile(out_path: str) -> dict:
    """Run the measured q4 operator profile at the mini protocol and
    commit it: ``opprofile.dryrun`` builds the compiled q4 circuit,
    profiles N segmented ticks (per-node wall time + rows, asserted
    bit-identical to the fused program, >= 90% of segmented tick time
    attributed to named nodes — it raises otherwise), and the report
    lands in ``out_path`` (PROFILE_q4.json) for future regenerates."""
    import json
    import platform as _platform

    from dbsp_tpu.obs.opprofile import dryrun

    events_per_tick = int(os.environ.get("ROOFLINE_PROFILE_EVENTS", "7500"))
    report = dryrun("q4", ticks=4, events_per_tick=events_per_tick, warm=6)
    report["protocol"] = {
        "query": "q4", "events_per_tick": events_per_tick,
        "warm_ticks": 6, "profiled_ticks": 4,
        "host_cores": os.cpu_count(), "machine": _platform.machine(),
        "note": ("mini protocol on the CI host (no TPU): per-node SHARES "
                 "are the deliverable; absolute ms are this host's and "
                 "inflated by segmentation overhead — see "
                 "segmentation_overhead"),
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    return report


def _load_profile(path: str | None):
    """The committed (or explicitly named) per-node profile report, or
    None when absent/unreadable."""
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = path or os.path.join(root, "PROFILE_q4.json")
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    return doc if doc.get("schema", "").startswith("dbsp_tpu.profile") \
        else None


def per_node_section(report: dict) -> list:
    """ROOFLINE §3c: the measured per-operator attribution table — the
    in-tree measurement that NAMES where §3b's kernel-side gap lives."""
    m = report.get("measured") or {}
    proto = report.get("protocol") or {}
    ops = [r for r in report.get("operators", ())
           if r.get("total_ms") or r.get("rows_out")]
    ticks = max(int(m.get("ticks", 1)), 1)
    total_ms = sum(r.get("total_ms", 0.0) for r in ops) or 1.0
    lines = []
    w = lines.append
    w("## 3c. Per-operator attribution (measured, q4 mini protocol)\n")
    w("Regenerate with `python tools/roofline.py --per-node` (runs the "
      "segmented profile and refreshes PROFILE_q4.json) or plain "
      "`python tools/roofline.py` (re-renders this table from the "
      "committed report). Numbers: `opprofile.measured_profile` over "
      "{} ticks of {} events each on a {}-core CI host — segmented per-"
      "node wall time asserted BIT-IDENTICAL to the fused step program, "
      "{:.1%} of segmented tick time attributed to named nodes, "
      "segmentation overhead x{:.2f} vs the fused tick (lost fusion; "
      "identity pass-throughs — state a node returns untouched — are "
      "ELIDED from segment outputs and reconstructed from the operands, "
      "obs/opprofile.py, so a trace node is charged for what it computes, "
      "not for echoing its deep levels; SHARES are the deliverable, "
      "absolute ms are not).\n".format(
          proto.get("profiled_ticks", m.get("ticks", "?")),
          proto.get("events_per_tick", "?"),
          proto.get("host_cores", "?"),
          m.get("attributed_fraction", 0.0),
          m.get("segmentation_overhead", 0.0)))
    w("| node | operator | kind | ms/tick (seg) | share | rows out/tick "
      "| XLA bytes/tick |")
    w("|---|---|---|---|---|---|---|")
    for r in ops:
        w("| {} | {} | {} | {:.2f} | {:.0%} | {:,} | {} |".format(
            r.get("node"), r.get("name"), r.get("kind"),
            r.get("total_ms", 0.0) / ticks,
            r.get("total_ms", 0.0) / total_ms,
            int(r.get("rows_out", 0)) // ticks,
            ("{:.2g}".format(r["bytes"]) if r.get("bytes") else "-")))
    w("")
    ctrace_ms = sum(r.get("total_ms", 0.0) for r in ops
                    if r.get("kind") == "CTrace")
    agg_ms = sum(r.get("total_ms", 0.0) for r in ops
                 if r.get("kind") == "CAggregate")
    join_ms = sum(r.get("total_ms", 0.0) for r in ops
                  if r.get("kind") == "CJoin")
    w("**Combined CTrace share: {:.0%}; CAggregate {:.0%} ({:.1f} "
      "ms/tick); CJoin {:.0%} ({:.1f} ms/tick).** History: the trace "
      "nodes were 59% of the attributed tick before PR-12's fused ladder "
      "megakernels + lazy post view; CAggregate was 29% and CJoin 20% "
      "before the reduction offensive (the agg_ladder megakernel took "
      "the whole CAggregate chain to one call; the sorted-emit join "
      "killed the pair-fn/mask glue and nets in-call, and where a "
      "post-join consolidate materializes it now rank-folds — in the "
      "fused q4 program it is DEFERRED entirely and the downstream map's "
      "consolidate reads netted, sorted input). SHARES renormalize "
      "against the collapsed total, so read them with the same-host "
      "absolute ms: the reduction round's recorded control profile (same "
      "host, `DBSP_TPU_NATIVE=segment_reduce,agg_ladder,join_sorted`) "
      "measured CAggregate 8.3 ms/tick (39%) and CJoin 3.6 ms/tick "
      "(17%) — the per-node A/B factors at that recording were x0.08 "
      "and x0.63.\n".format(
          ctrace_ms / total_ms, agg_ms / total_ms, agg_ms / ticks,
          join_ms / total_ms, join_ms / ticks))
    top = ops[:3]
    w("**Top-3 glue costs (named):** " + "; ".join(
        "**{}** ({}, node {}) — {:.0%} of attributed tick time".format(
            t.get("name"), t.get("kind"), t.get("node"),
            t.get("total_ms", 0.0) / total_ms) for t in top) +
      ". These are the per-node sensors ROADMAP item 5's \"XLA step-"
      "program glue\" narrative previously lacked: the gap now has "
      "names, and any kernel PR can re-run `--per-node` to show which "
      "line it moved.\n")
    return lines


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--print", action="store_true", dest="stdout")
    ap.add_argument("--bench", default=None,
                    help="bench JSON to calibrate against (default: newest "
                         "BENCH_local*/BENCH_r*.json in the repo root)")
    ap.add_argument("--bench-off", default=None, dest="bench_off",
                    help="same-host CONTROL run — the previous commit (a "
                         "HEAD worktree) or a DBSP_TPU_NATIVE force-off "
                         "run — enables the host-independent A/B refit "
                         "of the reference gap (default: the committed "
                         "BENCH_local_native_kernels_off.json, so a plain "
                         "regenerate keeps the refit instead of silently "
                         "reverting to the raw cross-host gap)")
    ap.add_argument("--per-node", action="store_true", dest="per_node",
                    help="RUN the measured q4 operator profile "
                         "(obs/opprofile.py segmented mode), write "
                         "PROFILE_q4.json, and regenerate §3c from it")
    ap.add_argument("--profile-json", default=None, dest="profile_json",
                    help="per-node profile report to render §3c from "
                         "(default: repo-root PROFILE_q4.json)")
    args = ap.parse_args()

    rows = kernel_table()
    host_gbs = _host_bandwidth_gbs()
    model = per_tick_model(host_gbs)
    root_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    meas = _bench_measurement(args.bench)
    # the A/B refit control defaults to the committed force-off run: its
    # pair (DEFAULT_BENCH) is also the default --bench pick, so a plain
    # regenerate reproduces the committed calibration instead of silently
    # reverting the headline gap to the raw cross-host figure
    bench_off = args.bench_off or os.path.join(root_dir, DEFAULT_BENCH_OFF)
    meas_off = _bench_measurement(bench_off) \
        if os.path.exists(bench_off) or args.bench_off else None
    if args.per_node:
        profile = _run_per_node_profile(
            os.path.join(root_dir, "PROFILE_q4.json"))
    else:
        profile = _load_profile(args.profile_json)

    lines = []
    w = lines.append
    w("# ROOFLINE — analytic TPU cost model\n")
    w("An analytic model, not a measurement (chip_smoke.py runs the "
      "served path on a TPU): XLA cost analysis of the TPU-path kernels at bench "
      "shapes, plus a bandwidth-roofline projection for a v5e-class "
      "chip. Regenerate with `python tools/roofline.py`.\n")
    w("## 1. Hot kernels at q4 steady-state shapes\n")
    w("All kernels sit far below the ~1 flop/byte ridge — the engine is "
      "HBM-bandwidth-bound, which is what the columnar static-shape "
      "design optimizes for (sequential column scans, "
      "delta-proportional probes).\n")
    w("XLA's accounting charges every gather op its FULL table operand, "
      "so the 'XLA bytes' column over-counts probe loops by orders of "
      "magnitude (each of the ~21 unrolled search steps bills the whole "
      "table); 'analytic bytes' is what the memory system actually "
      "moves and is what the roofline uses.\n")
    w("| kernel | shape | XLA flops | XLA bytes | flops/byte | analytic "
      "bytes |")
    w("|---|---|---|---|---|---|")
    for name, shape, c, analytic in rows:
        fb = c["flops"] / max(c["bytes"], 1)
        w(f"| {name} | {shape} | {c['flops']:.3g} | {c['bytes']:.3g} | "
          f"{fb:.3f} | {analytic:.3g} |")
    w("")
    w("## 2. Per-tick q4 projection (v5e-class: "
      f"{V5E_HBM_GBS} GB/s HBM)\n")
    w("LSM amortization: every row crosses each of K=4 spine levels once "
      "over its lifetime; probes/consolidations are delta-proportional. "
      "Per-tick HBM traffic and the bandwidth-bound tick time:\n")
    w("| protocol | events/tick | bytes/tick | v5e tick (pred) | "
      f"v5e events/s (pred) | CPU tick (pred, {host_gbs:.1f} GB/s "
      "measured on this host) |")
    w("|---|---|---|---|---|---|")
    for proto, m in model.items():
        w(f"| {proto} | {m['events_per_tick']:,} | "
          f"{m['bytes_per_tick']/1e6:.1f} MB | "
          f"{m['pred_v5e_tick_ms']:.2f} ms | "
          f"{m['pred_v5e_events_per_s']/1e6:.1f} M | "
          f"{m['pred_cpu_tick_ms']:.1f} ms |")
    w("")
    meas_cpu_ms = meas["kernel_ms"]
    host_gap = meas_cpu_ms / model["cpu"]["pred_cpu_tick_ms"]
    # host-independent refit: scale the fixed PR-4 reference calibration
    # by the same-host A/B ratio (kernel-side ms with the native kernel
    # set ON vs forced OFF). Raw cross-host ms comparisons are
    # meaningless — container core speed varies ~3x round to round.
    ab_ratio = None
    gap = host_gap
    applied_refits = []
    if meas_off is not None and meas_off["kernel_ms"] > 0:
        ab_ratio = meas_cpu_ms / meas_off["kernel_ms"]
        base, applied_refits = refit_base_for(meas_off["source"])
        gap = base * ab_ratio
    adj = model["tpu"]["pred_v5e_events_per_s"] / gap
    host_note = ""
    if meas["host_share"] is not None:
        host_note = (" Measured between-tick host overhead ({:.0f}% of "
                     "elapsed: validate fetches, maintain drains, snapshot "
                     "copies) is SUBTRACTED from elapsed before the fit — "
                     "the discount below is genuinely kernel-side (raw p50 "
                     "{:.1f} ms/tick).".format(100 * meas["host_share"],
                                               meas["p50_ms"]))
    w("Calibration: measured q4 kernel-side time is ~{:.1f} ms/tick at "
      "the CPU protocol ({}) vs the bandwidth model's {:.2f} ms at this "
      "host's measured {:.1f} GB/s — a {:.1f}x gap on this host from "
      "non-streaming access (scatters, probe irregularity) and per-op "
      "overheads that a roofline ignores.{}\n".format(
          meas_cpu_ms, meas["source"], model["cpu"]["pred_cpu_tick_ms"],
          host_gbs, host_gap, host_note))
    if ab_ratio is not None:
        w("**Kernel-side gap refit (same-host A/B):** the control run "
          "({} — the reduction offensive forced off via "
          "`DBSP_TPU_NATIVE=segment_reduce,agg_ladder,join_sorted`, i.e. "
          "the previous round's code path on the SAME host) measures "
          "{:.1f} ms/tick kernel-side; the fused CAggregate megakernel + "
          "sorted-emit join cut that to {:.1f} ms/tick — a x{:.2f} "
          "kernel-side factor under identical protocol, state and "
          "container. Chaining it onto the recorded refit history re-fits "
          "the kernel-side gap to **{:.1f}x**. (Raw cross-host ms are NOT "
          "comparable: container core speed varies ~3x round to round at "
          "similar memory bandwidth, which is exactly why every refit is "
          "A/B-based.)\n"
          .format(meas_off["source"], meas_off["kernel_ms"], meas_cpu_ms,
                  ab_ratio, gap))
        w("Gap-refit history (each row scales the previous one):\n")
        w("| round | A/B evidence | kernel-side ratio | gap after |")
        w("|---|---|---|---|")
        w("| PR-4 reference | BENCH_local_fused_cursors.json calibration "
          "({:.1f} ms vs {:.2f} ms predicted) | — | {:.1f}x |".format(
              REF_KERNEL_MS, REF_PRED_MS, REF_GAP))
        running = REF_GAP
        for label, prefix, ratio in applied_refits:
            running *= ratio
            w("| {} | {}[_off].json, same-host A/B | x{:.2f} | {:.1f}x |"
              .format(label, prefix, ratio, running))
        w("| this round (the reduction offensive: CAggregate megakernel "
          "+ sorted-emit join) | {} vs {} | x{:.2f} | **{:.1f}x** |".format(
              meas["source"], meas_off["source"], ab_ratio, gap))
        w("")
    w("Applying the {:.1f}x gap to the v5e projection as a conservative "
      "discount gives **~{:.0f}M events/s on one v5e chip** — "
      "{:.0f}x the reference protocol's 10M/s offered rate, before "
      "multi-chip scaling over the existing SPMD shard path.\n".format(
          gap, adj / 1e6, adj / 10e6))
    w("## 3. What this predicts for the north star\n")
    w("At the TPU protocol (100k-event ticks) the projected v5e tick is "
      "single-digit milliseconds — {:.0f}M events/s on ONE chip against "
      "the reference protocol's 10M/s offered rate, before any "
      "multi-chip scaling via the existing SPMD shard path. The "
      "prediction's biggest unknowns, in order: (a) XLA:TPU's actual "
      "fusion of the probe/gather loops (dependent gathers lower to "
      "while loops; the rank-merge path was designed for exactly this), "
      "(b) per-dispatch overhead (amortized by the scanned-chunk mode, "
      "one dispatch per validation interval), (c) bf16/int64 register pressure on the VPU.\n".format(
          model["tpu"]["pred_v5e_events_per_s"] / 1e6))
    w("## 3b. Host overhead is measured and subtracted, not folded in\n")
    w("Earlier calibrations fitted the discount against raw elapsed, "
      "silently folding between-tick host work (validation fetches, LSM "
      "maintenance drains, snapshot copies, program re-traces) into the "
      "\"kernel-side\" gap. Those phases are instrumented in-tree "
      "(`dbsp_tpu_compiled_tick_host_overhead_seconds{phase}` and "
      "bench.py's `host_overhead_ms` / `spike_causes` detail), and this "
      "script now subtracts them from elapsed before fitting "
      "(`_bench_measurement`) — pass `--bench PATH` to calibrate against "
      "a specific run. The remaining gap is what a bandwidth model can "
      "speak to: scatter irregularity and probe lowering, now attacked "
      "by the FUSED ladder consumers (zset/cursor.py: the whole "
      "join/gather/old-weights consumer — probe pair + cross-level "
      "expansion + gathers + weight combine — is ONE megakernel call per "
      "eval on the native CPU path, `join_ladder`/`gather_ladder`/"
      "`old_weights` in `kernel_paths`), the LAZY compiled trace post "
      "view (compiled/cnodes.py: consumers probe the appended delta as "
      "its own ladder level instead of re-reading the written slot — "
      "`DBSP_TPU_TRACE_LAZY_POST=0` is the control), the REDUCTION "
      "layer on top of them (cursor.agg_ladder: the whole CAggregate "
      "chain — unique keys, out-trace probe, ladder gather, cross-level "
      "netting and the aggregator's five-op segment reduction — is ONE "
      "native call, `agg_ladder`/`segment_reduce` in `kernel_paths`; the "
      "join's sorted-emit mode `join_sorted` applies permutation pair "
      "fns in-call and emits each side as one consolidated run, so the "
      "post-join consolidate rank-folds instead of sorting), the "
      "sorted-run consolidation regimes (zset/batch.py: skip / "
      "rank-merge fold / native argsort / sort, counted in "
      "`dbsp_tpu_zset_consolidate_total{path}`), and the full native "
      "CPU kernel set (merge/consolidate/probe/probe-ladder/expand/"
      "gather/compact/rank-fold — anchored breadth-first C++ searches, "
      "galloping block-copy merges; dispatch visible in "
      "`dbsp_tpu_zset_kernel_dispatch_total{kernel,backend}` and bench "
      "JSON `kernel_paths`, per-kernel A/B via DBSP_TPU_NATIVE). On "
      "accelerator backends the dispatch takes the plain-XLA "
      "formulations (tests/test_tpu_compile.py). What "
      "remained aggregate "
      "here — WHICH step-program glue the gap lives in — is now a "
      "per-operator measurement: §3c below names it, from the committed "
      "`PROFILE_q4.json` (obs/opprofile.py segmented profile; "
      "`tools/roofline.py --per-node` re-measures).\n")
    if profile is not None:
        lines.extend(per_node_section(profile))
    w("## 4. On the chip\n")
    w("`python chip_smoke.py` runs the served q4 path on a TPU and checks "
      "it against a from-scratch recomputation; "
      "`tests/test_tpu_compile.py` compiles the main path's kernels for "
      "a described v5e chip.\n")

    # §5+ (multi-worker sweep attribution, growth proof) are products of
    # measurement protocols this script does not run (bench.py
    # --workers-sweep / BENCH_GROWTH against MULTICHIP_r*.json) — carry
    # them over VERBATIM from the existing file so a regenerate can never
    # destroy committed acceptance evidence.
    out_path = os.path.join(root_dir, "ROOFLINE.md")
    try:
        with open(out_path) as f:
            old = f.read()
    except OSError:
        old = ""
    idx = old.find("\n## 5")
    if idx >= 0:
        lines.append(old[idx + 1:].rstrip("\n") + "\n")

    text = "\n".join(lines)
    if args.stdout:
        print(text)
    else:
        with open(out_path, "w") as f:
            f.write(text)
        print("wrote ROOFLINE.md")


if __name__ == "__main__":
    main()
