#!/usr/bin/env python
"""Unified lint runner: every static check the repo carries, one exit code.

Three fronts (each independently runnable; this bundles them for CI and
the tier-1 test in tests/test_analysis.py):

1. ``tools/check_metrics.py``  — Prometheus formatting stays in obs/,
   metric names follow the convention, label names stay on the closed
   allowlist, per-node families only via the opprofile gate.
2. ``tools/check_hotpath.py``  — no host round-trips in operator eval
   bodies / jitted functions; no load-bearing asserts in circuit/ and io/.
2b. ``tools/check_state.py``   — every serving-state field is claimed by
   the checkpoint schema registry (restore can never silently drop state).
2f. **Concurrency front** — ``tools/check_concurrency.py`` (every shared
   mutable serving-plane field obeys its declared guard; lock-order graph
   acyclic; no private-lock reach-through) plus, on the CLI, a TSAN smoke
   dryrun (``dbsp_tpu.testing.tsan.dryrun`` in a subprocess: a hammered
   instrumented pipeline must be race-clean AND a seeded unlocked write
   must be caught). ``DBSP_TPU_LINT_CONCURRENCY=0`` skips the smoke; the
   import-based tier-1 consumer is tests/test_concurrency.py.
2g. **Retrace front** — ``tools/check_retrace.py`` (every jitted
   step-path program's recompile causes declared in ``dbsp_tpu.retrace.
   RETRACE_SCHEMA``; no python-value branches on traced operands; every
   donation boundary declared and alias-escape-free) plus its seeded
   defect gallery (each R/D/W rule must fire exactly once, pure) plus,
   on the CLI, the runtime compilation sentinel dryrun
   (``dbsp_tpu.testing.retrace.dryrun`` in a subprocess: a compiled
   steady-state run must show zero undeclared recompiles and zero
   implicit transfers AND a seeded per-value retrace must be caught).
   ``DBSP_TPU_LINT_RETRACE=0`` skips the dryrun; the import-based tier-1
   consumer is tests/test_retrace.py.
2c. ``tools/build_native.py``  — cached native binaries carry the
   SHA-256 of their checked-out sources (a drifted ``.so`` is a red lint).
2d. ``tools/gen_metrics_doc.py --check`` — the committed METRICS.md
   matches the tree's metric registration sites (catalog drift is red).
2e. **Dashboard lint** — deploy/grafana_dashboard.json parses, every
   panel has targets, and every metric a target expr references exists
   (registration sites for ``dbsp_tpu_*``, the obs/export.py legacy
   exposition for ``dbsp_*``).
3. **Analyzer self-check** — build every Nexmark query circuit plus a set
   of representative demo circuits and run the static analyzer
   (dbsp_tpu/analysis) over each at workers 1/4/8 WITH --strict-shard:
   any ERROR finding is a lint failure (the zero-false-positive contract
   — known-good circuits must verify; a reintroduced mid-circuit
   unshard() is a P003 ERROR at workers>1).
4. **Multichip** (CLI only; DBSP_TPU_LINT_MULTICHIP=0 skips) —
   ``dryrun_multichip(8)`` 8 == 1 bit-identity plus the
   ``bench.py --workers-sweep`` mini-protocol, in subprocesses. The
   import-based tier-1 consumers (tests/test_analysis.py) run the static
   fronts only; tests/test_multichip.py carries the runtime coverage.
4b. **Kernel front** (CLI only; DBSP_TPU_LINT_KERNELS=0 skips) — a mini
   compiled q4 run in a subprocess must actually DISPATCH the fused
   megakernels at every layer of the force-off ladder: the reduction
   offensive on top (``join_sorted:native`` + ``agg_ladder:native``
   counted > 0 — the sorted-emit join and the whole-CAggregate megakernel
   cannot silently fall back), the PR-12 fused consumers when those are
   forced off (``join_ladder:native`` + ``gather_ladder:native`` re-engage
   with the aggregate's stitched chain live), and zero fused-native
   dispatches with the stitched XLA fallback engaged at full force-off —
   so every A/B control knob bench.py leans on is proven live, not
   vacuous. The import-based tier-1 consumer is tests/test_fused_ladder
   .py::test_compiled_q4_dispatches_fused_ladder_kernels.
4c. **Residency front** (CLI only; DBSP_TPU_LINT_RESIDENCY=0 skips) — a
   q4 compiled growth dryrun in a subprocess under a deliberately tiny
   DBSP_TPU_DEVICE_ROWS/_HOST_ROWS must observe residency transitions in
   both demotion directions (device->host, host->disk) with a non-empty
   disk tier, and the unbounded control run must observe NONE — the
   tiered-residency budgets and their A/B control are proven live. The
   import-based tier-1 consumer is tests/test_residency.py.
5. **Profiler dryrun** (CLI only; DBSP_TPU_LINT_PROFILE=0 skips) —
   ``opprofile.dryrun("q4")`` in a subprocess: one measured segmented
   profile end to end, red on schema drift, segmented/fused divergence,
   or attribution below 90% — the operator profiler cannot silently rot.
   The import-based tier-1 consumer is tests/test_opprofile.py.
6. **Lineage dryrun** (CLI only; DBSP_TPU_LINT_LINEAGE=0 skips) —
   ``lineage.dryrun("q4")`` in a subprocess: backward-slice one known q4
   output row and verify it against the provenance-semiring recompute
   oracle, red on divergence — EXPLAIN WHY cannot silently rot. The
   import-based tier-1 consumer is tests/test_lineage.py.
7. **Timeline front** (CLI only; DBSP_TPU_LINT_TIMELINE=0 skips) — a
   host q4 dryrun behind the full Controller + PipelineObs wiring, in
   subprocesses: a seeded >= 50ms in-step stall with a co-timed
   checkpoint flight event MUST surface as a spike attributed to
   ``checkpoint`` with evidence, the unperturbed control run MUST report
   zero spikes, freshness samples must flow arrival->visibility, and the
   always-on note_* hot path must stay under its per-op overhead bound.
   The import-based tier-1 consumer is tests/test_timeline.py.
8. **Read-path front** (CLI only; DBSP_TPU_LINT_READPATH=0 skips) — a
   served q4 under a tsan interleaving probe: hammered lock-free reads
   stay race-clean and consistent (see ``run_readpath_dryrun``). The
   import-based tier-1 consumer is tests/test_readpath.py.
9. **Tracing front** (CLI only; DBSP_TPU_LINT_TRACING=0 skips) — a
   served q4 + replica dryrun: span rings B/E-balanced on real
   pid/tid lanes, >= 95% of a fresh read's e2e age attributed to named
   stages, one delta's trace id identical across the writer and replica
   rings, and the ``DBSP_TPU_TRACE_E2E=0`` control recording zero e2e
   spans (see ``run_tracing_dryrun``). The import-based tier-1 consumer
   is tests/test_e2e_tracing.py.

Usage: ``python tools/lint_all.py`` — prints a per-front summary and exits
1 when any front fails. ``--static`` runs only the pure-static fronts
(no subprocess dryruns, no circuit builds): seconds instead of minutes,
the mode CI's lint job and pre-commit hooks use.
"""

from __future__ import annotations

import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
sys.path.insert(0, _ROOT)

PKG = os.path.join(_ROOT, "dbsp_tpu")


def run_check_metrics() -> list:
    from tools.check_metrics import check_tree

    return check_tree(PKG)


def run_check_hotpath() -> list:
    from tools.check_hotpath import check_tree

    return check_tree(PKG)


def run_check_state() -> list:
    from tools.check_state import check_tree

    return check_tree(_ROOT)


def run_check_concurrency_static() -> list:
    """2f's static half alone: the lock-discipline AST pass."""
    from tools.check_concurrency import check_tree

    return check_tree(_ROOT)


def run_concurrency() -> list:
    """2f. Static lock-discipline pass + (CLI-only) TSAN smoke dryrun."""
    import subprocess

    violations = run_check_concurrency_static()
    if os.environ.get("DBSP_TPU_LINT_CONCURRENCY", "1") == "0":
        print("lint_all: concurrency: tsan smoke skipped "
              "(DBSP_TPU_LINT_CONCURRENCY=0)")
        return violations
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        p = subprocess.run(
            [sys.executable, "-m", "dbsp_tpu.testing.tsan"],
            cwd=_ROOT, env=env, capture_output=True, text=True, timeout=600)
    except subprocess.TimeoutExpired:
        return violations + ["tsan dryrun timed out after 600s"]
    if p.returncode != 0:
        violations.append(
            f"tsan dryrun failed (runtime sanitizer rotted?):\n"
            f"{p.stdout[-800:]}\n{p.stderr[-800:]}")
    return violations


def run_check_retrace() -> list:
    """2g's static half: the retrace/donation AST pass over the tree plus
    the seeded-defect gallery — each rule must fire on its own defect
    (non-vacuous) and on NO other defect (pure), so a regression in any
    single rule turns this front red even on a violation-free tree."""
    from tools.check_retrace import _ALL_RULES, check_tree, run_defects

    violations = check_tree(PKG)
    for rule, desc, findings in run_defects():
        if not any(f"{rule}:" in f for f in findings):
            violations.append(
                f"retrace gallery: seeded defect for {rule} ({desc}) "
                "produced no finding — the rule is vacuous")
        violations.extend(
            f"retrace gallery impurity on the {rule} defect: {f}"
            for f in findings
            if any(f"{r}:" in f for r in _ALL_RULES if r != rule))
    return violations


def run_retrace() -> list:
    """2g. Static retrace/donation pass + gallery + (CLI-only) the
    runtime compilation sentinel dryrun (``dbsp_tpu.testing.retrace``):
    a compiled steady-state run must be free of undeclared recompiles
    and implicit transfers, AND a seeded per-value retrace must be
    caught — proving the sentinel's ledger and its teeth in one shot.
    ``DBSP_TPU_LINT_RETRACE=0`` skips the dryrun (tests/test_retrace.py
    is the import-based tier-1 consumer)."""
    import subprocess

    violations = run_check_retrace()
    if os.environ.get("DBSP_TPU_LINT_RETRACE", "1") == "0":
        print("lint_all: retrace: sentinel dryrun skipped "
              "(DBSP_TPU_LINT_RETRACE=0)")
        return violations
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        p = subprocess.run(
            [sys.executable, "-m", "dbsp_tpu.testing.retrace"],
            cwd=_ROOT, env=env, capture_output=True, text=True, timeout=600)
    except subprocess.TimeoutExpired:
        return violations + ["retrace sentinel dryrun timed out after 600s"]
    if p.returncode != 0:
        violations.append(
            f"retrace sentinel dryrun failed (compilation sanitizer "
            f"rotted?):\n{p.stdout[-800:]}\n{p.stderr[-800:]}")
    return violations


def run_check_native() -> list:
    from tools.build_native import check_tree

    return check_tree(_ROOT)


def run_gen_metrics_doc() -> list:
    from tools.gen_metrics_doc import check_drift

    return check_drift()


def _legacy_metric_names() -> set:
    """The ``dbsp_*`` (pre-obs) exposition names, derived from the one
    code path that renders them — never a second hand-kept list."""
    from dbsp_tpu.obs.export import legacy_controller_lines

    stats = {"steps": 0,
             "inputs": {"x": {"total_records": 0, "buffered_records": 0}},
             "outputs": {"x": {"total_records": 0}}}
    names = set()
    for line in legacy_controller_lines(stats):
        if line and not line.startswith("#"):
            names.add(line.split("{")[0].split(" ")[0])
    return names


def run_check_dashboard() -> list:
    """2e. Grafana dashboard lint: the committed dashboard JSON parses,
    every panel carries at least one target expr, and every metric name
    an expr references actually exists — ``dbsp_tpu_*`` against the
    tree's registration sites (tools/gen_metrics_doc.py), legacy
    ``dbsp_*`` against the obs/export.py legacy exposition. A renamed or
    dropped metric family turns its dashboard panel red here instead of
    silently flatlining in Grafana."""
    import json
    import re as _re

    from tools.gen_metrics_doc import collect

    path = os.path.join(_ROOT, "deploy", "grafana_dashboard.json")
    rel = os.path.relpath(path, _ROOT)
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return [f"{rel}: {type(e).__name__}: {e}"]
    known = set(collect(PKG)) | _legacy_metric_names()
    violations = []
    panels = doc.get("panels") or []
    if not panels:
        violations.append(f"{rel}: no panels")
    for panel in panels:
        title = panel.get("title", "<untitled>")
        targets = panel.get("targets") or []
        if not targets:
            violations.append(f"{rel}: panel {title!r} has no targets")
        for t in targets:
            expr = t.get("expr", "")
            names = _re.findall(r"dbsp_[a-z0-9_]+", expr)
            if not names:
                violations.append(f"{rel}: panel {title!r} target "
                                  f"references no dbsp metric: {expr!r}")
            for n in names:
                # histogram/summary families register under the base
                # name but expose _bucket/_sum/_count series — exprs
                # like histogram_quantile(..., name_bucket) are valid
                base = _re.sub(r"_(bucket|sum|count)$", "", n)
                if n not in known and base not in known:
                    violations.append(
                        f"{rel}: panel {title!r} references unknown "
                        f"metric {n!r} (not a registration site under "
                        "dbsp_tpu/ nor a legacy exposition name)")
    return violations


def _demo_circuits():
    """Representative known-good circuits beyond Nexmark: the operator
    shapes the test suite leans on (feedback sugar, linear + general
    aggregates, distinct, semijoin, recursion, windows)."""
    import jax.numpy as jnp

    from dbsp_tpu.circuit import RootCircuit
    from dbsp_tpu.operators import LinearCount, Max, add_input_zset
    from dbsp_tpu.zset.batch import Batch

    def basic(c):
        s, h = add_input_zset(c, [jnp.int64], [jnp.int64])
        s.differentiate().integrate().output()
        s.distinct().output()
        return h

    def joins(c):
        a, _ = add_input_zset(c, [jnp.int64], [jnp.int64])
        b, _ = add_input_zset(c, [jnp.int64], [jnp.int64])
        a.join_index(b, lambda k, lv, rv: (k, (*lv, *rv)),
                     [jnp.int64], [jnp.int64, jnp.int64]).output()
        a.semijoin(b).output()
        return None

    def aggregates(c):
        s, _ = add_input_zset(c, [jnp.int64], [jnp.int64])
        s.aggregate(LinearCount()).output()
        s.aggregate(Max()).output()
        s.topk(3).output()
        return None

    def recursion(c):
        edges, _ = add_input_zset(c, [jnp.int64], [jnp.int64])
        closure = edges.recurse(
            lambda child, r: r.join_index(
                child.import_stream(edges),
                lambda k, lv, rv: ((lv[0],), (rv[0],)),
                [jnp.int64], [jnp.int64], name="step"))
        closure.output()
        return None

    names = {"basic": basic, "joins": joins, "aggregates": aggregates,
             "recursion": recursion}
    for name, build in names.items():
        circuit, _ = RootCircuit.build(build)
        yield name, circuit


def run_analyzer_selfcheck() -> list:
    """ERROR findings over known-good circuits, as violation strings."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    from dbsp_tpu.analysis import ERROR, analyze
    from dbsp_tpu.analysis.__main__ import (_build_query,
                                            _nexmark_query_names)

    from dbsp_tpu.circuit.runtime import Runtime

    violations = []
    targets = [(n, _build_query(n)) for n in _nexmark_query_names()]
    targets += list(_demo_circuits())
    for name, circuit in targets:
        # workers=4/8 are the what-if sweeps: a single-worker build carries
        # placement intent (elided exchanges), so probing a larger mesh
        # must stay free of false P001 errors too
        for workers in (1, 4, 8):
            for f in analyze(circuit, workers=workers, strict_shard=True):
                if f.severity == ERROR:
                    violations.append(
                        f"analyzer false positive on {name} "
                        f"(workers={workers}): {f.render()}")
    # The machine-enforced zero-unshard invariant: REBUILD every target
    # under an 8-worker build-only Runtime so the sugar materializes the
    # real multi-worker node shapes (a 1-worker build elides unshard() to
    # intent metadata, which P003 cannot see — a reintroduced mid-circuit
    # unshard would sail through the what-if sweep above). build_only
    # skips mesh construction, so this runs on any host.
    prev = Runtime._swap(Runtime(8, build_only=True))
    try:
        targets8 = [(n, _build_query(n)) for n in _nexmark_query_names()]
        targets8 += list(_demo_circuits())
    finally:
        Runtime._swap(prev)
    for name, circuit in targets8:
        for f in analyze(circuit, workers=8, strict_shard=True):
            if f.severity == ERROR:
                violations.append(
                    f"analyzer error on the REAL 8-worker build of {name}: "
                    f"{f.render()}")
    return violations


def run_multichip() -> list:
    """4. **Multichip dryrun + workers-sweep mini-protocol** (subprocess;
    CLI runs it by default, ``DBSP_TPU_LINT_MULTICHIP=0`` skips — the
    import-based tier-1 consumers get the same coverage from
    tests/test_multichip.py instead of paying it twice):

    * ``dryrun_multichip(8)`` — the full sharded q4 circuit, host and
      compiled, 8 == 1 bit-identical (the zero-unshard invariant's
      runtime half; the static half is P003 in the analyzer sweep);
    * ``bench.py --workers-sweep 1,8`` at mini scale — the MULTICHIP
      protocol end-to-end: per-W children, scaling JSON, exchange
      skew/overflow export.
    """
    import json
    import subprocess

    if os.environ.get("DBSP_TPU_LINT_MULTICHIP", "1") == "0":
        print("lint_all: multichip: skipped (DBSP_TPU_LINT_MULTICHIP=0)")
        return []
    violations = []
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    try:
        p = subprocess.run(
            [sys.executable, "-c",
             "import __graft_entry__ as g; g.dryrun_multichip(8)"],
            cwd=_ROOT, env=env, capture_output=True, text=True, timeout=900)
    except subprocess.TimeoutExpired:
        return ["dryrun_multichip(8) timed out after 900s"]
    if p.returncode != 0:
        violations.append(
            f"dryrun_multichip(8) failed (8 == 1 broken?):\n"
            f"{p.stdout[-800:]}\n{p.stderr[-800:]}")
    env2 = dict(os.environ, BENCH_QUERIES="q4", BENCH_QUERY="q4",
                BENCH_EVENTS="30000", BENCH_BATCH="3000",
                BENCH_TIME_BUDGET_S="600")
    try:
        p = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "bench.py"),
             "--workers-sweep", "1,8"],
            cwd=_ROOT, env=env2, capture_output=True, text=True,
            timeout=900)
    except subprocess.TimeoutExpired:
        violations.append("workers-sweep mini-protocol timed out (900s)")
        return violations
    from bench import last_json_object

    obj = last_json_object(p.stdout)
    if obj is None:
        violations.append(
            f"workers-sweep mini-protocol emitted no JSON:\n"
            f"{p.stdout[-400:]}\n{p.stderr[-400:]}")
    else:
        q4 = (obj.get("scaling") or {}).get("q4", {})
        if "8" not in q4:
            violations.append(
                f"workers-sweep mini-protocol missing W=8 q4 scaling "
                f"entry: {json.dumps(obj.get('scaling'))[:400]}")
    return violations


def _kernel_dryrun_child() -> None:
    """Subprocess body for the kernel front: compile the q4 circuit, run a
    few ticks, print the fused-consumer dispatch-count deltas as JSON."""
    import json

    import jax

    jax.config.update("jax_platforms", "cpu")

    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.compiled import compile_circuit
    from dbsp_tpu.nexmark import (GeneratorConfig, build_inputs, device_gen,
                                  queries)
    from dbsp_tpu.zset import kernels as zk

    cfg = GeneratorConfig(seed=3)

    def build(c):
        streams, handles = build_inputs(c)
        return handles, queries.q4(*streams).output()

    handle, (handles, out) = Runtime.init_circuit(1, build)
    hp, ha, hb = handles

    def gen_fn(tick):
        p, a, b = device_gen.generate_tick(cfg, tick * 40, 40)
        return {hp: p, ha: a, hb: b}

    before = dict(zk.KERNEL_DISPATCH_COUNTS)
    ch = compile_circuit(handle, gen_fn=gen_fn)
    ch.run_ticks(0, 3, validate_every=1)
    delta = {f"{k}:{b}": int(v - before.get((k, b), 0))
             for (k, b), v in sorted(zk.KERNEL_DISPATCH_COUNTS.items())
             if v - before.get((k, b), 0)}
    print(json.dumps(delta))


def run_kernel_dryrun() -> list:
    """4b. **Kernel front** (subprocess; CLI runs it by default,
    ``DBSP_TPU_LINT_KERNELS=0`` skips): the q4 dryrun must dispatch the
    fused ladder megakernels (non-vacuous: ``join_ladder:native`` and
    ``gather_ladder:native`` counted > 0), and the ``DBSP_TPU_NATIVE``
    force-off run must show zero fused-native dispatches with the
    stitched XLA fallback live — proving both the hot path and its A/B
    control."""
    import json
    import subprocess

    if os.environ.get("DBSP_TPU_LINT_KERNELS", "1") == "0":
        print("lint_all: kernel_dryrun: skipped (DBSP_TPU_LINT_KERNELS=0)")
        return []

    def child(extra_env):
        env = dict(os.environ, JAX_PLATFORMS="cpu", **extra_env)
        try:
            p = subprocess.run(
                [sys.executable, "-c",
                 "from tools.lint_all import _kernel_dryrun_child; "
                 "_kernel_dryrun_child()"],
                cwd=_ROOT, env=env, capture_output=True, text=True,
                timeout=600)
        except subprocess.TimeoutExpired:
            return None, "kernel dryrun timed out after 600s"
        if p.returncode != 0:
            return None, (f"kernel dryrun failed:\n{p.stdout[-800:]}\n"
                          f"{p.stderr[-800:]}")
        try:
            return json.loads(p.stdout.strip().splitlines()[-1]), None
        except (ValueError, IndexError):
            return None, f"kernel dryrun emitted no JSON:\n{p.stdout[-400:]}"

    violations = []
    paths, err = child({"DBSP_TPU_NATIVE": "1"})
    if err:
        return [err]
    for kern in ("join_sorted", "agg_ladder"):
        if not paths.get(f"{kern}:native"):
            violations.append(
                f"q4 dryrun never dispatched the fused {kern} megakernel "
                f"(kernel_paths: {json.dumps(paths)}) — the reduction "
                "offensive silently fell back to the stitched chain")
    # one layer down: the reduction offensive off, the PR-12 fused
    # consumers must carry the hot loop with the stitched aggregate live
    reduce_off = "join_sorted,agg_ladder,segment_reduce"
    paths_mid, err = child({"DBSP_TPU_NATIVE": reduce_off})
    if err:
        return violations + [err]
    for kern in ("join_sorted", "agg_ladder"):
        if paths_mid.get(f"{kern}:native"):
            violations.append(
                f"DBSP_TPU_NATIVE={reduce_off} still dispatched "
                f"{kern}:native ({json.dumps(paths_mid)}) — the A/B "
                "control BENCH_local_aggfuse_off.json rests on is vacuous")
    for kern in ("join_ladder", "gather_ladder"):
        if not paths_mid.get(f"{kern}:native"):
            violations.append(
                f"reduction-off run never re-engaged {kern}:native "
                f"({json.dumps(paths_mid)}) — the PR-12 layer rotted")
    if not paths_mid.get("agg_ladder:xla"):
        violations.append(
            f"reduction-off run never took the stitched aggregate chain "
            f"({json.dumps(paths_mid)})")
    off = ("join_ladder,gather_ladder,old_weights,"
           "join_sorted,agg_ladder,segment_reduce")
    paths_off, err = child({"DBSP_TPU_NATIVE": off})
    if err:
        return violations + [err]
    for kern in ("join_ladder", "gather_ladder"):
        if paths_off.get(f"{kern}:native"):
            violations.append(
                f"DBSP_TPU_NATIVE={off} still dispatched {kern}:native "
                f"({json.dumps(paths_off)}) — the force-off control is "
                "vacuous and A/B runs would measure nothing")
        if not paths_off.get(f"{kern}:xla"):
            violations.append(
                f"force-off run never engaged the stitched {kern} XLA "
                f"fallback ({json.dumps(paths_off)})")
    return violations


def _residency_dryrun_child() -> None:
    """Subprocess body for the residency front: run a q4 compiled growth
    dryrun under whatever residency env the parent set and print the
    transition counts + the max observed device-resident rows as JSON."""
    import json

    import jax

    jax.config.update("jax_platforms", "cpu")

    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.compiled import compile_circuit
    from dbsp_tpu.nexmark import (GeneratorConfig, build_inputs, device_gen,
                                  queries)

    cfg = GeneratorConfig(seed=3)

    def build(c):
        streams, handles = build_inputs(c)
        return handles, queries.q4(*streams).output()

    handle, (handles, out) = Runtime.init_circuit(1, build)
    hp, ha, hb = handles

    def gen_fn(tick):
        p, a, b = device_gen.generate_tick(cfg, tick * 8, 8)
        return {hp: p, ha: a, hb: b}

    ch = compile_circuit(handle, gen_fn=gen_fn)
    max_device = 0

    def watch(next_tick):
        nonlocal max_device
        max_device = max(max_device, ch.tier_rows()["device"])

    ch.run_ticks(0, 4, validate_every=1, on_validated=watch)
    print(json.dumps({
        "budget": ch.residency_cfg.device_rows,
        "max_device_rows": int(max_device),
        "final_tiers": {k: int(v) for k, v in ch.tier_rows().items()},
        "transitions": {f"{f}>{t}:{c}": int(n) for (f, t, c), n in
                        sorted(ch.residency_stats.items())}}))


def run_residency_dryrun() -> list:
    """7. **Residency front** (subprocess; CLI runs it by default,
    ``DBSP_TPU_LINT_RESIDENCY=0`` skips — tests/test_residency.py carries
    the import-based tier-1 coverage): a q4 growth dryrun under a
    deliberately tiny DBSP_TPU_DEVICE_ROWS/_HOST_ROWS must observe
    transitions in BOTH demotion directions (device->host, host->disk)
    with the disk tier non-empty, while the unbounded control run
    observes none — proving the budget path and its A/B control are both
    live, not silently wired to a no-op."""
    import json
    import subprocess
    import tempfile

    if os.environ.get("DBSP_TPU_LINT_RESIDENCY", "1") == "0":
        print("lint_all: residency_dryrun: skipped "
              "(DBSP_TPU_LINT_RESIDENCY=0)")
        return []

    def child(extra_env):
        env = dict(os.environ, JAX_PLATFORMS="cpu", **extra_env)
        for k in ("DBSP_TPU_DEVICE_ROWS", "DBSP_TPU_HOST_ROWS",
                  "DBSP_TPU_COLD_DIR"):
            env.pop(k, None)
            if k in extra_env:
                env[k] = extra_env[k]
        try:
            p = subprocess.run(
                [sys.executable, "-c",
                 "from tools.lint_all import _residency_dryrun_child; "
                 "_residency_dryrun_child()"],
                cwd=_ROOT, env=env, capture_output=True, text=True,
                timeout=600)
        except subprocess.TimeoutExpired:
            return None, "residency dryrun timed out after 600s"
        if p.returncode != 0:
            return None, (f"residency dryrun failed:\n{p.stdout[-800:]}\n"
                          f"{p.stderr[-800:]}")
        try:
            return json.loads(p.stdout.strip().splitlines()[-1]), None
        except (ValueError, IndexError):
            return None, f"residency dryrun emitted no JSON:\n" \
                         f"{p.stdout[-400:]}"

    violations = []
    with tempfile.TemporaryDirectory(prefix="lint-cold-") as cold:
        tiny, err = child({"DBSP_TPU_DEVICE_ROWS": "512",
                           "DBSP_TPU_HOST_ROWS": "512",
                           "DBSP_TPU_COLD_DIR": cold})
        if err:
            return [err]
        trans = tiny.get("transitions", {})
        if not any(k.startswith("device>host") for k in trans):
            violations.append(
                f"tiny-budget q4 dryrun never demoted device->host "
                f"({json.dumps(tiny)}) — the compiled residency budget "
                "is silently ignored")
        if not any(k.startswith("host>disk") for k in trans):
            violations.append(
                f"tiny-budget q4 dryrun never demoted host->disk "
                f"({json.dumps(tiny)}) — the disk tier is dead")
        if not tiny.get("final_tiers", {}).get("disk"):
            violations.append(
                f"tiny-budget q4 dryrun ended with an empty disk tier "
                f"({json.dumps(tiny)})")
    control, err = child({})
    if err:
        return violations + [err]
    if control.get("transitions"):
        violations.append(
            f"unbounded control run recorded residency transitions "
            f"({json.dumps(control)}) — the budget engages without being "
            "configured, every unbudgeted pipeline would pay the tiering")
    return violations


def run_profile_dryrun() -> list:
    """5. **Profiler dryrun** (subprocess; CLI runs it by default,
    ``DBSP_TPU_LINT_PROFILE=0`` skips — tests/test_opprofile.py carries
    the import-based tier-1 coverage): ``opprofile.dryrun("q4")`` runs
    one measured segmented profile end to end and raises on schema
    drift, segmented/fused divergence, or attribution below 90%."""
    import subprocess

    if os.environ.get("DBSP_TPU_LINT_PROFILE", "1") == "0":
        print("lint_all: profile_dryrun: skipped (DBSP_TPU_LINT_PROFILE=0)")
        return []
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        p = subprocess.run(
            [sys.executable, "-c",
             "from dbsp_tpu.obs.opprofile import dryrun; dryrun('q4')"],
            cwd=_ROOT, env=env, capture_output=True, text=True, timeout=900)
    except subprocess.TimeoutExpired:
        return ["opprofile.dryrun('q4') timed out after 900s"]
    if p.returncode != 0:
        return [f"opprofile.dryrun('q4') failed (profiler rotted?):\n"
                f"{p.stdout[-800:]}\n{p.stderr[-800:]}"]
    return []


def run_lineage_dryrun() -> list:
    """6. **Lineage dryrun** (subprocess; CLI runs it by default,
    ``DBSP_TPU_LINT_LINEAGE=0`` skips — tests/test_lineage.py carries the
    import-based tier-1 coverage): ``lineage.dryrun("q4")`` backward-
    slices one known q4 output row on the host engine and raises
    LineageError when the slice diverges from the provenance-semiring
    full-recompute oracle."""
    import subprocess

    if os.environ.get("DBSP_TPU_LINT_LINEAGE", "1") == "0":
        print("lint_all: lineage_dryrun: skipped (DBSP_TPU_LINT_LINEAGE=0)")
        return []
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        p = subprocess.run(
            [sys.executable, "-c",
             "from dbsp_tpu.obs.lineage import dryrun; "
             "dryrun('q4', events=2000, steps=2)"],
            cwd=_ROOT, env=env, capture_output=True, text=True, timeout=900)
    except subprocess.TimeoutExpired:
        return ["lineage.dryrun('q4') timed out after 900s"]
    if p.returncode != 0:
        return [f"lineage.dryrun('q4') failed (oracle divergence?):\n"
                f"{p.stdout[-800:]}\n{p.stderr[-800:]}"]
    return []


def _timeline_dryrun_child() -> None:
    """Subprocess body for the timeline front: a host-engine q4 growth
    dryrun behind a Controller + PipelineObs (the full serving wiring:
    note_tick / note_arrival / note_visible + flight ingest). With
    DBSP_TPU_LINT_TL_STALL=1 one target tick is stalled inside the step
    lock (>= 50ms, scaled past the spike threshold) with a co-timed
    checkpoint flight event; prints spikes + freshness + the note_* hot
    path's per-op overhead as one JSON line."""
    import json
    import time

    import jax

    jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp

    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.io.catalog import Catalog
    from dbsp_tpu.io.controller import Controller, ControllerConfig
    from dbsp_tpu.nexmark import (GeneratorConfig, NexmarkGenerator,
                                  build_inputs, queries)
    from dbsp_tpu.nexmark import model as M
    from dbsp_tpu.obs import PipelineObs

    def build(c):
        streams, handles = build_inputs(c)
        return handles, queries.q4(*streams).output()

    handle, (handles, out) = Runtime.init_circuit(1, build)
    catalog = Catalog()
    for name, h, key, vals in (("persons", handles[0], M.PERSON_KEY,
                                M.PERSON_VALS),
                               ("auctions", handles[1], M.AUCTION_KEY,
                                M.AUCTION_VALS),
                               ("bids", handles[2], M.BID_KEY, M.BID_VALS)):
        catalog.register_input(name, h, key + vals)
    catalog.register_output("q4", out, (jnp.int64, jnp.int64))
    ctl = Controller(handle, catalog, ControllerConfig(
        min_batch_records=10**9, flush_interval_s=3600.0))
    obs = PipelineObs(name="lint")
    obs.attach_circuit(handle.circuit)
    obs.attach_controller(ctl)
    tl = obs.timeline

    gen = NexmarkGenerator(GeneratorConfig(seed=7))
    ept, warm, target, total = 100, 10, 16, 20
    stall = {"at": None, "s": 0.0}

    def stall_monitor():
        if ctl.steps == stall["at"]:
            ctl.flight.record("checkpoint", tick=ctl.steps,
                              ns=int(stall["s"] * 1e9), seeded=True)
            time.sleep(stall["s"])

    ctl.add_monitor(stall_monitor)

    def drive(t0, t1):
        for t in range(t0, t1):
            gen.feed(handles, t * ept, (t + 1) * ept)
            ctl.note_pushed(ept)
            ctl.step()

    drive(0, warm)
    if os.environ.get("DBSP_TPU_LINT_TL_STALL") == "1":
        lats = sorted(r["latency_ns"] for r in tl.records()
                      if r["kind"] == "tick" and r.get("src") == "ctl")
        med_s = lats[len(lats) // 2] / 1e9
        # past the detector's max(mult*med, med+floor) threshold with
        # margin, never below the issue's 50ms floor
        stall["s"] = max(0.05, 4.0 * med_s + 0.02)
        stall["at"] = target
    drive(warm, total)
    obs.watch()  # fold the last tick's flight events into the timeline

    sp = tl.explain_spikes()
    print(json.dumps({
        "ticks": sp["ticks_seen"],
        "target_tick": stall["at"],
        "stall_s": stall["s"],
        "spikes": [{"tick": s["tick"], "cause": s["cause"],
                    "latency_ns": s["latency_ns"],
                    "evidence": s["evidence"]} for s in sp["spikes"]],
        "freshness": tl.freshness_summary(),
        "note_overhead_ns": _timeline_note_overhead_ns(),
    }))


def _timeline_note_overhead_ns() -> float:
    """Per-op cost of the always-on note_tick/note_arrival/note_visible
    hot path (a standalone ring: the measurement must not disturb the
    dryrun's records)."""
    import time

    from dbsp_tpu.obs.timeline import Timeline

    tl = Timeline(capacity=256, enabled=True)
    n = 2000
    t0 = time.perf_counter_ns()
    for i in range(n):
        tl.note_arrival(8)
        tl.note_tick(i, 1_000_000, rows_in=8, rows_out=8, queue_depth=0)
        tl.note_visible(["q4"])
    return (time.perf_counter_ns() - t0) / (3 * n)


def run_timeline_dryrun() -> list:
    """7b. **Timeline front** (subprocess; CLI runs it by default,
    ``DBSP_TPU_LINT_TIMELINE=0`` skips — tests/test_timeline.py carries
    the import-based tier-1 coverage): a host q4 dryrun with a seeded
    >= 50ms in-step stall + co-timed checkpoint flight event MUST surface
    the stalled tick as a spike attributed to ``checkpoint`` with
    evidence; the unperturbed control run MUST report zero spikes (the
    detector neither rots nor cries wolf); and the always-on note_* hot
    path must stay under the per-op overhead bound."""
    import json
    import subprocess

    if os.environ.get("DBSP_TPU_LINT_TIMELINE", "1") == "0":
        print("lint_all: timeline_dryrun: skipped "
              "(DBSP_TPU_LINT_TIMELINE=0)")
        return []

    def child(stall):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   DBSP_TPU_LINT_TL_STALL="1" if stall else "0",
                   # explicit detector floor: perturbation (>=50ms) sits
                   # above it, host scheduling noise sits below it
                   DBSP_TPU_SPIKE_FLOOR_MS="40")
        try:
            p = subprocess.run(
                [sys.executable, "-c",
                 "from tools.lint_all import _timeline_dryrun_child; "
                 "_timeline_dryrun_child()"],
                cwd=_ROOT, env=env, capture_output=True, text=True,
                timeout=600)
        except subprocess.TimeoutExpired:
            return None, "timeline dryrun timed out after 600s"
        if p.returncode != 0:
            return None, (f"timeline dryrun failed:\n{p.stdout[-800:]}\n"
                          f"{p.stderr[-800:]}")
        try:
            return json.loads(p.stdout.strip().splitlines()[-1]), None
        except (ValueError, IndexError):
            return None, f"timeline dryrun emitted no JSON:\n" \
                         f"{p.stdout[-400:]}"

    violations = []
    stalled, err = child(stall=True)
    if err:
        return [err]
    hits = [s for s in stalled.get("spikes", [])
            if s["tick"] == stalled.get("target_tick")]
    if not hits:
        violations.append(
            f"seeded {stalled.get('stall_s', 0):.3f}s stall on tick "
            f"{stalled.get('target_tick')} was not flagged as a spike "
            f"({json.dumps(stalled.get('spikes'))}) — EXPLAIN SPIKE is "
            "blind to a real latency outlier")
    elif hits[0]["cause"] != "checkpoint" or not hits[0]["evidence"]:
        violations.append(
            f"seeded stall flagged but misattributed "
            f"({json.dumps(hits[0])}) — expected cause=checkpoint with "
            "co-timed evidence")
    if not stalled.get("freshness", {}).get("q4", {}).get("samples"):
        violations.append(
            f"q4 dryrun produced no freshness samples "
            f"({json.dumps(stalled.get('freshness'))}) — the arrival->"
            "visibility pipeline is dead")
    if stalled.get("note_overhead_ns", 1e9) > 25_000:
        violations.append(
            f"timeline note_* hot path costs "
            f"{stalled['note_overhead_ns']:.0f}ns/op (bound: 25000) — "
            "the always-on ring is too expensive for the step lock")
    control, err = child(stall=False)
    if err:
        return violations + [err]
    if control.get("spikes"):
        violations.append(
            f"unperturbed control run reported spikes "
            f"({json.dumps(control['spikes'])}) — the detector cries "
            "wolf on clean q4 ticks and every attribution is suspect")
    return violations


def _readpath_dryrun_child() -> None:
    """Subprocess body for the readpath front: a served host-engine q4
    pipeline under a tsan lock probe.  Reader threads storm ``/view``
    (point, range, scan) and ``/output_endpoint`` while MainThread
    drives steps AND keeps a changefeed cursor paced over HTTP; prints
    one JSON line with the handler threads' traced lock set, the
    MainThread step-lock sighting, the delivered changefeed epochs and
    the view's final published epoch."""
    import json
    import threading
    import urllib.request

    import jax

    jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp

    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.io.catalog import Catalog
    from dbsp_tpu.io.controller import Controller, ControllerConfig
    from dbsp_tpu.io.server import CircuitServer
    from dbsp_tpu.nexmark import (GeneratorConfig, NexmarkGenerator,
                                  build_inputs, queries)
    from dbsp_tpu.nexmark import model as M
    from dbsp_tpu.obs import PipelineObs
    from dbsp_tpu.testing import tsan

    class Probe:
        """Records (thread name, lock name) for every traced acquire."""

        def __init__(self):
            self.lock = threading.Lock()
            self.acquires = []

        def yield_point(self, hook, lock_name):
            if hook == "acquire":
                with self.lock:
                    self.acquires.append(
                        (threading.current_thread().name, lock_name))

    probe = Probe()
    feed_epochs, reads = [], {"n": 0}
    with tsan.session(schedule=probe) as report:
        def build(c):
            streams, handles = build_inputs(c)
            return handles, queries.q4(*streams).output()

        handle, (handles, out) = Runtime.init_circuit(1, build)
        catalog = Catalog()
        for name, h, key, vals in (("persons", handles[0], M.PERSON_KEY,
                                    M.PERSON_VALS),
                                   ("auctions", handles[1], M.AUCTION_KEY,
                                    M.AUCTION_VALS),
                                   ("bids", handles[2], M.BID_KEY,
                                    M.BID_VALS)):
            catalog.register_input(name, h, key + vals)
        catalog.register_output("q4", out, (jnp.int64, jnp.int64))
        ctl = Controller(handle, catalog, ControllerConfig(
            min_batch_records=10**9, flush_interval_s=3600.0))
        # obs wiring binds the read metrics: their per-increment Metric
        # lock is what makes handler threads visible to the probe (the
        # read path itself acquires no serving-plane lock at all)
        obs = PipelineObs(name="lint-readpath")
        obs.attach_circuit(handle.circuit)
        obs.attach_controller(ctl)
        srv = CircuitServer(ctl, obs=obs)
        srv.start()
        base = f"http://127.0.0.1:{srv.port}"
        gen = NexmarkGenerator(GeneratorConfig(seed=11))

        def get(path):
            with urllib.request.urlopen(base + path, timeout=30) as r:
                body = r.read() or b"{}"
            reads["n"] += 1
            return json.loads(body)

        def storm():
            for _ in range(5):
                get("/view/q4?key=1")
                get("/view/q4?lo=0&hi=50")
                get("/view/q4")
                get("/output_endpoint/q4?format=json")

        try:
            for t in range(2):
                gen.feed(handles, t * 150, (t + 1) * 150)
                ctl.note_pushed(150)
                ctl.step()
            readers = [threading.Thread(target=storm, name=f"reader-{i}")
                       for i in range(2)]
            for r in readers:
                r.start()
            cursor = 0
            for t in range(2, 5):
                gen.feed(handles, t * 150, (t + 1) * 150)
                ctl.note_pushed(150)
                ctl.step()
                # the subscriber keeps pace over HTTP: every published
                # interval must arrive exactly once, cursor-ordered
                for rec in get(f"/changefeed?view=q4&after={cursor}"
                               )["records"]:
                    feed_epochs.append(rec["epoch"])
                    cursor = rec["epoch"]
            for r in readers:
                r.join(timeout=60)
            final_epoch = ctl.read_plane.snapshot("q4").epoch
        finally:
            srv.stop()

    handler = sorted({(t, l) for t, l in probe.acquires
                      if t != "MainThread"})
    print(json.dumps({
        "handler_locks": [list(x) for x in handler],
        "handler_lock_names": sorted({l for _, l in handler}),
        "main_step_lock": ("MainThread", "Controller._step_lock")
                          in probe.acquires,
        "feed_epochs": feed_epochs,
        "final_epoch": final_epoch,
        "reads": reads["n"],
        "tsan_violations": [str(v) for v in report.violations],
    }))


def run_readpath_dryrun() -> list:
    """8. **Read-path front** (subprocess; CLI runs it by default,
    ``DBSP_TPU_LINT_READPATH=0`` skips — tests/test_readpath.py carries
    the import-based tier-1 coverage): a served q4 dryrun under a tsan
    lock probe MUST show (a) the HTTP read routes (``/view``,
    ``/changefeed``, ``/output_endpoint``) never acquiring the
    controller's step or push locks while MainThread demonstrably does
    (the probe is live, not vacuous), and (b) a paced changefeed
    subscriber receiving every published interval exactly once, in
    cursor order, ending at the view's final published epoch."""
    import json
    import subprocess

    if os.environ.get("DBSP_TPU_LINT_READPATH", "1") == "0":
        print("lint_all: readpath_dryrun: skipped "
              "(DBSP_TPU_LINT_READPATH=0)")
        return []
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        p = subprocess.run(
            [sys.executable, "-c",
             "from tools.lint_all import _readpath_dryrun_child; "
             "_readpath_dryrun_child()"],
            cwd=_ROOT, env=env, capture_output=True, text=True,
            timeout=600)
    except subprocess.TimeoutExpired:
        return ["readpath dryrun timed out after 600s"]
    if p.returncode != 0:
        return [f"readpath dryrun failed:\n{p.stdout[-800:]}\n"
                f"{p.stderr[-800:]}"]
    try:
        out = json.loads(p.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return [f"readpath dryrun emitted no JSON:\n{p.stdout[-400:]}"]

    violations = []
    taken = set(out.get("handler_lock_names", []))
    if taken & {"Controller._step_lock", "Controller._pushed_lock"}:
        violations.append(
            f"read storm acquired a serving-plane lock from an HTTP "
            f"handler thread ({json.dumps(out['handler_locks'])}) — the "
            "read plane is NOT lock-free against the step path")
    if not out.get("main_step_lock"):
        violations.append(
            "probe never saw MainThread take Controller._step_lock — "
            "the lock probe is blind to the step path and the zero-"
            "step-lock claim above is vacuous")
    if not out.get("handler_locks"):
        violations.append(
            f"probe recorded no handler-thread lock acquisitions at all "
            f"(reads={out.get('reads')}) — handler threads are invisible "
            "to the probe and the zero-step-lock claim is vacuous")
    eps = out.get("feed_epochs", [])
    if len(eps) < 3 or eps != sorted(set(eps)):
        violations.append(
            f"changefeed delivery is not exactly-once in order "
            f"({eps}) — a resumed cursor would replay or gap")
    elif eps[-1] != out.get("final_epoch"):
        violations.append(
            f"changefeed cursor ended at epoch {eps[-1]} but the view's "
            f"final published epoch is {out.get('final_epoch')} — a "
            "published interval was never delivered")
    if out.get("tsan_violations"):
        violations.append(
            f"tsan flagged the read storm: {out['tsan_violations']}")
    return violations


def _tracing_dryrun_child() -> None:
    """Subprocess body for the tracing front: a served host-engine q4
    pipeline (CircuitServer) feeding a live ReplicaServer, with
    DBSP_TPU_TRACE_E2E taken from the environment. Pushes one delta
    under a known trace id, reads it back over HTTP from the primary
    the instant the tick lands (age attribution) and from the replica
    after its fold (trace-id identity across process rings), then dumps
    both span rings' per-(pid,tid) B/E balance, the e2e span counts and
    stage ids, and the stage histogram's populated label set as one
    JSON line."""
    import json
    import re
    import time
    import urllib.request

    import jax

    jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp

    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.io.catalog import Catalog
    from dbsp_tpu.io.controller import Controller, ControllerConfig
    from dbsp_tpu.io.server import CircuitServer
    from dbsp_tpu.nexmark import (GeneratorConfig, NexmarkGenerator,
                                  build_inputs, queries)
    from dbsp_tpu.nexmark import model as M
    from dbsp_tpu.obs import PipelineObs
    from dbsp_tpu.obs.export import prometheus_text
    from dbsp_tpu.serving import ReplicaServer

    def build(c):
        streams, handles = build_inputs(c)
        return handles, queries.q4(*streams).output()

    handle, (handles, out) = Runtime.init_circuit(1, build)
    catalog = Catalog()
    for name, h, key, vals in (("persons", handles[0], M.PERSON_KEY,
                                M.PERSON_VALS),
                               ("auctions", handles[1], M.AUCTION_KEY,
                                M.AUCTION_VALS),
                               ("bids", handles[2], M.BID_KEY,
                                M.BID_VALS)):
        catalog.register_input(name, h, key + vals)
    catalog.register_output("q4", out, (jnp.int64, jnp.int64))
    ctl = Controller(handle, catalog, ControllerConfig(
        min_batch_records=10**9, flush_interval_s=3600.0))
    obs = PipelineObs(name="lint-tracing")
    obs.attach_circuit(handle.circuit)
    obs.attach_controller(ctl)
    srv = CircuitServer(ctl, obs=obs)
    srv.start()
    base = f"http://127.0.0.1:{srv.port}"
    rep = ReplicaServer(base, ["q4"], name="lint-replica",
                        e2e=ctl.e2e).start()
    gen = NexmarkGenerator(GeneratorConfig(seed=23))

    def get(url):
        with urllib.request.urlopen(url, timeout=30) as r:
            return json.loads(r.read() or b"{}"), dict(r.headers)

    ept = 400  # big enough that the tick dominates the delta's age
    try:
        for t in range(3):
            gen.feed(handles, t * ept, (t + 1) * ept)
            ctl.note_pushed(ept)
            ctl.step()
        # the probed delta: a known trace id through the whole path
        gen.feed(handles, 3 * ept, 4 * ept)
        delta_id = ctl.note_pushed(ept)
        ctl.step()
        obj, hdrs = get(base + "/view/q4")  # read NOW: age ~= stages
        deadline = time.time() + 30
        while time.time() < deadline and \
                rep.status()["epochs"]["q4"] < ctl.read_plane.epoch:
            time.sleep(0.005)
        robj, rhdrs = get(rep.base_url + "/view/q4")
        rings = {"writer": obs.spans.to_chrome_trace(),
                 "replica": rep.spans.to_chrome_trace()}
    finally:
        rep.stop()
        srv.stop()

    def ring_summary(ct):
        depth, nbe, e2e_ids = {}, 0, {}
        for e in ct["traceEvents"]:
            if e["ph"] not in ("B", "E"):
                continue
            nbe += 1
            lane = f"{e['pid']}/{e['tid']}"
            d = depth.get(lane, 0) + (1 if e["ph"] == "B" else -1)
            depth[lane] = d
            if d < 0:
                break  # negative depth: report it as-is
            if e["ph"] == "B" and e.get("cat") == "e2e":
                for tid_ in (e.get("args", {}).get("trace") or ()):
                    e2e_ids.setdefault(
                        e["name"].replace("e2e:", ""), []).append(tid_)
        return {"events": nbe, "lane_depths": depth,
                "e2e_spans": sum(len(v) for v in e2e_ids.values()),
                "ids_by_stage": e2e_ids}

    stages = obj.get("stages") or {}
    hist_stages = sorted(set(re.findall(
        r'dbsp_tpu_e2e_stage_seconds_count\{[^}]*stage="(\w+)"[^}]*\} '
        r'[1-9]', prometheus_text(obs.registry))))
    print(json.dumps({
        "enabled": ctl.e2e.enabled,
        "delta_id": delta_id,
        "view": {"age_s": obj.get("age_s"), "stages": stages,
                 "trace_ids": (obj.get("trace") or {}).get("ids"),
                 "header": hdrs.get("X-Dbsp-Trace")},
        "attributed_frac": (sum(stages.values()) / obj["age_s"]
                            if stages and obj.get("age_s") else 0.0),
        "replica_view": {"trace_ids":
                         (robj.get("trace") or {}).get("ids"),
                         "stages": sorted(robj.get("stages") or ()),
                         "header": rhdrs.get("X-Dbsp-Trace")},
        "rings": {k: ring_summary(v) for k, v in rings.items()},
        "hist_stages": hist_stages,
    }))


def run_tracing_dryrun() -> list:
    """9. **Tracing front** (subprocess; CLI runs it by default,
    ``DBSP_TPU_LINT_TRACING=0`` skips — tests/test_e2e_tracing.py
    carries the import-based tier-1 coverage): a served q4 + replica
    dryrun MUST show (a) every span ring lane B/E-balanced, (b) >= 95%
    of a fresh read's measured e2e age attributed to named stages,
    (c) the SAME trace id on the writer ring's publish span and the
    replica ring's transport/apply spans for one delta (the fleet-trace
    join key), and (d) the OFF control (``DBSP_TPU_TRACE_E2E=0``)
    recording zero e2e spans, no read annotations and an empty stage
    histogram — the kill switch proven live, the detector non-vacuous."""
    import json
    import subprocess

    if os.environ.get("DBSP_TPU_LINT_TRACING", "1") == "0":
        print("lint_all: tracing_dryrun: skipped "
              "(DBSP_TPU_LINT_TRACING=0)")
        return []

    def child(on):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   DBSP_TPU_TRACE_E2E="1" if on else "0")
        try:
            p = subprocess.run(
                [sys.executable, "-c",
                 "from tools.lint_all import _tracing_dryrun_child; "
                 "_tracing_dryrun_child()"],
                cwd=_ROOT, env=env, capture_output=True, text=True,
                timeout=600)
        except subprocess.TimeoutExpired:
            return None, "tracing dryrun timed out after 600s"
        if p.returncode != 0:
            return None, (f"tracing dryrun failed:\n{p.stdout[-800:]}\n"
                          f"{p.stderr[-800:]}")
        try:
            return json.loads(p.stdout.strip().splitlines()[-1]), None
        except (ValueError, IndexError):
            return None, f"tracing dryrun emitted no JSON:\n" \
                         f"{p.stdout[-400:]}"

    violations = []
    on, err = child(on=True)
    if err:
        return [err]
    for ring, summ in on.get("rings", {}).items():
        if not summ.get("events"):
            violations.append(
                f"{ring} span ring recorded no events — the trace "
                "surface is dead and every claim below is vacuous")
        bad = {k: v for k, v in summ.get("lane_depths", {}).items() if v}
        if bad:
            violations.append(
                f"{ring} span ring has unbalanced B/E lanes {bad} — "
                "the Chrome trace would render phantom open spans")
    frac = on.get("attributed_frac", 0.0)
    if frac < 0.95:
        violations.append(
            f"only {frac:.1%} of the fresh read's e2e age is attributed "
            f"to named stages (stages={json.dumps(on['view']['stages'])},"
            f" age_s={on['view']['age_s']}) — the decomposition leaks")
    did = on.get("delta_id")
    wids = on.get("rings", {}).get("writer", {}).get("ids_by_stage", {})
    rids = on.get("rings", {}).get("replica", {}).get("ids_by_stage", {})
    if not did or did not in wids.get("publish", []):
        violations.append(
            f"probed delta id {did} missing from the writer ring's "
            f"publish spans ({json.dumps(wids)}) — writer-side stage "
            "spans are not keyed by trace id")
    for st in ("transport", "apply"):
        if did and did not in rids.get(st, []):
            violations.append(
                f"probed delta id {did} missing from the replica ring's "
                f"{st} spans ({json.dumps(rids)}) — the fleet trace "
                "cannot join this delta across processes")
    if did and did not in (on["view"]["trace_ids"] or []):
        violations.append(
            f"/view response served the probed epoch without its trace "
            f"id ({json.dumps(on['view'])}) — read attribution is "
            "disconnected from ingest")
    need = {"queue_wait", "tick", "publish", "serve", "transport",
            "apply"}
    have = set(on.get("hist_stages", []))
    if not need <= have:
        violations.append(
            f"stage histogram missing samples for "
            f"{sorted(need - have)} (have {sorted(have)}) — "
            "dbsp_tpu_e2e_stage_seconds does not cover the taxonomy")

    off, err = child(on=False)
    if err:
        return violations + [err]
    off_e2e = {k: v.get("e2e_spans", 0)
               for k, v in off.get("rings", {}).items()}
    if off.get("enabled") or any(off_e2e.values()):
        violations.append(
            f"OFF control (DBSP_TPU_TRACE_E2E=0) still recorded e2e "
            f"spans ({off_e2e}) — the kill switch is dead")
    if off.get("delta_id") is not None or off.get("view", {}).get(
            "age_s") is not None or off.get("hist_stages"):
        violations.append(
            f"OFF control still minted ids / annotated reads / filled "
            f"the stage histogram (id={off.get('delta_id')}, "
            f"view={json.dumps(off.get('view'))}, "
            f"hist={off.get('hist_stages')}) — tracing work survives "
            "the kill switch")
    return violations


#: the pure-static fronts (``--static``): AST/file passes only — no
#: subprocess dryruns, no circuit builds, no jax compilation
STATIC_FRONTS = (("check_metrics", run_check_metrics),
                 ("check_hotpath", run_check_hotpath),
                 ("check_state", run_check_state),
                 ("check_concurrency", run_check_concurrency_static),
                 ("check_retrace", run_check_retrace),
                 ("check_native", run_check_native),
                 ("gen_metrics_doc", run_gen_metrics_doc),
                 ("check_dashboard", run_check_dashboard))


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else list(argv)
    if "--static" in args:
        fronts = list(STATIC_FRONTS)
    else:
        fronts = [("check_metrics", run_check_metrics),
                  ("check_hotpath", run_check_hotpath),
                  ("check_state", run_check_state),
                  ("concurrency", run_concurrency),
                  ("retrace", run_retrace),
                  ("check_native", run_check_native),
                  ("gen_metrics_doc", run_gen_metrics_doc),
                  ("check_dashboard", run_check_dashboard),
                  ("analyzer_selfcheck", run_analyzer_selfcheck),
                  ("multichip", run_multichip),
                  ("kernel_dryrun", run_kernel_dryrun),
                  ("residency", run_residency_dryrun),
                  ("profile_dryrun", run_profile_dryrun),
                  ("lineage_dryrun", run_lineage_dryrun),
                  ("timeline_dryrun", run_timeline_dryrun),
                  ("readpath_dryrun", run_readpath_dryrun),
                  ("tracing_dryrun", run_tracing_dryrun)]
    failed = 0
    for name, fn in fronts:
        violations = fn()
        for v in violations:
            print(v)
        status = "ok" if not violations else f"{len(violations)} violation(s)"
        print(f"lint_all: {name}: {status}")
        failed += bool(violations)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
