"""Operator-level attribution for the compiled engine (obs/opprofile.py).

Tier-1 contract (ISSUE 9):

* the SEGMENTED profile mode is bit-identical to the fused step program
  on q1-q8 (the fused program is the production path; the segmented one
  must describe the same computation, not a divergent replica);
* host and compiled ``/profile`` answer through ONE report schema
  (``opprofile.PROFILE_SCHEMA``), round-tripped over HTTP for both modes;
* the per-node metric families are GATED: absent unless a measured
  profile ran, top-N capped when it did, and registrable only through
  the ``obs/opprofile.py`` gate (``tools/check_metrics.py`` rule 4);
* a seeded slow node is attributed to the right operator — the property
  the whole subsystem exists for;
* the committed ``PROFILE_q4.json`` (``tools/roofline.py --per-node``)
  stays schema-valid, bit-identical, and >= 90% attributed.
"""

import json
import os
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dbsp_tpu.circuit import Runtime
from dbsp_tpu.compiled import compile_circuit
from dbsp_tpu.nexmark import GeneratorConfig, build_inputs, device_gen, queries
from dbsp_tpu.obs import opprofile
from dbsp_tpu.obs.registry import MetricsRegistry
from dbsp_tpu.operators import add_input_zset
from dbsp_tpu.zset.batch import Batch

CFG = GeneratorConfig(seed=1)
EPT = 4  # epochs/tick -> 200 events/tick (mini scale; compile dominates)


def _mini_compiled(qname: str, warm: int = 1):
    """A mini compiled Nexmark circuit with device generation (the
    dryrun's build, without its q4-sized attribution gate)."""
    query = getattr(queries, qname)

    def build(c):
        streams, handles = build_inputs(c)
        return handles, query(*streams).output()

    handle, (handles, _out) = Runtime.init_circuit(1, build)
    hp, ha, hb = handles

    def gen_fn(tick):
        p, a, b = device_gen.generate_tick(CFG, tick * EPT, EPT)
        return {hp: p, ha: a, hb: b}

    ch = compile_circuit(handle, gen_fn=gen_fn)
    if warm:
        ch.run_ticks(0, warm, validate_every=1)
    return ch, warm


@pytest.fixture(scope="module")
def q4_profiled():
    """One measured q4 profile shared by the schema/metrics/dot tests
    (the per-query compile cost is the expensive part)."""
    ch, warm = _mini_compiled("q4", warm=2)
    report = opprofile.measured_profile(ch, n=2, t0=warm)
    return ch, warm, report


@pytest.mark.parametrize("qname", ["q1", "q2", "q3", "q4", "q5", "q6",
                                   "q7", "q8"])
def test_segmented_bit_identity(qname):
    """The acceptance gate: segmented == fused, bit for bit, on every
    north-star query — outputs of every tick AND the final states."""
    ch, warm = _mini_compiled(qname)
    report = opprofile.check_report(
        opprofile.measured_profile(ch, n=2, t0=warm))
    m = report["measured"]
    assert m["bit_identical"], (qname, m["mismatches"])
    assert report["attribution"] == "measured"
    # named rows carry the timing the mode exists for
    assert sum(r["total_ms"] for r in report["operators"]) > 0


def test_profile_rewinds_engine(q4_profiled):
    """Profiling is hypothetical: after the rewind the engine continues
    from its pre-profile state and produces the same ticks the fused
    path would have produced without any profiling."""
    ch, warm, _report = q4_profiled
    snap_before = jax.tree_util.tree_leaves(ch.snapshot())

    opprofile.measured_profile(ch, n=2, t0=warm)
    snap_after = jax.tree_util.tree_leaves(ch.snapshot())
    assert len(snap_before) == len(snap_after)
    for a, b in zip(snap_before, snap_after):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # latency bookkeeping rewound too: a profile must not pollute the
    # samples production SLOs evaluate over
    n_samples = len(ch.step_times_ns)
    opprofile.measured_profile(ch, n=2, t0=warm)
    assert len(ch.step_times_ns) == n_samples


def test_report_schema_shared_by_host_and_compiled(q4_profiled):
    """Both engines emit the same row keys under one schema id — the
    'one question, one answer shape' contract of /profile."""
    from dbsp_tpu.profile import CPUProfiler

    _ch, _warm, compiled_report = q4_profiled

    def build(c):
        s, h = add_input_zset(c, [jnp.int64], [jnp.int64])
        return h, s.distinct().integrate().output()

    handle, (h, _out) = Runtime.init_circuit(1, build)
    prof = CPUProfiler(handle.circuit)
    h.push_batch(Batch((jnp.arange(8, dtype=jnp.int64),),
                       (jnp.ones(8, dtype=jnp.int64),),
                       jnp.ones(8, dtype=jnp.int64)))
    handle.step()
    host_report = opprofile.check_report(prof.profile_report())
    assert host_report["mode"] == "host"
    assert compiled_report["mode"] == "compiled"
    for report in (host_report, compiled_report):
        for row in report["operators"]:
            assert set(opprofile.ROW_KEYS) <= set(row)
    # graph fallback (sharded circuits) speaks the same schema as well
    opprofile.check_report(opprofile.graph_profile(_ch))
    assert opprofile.graph_profile(_ch)["attribution"] == "graph"


def test_http_profile_roundtrip_host_and_compiled():
    """/profile over HTTP on BOTH engines from one hand-built circuit:
    host = the continuous CPUProfiler report; compiled = static (free)
    and measured (?ticks=N, quiesced + rewound), plus the dot render and
    the gated node metrics appearing in /metrics only after measuring."""
    from dbsp_tpu.compiled.driver import try_compiled_driver
    from dbsp_tpu.io import Catalog, CircuitServer
    from dbsp_tpu.io.controller import Controller, ControllerConfig
    from dbsp_tpu.obs import PipelineObs
    from dbsp_tpu.operators import Count
    from dbsp_tpu.profile import CompiledProfiler, CPUProfiler

    def build(c):
        s, h = add_input_zset(c, [jnp.int64], [jnp.int64])
        return h, s.aggregate(Count()).integrate().output()

    reports = {}
    for want_mode in ("host", "compiled"):
        handle, (h, out) = Runtime.init_circuit(1, build)
        catalog = Catalog()
        catalog.register_input("events", h, (jnp.int64, jnp.int64))
        catalog.register_output("counts", out, (jnp.int64, jnp.int64))
        obs = PipelineObs(name=f"opprof-{want_mode}")
        if want_mode == "compiled":
            driver = try_compiled_driver(handle, registry=obs.registry)
            assert driver is not None
            profiler = CompiledProfiler(driver)
            obs.attach_compiled(driver)
        else:
            driver = handle
            profiler = CPUProfiler(handle.circuit)
            obs.attach_circuit(handle.circuit)
        ctl = Controller(driver, catalog,
                         ControllerConfig(min_batch_records=1))
        server = CircuitServer(ctl, profiler=profiler, obs=obs)
        server.start()
        base = f"http://127.0.0.1:{server.port}"

        def get(path):
            with urllib.request.urlopen(base + path, timeout=120) as r:
                return r.read()

        ctl.push("events", [((7, 1), 1), ((7, 2), 1), ((8, 5), 1)])
        ctl.step()
        metrics_before = get("/metrics").decode()
        assert "dbsp_tpu_compiled_node_seconds" not in metrics_before

        report = opprofile.check_report(json.loads(get("/profile")))
        assert report["mode"] == want_mode
        reports[want_mode] = report
        dot = get("/profile?format=dot").decode()
        assert dot.startswith("digraph")
        if want_mode == "compiled":
            assert report["attribution"] == "static"
            measured = opprofile.check_report(
                json.loads(get("/profile?ticks=2")))
            assert measured["measured"]["bit_identical"]
            # nothing retained at serve cadence 1: the profiled ticks ran
            # empty and the report must say so
            assert measured["measured"]["idle_inputs"] is True
            # gated per-node families exist ONLY now
            metrics_after = get("/metrics").decode()
            assert "dbsp_tpu_compiled_node_seconds" in metrics_after
            # profiled ticks landed operator slices in the /trace window
            trace = json.loads(get("/trace"))
            names = {e.get("name", "") for e in trace["traceEvents"]}
            assert any(n.startswith("profile_tick") for n in names)
            # serving continues after the rewind
            ctl.push("events", [((8, 6), 1)])
            ctl.step()
            st = ctl.stats()
            assert st["steps"] == 2
        ctl.stop()
        server.stop()
    # the two modes emitted the same row shape
    host_keys = set(reports["host"]["operators"][0])
    compiled_keys = set(reports["compiled"]["operators"][0])
    assert set(opprofile.ROW_KEYS) <= host_keys & compiled_keys


def test_metrics_gating_and_top_n_cap(q4_profiled, monkeypatch):
    """Per-node families: absent until a measured profile exports them;
    top-N capped with the tail aggregated as node="other"."""
    ch, warm, report = q4_profiled
    reg = MetricsRegistry()
    assert reg.get("dbsp_tpu_compiled_node_seconds") is None
    monkeypatch.setenv("DBSP_TPU_PROFILE_TOP_N", "3")
    opprofile.export_node_metrics(reg, report)
    sec = reg.get("dbsp_tpu_compiled_node_seconds")
    assert sec is not None
    keys = {k for k, _ in sec.samples()}
    assert len(keys) <= 4  # 3 named + the "other" aggregate
    assert ("other", "other") in keys
    rows = reg.get("dbsp_tpu_compiled_node_rows_total")
    assert rows is not None and len({k for k, _ in rows.samples()}) <= 4
    # the gauge is "the LAST run": a re-export whose top-N no longer
    # contains a node must drop that node's child, not serve stale
    # seconds next to the fresh series
    shrunk = dict(report, operators=report["operators"][:1])
    opprofile.export_node_metrics(reg, shrunk)
    assert len({k for k, _ in sec.samples()}) == 1
    # ...while the counter keeps its cumulative children by contract
    assert len({k for k, _ in rows.samples()}) >= 1


def test_slow_node_attribution():
    """Seeded hot spot: a map whose kernel burns ~100x the work of its
    neighbors must top the measured attribution — the report points at
    the RIGHT operator, not merely at 'somewhere'."""

    def hot(k, v):
        x = v[0].astype(jnp.float32)
        for _ in range(300):
            x = jnp.sin(x) * 1.0001
        return k, (x.astype(jnp.int64) + v[0],)

    def build(c):
        s, h = add_input_zset(c, [jnp.int64], [jnp.int64])
        cold = s.map_rows(lambda k, v: (k, (v[0] + 1,)),
                          [jnp.int64], [jnp.int64], name="cold")
        hot_s = cold.map_rows(hot, [jnp.int64], [jnp.int64], name="hot")
        return h, hot_s.integrate().output()

    handle, (h, _out) = Runtime.init_circuit(1, build)
    ch = compile_circuit(handle)

    def feed(i):
        n = 4096
        keys = jnp.arange(n, dtype=jnp.int64) + i
        return {h: Batch((keys,), (keys % 97,),
                         jnp.ones(n, dtype=jnp.int64))}

    ch.step(tick=0, feeds=feed(0))
    # heterogeneous feed presence (tick 2 is empty): each distinct
    # pattern warms its own segments outside the measured walls, and the
    # mixed run must still match the fused program bit for bit
    report = opprofile.check_report(
        opprofile.measured_profile(ch, n=3, t0=1,
                                   feeds_list=[feed(1), {}, feed(3)]))
    assert report["measured"]["bit_identical"]
    assert report["measured"]["idle_inputs"] is False
    top = report["operators"][0]
    assert top["name"] == "hot", [
        (r["name"], r["total_ms"]) for r in report["operators"]]
    assert top["rows_in"] > 0 and top["rows_out"] > 0


def test_check_metrics_rule4_seeded(tmp_path):
    """The cardinality gate: a per-node family registered outside
    obs/opprofile.py is a violation; `# metrics: ok` waives it; the gate
    module itself is allowed."""
    from tools.check_metrics import check_tree

    pkg = tmp_path / "dbsp_tpu"
    (pkg / "obs").mkdir(parents=True)
    bad = ('def f(reg):\n'
           '    reg.gauge("dbsp_tpu_compiled_node_seconds", "x",\n'
           '              labels=("node", "kind"))\n')
    (pkg / "rogue.py").write_text(bad)
    violations = check_tree(str(pkg))
    assert any("opprofile.py gate" in v for v in violations), violations

    (pkg / "rogue.py").write_text(bad.replace(
        '"x",', '"x",  # metrics: ok'))
    assert not any("opprofile.py gate" in v
                   for v in check_tree(str(pkg)))

    (pkg / "rogue.py").unlink()
    (pkg / "obs" / "opprofile.py").write_text(bad)
    assert not any("opprofile.py gate" in v
                   for v in check_tree(str(pkg)))


def test_lint_fronts_green():
    """The static lint fronts this PR added stay green on the committed
    tree: METRICS.md matches the registration sites, the dashboard's
    exprs reference metrics that exist."""
    from tools.lint_all import run_check_dashboard, run_gen_metrics_doc

    assert run_gen_metrics_doc() == []
    assert run_check_dashboard() == []


def test_committed_profile_artifact():
    """PROFILE_q4.json (tools/roofline.py --per-node) is the acceptance
    artifact: schema-valid, bit-identical, >= 90% of segmented tick time
    attributed to named circuit nodes."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "PROFILE_q4.json")) as f:
        report = opprofile.check_report(json.load(f))
    m = report["measured"]
    assert m["bit_identical"]
    assert m["attributed_fraction"] >= 0.9


def test_report_dot_and_bench_summary(q4_profiled):
    _ch, _warm, report = q4_profiled
    dot = opprofile.report_dot(report)
    assert dot.startswith("digraph")
    # every operator row renders, edges come from the graph metadata
    assert dot.count("[label=") == len(report["operators"])
    assert "->" in dot
    s = opprofile.summarize_for_bench(report, top=3)
    assert s["bit_identical"] and len(s["top_operators"]) == 3
    assert s["segmentation_overhead"] == \
        report["measured"]["segmentation_overhead"]
