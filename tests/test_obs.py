"""Unified observability subsystem (dbsp_tpu.obs): registry primitives,
Prometheus exposition round-trip, Chrome-trace spans, host/compiled/manager
instrumentation, the exactly-once on_validated fix, the compiled-fallback
counter, the sharded spine-budget semantics, and the metrics naming lint.

ISSUE 1 acceptance: a single GET /metrics on a running manager pipeline
returns per-operator eval-latency histogram buckets, spine residency
gauges, exchange row counters, and step-latency quantile summaries; /trace
returns perfetto-loadable Chrome-trace JSON with balanced spans.
"""

import json
import re

import pytest
import jax.numpy as jnp

from dbsp_tpu.obs import (CircuitInstrumentation, MetricNameError,
                          MetricsRegistry, PipelineObs, SpanRecorder,
                          legacy_controller_lines, prometheus_text,
                          prometheus_text_many, validate_metric_name)

# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------


def test_counter_inc_labels_and_monotonicity():
    r = MetricsRegistry()
    c = r.counter("dbsp_tpu_io_steps_total", "steps")
    c.inc()
    c.inc(4)
    assert c.value == 5
    lc = r.counter("dbsp_tpu_io_input_records_total", "rows",
                   labels=("endpoint",))
    lc.labels(endpoint="a").inc(3)
    lc.labels(endpoint="b").inc(7)
    assert r.value("dbsp_tpu_io_input_records_total", endpoint="a") == 3
    assert r.value("dbsp_tpu_io_input_records_total", endpoint="b") == 7
    with pytest.raises(ValueError):
        c.inc(-1)
    # collector mirror API never regresses
    c.set_total(3)
    assert c.value == 5
    c.set_total(9)
    assert c.value == 9
    # get-or-create returns the same object; a type change is an error
    assert r.counter("dbsp_tpu_io_steps_total") is c
    with pytest.raises(ValueError):
        r.gauge("dbsp_tpu_io_steps_total")


def test_gauge_set_inc_dec():
    r = MetricsRegistry()
    g = r.gauge("dbsp_tpu_trace_level_count", "levels", labels=("node",))
    g.labels(node="3").set(5)
    g.labels(node="3").inc()
    g.labels(node="3").dec(2)
    assert r.value("dbsp_tpu_trace_level_count", node="3") == 4


def test_histogram_buckets_count_sum_quantile():
    r = MetricsRegistry()
    h = r.histogram("dbsp_tpu_circuit_operator_eval_seconds", "lat",
                    buckets=(0.001, 0.01, 0.1, 1.0))
    for v in (0.0005, 0.005, 0.005, 0.05, 2.0):
        h.observe(v)
    child = h._default
    assert child.count == 5
    assert child.buckets == [1, 2, 1, 0, 1]  # last = +Inf overflow
    assert abs(child.sum - 2.0605) < 1e-9
    q50 = h.quantile(0.5)
    assert 0.001 <= q50 <= 0.01  # the two 5ms observations
    text = prometheus_text(r)
    # cumulative buckets + +Inf == count
    assert re.search(r'_bucket\{le="0\.001"\} 1\b', text)
    assert re.search(r'_bucket\{le="\+Inf"\} 5\b', text)
    assert "dbsp_tpu_circuit_operator_eval_seconds_count 5" in text
    assert "# TYPE dbsp_tpu_circuit_operator_eval_seconds histogram" in text


def test_summary_quantile_exposition():
    r = MetricsRegistry()
    s = r.summary("dbsp_tpu_circuit_step_seconds", "step lat")
    for v in (0.001, 0.002, 0.004, 0.1):
        s.observe(v)
    text = prometheus_text(r)
    assert "# TYPE dbsp_tpu_circuit_step_seconds summary" in text
    for q in ("0.5", "0.95", "0.99"):
        assert f'dbsp_tpu_circuit_step_seconds{{quantile="{q}"}}' in text
    assert "dbsp_tpu_circuit_step_seconds_count 4" in text


def test_summary_empty_child_scrape_does_not_crash():
    """labels() creates a child with zero observations; its quantiles are
    NaN and must render as 'NaN', not raise mid-scrape."""
    r = MetricsRegistry()
    r.summary("dbsp_tpu_circuit_step_seconds", "lat",
              labels=("w",)).labels(w="0")
    text = prometheus_text(r)
    assert 'dbsp_tpu_circuit_step_seconds{w="0",quantile="0.5"} NaN' in text
    assert 'dbsp_tpu_circuit_step_seconds_count{w="0"} 0' in text


def test_metric_name_validation():
    validate_metric_name("dbsp_tpu_trace_device_resident_rows")
    validate_metric_name("dbsp_tpu_io_steps_total", "counter")
    for bad, kind in [
        ("steps_total", "counter"),              # missing prefix
        ("dbsp_tpu_steps", None),                # bad unit
        ("dbsp_tpu_io_steps", "counter"),        # counter without _total
        ("dbsp_tpu_io_latency_total", "summary"),  # _total non-counter
        ("dbsp_tpu_Io_steps_total", "counter"),  # uppercase
    ]:
        with pytest.raises(MetricNameError):
            validate_metric_name(bad, kind)
    r = MetricsRegistry()
    with pytest.raises(MetricNameError):
        r.counter("dbsp_tpu_bad_unit_frobs")
    with pytest.raises(MetricNameError):
        r.gauge("dbsp_tpu_trace_rows", labels=("Bad-Label",))


def test_prometheus_text_round_trip():
    """Parse the exposition back and recover every scalar sample."""
    r = MetricsRegistry()
    r.counter("dbsp_tpu_io_steps_total", "steps").inc(12)
    g = r.gauge("dbsp_tpu_trace_device_resident_rows", "rows",
                labels=("node",))
    g.labels(node="0.3").set(4096)
    g.labels(node="7").set(128)
    text = prometheus_text(r)
    samples = {}
    for line in text.splitlines():
        if line.startswith("#") or not line:
            continue
        m = re.match(r'^([a-z0-9_]+)(\{[^}]*\})? ([0-9.eE+-]+|\+Inf)$', line)
        assert m, f"unparsable exposition line: {line!r}"
        samples[(m.group(1), m.group(2) or "")] = float(m.group(3))
    assert samples[("dbsp_tpu_io_steps_total", "")] == 12
    assert samples[("dbsp_tpu_trace_device_resident_rows",
                    '{node="0.3"}')] == 4096
    assert samples[("dbsp_tpu_trace_device_resident_rows",
                    '{node="7"}')] == 128
    # headers present once per family
    assert text.count("# TYPE dbsp_tpu_trace_device_resident_rows gauge") == 1


def test_prometheus_text_many_merges_families():
    ra, rb = MetricsRegistry(), MetricsRegistry()
    ra.counter("dbsp_tpu_io_steps_total", "steps").inc(1)
    rb.counter("dbsp_tpu_io_steps_total", "steps").inc(2)
    text = prometheus_text_many([({"pipeline": "a"}, ra),
                                 ({"pipeline": "b"}, rb)])
    assert text.count("# TYPE dbsp_tpu_io_steps_total counter") == 1
    assert 'dbsp_tpu_io_steps_total{pipeline="a"} 1' in text
    assert 'dbsp_tpu_io_steps_total{pipeline="b"} 2' in text


def test_collector_runs_at_exposition():
    r = MetricsRegistry()
    g = r.gauge("dbsp_tpu_trace_level_count", "levels")
    state = {"levels": 3}
    r.register_collector(lambda: g.set(state["levels"]))
    assert "dbsp_tpu_trace_level_count 3" in prometheus_text(r)
    state["levels"] = 8
    assert "dbsp_tpu_trace_level_count 8" in prometheus_text(r)


def test_legacy_controller_lines():
    stats = {"steps": 4,
             "inputs": {"in1": {"total_records": 10, "total_bytes": 99,
                                "buffered_records": 2}},
             "outputs": {"out1": {"total_records": 7, "total_bytes": 50}}}
    lines = legacy_controller_lines(stats)
    assert "dbsp_steps 4" in lines
    assert 'dbsp_input_records{endpoint="in1"} 10' in lines
    assert 'dbsp_input_buffered{endpoint="in1"} 2' in lines
    assert 'dbsp_output_records{endpoint="out1"} 7' in lines


# ---------------------------------------------------------------------------
# span recorder
# ---------------------------------------------------------------------------


def _assert_balanced(events):
    stack = []
    for ev in events:
        if ev["ph"] == "B":
            stack.append(ev["name"])
        elif ev["ph"] == "E":
            assert stack, f"E without B: {ev}"
            assert stack.pop() == ev["name"], ev
    assert not stack, f"unclosed spans: {stack}"


def test_span_recorder_nesting_window_and_json():
    rec = SpanRecorder(max_steps=2)
    for t in range(4):
        with rec.span(f"step{t}", "step"):
            with rec.span("join[0.1]"):
                pass
            with rec.span("shard[0.2]", "exchange"):
                pass
    doc = json.loads(rec.to_json())  # valid JSON by construction
    evs = doc["traceEvents"]
    _assert_balanced(evs)
    # bounded window: only the last 2 steps retained
    names = {e["name"] for e in evs if e["ph"] == "B"}
    assert names == {"step2", "step3", "join[0.1]", "shard[0.2]"}
    assert rec.dropped_steps == 2
    assert doc["otherData"]["dropped_steps"] == 2
    cats = {e["name"]: e.get("cat") for e in evs if e["ph"] == "B"}
    assert cats["shard[0.2]"] == "exchange"
    # timestamps are microseconds, monotone within a step
    b = [e for e in evs if e["name"] == "step2"]
    assert b[0]["ts"] <= b[-1]["ts"]


def test_span_recorder_tolerates_unbalanced_end():
    rec = SpanRecorder()
    rec.end("phantom")  # attached mid-step: must not corrupt state
    with rec.span("step", "step"):
        pass
    _assert_balanced(rec.events())


# ---------------------------------------------------------------------------
# instrumentation: host circuit (no HTTP)
# ---------------------------------------------------------------------------


def _join_agg_build(c):
    from dbsp_tpu.operators import add_input_zset
    from dbsp_tpu.operators.aggregate import Max

    a, ha = add_input_zset(c, (jnp.int64,), (jnp.int64,))
    b, hb = add_input_zset(c, (jnp.int64,), (jnp.int64,))
    j = a.join_index(b, lambda k, av, bv: (av[0], (bv[0],)),
                     (jnp.int64,), (jnp.int64,))
    return (ha, hb), j.aggregate(Max(0)).integrate().output()


def test_circuit_instrumentation_host_path():
    from dbsp_tpu.circuit import Runtime

    handle, ((ha, hb), out) = Runtime.init_circuit(1, _join_agg_build)
    obs = PipelineObs(name="t")
    obs.attach_circuit(handle.circuit)
    for t in range(3):
        ha.extend([((t * 10 + i, i % 5), 1) for i in range(10)])
        hb.extend([((t * 10 + i, i % 3), 1) for i in range(10)])
        handle.step()
    assert obs.registry.value("dbsp_tpu_circuit_steps_total") == 3
    text = prometheus_text(obs.registry)
    assert "dbsp_tpu_circuit_operator_eval_seconds_bucket" in text
    assert 'operator="join"' in text
    assert 'dbsp_tpu_circuit_step_seconds{quantile="0.5"}' in text
    # spine gauges from the graph walk (join/aggregate build traces)
    assert "dbsp_tpu_trace_device_resident_rows{" in text
    assert "dbsp_tpu_trace_level_count{" in text
    hist = obs.registry.get("dbsp_tpu_circuit_operator_eval_seconds")
    assert all(c.count == 3 for _, c in hist.samples())
    # spans: balanced, step spans wrap operator spans
    evs = obs.spans.events()
    _assert_balanced(evs)
    assert sum(1 for e in evs if e["ph"] == "B" and e["name"] == "step") == 3
    assert any(e.get("cat") == "operator" for e in evs)
    json.loads(obs.spans.to_json())


def test_circuit_instrumentation_sharded_exchange_counters():
    from dbsp_tpu.circuit import Runtime

    handle, ((ha, hb), out) = Runtime.init_circuit(2, _join_agg_build)
    obs = PipelineObs(name="t2")
    obs.attach_circuit(handle.circuit)
    ha.extend([((i, i % 7), 1) for i in range(50)])
    hb.extend([((i, (i * 3) % 11), 1) for i in range(50)])
    handle.step()
    text = prometheus_text(obs.registry)
    rows = {m.group(1): float(m.group(2)) for m in re.finditer(
        r'dbsp_tpu_exchange_rows_total\{node="([^"]+)"\} ([0-9.]+)', text)}
    assert rows and any(v > 0 for v in rows.values()), text
    assert "dbsp_tpu_exchange_bytes_total{" in text


# ---------------------------------------------------------------------------
# compiled path: exactly-once on_validated + overflow counter
# ---------------------------------------------------------------------------


def test_run_ticks_on_validated_exactly_once_across_replay():
    """ADVICE #5: with snapshot_every > 1, an overflow replay re-runs
    validated intervals; on_validated must NOT re-fire for ticks already
    reported (accumulating callbacks would double-count)."""
    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.compiled import compile_circuit
    from dbsp_tpu.zset.batch import Batch

    def build(c):
        from dbsp_tpu.operators import add_input_zset

        s, h = add_input_zset(c, (jnp.int64,), ())
        return h, s.distinct().integrate().output()

    handle, (h, out) = Runtime.init_circuit(1, build)
    C = 512  # rows per tick: trace level 0 (init cap < 12*C) must overflow

    def gen_fn(tick):
        keys = tick * C + jnp.arange(C, dtype=jnp.int64)
        return {h: Batch((keys,), (),
                         jnp.ones((C,), jnp.int64))}

    ch = compile_circuit(handle, gen_fn=gen_fn)
    reported = []
    ch.run_ticks(0, 12, validate_every=1, snapshot_every=4,
                 on_validated=reported.append)
    assert ch.overflow_replays >= 1, "test vacuous: no overflow happened"
    assert reported == sorted(set(reported)), reported
    assert reported[-1] == 12
    # every validated interval reported exactly once despite the replays
    assert reported == list(range(1, 13))


def test_try_compiled_driver_catches_any_compile_failure(monkeypatch):
    """ADVICE #1: AssertionError (or anything) raised while building the
    compiled driver must fall back to host mode — counted with a reason."""
    from dbsp_tpu.compiled import driver as driver_mod

    def boom(self, handle, compiled=None):
        raise AssertionError("compiled z^-1 supports Batch-valued only")

    monkeypatch.setattr(driver_mod.CompiledCircuitDriver, "__init__", boom)
    reg = MetricsRegistry()
    assert driver_mod.try_compiled_driver(object(), registry=reg) is None
    assert reg.value("dbsp_tpu_compiled_fallback_total",
                     reason="AssertionError") == 1

    def boom2(self, handle, compiled=None):
        raise NotImplementedError("no compiled equivalent")

    monkeypatch.setattr(driver_mod.CompiledCircuitDriver, "__init__", boom2)
    assert driver_mod.try_compiled_driver(object(), registry=reg) is None
    assert reg.value("dbsp_tpu_compiled_fallback_total",
                     reason="NotImplementedError") == 1
    # no registry attached: still falls back silently
    assert driver_mod.try_compiled_driver(object()) is None


# ---------------------------------------------------------------------------
# spine budget vs residency gauge agreement (ADVICE #2)
# ---------------------------------------------------------------------------


def test_spine_budget_counts_sharded_batches():
    """Sharded batches count toward the enforced resident total (and the
    gauge), but only unsharded levels are offload candidates."""
    from dbsp_tpu.trace.spine import Spine, _is_cold
    from dbsp_tpu.zset.batch import Batch

    s = Spine((jnp.int64,), (), device_budget_rows=1024)
    sharded = Batch.empty((jnp.int64,), (), cap=1024, lead=(2,))
    unsharded = Batch.from_tuples([((i,), 1) for i in range(512)],
                                  (jnp.int64,))
    assert sharded.sharded and not unsharded.sharded
    s.batches = [sharded, unsharded]
    assert s.device_resident_rows() == 1024 + unsharded.cap
    s._enforce_budget()
    # the sharded level alone saturates the budget -> the unsharded level
    # was offloaded; the gauge and the enforcement agree on what's resident
    kinds = [(b.sharded, _is_cold(b)) for b in s.batches]
    assert (True, False) in kinds  # sharded stays on device
    assert (False, True) in kinds  # unsharded went cold
    assert s.device_resident_rows() == 1024
    assert s.host_offloaded_rows() == unsharded.cap


# ---------------------------------------------------------------------------
# watermark lag semantics (the gauge must carry signal, not equal lateness)
# ---------------------------------------------------------------------------


def test_watermark_lag_tracks_out_of_order_arrival():
    """frontier - latest_batch_max: 0 for in-order data, >0 when a batch
    arrives event-time-late. (frontier - watermark would be identically
    the configured lateness — no signal.)"""
    from dbsp_tpu.timeseries.watermark import WatermarkMonotonic
    from dbsp_tpu.zset.batch import Batch

    op = WatermarkMonotonic(lambda k, v: k[0], lateness=5)
    op.eval(Batch.from_tuples([((100,), 1)], (jnp.int64,)))
    md = op.metadata()
    assert md["max_event_time"] == 100 and md["last_batch_max"] == 100
    op.eval(Batch.from_tuples([((40,), 1)], (jnp.int64,)))  # late batch
    md = op.metadata()
    assert md["watermark"] == 95          # never regresses
    assert md["max_event_time"] == 100    # frontier holds
    assert md["last_batch_max"] == 40     # lag gauge reads 60
    # restored checkpoints have no last batch: collector must skip the lag
    op.load_state_dict(op.state_dict())
    assert op.metadata()["last_batch_max"] is None


# ---------------------------------------------------------------------------
# end-to-end: manager pipeline scrape (ISSUE acceptance)
# ---------------------------------------------------------------------------

TABLES = {
    "bids": {"columns": ["auction", "bidder", "price"],
             "dtypes": ["int64", "int64", "int64"], "key_columns": 1},
    "auctions": {"columns": ["id", "category"],
                 "dtypes": ["int64", "int64"], "key_columns": 1},
}
SQL = {"cat_stats":
       "SELECT auctions.category, COUNT(*) AS n, MAX(bids.price) AS hi "
       "FROM bids JOIN auctions ON bids.auction = auctions.id "
       "GROUP BY auctions.category"}


@pytest.fixture()
def manager():
    from dbsp_tpu.manager import PipelineManager

    m = PipelineManager()
    m.start()
    yield m
    m.stop()


def _feed(pipe):
    pipe.push("auctions", [[1, 7], [2, 9], [3, 9]])
    pipe.push("bids", [[1, 10, 100], [2, 11, 250], [3, 12, 50]])
    pipe.step()
    pipe.step()


def test_manager_metrics_scrape_host_mode(manager, monkeypatch):
    """One GET /metrics answers: operator latency histograms, spine
    residency, exchange counters (sharded deploy), step quantiles, IO
    counters, legacy names — and /trace is perfetto-loadable."""
    from dbsp_tpu.client import Connection

    monkeypatch.setenv("DBSP_TPU_MANAGER_COMPILED", "0")
    conn = Connection(port=manager.port)
    conn.create_program("prog", TABLES, SQL)
    pipe = conn.start_pipeline("p1", "prog", config={"workers": 2})
    assert [p for p in conn.pipelines()
            if p["name"] == "p1"][0]["mode"] == "host"
    _feed(pipe)
    assert pipe.read("cat_stats") == {(7, 1, 100): 1, (9, 2, 250): 1}
    text = pipe.metrics()
    assert re.search(
        r'dbsp_tpu_circuit_operator_eval_seconds_bucket\{[^}]*le="', text)
    assert "dbsp_tpu_trace_device_resident_rows{" in text
    rows = [float(m) for m in re.findall(
        r'dbsp_tpu_exchange_rows_total\{[^}]*\} ([0-9.]+)', text)]
    assert rows and any(v > 0 for v in rows)
    assert 'dbsp_tpu_circuit_step_seconds{quantile="0.5"}' in text
    assert "dbsp_tpu_io_pushed_records_total 6" in text
    steps = re.search(r"dbsp_tpu_io_steps_total (\d+)", text)
    assert steps and int(steps.group(1)) >= 2
    # legacy surface intact (pre-registry scrapers)
    assert "dbsp_steps" in text
    # Chrome-trace export: valid JSON, balanced, nested operator spans
    doc = pipe.trace()
    evs = doc["traceEvents"]
    _assert_balanced(evs)
    assert any(e["ph"] == "B" and e["name"] == "step" for e in evs)
    assert any(e.get("cat") == "operator" for e in evs)
    # fleet-wide aggregate on the manager port
    fleet = conn.metrics()
    assert 'pipeline="p1"' in fleet
    assert "dbsp_tpu_circuit_operator_eval_seconds_bucket" in fleet
    assert fleet.count(
        "# TYPE dbsp_tpu_circuit_steps_total counter") == 1


def test_manager_metrics_scrape_compiled_mode(manager):
    from dbsp_tpu.client import Connection

    conn = Connection(port=manager.port)
    conn.create_program("prog", TABLES, SQL)
    pipe = conn.start_pipeline("pc", "prog")
    assert [p for p in conn.pipelines()
            if p["name"] == "pc"][0]["mode"] == "compiled"
    _feed(pipe)
    text = pipe.metrics()
    ticks = re.search(r"dbsp_tpu_compiled_ticks_total (\d+)", text)
    assert ticks and int(ticks.group(1)) >= 2
    assert 'dbsp_tpu_compiled_tick_seconds{quantile="0.5"}' in text
    assert "dbsp_tpu_trace_device_resident_rows{" in text
    assert "dbsp_tpu_compiled_overflow_replays_total" in text
    doc = pipe.trace()
    evs = doc["traceEvents"]
    _assert_balanced(evs)
    assert any(e["ph"] == "B" and e["name"] == "tick"
               and isinstance(e["args"]["tick"], int) for e in evs)
    assert any(e["ph"] == "B" and e["name"] == "tick.dispatch"
               for e in evs)


# ---------------------------------------------------------------------------
# metrics lint (tools/check_metrics.py) as a tier-1 gate
# ---------------------------------------------------------------------------


def test_metrics_lint_tree_is_clean():
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))
    from tools.check_metrics import check_tree

    root = os.path.join(os.path.dirname(__file__), os.pardir, "dbsp_tpu")
    assert check_tree(os.path.abspath(root)) == []


def test_metrics_lint_catches_violations(tmp_path):
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))
    from tools.check_metrics import check_tree

    bad = tmp_path / "pkg"
    bad.mkdir()
    (bad / "rogue.py").write_text(
        'TEXT = "# TYPE my_metric counter"\n'
        'LINE = f\'dbsp_steps{{endpoint="{0}"}} 1\'\n'
        'NAME = "dbsp_tpu_foo_frobs"\n'
        'reg.counter("dbsp_tpu_io_records")\n'
        'reg.gauge("dbsp_tpu_trace_level_count", "x", labels=("tick_id",))\n')
    got = check_tree(str(bad))
    # line 1 (# TYPE header), line 2 (f-string label rendering — the ast
    # constant holds ONE brace after {{ unescaping), line 3 (bad unit),
    # line 4 twice (counter-kind _total rule + bare-literal unit rule),
    # line 5 (label name outside the closed allowlist — cardinality lint)
    assert len(got) == 6, got
    assert sum("exposition formatting" in v for v in got) == 2
    assert any("unit suffix" in v for v in got)
    assert any("_total" in v for v in got)
    assert any("allowlist" in v for v in got)


def test_metrics_lint_label_allowlist_positional(tmp_path):
    """The cardinality lint also sees positional labels args, and
    allowlisted labels pass."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))
    from tools.check_metrics import check_tree

    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "ok.py").write_text(
        'reg.counter("dbsp_tpu_slo_breaches_total", "x", ("slo",))\n')
    assert check_tree(str(pkg)) == []
    (pkg / "bad.py").write_text(
        'reg.counter("dbsp_tpu_io_rows_total", "x", ("row_key",))\n')
    got = check_tree(str(pkg))
    assert len(got) == 1 and "allowlist" in got[0], got
