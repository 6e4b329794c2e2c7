"""The phase spans of the served path, the scopes inside its programs and
the benchmark's readers over them (ISSUE 28).

A served q4 pipeline at a tiny tick, behind ``CircuitServer`` over HTTP as
``chip_smoke.run_served`` drives it, records into a ring of its own; the
recorder's repairs and the ``program_span`` readers are checked on events
written by hand. Every test runs under a time limit of its own.
"""

import glob
import importlib.util
import json
import os
import re
import signal
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_ROOT, "benchmark")
for _p in (_ROOT, _BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import chip_smoke  # noqa: E402
from tools import trace_scopes  # noqa: E402
import measures  # noqa: E402 — benchmark/measures.py
import span_measures as sm  # noqa: E402 — benchmark/span_measures.py
from dbsp_tpu.obs.tracing import SpanRecorder, default_recorder  # noqa: E402

LIMIT_S = 240

TICK_PHASES = ("tick.drain_endpoints", "tick.build_inputs", "tick.snapshot",
               "tick.dispatch", "tick.validate", "tick.maintain",
               "tick.deliver", "tick.emit_outputs", "tick.publish")


@pytest.fixture(autouse=True)
def _time_limit():
    def on_alarm(*_):
        raise TimeoutError(f"test ran over {LIMIT_S} s")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(LIMIT_S)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


class _Served:
    """q4 behind CircuitServer, stepped only by POST /step."""

    def __init__(self):
        import dbsp_tpu  # noqa: F401
        from dbsp_tpu.circuit import Runtime
        from dbsp_tpu.compiled.driver import CompiledCircuitDriver
        from dbsp_tpu.io import Catalog
        from dbsp_tpu.io.controller import Controller, ControllerConfig
        from dbsp_tpu.io.server import CircuitServer
        from dbsp_tpu.nexmark import (GeneratorConfig, NexmarkGenerator,
                                      build_inputs, model as M, queries)

        def build(c):
            streams, handles = build_inputs(c)
            return handles, queries.q4(*streams).output()

        handle, (handles, out) = Runtime.init_circuit(1, build)
        self.driver = CompiledCircuitDriver(handle, validate_every=1)
        catalog = Catalog()
        for name, h, dts in (
                ("persons", handles[0], M.PERSON_KEY + M.PERSON_VALS),
                ("auctions", handles[1], M.AUCTION_KEY + M.AUCTION_VALS),
                ("bids", handles[2], M.BID_KEY + M.BID_VALS)):
            catalog.register_input(name, h, dts)
        catalog.register_output("q4", out, (jnp.int64, jnp.int64))
        self.ctl = Controller(self.driver, catalog, ControllerConfig(
            min_batch_records=10 ** 9, flush_interval_s=3600.0))
        self.srv = CircuitServer(self.ctl)
        # what the served path records into when handed nothing
        self.defaults = (self.driver.spans, self.ctl.spans, self.srv.spans)
        self.rec = SpanRecorder(max_steps=64)
        self.driver.spans = self.ctl.spans = self.srv.spans = self.rec
        self.srv.start()
        self.base = f"http://127.0.0.1:{self.srv.port}"
        self.gen = NexmarkGenerator(GeneratorConfig(seed=11))
        self.sent = 0

    def tick(self, events: int) -> list:
        """Push one batch (three POSTs) and step; the pushes' trace ids."""
        cols = self.gen.generate(self.sent, self.sent + events)
        self.sent += events
        ids = []
        for name, keys in (
                ("persons", ("id", "name", "city", "state", "email",
                             "date_time")),
                ("auctions", ("id", "item", "seller", "category",
                              "initial_bid", "reserve", "date_time",
                              "expires")),
                ("bids", ("auction", "bidder", "price", "channel",
                          "date_time"))):
            body = chip_smoke._ndjson([cols[name][k] for k in keys])
            r = chip_smoke._http(
                f"{self.base}/input_endpoint/{name}?format=json", data=body)
            ids.append(r["trace"])
        chip_smoke._http(self.base + "/step", data=b"")
        # a handler's span ends after its response is written: wait for
        # the server's threads to close theirs
        deadline = time.monotonic() + 10.0
        while self.rec.open_threads() and time.monotonic() < deadline:
            time.sleep(0.001)
        assert self.rec.open_threads() == 0
        return ids

    def close(self):
        self.srv.stop()
        self.ctl.stop()


@pytest.fixture(scope="module")
def served():
    s = _Served()
    s.ids = [s.tick(200), s.tick(200), s.tick(1600)]  # the third overflows
    chip_smoke._http(s.base + "/view/q4?limit=10")
    yield s
    s.close()


def _spans(rec):
    return sm.closed_spans(rec.events())


def _ticks(rec):
    return {s.args["tick"]: s for s in _spans(rec) if s.name == "tick"}


# -- the served tick ---------------------------------------------------------

def test_served_path_records_into_the_process_ring_by_default(served):
    assert all(r is default_recorder() for r in served.defaults)


def test_a_served_tick_is_one_span_with_every_phase_nested(served):
    events = served.rec.events()
    stack = []  # balanced, per thread, and names fixed strings
    for ev in events:
        assert "[" not in ev["name"]
        if ev["ph"] == "B":
            stack.append((ev["tid"], ev["name"]))
        elif ev["ph"] == "E":
            assert stack.pop() == (ev["tid"], ev["name"])
    assert not stack
    ticks = _ticks(served.rec)
    assert sorted(ticks) == [0, 1, 2]
    tick = ticks[0]
    assert tick.parent.name == "step_request"
    assert [c.name for c in tick.parent.children] == ["step.lock_wait",
                                                       "tick"]
    names = [c.name for c in tick.children]
    for phase in TICK_PHASES:
        assert phase in names, phase
    assert names.count("tick.build_inputs") == 3
    assert [c.args["table"] for c in tick.children
            if c.name == "tick.build_inputs"] == ["persons", "auctions",
                                                   "bids"]
    # opened before the drain, and every phase lies inside it
    assert names[0] == "tick.drain_endpoints"
    assert all(tick.t0 <= c.t0 and c.t1 <= tick.t1 for c in tick.children)
    validate = next(c for c in tick.children if c.name == "tick.validate")
    assert "tick.device_wait" in [c.name for c in validate.children]
    assert tick.args["rows_in"] == 200
    maintain = next(c for c in tick.children if c.name == "tick.maintain")
    assert {"drains", "rows_moved"} <= set(maintain.args)
    dispatch = next(c for c in tick.children if c.name == "tick.dispatch")
    assert dispatch.args["retraced"] is True


def test_build_inputs_spans_say_the_one_program_built_each_table(served):
    for k, tick in _ticks(served.rec).items():
        built = [c for c in tick.children if c.name == "tick.build_inputs"]
        assert [c.args["path"] for c in built] == ["host_block"] * 3, k
        assert sum(c.args["rows"] for c in built) == tick.args["rows_in"]
        assert all(c.args["rows"] > 0 for c in built)


def test_a_tick_fed_by_push_batch_says_device():
    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.compiled.driver import CompiledCircuitDriver
    from dbsp_tpu.operators import add_input_zset
    from dbsp_tpu.zset.batch import Batch, ColumnBlock

    def build(c):
        s, h = add_input_zset(c, [jnp.int64], [jnp.int32])
        return h, s.output()

    handle, (h, out) = Runtime.init_circuit(1, build)
    driver = CompiledCircuitDriver(handle, validate_every=1)
    driver.spans = rec = SpanRecorder(max_steps=64)
    rows = [((k, k % 3), 1) for k in range(6)]
    h.push_batch(Batch.from_tuples(rows, [jnp.int64], [jnp.int32]))
    driver.step()
    h.extend(ColumnBlock.from_rows(rows, (jnp.int64, jnp.int32)))
    h.push((9, 9), 1)
    driver.step()
    assert out.to_dict() == {**dict(rows), (9, 9): 1}
    assert [(s.args["path"], s.args["rows"]) for s in _spans(rec)
            if s.name == "tick.build_inputs"] == [("device", 8), ("mixed", 7)]


def test_tick_args_hold_the_trace_ids_of_the_ingests_that_caused_it(served):
    ticks = _ticks(served.rec)
    ingests = {s.args["trace"]: s for s in _spans(served.rec)
               if s.name == "ingest"}
    for k, ids in enumerate(served.ids):
        assert ticks[k].args["batches"] == ids
        for i, table in zip(ids, ("persons", "auctions", "bids")):
            push = ingests[i]
            assert push.args["table"] == table
            assert push.args["records"] > 0 and push.args["bytes"] > 0
            assert [c.name for c in push.children] == [
                "ingest.read_body", "ingest.parse", "ingest.push_rows"]
            # (the span itself may end after the tick began: its thread
            # waits for the interpreter lock once the response is written)
            assert push.children[-1].t1 <= ticks[k].t0


def test_what_no_phase_names_is_under_five_percent_of_the_request(served):
    tick = _ticks(served.rec)[0]
    req = tick.parent
    assert (req.self_seconds + tick.self_seconds) / req.seconds < 0.05


def test_host_overhead_is_fed_from_the_spans_own_clock_readings(served):
    overhead = served.driver.ch.host_overhead_ns
    ticks = _ticks(served.rec)
    for phase in ("snapshot", "validate", "maintain"):
        spans = [next(c for c in ticks[k].children
                      if c.name == "tick." + phase) for k in sorted(ticks)]
        assert len(overhead[phase]) == len(spans)
        for ns, span in zip(overhead[phase], spans):
            assert abs(ns / 1e9 - span.seconds) < 2e-6  # us rounding only


def test_a_forced_overflow_shows_grow_and_replay(served):
    assert served.driver.ch.overflow_replays >= 1
    grown = [t for t in _ticks(served.rec).values()
             if t.total("tick.grow") > 0]
    assert grown
    validate = next(c for c in grown[0].children
                    if c.name == "tick.validate")
    names = [c.name for c in validate.children]
    i = names.index("tick.grow")
    assert names[i - 1] == "tick.device_wait"
    assert names[i + 1] == "tick.replay"
    assert names[-1] == "tick.device_wait"  # the replay validated


def test_the_read_span_ends_after_the_response(served):
    read = [s for s in _spans(served.rec) if s.name == "read"][-1]
    assert [c.name for c in read.children] == ["read.query", "read.respond"]
    assert read.args["view"] == "q4" and read.args["rows"] > 0
    assert read.args["epoch"] >= 1


def test_trace_route_serves_the_ring_without_an_obs_bundle(served):
    doc = chip_smoke._http(served.base + "/trace")
    assert any(e.get("name") == "tick" for e in doc["traceEvents"])


# -- the recorder's repairs --------------------------------------------------

def test_2000_reads_evict_no_tick_and_leave_the_thread_maps_bounded():
    rec = SpanRecorder(max_steps=64)
    with rec.span("step_request", "step"):
        with rec.span("tick", "step", args={"tick": 7}):
            pass

    def read():
        with rec.span("read", "read"):
            with rec.span("read.query", "read"):
                pass

    for _ in range(40):  # one thread per connection, as the server has
        threads = [threading.Thread(target=read) for _ in range(50)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()
    names = [e["name"] for e in rec.events() if e["ph"] == "B"]
    assert names.count("tick") == 1 and names.count("read") == 64
    assert rec.dropped_steps == 2000 - 64
    assert rec.open_threads() == 0
    lanes = [e for e in rec.to_chrome_trace()["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"]
    assert len(lanes) <= 65


def test_spans_and_the_load_generators_clock_are_one_clock():
    # span_measures compares ring times with time.monotonic() stamps
    a, b = time.perf_counter(), time.monotonic()
    assert abs(a - b) < 1e-3
    rec = SpanRecorder()
    t0 = time.monotonic()
    with rec.span("x"):
        pass
    t1 = time.monotonic()
    (span,) = _spans(rec)
    assert t0 <= span.t0 <= span.t1 <= t1


def test_a_new_eager_shape_shows_a_compile_child_in_the_phase_that_asked():
    rec = SpanRecorder()
    with rec.span("tick", "step"):
        with rec.span("tick.build_inputs", "tick"):
            jax.jit(lambda x: x * 3 + 41)(jnp.arange(23)).block_until_ready()
        with rec.span("tick.snapshot", "tick"):
            pass
    tick = next(s for s in _spans(rec) if s.name == "tick")
    build, snap = tick.children
    compiles = [c for c in build.children if c.name == "compile"]
    assert compiles and not snap.children
    for c in compiles:
        assert build.t0 <= c.t0 <= c.t1 <= build.t1
        assert c.args["seconds"] > 0 and c.args["cache_hit"] in (True, False)
    # with no span open on the thread it is a top-level span of the
    # process's ring
    n0 = sum(1 for e in default_recorder().events()
             if e["name"] == "compile" and e["ph"] == "B")
    jax.jit(lambda x: x * 5 + 43)(jnp.arange(29)).block_until_ready()
    n1 = sum(1 for e in default_recorder().events()
             if e["name"] == "compile" and e["ph"] == "B")
    assert n1 > n0


def test_under_a_profiler_session_the_host_plane_holds_the_phases(
        served, tmp_path):
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        served.tick(200)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    marks = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("dbsp."):
                        marks.setdefault(e.name, []).append(e.duration_ns)
    tick = _ticks(served.rec)[3]
    for name in ("dbsp.step_request", "dbsp.tick", "dbsp.tick.maintain",
                 "dbsp.tick.dispatch", "dbsp.ingest.parse"):
        assert name in marks, sorted(marks)
    maintain = next(c for c in tick.children if c.name == "tick.maintain")
    (ns,) = marks["dbsp.tick.maintain"]
    assert abs(ns / 1e9 - maintain.seconds) < 1e-3
    # the tool's account of the same capture: every phase's own host time,
    # and one offset ties every span of the ring to its annotation
    out = trace_scopes.reduce(path)
    assert out["device_ops"] == 0  # a CPU capture has no TPU plane
    phases = out["host_phases"]
    assert phases["tick.maintain"]["spans"] == 1
    assert phases["tick.maintain"]["host_s"] == pytest.approx(
        maintain.self_seconds, abs=1e-3)
    assert phases["tick.build_inputs"]["spans"] == 3
    clocks = trace_scopes.tie_clocks(out["host_annotations"],
                                     served.rec.to_chrome_trace())
    assert clocks["spans"] >= 20 and clocks["unmatched"] == 0
    assert clocks["max_stray_ms"] < 1.0
    assert clocks["max_length_diff_ms"] < 1.0


def test_trace_scopes_counts_each_nanosecond_once():
    ops = [(0, 100, "jit(step_fn)/n6.CJoin/k.lex_probe/while"),
           (10, 40, "jit(step_fn)/n6.CJoin/k.lex_probe/while/body/add"),
           (100, 130, ""), (120, 130, "jit(f)/k.compact/gather"),
           (200, 260, "jit(_drain_pair)/maintain.drain/k.merge_sorted_cols")]
    top = trace_scopes.outermost(ops)
    assert [(s, e) for s, e, _ in top] == [(0, 100), (100, 130), (200, 260)]
    assert trace_scopes.NODE.search(ops[0][2]).group(1) == "n6.CJoin"
    assert trace_scopes.KERNEL.search(ops[0][2]).group(1) == "k.lex_probe"
    assert trace_scopes.NODE.search(ops[4][2]).group(1) == "maintain.drain"
    assert trace_scopes.NODE.search("jit(f)/k.compact/gather") is None
    pieces = trace_scopes.self_intervals(
        [("tick", 0, 100), ("tick.maintain", 10, 60), ("compile", 20, 30)])
    assert pieces == [("tick", 0, 10), ("tick.maintain", 10, 20),
                      ("compile", 20, 30), ("tick.maintain", 30, 60),
                      ("tick", 60, 100)]
    busy = [(0, 100), (100, 130), (200, 260)]
    starts = [s for s, _ in busy]
    assert trace_scopes.overlap(busy, starts, 50, 220) == 50 + 30 + 20
    assert trace_scopes.overlap(busy, starts, 140, 190) == 0


def test_chip_smoke_keeps_a_trace_of_its_last_ticks(tmp_path):
    lines = []
    summary = chip_smoke.run_served(
        ticks=3, events_per_tick=300, seed=5, emit=lines.append,
        profile_dir=str(tmp_path), profile_ticks=1)
    assert summary["ok"]
    out = trace_scopes.reduce(trace_scopes.find_xplane(str(tmp_path)))
    assert out["host_phases"]["tick"]["spans"] == 1  # the last tick only
    with open(os.path.join(str(tmp_path), "spans.json")) as f:
        ring = json.load(f)
    assert trace_scopes.tie_clocks(out["host_annotations"],
                                   ring)["max_stray_ms"] < 1.0


# -- scopes inside the programs ----------------------------------------------

def test_the_step_programs_text_holds_a_scope_for_every_node(served):
    from dbsp_tpu.compiled import cnodes

    ch = served.driver.ch
    hot, cold = ch._split_states()
    text = ch._make_step().lower(
        hot, ch._tick_operand(0), {}, cold).as_text(debug_info=True)
    scopes = set(re.findall(r"n\d+\.C\w+", text))
    for cn in ch.cnodes:
        if isinstance(cn, (cnodes.CInput, cnodes.COutput)):
            continue  # they lower to no operation
        assert f"n{cn.node.index}.{type(cn).__name__}" in scopes
    assert re.search(r"jit\(step_fn\)/n\d+\.C\w+/k\.consolidate_cols/", text)
    assert {"k.lex_probe", "k.compact", "k.expand_ranges",
            "k.merge_sorted_cols"} <= set(re.findall(r"k\.\w+", text))


def test_the_maintenance_drains_have_their_scope():
    from dbsp_tpu.compiled.compiler import _drain_pair, _drain_slice
    from dbsp_tpu.zset import Batch

    a = Batch.from_tuples([((1, 10), 1), ((2, 20), 1)],
                          [jnp.int64], [jnp.int64])
    b = Batch.from_tuples([((3, 30), 1)], [jnp.int64], [jnp.int64])
    text = _drain_pair.lower(a, b, 8).as_text(debug_info=True)
    assert "jit(_drain_pair)/maintain.drain/" in text
    assert "k.merge_sorted_cols/" in text  # inside the merge's own jit
    text = _drain_slice.lower(a, b, jnp.asarray(1, jnp.int32), 8).as_text(
        debug_info=True)
    assert "jit(_drain_slice)/maintain.drain/" in text


def test_the_chunked_sort_has_its_scope(accelerator_dispatch):
    # off the CPU sort_rows takes the chunked merge sort: its loops are what
    # a TPU trace shows as %while
    from dbsp_tpu.zset import kernels

    x = jnp.arange(5000, dtype=jnp.int64)[::-1]
    text = jax.jit(lambda c: kernels.sort_rows((c,), (c,))).lower(
        x).as_text(debug_info=True)
    assert "k.sort_rows/" in text and "while" in text


# -- the benchmark's readers over a recorded event list ----------------------

def _ev(ph, name, t_s, tid=1, **args):
    ev = {"name": name, "ph": ph, "ts": t_s * 1e6, "pid": 1, "tid": tid}
    if args:
        ev["args"] = args
    return ev


def _span(name, t0, t1, *children, tid=1, **args):
    """A span and its children as events, all on thread ``tid``."""
    events = [_ev("B", name, t0, **args),
              *(e for c in children for e in c), _ev("E", name, t1)]
    for e in events:
        e["tid"] = tid
    return events


def _recorded(ticks=(3, 4)):
    """Two window ticks written by hand. Tick k starts at 100 + 10 * (k -
    3); all its parts are whole tenths of a second."""
    events = []
    for k in ticks:
        t = 100.0 + 10.0 * (k - 3)
        for j, table in enumerate(("persons", "auctions", "bids")):
            p = t + 0.2 * j
            events += _span(
                "ingest", p, p + 0.2,
                _span("ingest.read_body", p, p + 0.05),
                _span("ingest.parse", p + 0.05, p + 0.15),
                _span("ingest.push_rows", p + 0.15, p + 0.19),
                tid=2, table=table, trace=f"b{k}.{j}")
        s = t + 1.0
        dispatch = 0.5 if k == 3 else 0.7
        events += _span(
            "step_request", s, s + 5.1,
            _span("step.lock_wait", s, s + 0.1),
            _span("tick", s + 0.1, s + 5.0,
                  _span("tick.drain_endpoints", s + 0.1, s + 0.2),
                  _span("tick.build_inputs", s + 0.2, s + 0.5,
                        _span("compile", s + 0.3, s + 0.4)),
                  _span("tick.build_inputs", s + 0.5, s + 0.9),
                  _span("tick.snapshot", s + 0.9, s + 1.2),
                  _span("tick.dispatch", s + 1.2, s + 1.2 + dispatch,
                        _span("compile", s + 1.25, s + 1.45)),
                  _span("tick.validate", s + 2.0, s + 3.0,
                        _span("tick.device_wait", s + 2.0, s + 2.9)),
                  _span("tick.maintain", s + 3.0, s + 4.0),
                  _span("tick.deliver", s + 4.0, s + 4.1),
                  _span("tick.emit_outputs", s + 4.1, s + 4.2),
                  _span("tick.publish", s + 4.2, s + 4.5),
                  tick=k, batches=[f"b{k}.{j}" for j in range(3)]))
    for i, ms in enumerate((2, 4, 6, 8)):  # four reads, one per second
        r = 102.0 + i
        events += _span("read", r, r + ms / 1e3,
                        _span("read.query", r, r + ms / 2e3),
                        _span("read.respond", r + ms / 2e3, r + ms / 1e3),
                        tid=3, view="q4")
    return sorted(events, key=lambda e: e["ts"])


def _run():
    return {
        "open": 99.5, "close": 116.2,
        "step_sent": {"3": 101.0, "4": 111.0},
        "step_done": {"3": 106.1, "4": 116.15},
        "push": {"3": [100.0, 100.6], "4": [110.0, 110.6]},
        "reads": [[102.0 + i - 0.001, 102.0 + i - 0.0005,
                   102.0 + i + 0.02, True] for i in range(4)],
        "visible": {}, "ops": [], "acked": {},
    }


def _ctx(monkeypatch, events):
    monkeypatch.setattr(sm, "recorder_events", lambda: events)
    # the trace's clock starts 90 s after the spans': two gaps, one inside
    # tick 3's maintain (0.8 s) and snapshot (0.1 s), one between requests
    trace = {"window_ns": [int(9.5e9), int(26.15e9)], "window_s": 16.65,
             "gaps": [[int(13.95e9), int(14.85e9)],
                      [int(16.5e9), int(17.0e9)],
                      [int(11.05e9), int(11.35e9)]]}
    return {"run": _run(), "measures": measures, "trace": trace,
            "traffic": {"setup_ticks": 3, "trace_ticks": 2}}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name, os.path.join(_BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# each worked out by hand from _recorded(): the median of two is the lower
# (measures.percentile, nearest rank), so tick 3's value where they differ
BY_HAND = {
    "ingest_parse_ms": 3 * (100.0 + 40.0),
    "build_inputs_ms": 300.0 + 400.0,
    "compile_in_tick_ms": 100.0 + 200.0,
    "tick_snapshot_ms": 300.0,
    "tick_dispatch_ms": 500.0 - 200.0,
    "tick_device_wait_ms": 900.0,
    "tick_maintain_ms": 1000.0,
    "tick_publish_ms": 100.0 + 100.0 + 300.0,
    # step_request 5.1 s: its own 0.1 s after the tick; the tick's own:
    # tick 3: 4.9 - (0.1+0.3+0.4+0.3+0.5+1.0+1.0+0.1+0.1+0.3) = 0.8,
    # tick 4: 0.6 -> shares 0.9 / 5.1 and 0.7 / 5.1, the median the lower
    "tick_unnamed_pct": 100.0 * 0.7 / 5.1,
    "read_handler_p95_ms": 8.0,
    # the trace's clock + 90 s is the spans'. Gap 1 [103.95, 104.85]: 0.05
    # of validate's own time (device_wait ended 103.9), 0.85 of maintain;
    # gap 2 [106.5, 107.0]: no request in the program; gap 3 [101.05,
    # 101.35]: 0.05 lock_wait, 0.1 drain_endpoints, 0.1 build_inputs'
    # own, 0.05 of the compile inside it
    "idle_named_pct": 100.0 * (0.9 + 0.3) / (0.9 + 0.5 + 0.3),
}


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_reader_gives_the_number_worked_out_by_hand(name, monkeypatch,
                                                    capsys):
    value = _reader(name).read(_ctx(monkeypatch, _recorded()))
    assert value == pytest.approx(BY_HAND[name], rel=1e-6)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    if name == "idle_named_pct":
        (fact,) = lines
        assert fact["phase"] == "idle_by_span"
        by_span = fact["by_span"]
        assert by_span["tick.maintain"] == pytest.approx(0.85)
        assert by_span["tick.validate"] == pytest.approx(0.05)
        assert by_span["no_request"] == pytest.approx(0.5)
        assert by_span["tick.build_inputs"] == pytest.approx(0.1)
        assert by_span["compile"] == pytest.approx(0.05)
        assert by_span["step.lock_wait"] == pytest.approx(0.05)
        assert "unnamed" not in by_span
        assert fact["gaps"][0][0] == "tick.maintain"
        # the traced stretch on the trace's clock (16.65 s) less open ->
        # the last traced /step's answer (116.15 - 99.5)
        assert fact["clock_error_bound_ms"] == pytest.approx(0.0, abs=1e-6)
    elif name == "tick_unnamed_pct":
        (fact,) = lines
        assert [r["tick"] for r in fact["ticks"]] == [3, 4]
        assert fact["ticks"][0]["phases_s"]["tick.maintain"] == \
            pytest.approx(1.0)
        assert [c[0] for c in fact["ticks"][0]["compiles"]] == [
            "tick.build_inputs", "tick.dispatch"]
    else:
        assert not lines


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_reader_gives_nothing_when_a_window_tick_is_missing(name,
                                                            monkeypatch):
    assert _reader(name).read(_ctx(monkeypatch, _recorded((3,)))) is None
    assert _reader(name).read(_ctx(monkeypatch, None)) is None  # no recorder


def test_reader_gives_nothing_when_a_windows_read_was_evicted(monkeypatch):
    events = [e for e in _recorded()
              if not (e["tid"] == 3 and 103.9 < e["ts"] / 1e6 < 104.1)]
    assert _reader("read_handler_p95_ms").read(
        _ctx(monkeypatch, events)) is None
    assert _reader("tick_maintain_ms").read(
        _ctx(monkeypatch, events)) == pytest.approx(1000.0)


def test_an_unnamed_gap_and_a_reads_phase_are_told_apart():
    spans = sm.closed_spans(_recorded())
    # [105.95, 106.05]: tick 3's own tail (0.05), step_request's own (0.05)
    # [102.0005, 102.0015]: inside tick 3's snapshot and read 0's phases
    (a, b) = sm.name_gaps(spans, [(105.95, 106.05), (102.0005, 102.0015)])
    assert a == pytest.approx({"unnamed": 0.1})
    assert b == pytest.approx({"tick.snapshot": 0.001})
    # with no step in the program a read's phases name the instant
    reads_only = sm.closed_spans([e for e in _recorded() if e["tid"] == 3])
    (c,) = sm.name_gaps(reads_only, [(102.0005, 102.0015)])
    assert c == pytest.approx({"read.query": 0.0005, "read.respond": 0.0005})
