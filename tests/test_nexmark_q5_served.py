"""NEXmark q5 (hot items over 10 s windows hopping by 2 s) through the
served path — the configuration ``nexmark-q5`` and its cell
``nexmark-q5.saturated-steady`` (ISSUE 36), small, on the CPU: watermark,
window and trace-bound GC inside the step program, the view checked after
every tick against the benchmark's plain reference, the controls, the time
nodes' counters, the capacities after the harness's presize, and four
workers against one. 2,000-event ticks at 500 events/s of event time are
4 s = two hops a tick, as in the cell, so windows retire from tick 8 on.
Every test runs under a time limit."""

import copy
import importlib.util
import json
import os
import signal
import sys
import urllib.request

import jax.numpy as jnp
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_ROOT, "benchmark")
for _p in (_ROOT, _BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import generator  # noqa: E402 — benchmark/generator.py
import run as harness  # noqa: E402 — benchmark/run.py

LIMIT_S = 300
CELL = "nexmark-q5.saturated-steady"
EVENTS_PER_TICK = 2000
EVENT_RATE = 500
TICKS = 31             # ticks 0..30
FIRST_RETIREMENT = 8   # 40 s of lingering / 4 s a tick, less the 8 s that
#                        the first window starts before the stream does
SEEDS = (1, 3600000007)


@pytest.fixture(autouse=True)
def _time_limit():
    def on_alarm(*_):
        raise TimeoutError(f"test ran over {LIMIT_S} s")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(LIMIT_S)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def reference():
    return _load(os.path.join(_BENCH, "references", "q5.py"), "reference_q5")


def _config() -> dict:
    """The cell's configuration at the test's size: only the tick and the
    event rate are cut, by the same factor."""
    config = harness.load_cell(CELL)["config"]
    config["events_per_tick"] = EVENTS_PER_TICK
    config["generator"]["first_event_rate"] = EVENT_RATE
    return config


def _events(config, seed, ticks) -> dict:
    """Every event of ticks [0, ticks) as the reference takes them."""
    n = config["events_per_tick"]
    cols = generator.from_config(config, seed).generate(0, ticks * n)
    return {rel: {c: cols[rel][c].tolist() for c in names}
            for rel, names in generator.COLUMNS.items()}


def _http(url, data=None):
    req = urllib.request.Request(url, data=data,
                                 method="GET" if data is None else "POST")
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


def _serve(seed: int) -> dict:
    """q5 behind ``Controller`` and ``CircuitServer`` as ``run.py`` serves
    it: NDJSON pushes, ``/step``, the harness's presize after tick 0, and
    after every tick the whole ``/view`` and what the program counted."""
    import dbsp_tpu  # noqa: F401
    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.compiled.driver import CompiledCircuitDriver
    from dbsp_tpu.io import Catalog
    from dbsp_tpu.io.controller import Controller, ControllerConfig
    from dbsp_tpu.io.server import CircuitServer
    from dbsp_tpu.nexmark import build_inputs, model as M, queries
    from dbsp_tpu.obs import PipelineObs
    from dbsp_tpu.timeseries import counters

    import loadgen  # benchmark/loadgen.py: the bodies the cell pushes

    config = _config()

    def build(c):
        streams, handles = build_inputs(c)
        return handles, queries.q5(*streams).output()

    handle, (handles, out) = Runtime.init_circuit(1, build)
    driver = CompiledCircuitDriver(handle, validate_every=1)
    catalog = Catalog()
    for name, h, dts in (
            ("persons", handles[0], M.PERSON_KEY + M.PERSON_VALS),
            ("auctions", handles[1], M.AUCTION_KEY + M.AUCTION_VALS),
            ("bids", handles[2], M.BID_KEY + M.BID_VALS)):
        catalog.register_input(name, h, dts)
    catalog.register_output("q5", out, (jnp.int64, jnp.int64))
    ctl = Controller(driver, catalog, ControllerConfig(
        min_batch_records=10 ** 9, flush_interval_s=3600.0))
    # with the pipeline's own registry and span ring, as a deployed
    # pipeline has them: /metrics exports what the time nodes counted
    obs = PipelineObs(name="q5", max_trace_steps=4 * TICKS)
    obs.attach_compiled(driver)
    obs.attach_controller(ctl)
    srv = CircuitServer(ctl, obs=obs)
    srv.start()
    base = f"http://127.0.0.1:{srv.port}"
    bodies = loadgen.make_bodies(config, seed, TICKS)
    for kept in (counters.VALIDATED_TICKS, counters.WINDOW_SLIDE_TOTAL,
                 counters.TRACE_GC_ROWS, counters.WATERMARK_MS):
        kept.clear()  # process-wide, and this process serves twice
    run = {"config": config, "seed": seed, "views": [], "replays": []}
    try:
        for k in range(TICKS):
            for rel in generator.COLUMNS:
                _http(f"{base}/input_endpoint/{rel}?format=json",
                      bodies[k][rel])
            before = driver.ch.overflow_replays
            _http(base + "/step", b"")
            if k == 0:
                harness.presize(driver.ch, config)
            run["replays"].append(driver.ch.overflow_replays - before)
            view = _http(f"{base}/view/q5")
            assert view["step"] == k + 1
            run["views"].append({tuple(r[:-1]): r[-1]
                                 for r in view["rows"]})
        run["ticks"] = list(counters.VALIDATED_TICKS)
        run["slot_caps"] = {cn.node.index: getattr(cn, "_slot_cap", None)
                            for cn in driver.ch.cnodes
                            if hasattr(cn, "level_keys")}
        run["slide_last"] = copy.deepcopy(counters.WINDOW_SLIDE_LAST)
        run["slide_total"] = copy.deepcopy(counters.WINDOW_SLIDE_TOTAL)
        run["gc"] = copy.deepcopy(counters.TRACE_GC_ROWS)
        run["watermarks"] = copy.deepcopy(counters.WATERMARK_MS)
        run["metrics_text"] = urllib.request.urlopen(
            base + "/metrics", timeout=60).read().decode()
        run["spans"] = obs.spans.events()
    finally:
        srv.stop()
        ctl.stop()
    return run


_RUNS: dict = {}


def _run_of(seed: int) -> dict:
    if seed not in _RUNS:
        _RUNS[seed] = _serve(seed)
    return _RUNS[seed]


# -- (1) the served view against the plain reference, after every tick -------


@pytest.mark.parametrize("seed", SEEDS)
def test_served_view_equals_the_reference_after_every_tick(seed, reference):
    run = _run_of(seed)
    sizes = []
    for k, got in enumerate(run["views"]):
        want = reference.recompute(_events(run["config"], seed, k + 1))
        assert got == want, f"tick {k}: {len(got)} rows, want {len(want)}"
        sizes.append(len({w for w, _ in want}))
    # the view shrinks as well as grows: windows leave it from the first
    # retirement on, and it ends no larger than the 25 windows that live
    # at once (40 s of lingering + 10 s, a start every 2 s)
    assert sizes[FIRST_RETIREMENT - 1] > sizes[0]
    assert max(sizes) <= 25 and sizes[-1] <= sizes[FIRST_RETIREMENT + 5]


# -- (2) the controls give another view at this size --------------------------


@pytest.mark.parametrize("control", ("no_retire", "int32", "lost_batch"))
def test_control_gives_a_different_view(control, reference):
    config = _config()
    events = _events(config, SEEDS[0], TICKS)
    want = reference.recompute(events)
    if control == "lost_batch":  # the harness's own: the last batch lost
        got = reference.recompute(_events(config, SEEDS[0], TICKS - 1))
    else:
        assert control in reference.CONTROLS
        got = reference.recompute(events, control=control)
    assert want and got != want
    c = harness.compare_view({"rows": [[*k, w] for k, w in got.items()],
                              "step": TICKS}, want, TICKS, 1, 1)
    assert not harness.is_correct(c) and c["rows_mismatched"]["value"] > 0


# -- (3) the time nodes' counters, and where they are exported ----------------


def test_counters_of_the_time_nodes():
    run = _run_of(SEEDS[0])
    ticks = run["ticks"]
    assert len(ticks) == TICKS
    out = [t["retired_rows"] for t in ticks]
    assert out[:FIRST_RETIREMENT] == [0] * FIRST_RETIREMENT
    assert all(n > 0 for n in out[FIRST_RETIREMENT:]), out
    # what the windows slid out is what the bound truncated from the trace
    assert [t["gc_truncated_rows"] for t in ticks][1:] == out[1:]
    assert all(t["slid_in_rows"] == 0 for t in ticks)
    # a bounded view: the trace under the GC bound holds at tick 30 what it
    # held at tick 15, and within its capacity
    assert 0 < ticks[30]["gc_live_rows"] <= 1.3 * ticks[15]["gc_live_rows"]
    assert ticks[30]["gc_live_rows"] <= ticks[30]["gc_capacity_rows"]
    assert ticks[30]["trace_live_rows"] >= ticks[30]["gc_live_rows"]
    # a tick is 4 s of event time
    marks = [t["watermark_ms"] for t in ticks]
    assert all(3900 <= b - a <= 4100 for a, b in zip(marks, marks[1:]))
    (win, total), = run["slide_total"].items()
    assert total == {"out": sum(out), "in": 0}
    assert run["slide_last"] == {win: {"out": out[-1], "in": 0}}
    (trace, gc), = run["gc"].items()
    assert gc["truncated_total"] == sum(out) and gc["live"] == \
        ticks[30]["gc_live_rows"]
    (wm, mark), = run["watermarks"].items()
    assert mark["ms"] == marks[-1] and 3900 <= mark["advance"] <= 4100
    text = run["metrics_text"]
    for line in (
            f'dbsp_tpu_window_slide_rows_total{{node="{win}",dir="out"}} '
            f'{sum(out)}',
            f'dbsp_tpu_trace_gc_rows_total{{node="{trace}"}} {sum(out)}',
            f'dbsp_tpu_trace_gc_live_rows{{node="{trace}"}} {gc["live"]}',
            f'dbsp_tpu_watermark_ms{{node="{wm}"}} {marks[-1]}'):
        assert line in text, line


def test_spans_carry_the_time_nodes_args():
    import span_measures as sm  # benchmark/span_measures.py

    run = _run_of(SEEDS[0])
    spans = sm.closed_spans(run["spans"])
    validates = [s for s in spans if s.name == "tick.validate"]
    snapshots = [s for s in spans if s.name == "tick.snapshot"]
    assert len(validates) == TICKS and len(snapshots) == TICKS
    assert [s.args["retired_rows"] for s in validates] == \
        [t["retired_rows"] for t in run["ticks"]]
    assert [s.args["watermark_ms"] for s in validates] == \
        [t["watermark_ms"] for t in run["ticks"]]
    # every level of the trace under the GC bound is copied every tick
    assert {s.args["gc_levels"] for s in snapshots} == {4}


def test_metric_readers_read_the_counters():
    import measures  # benchmark/measures.py

    run = _run_of(SEEDS[0])
    window = 16  # the last 16 ticks stand for a window
    ctx = {"run": {"step_done": {str(k): 1.0 + k for k in range(
        TICKS - window, TICKS)}, "open": 0.0, "close": 1e9},
        "measures": measures}
    assert len(measures.window_ticks(ctx["run"])) == window
    ticks = run["ticks"][-window:]
    readers = {name: _load(os.path.join(_BENCH, "metrics", name + ".py"),
                           "metric_" + name)
               for name in ("window_retired_rows", "gc_state_fill_pct",
                            "state_plateau_pct")}
    from dbsp_tpu.timeseries import counters

    counters.VALIDATED_TICKS.clear()
    assert readers["window_retired_rows"].read(dict(ctx)) is None
    counters.VALIDATED_TICKS.extend(run["ticks"])
    assert readers["window_retired_rows"].read(dict(ctx)) == \
        measures.percentile([t["retired_rows"] for t in ticks], 50) > 0
    fill = readers["gc_state_fill_pct"].read(dict(ctx))
    assert fill == 100.0 * ticks[-1]["gc_live_rows"] / \
        ticks[-1]["gc_capacity_rows"] and 0 < fill <= 100
    plateau = readers["state_plateau_pct"].read(dict(ctx))
    assert plateau == 100.0 * ticks[-1]["trace_live_rows"] / \
        ticks[0]["trace_live_rows"] and 50 < plateau < 200


# -- (4) the capacities after the harness's presize and the set-up ticks ------


@pytest.mark.parametrize("seed", SEEDS)
def test_no_overflow_replay_after_the_set_up_ticks(seed):
    run = _run_of(seed)
    setup = harness.load_cell(CELL)["traffic"]["setup_ticks"]
    assert setup == 14
    assert run["replays"][setup:] == [0] * (TICKS - setup), run["replays"]
    # the ramp, while the windows fill, costs at most three step programs
    # after the presize's
    assert sum(run["replays"][1:setup]) <= 3, run["replays"]
    # no trace of the windowed view took slots: a slot size pinned at the
    # first trace (a delta of 128) made consumers probe level 0 of the
    # by_window trace as cap / 128 runs once the aggregate's capacity grew
    assert len(run["slot_caps"]) == 3
    assert set(run["slot_caps"].values()) == {None}, run["slot_caps"]


# -- (5) four workers against one ---------------------------------------------


def _views_per_tick(workers: int, seed: int, ticks: int) -> list:
    import dbsp_tpu  # noqa: F401
    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.compiled.driver import CompiledCircuitDriver
    from dbsp_tpu.nexmark import build_inputs, queries

    config = _config()

    def build(c):
        streams, handles = build_inputs(c)
        return handles, queries.q5(*streams).output()

    handle, (handles, out) = Runtime.init_circuit(workers, build)
    driver = CompiledCircuitDriver(handle, validate_every=1)
    gen = generator.from_config(config, seed)
    n = config["events_per_tick"]
    acc: dict = {}
    views = []
    for k in range(ticks):
        cols = gen.generate(k * n, (k + 1) * n)
        for h, (rel, names) in zip(handles, generator.COLUMNS.items()):
            h.extend([(r, 1) for r in zip(*(cols[rel][c].tolist()
                                            for c in names))])
        driver.step()
        for key, w in out.to_dict().items():
            acc[key] = acc.get(key, 0) + w
            if not acc[key]:
                del acc[key]
        views.append(dict(acc))
    return views


def test_four_workers_equal_one_per_tick(reference):
    ticks = 14  # past the first retirements
    one = _views_per_tick(1, SEEDS[0], ticks)
    four = _views_per_tick(4, SEEDS[0], ticks)
    assert one == four
    assert one[-1] == reference.recompute(
        _events(_config(), SEEDS[0], ticks))
    assert len(one[-1]) < len(one[FIRST_RETIREMENT - 1]) + 10
