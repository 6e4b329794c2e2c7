"""NEXmark q6 (average selling price by seller) through the served path —
the configuration ``nexmark-q6`` and its cell ``nexmark-q6.saturated``
(ISSUE 38), small, on the CPU: two chained ``CTopK`` nodes (the top-1 per
auction over each touched auction's history, the top-10 per seller by
expiry) and an average over their retractions, the view checked after
every tick against the benchmark's plain reference, the controls, the
top-K counters and where they are exported, the metric readers, the
capacities after the harness's presize, and four workers against one.
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
CELL = "nexmark-q6.saturated"
EVENTS_PER_TICK = 2000
TICKS = 20
SEEDS = (1, 3800000011)
READERS = ("topk_gather_rows", "topk_gather_fill_pct", "topk_rows_roofline")


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
    return _load(os.path.join(_BENCH, "references", "q6.py"), "reference_q6")


def _config() -> dict:
    """The cell's configuration at the test's size: the tick and the hot
    window cut by the same factor, so that a tick's new auctions outnumber
    the hot ones 24 to 1 as in the cell (2,400 to 100) and the histories a
    tick re-reads ramp as the cell's do (at a window of 100 they ramp
    further, and the per-delta room the cell states does not hold them)."""
    config = harness.load_cell(CELL)["config"]
    full = config["events_per_tick"]
    config["events_per_tick"] = EVENTS_PER_TICK
    config["generator"]["hot_window"] = \
        config["generator"]["hot_window"] * EVENTS_PER_TICK // full
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
    """q6 behind ``Controller`` and ``CircuitServer`` as ``run.py`` serves
    it: the query by the configuration's name, NDJSON pushes, ``/step``,
    the harness's presize after tick 0, and after every tick the whole
    ``/view`` and what the program counted."""
    import dbsp_tpu  # noqa: F401
    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.compiled import cnodes
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
        return handles, getattr(queries, config["query"])(*streams).output()

    handle, (handles, out) = Runtime.init_circuit(1, build)
    driver = CompiledCircuitDriver(handle, validate_every=1)
    assert driver.mode == "compiled"
    catalog = Catalog()
    for name, h, dts in (
            ("persons", handles[0], M.PERSON_KEY + M.PERSON_VALS),
            ("auctions", handles[1], M.AUCTION_KEY + M.AUCTION_VALS),
            ("bids", handles[2], M.BID_KEY + M.BID_VALS)):
        catalog.register_input(name, h, dts)
    catalog.register_output("q6", out, (jnp.int64, jnp.int64))
    ctl = Controller(driver, catalog, ControllerConfig(
        min_batch_records=10 ** 9, flush_interval_s=3600.0))
    # with the pipeline's own registry and span ring, as a deployed
    # pipeline has them: /metrics exports what the top-K nodes counted
    obs = PipelineObs(name="q6", max_trace_steps=4 * TICKS)
    obs.attach_compiled(driver)
    obs.attach_controller(ctl)
    srv = CircuitServer(ctl, obs=obs)
    srv.start()
    base = f"http://127.0.0.1:{srv.port}"
    bodies = loadgen.make_bodies(config, seed, TICKS)
    counters.VALIDATED_TICKS.clear()  # process-wide; this process serves
    counters.TOPK_ROWS.clear()        # more than one circuit
    run = {"config": config, "seed": seed, "views": [], "replays": [],
           "grown": []}
    grow = driver.ch.grow

    def grow_noted(overflow, *a, **kw):  # what each replay grew
        run["grown"].extend((type(cn).__name__, key)
                            for cn, key, _ in overflow.items)
        return grow(overflow, *a, **kw)

    driver.ch.grow = grow_noted
    try:
        for k in range(TICKS):
            for rel in generator.COLUMNS:
                _http(f"{base}/input_endpoint/{rel}?format=json",
                      bodies[k][rel])
            before = driver.ch.overflow_replays
            _http(base + "/step", b"")
            if k == 0:
                # the top-K capacities the first validated tick settled,
                # beside what it read
                run["settled"] = [
                    (cn.caps[key], cn._read[key]) for cn in driver.ch.cnodes
                    if isinstance(cn, cnodes.CTopK)
                    for key in ("queries", "gather", "out")]
                harness.presize(driver.ch, config)
            run["replays"].append(driver.ch.overflow_replays - before)
            view = _http(f"{base}/view/q6")
            assert view["step"] == k + 1
            run["views"].append({tuple(r[:-1]): r[-1]
                                 for r in view["rows"]})
        ch = driver.ch
        run["ticks"] = list(counters.VALIDATED_TICKS)
        run["topk"] = copy.deepcopy(counters.TOPK_ROWS)
        run["tops"] = [(cn.node.index, cn.op.k, dict(cn.caps))
                       for cn in ch.cnodes if isinstance(cn, cnodes.CTopK)]
        # every trace downstream of a top-K, and its slot decision
        seeded, behind = {i for i, _, _ in run["tops"]}, {}
        for cn in ch.cnodes:
            if any(i in seeded for i in cn.node.inputs):
                seeded.add(cn.node.index)
                if isinstance(cn, cnodes.CTrace):
                    behind[cn.node.index] = (
                        getattr(cn, "_slot_cap", None),
                        getattr(cn, "_no_slots", False))
        run["behind"] = behind
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
    for k, got in enumerate(run["views"]):
        want = reference.recompute(_events(run["config"], seed, k + 1))
        assert got == want, f"tick {k}: {len(got)} rows, want {len(want)}"
    # a seller's average moves as its winners do: rows leave the view as
    # well as enter it
    left = [len(set(a) - set(b))
            for a, b in zip(run["views"], run["views"][1:])]
    assert sum(left) > 0, left


# -- (2) the controls give another view at this size --------------------------


@pytest.mark.parametrize("control", ("latest_bid_wins", "last_9",
                                     "lost_batch"))
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


def test_int32_reading_equals_the_exact_view(reference):
    """Every number q6's view depends on fits in 32 bits, so the reference
    computed in int32 gives the exact view: no run can check int64
    precision, and the configuration claims none (``references/q6.py``)."""
    config = _config()
    events = _events(config, SEEDS[1], TICKS)
    assert "int32" not in reference.CONTROLS
    assert reference.recompute(events, control="int32") == \
        reference.recompute(events)
    assert any("no int64 precision is claimed" in g for g in
               harness.load_cell(CELL)["config"]["guarantees"])


# -- (3) the top-K nodes' counters, and where they are exported ---------------


def test_counters_of_the_top_k_nodes():
    run = _run_of(SEEDS[0])
    ticks = run["ticks"]
    assert len(ticks) == TICKS
    (top1, _, caps1), (top10, _, caps10) = run["tops"]
    assert set(run["topk"]) == {top1, top10}
    last = {n: run["topk"][n] for n in (top1, top10)}
    # the record sums the nodes' last tick
    assert ticks[-1]["topk_gathered_rows"] == sum(
        e["gathered"] for e in last.values())
    assert ticks[-1]["topk_groups"] == sum(e["groups"] for e in last.values())
    assert ticks[-1]["topk_gather_capacity_rows"] == \
        caps1["gather"] + caps10["gather"]
    for t in ticks:
        # a touched group re-reads at least the row that touched it
        assert t["topk_gathered_rows"] >= t["topk_groups"] > 0
        assert t["topk_gathered_rows"] <= t["topk_gather_capacity_rows"]
        assert t["topk_inserted_rows"] > 0
    # from tick 1 on winners move: retractions flow into the top-10 and the
    # average behind it
    assert all(t["topk_retracted_rows"] > 0 for t in ticks[1:])
    assert "retired_rows" not in ticks[-1]  # no time node in this circuit
    gathered = sum(t["topk_gathered_rows"] for t in ticks)
    assert sum(e["gathered_total"] for e in run["topk"].values()) == gathered
    text = run["metrics_text"]
    for node, ent in run["topk"].items():
        for line in (
                f'dbsp_tpu_topk_gathered_rows_total{{node="{node}"}} '
                f'{ent["gathered_total"]}',
                f'dbsp_tpu_topk_groups_total{{node="{node}"}} '
                f'{ent["groups_total"]}',
                f'dbsp_tpu_topk_changed_rows_total{{node="{node}"}} '
                f'{ent["changed_total"]}',
                f'dbsp_tpu_capacity_rows{{node="{node}",kind="gather"}} '
                f'{ent["capacity"]}'):
            assert line in text, line


def test_spans_carry_the_top_k_arg():
    import span_measures as sm  # benchmark/span_measures.py

    run = _run_of(SEEDS[0])
    validates = [s for s in sm.closed_spans(run["spans"])
                 if s.name == "tick.validate"]
    assert len(validates) == TICKS
    assert [s.args["topk_gathered_rows"] for s in validates] == \
        [t["topk_gathered_rows"] for t in run["ticks"]]
    assert all("retired_rows" not in s.args for s in validates)


def test_metric_readers_read_the_counters():
    import measures  # benchmark/measures.py
    from dbsp_tpu.timeseries import counters

    run = _run_of(SEEDS[0])
    window = 12  # the last 12 ticks stand for a window
    ctx = {"run": {"step_done": {str(k): 1.0 + k for k in range(
        TICKS - window, TICKS)}, "open": 0.0, "close": 1e9},
        "measures": measures, "config": harness.load_cell(CELL)["config"],
        "peaks": {"hbm_bytes_per_s": 819e9}}
    assert len(measures.window_ticks(ctx["run"])) == window
    ticks = run["ticks"][-window:]
    readers = {name: _load(os.path.join(_BENCH, "metrics", name + ".py"),
                           "metric_" + name) for name in READERS}
    # no counter (the parent), or a circuit with no top-K node: None
    counters.VALIDATED_TICKS.clear()
    for r in readers.values():
        assert r.read(dict(ctx, probe_trace=None)) is None
    counters.VALIDATED_TICKS.extend(
        {k: v for k, v in t.items() if not k.startswith("topk_")}
        for t in run["ticks"])
    for r in readers.values():
        assert r.read(dict(ctx, probe_trace=None)) is None
    counters.VALIDATED_TICKS.clear()
    counters.VALIDATED_TICKS.extend(run["ticks"])
    rows = readers["topk_gather_rows"].read(dict(ctx))
    assert rows == measures.percentile(
        [t["topk_gathered_rows"] for t in ticks], 50) > 0
    fill = readers["topk_gather_fill_pct"].read(dict(ctx))
    assert fill == 100.0 * ticks[-1]["topk_gathered_rows"] / \
        ticks[-1]["topk_gather_capacity_rows"] and 0 < fill <= 100
    # the roofline: the shape the widest top-K ran in the last tick (the
    # top-1 per auction), its bytes, and the share of one recorded run of
    # the probe; None without the program's record
    roof = readers["topk_rows_roofline"]
    (top1, _, caps1), _ = run["tops"]
    counters.TOPK_ROWS.clear()
    assert roof.shape() is None
    assert roof.read(dict(ctx, probe_trace=None)) is None
    counters.TOPK_ROWS.update(copy.deepcopy(run["topk"]))
    gather, queries = caps1["gather"], caps1["queries"]
    assert roof.shape() == (gather, queries, 1, 5)
    need = gather * 52 + min(gather, queries) * 56
    assert roof.needed_bytes(*roof.shape()) == need
    assert roof.read(dict(ctx, probe_trace=None)) is None
    probe = {"whole_modules": {"jit_bench_topk_rows": (5, 5 * 0.01)}}
    assert roof.read(dict(ctx, probe_trace=probe)) == pytest.approx(
        100.0 * need / 819e9 / 0.01)
    # at the cell's own capacities (PERF.md 3)
    assert roof.needed_bytes(262_144, 16_384, 1, 5) == \
        262_144 * 52 + 16_384 * 56


def test_roofline_probe_runs_the_kernel():
    """The probe's wrapper at a small size: the kernel keeps one row per
    group, the group's largest."""
    import numpy as np

    from dbsp_tpu.timeseries import counters

    roof = _load(os.path.join(_BENCH, "metrics", "topk_rows_roofline.py"),
                 "metric_topk_rows_roofline")
    saved = dict(counters.TOPK_ROWS)
    counters.TOPK_ROWS.clear()
    # two top-K nodes recorded: the probe takes the widest gather's shape
    counters.TOPK_ROWS.update({
        10: {"capacity": 2048, "queries": 128, "k": 1, "values": 5},
        13: {"capacity": 512, "queries": 64, "k": 10, "values": 3}})
    try:
        assert roof.shape() == (2048, 128, 1, 5)
        fn, (qrow, qkeys, vals, w) = roof.prepare({"seed": 7})
    finally:
        counters.TOPK_ROWS.clear()
        counters.TOPK_ROWS.update(saved)
    keys, out_vals, out_w = fn(qrow, qkeys, vals, w)
    assert int(np.sum(np.asarray(out_w) == 1)) == 128
    qrow, v0 = np.asarray(qrow), np.asarray(vals[0])
    best = {int(q): int(v0[qrow == q].max()) for q in range(128)}
    kept = np.asarray(out_w) == 1
    got = dict(zip((np.asarray(keys[0])[kept] - 1000).tolist(),
                   np.asarray(out_vals[0])[kept].tolist()))
    assert got == best


@pytest.mark.parametrize("cell", ("nexmark-q3.saturated",
                                  "nexmark-q4.saturated",
                                  "nexmark-q4-4w.saturated",
                                  "nexmark-q5.saturated-steady"))
def test_roofline_probe_skips_a_cell_without_top_k(cell):
    """run.py prepares every reader's probe in every traced run: in a cell
    whose circuit has no top-K (the program recorded none) the probe is
    None, and its reading too, not an error that would end that cell's
    run."""
    from dbsp_tpu.timeseries import counters

    roof = _load(os.path.join(_BENCH, "metrics", "topk_rows_roofline.py"),
                 "metric_topk_rows_roofline")
    config = harness.load_cell(cell)["config"]
    saved = dict(counters.TOPK_ROWS)
    counters.TOPK_ROWS.clear()  # process-wide: as a run of that cell has it
    try:
        ctx = {"config": config, "seed": 3800000201, "probe_trace": None}
        assert roof.prepare(ctx) is None
        roof.probe(ctx, None)
        assert roof.read(ctx) is None
    finally:
        counters.TOPK_ROWS.update(saved)


# -- (4) the capacities after the harness's presize and the set-up ticks ------


@pytest.mark.parametrize("seed", SEEDS)
def test_capacities_after_the_presize(seed):
    run = _run_of(seed)
    setup = harness.load_cell(CELL)["traffic"]["setup_ticks"]
    assert setup == 3
    assert run["replays"][setup + 1:] == [0] * (TICKS - setup - 1), \
        run["replays"]
    for _, k, caps in run["tops"]:
        # the output delta has a capacity of its own, narrower than the
        # buffers it is made of (the gather the re-read histories are
        # sorted in and the k rows a key the old top-K held): where the
        # top-K changes little, as the top-1's does, narrower than the
        # gather alone
        assert 0 < caps["out"] < caps["gather"] + k * caps["queries"]
        if k == 1:
            assert caps["out"] < caps["gather"]
    # no trace behind a top-K pinned a slot size at the first trace
    assert run["behind"], "no trace behind a top-K"
    assert set(run["behind"].values()) == {(None, True)}, run["behind"]


@pytest.mark.parametrize("seed", SEEDS)
def test_top_k_capacities_are_provisional_until_the_first_tick(seed):
    """Before any interval validated, a top-K's ``queries``, ``gather`` and
    ``out`` are static bounds that no tick can overflow, so no replay of the
    first tick grows one; the first validated tick sets each to twice what
    it read (``CTopK.settle``)."""
    from dbsp_tpu.zset.batch import bucket_cap

    run = _run_of(seed)
    grown = {(node, key) for node, key in run["grown"]}
    assert not grown & {("CTopK", k) for k in ("queries", "gather", "out")}
    assert run["settled"] and all(
        cap == bucket_cap(max(64, 2 * read)) for cap, read in run["settled"])


# -- (5) four workers against one ---------------------------------------------


def _views_per_tick(workers: int, seed: int, ticks: int) -> list:
    import dbsp_tpu  # noqa: F401
    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.compiled.driver import CompiledCircuitDriver
    from dbsp_tpu.nexmark import build_inputs, queries

    config = _config()

    def build(c):
        streams, handles = build_inputs(c)
        return handles, queries.average_selling_price_by_seller(
            *streams).output()

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
    from dbsp_tpu.parallel import exchange

    ticks = 8
    one = _views_per_tick(1, SEEDS[0], ticks)
    sites = dict(exchange.EXCHANGE_SITE_ROWS)  # process-wide: as it was
    try:
        four = _views_per_tick(4, SEEDS[0], ticks)
    finally:
        exchange.EXCHANGE_SITE_ROWS.clear()
        exchange.EXCHANGE_SITE_ROWS.update(sites)
    assert one == four
    assert one[-1] == reference.recompute(
        _events(_config(), SEEDS[0], ticks))
