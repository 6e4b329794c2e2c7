"""How full the step program's buffers are: the record every compiled
circuit keeps of each validated interval (``timeseries/counters.py``
``VALIDATED_TICKS``: each checked capacity's requirement against its width,
and whether it sizes carried state or a per-tick buffer), the gauges
``dbsp_tpu_capacity_rows`` / ``dbsp_tpu_capacity_required_rows`` that export
it, and the benchmark's readers ``delta_lane_fill_pct`` and
``capacity_peak_fill_pct``. Small, on the CPU, the five benchmark cells'
circuits; every test runs under a time limit."""

import importlib.util
import json
import os
import signal
import sys
import urllib.request

import jax
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
CELLS = ("nexmark-q3.saturated", "nexmark-q4.saturated",
         "nexmark-q4-4w.saturated", "nexmark-q5.saturated-steady",
         "nexmark-q6.saturated")
READERS = ("delta_lane_fill_pct", "capacity_peak_fill_pct")
SEED = 3900000401


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


def _config(cell: str, events: int) -> dict:
    config = harness.load_cell(cell)["config"]
    config["events_per_tick"] = events
    return config


def _build(config):
    import dbsp_tpu  # noqa: F401
    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.nexmark import build_inputs, queries

    def build(c):
        streams, handles = build_inputs(c)
        return handles, getattr(queries, config["query"])(*streams).output()

    return Runtime.init_circuit(config["workers"], build)


def _push(config, handles, gen, k):
    n = config["events_per_tick"]
    cols = gen.generate(k * n, (k + 1) * n)
    for h, (rel, names) in zip(handles, generator.COLUMNS.items()):
        h.extend([(r, 1) for r in zip(*(cols[rel][c].tolist()
                                        for c in names))])


def _stepped(cell: str, events: int, ticks: int):
    """The cell's circuit under ``CompiledCircuitDriver``, ``ticks`` ticks
    of ``events`` events pushed through its input handles, the harness's
    presize after tick 0; the driver, the records the ring gained, and
    what pushes the next tick."""
    from dbsp_tpu.compiled.driver import CompiledCircuitDriver
    from dbsp_tpu.timeseries import counters

    config = _config(cell, events)
    handle, (handles, _) = _build(config)
    driver = CompiledCircuitDriver(handle, validate_every=1)
    gen = generator.from_config(config, SEED)
    counters.VALIDATED_TICKS.clear()  # process-wide
    for k in range(ticks):
        _push(config, handles, gen, k)
        driver.step()
        if k == 0:
            harness.presize(driver.ch, config)
    return driver, list(counters.VALIDATED_TICKS), \
        lambda k: _push(config, handles, gen, k)


_STEPPED: dict = {}


def _stepped_of(cell: str):
    if cell not in _STEPPED:
        _STEPPED[cell] = _stepped(cell, 600, 4)
    return _STEPPED[cell]


# -- (1) one record a validated tick, in every circuit, each check classed ----


@pytest.mark.parametrize("cell", CELLS)
def test_one_record_a_tick_and_every_check_classed(cell):
    """Every circuit records each validated tick once, whatever nodes it
    has; each of its checked capacities is ``state`` exactly where
    ``repad_state`` refits the node's carried state to it (a trace's
    levels, an output or accumulator trace), else ``tick``."""
    from dbsp_tpu.circuit.runtime import Runtime
    from dbsp_tpu.compiled.compiler import node_scope

    driver, records, _ = _stepped_of(cell)
    ch = driver.ch
    assert len(records) == 4
    classes = {}
    prev = Runtime._swap(ch.runtime) if ch.mesh is not None else None
    try:
        for cn, key in ch._checks:
            st = ch.states.get(str(cn.node.index))
            shapes = [a.shape for a in jax.tree_util.tree_leaves(st)]
            cap = cn.caps[key]
            if not cap:
                continue  # not sized yet: no record names it
            cn.caps[key] = 2 * cap
            try:
                refit = [a.shape for a in jax.tree_util.tree_leaves(
                    cn.repad_state(st))] != shapes
            finally:
                cn.caps[key] = cap
            assert refit == cn.sizes_state(key), (node_scope(cn), key)
            assert refit == (key in cn.STATE_CAPS or
                             key in getattr(cn, "level_keys", ())), key
            classes[(node_scope(cn), key)] = "state" if refit else "tick"
    finally:
        if ch.mesh is not None:
            Runtime._swap(prev)
    assert set(classes.values()) == {"state", "tick"}, classes
    for rec in records:
        assert {(s, k): c for s, k, c, _, _ in rec["capacities"]} == \
            {sk: c for sk, c in classes.items()
             if sk in {(s, k) for s, k, _, _, _ in rec["capacities"]}}
        tick = [e for e in rec["capacities"] if e[2] == "tick"]
        assert rec["tick_live_rows"] == sum(e[3] for e in tick)
        assert rec["tick_capacity_rows"] == sum(e[4] for e in tick) > 0
        assert all(0 <= e[3] <= e[4] for e in rec["capacities"])
    # every check was sized by the last tick, and each is recorded
    assert len(records[-1]["capacities"]) == len(ch._checks)


def test_compiled_upsert_state_grows_past_its_first_capacity():
    """CUpsertIn's map state starts at 1,024 rows: 1,500 keys in one tick
    overflow it, and the replay must run on a state refitted to the grown
    capacity (``CNode.sizes_state``: ``state`` sizes carried state), or
    the keys past the old width are dropped from the map and a later
    overwrite of them retracts nothing."""
    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.compiled import cnodes
    from dbsp_tpu.compiled.driver import CompiledCircuitDriver
    from dbsp_tpu.operators.upsert import add_input_map

    n = 1500

    def run(compiled: bool):
        def build(c):
            s, h = add_input_map(c, (jnp.int64,), (jnp.int64,))
            return h, s.integrate().output()

        handle, (h, out) = Runtime.init_circuit(1, build)
        driver = CompiledCircuitDriver(handle) if compiled else handle
        seen = []
        for t in range(3):
            for k in range(n):
                h.upsert((k,), (10 * k + t,))
            driver.step()
            seen.append(out.to_dict())
        if compiled:
            node = next(cn for cn in driver.ch.cnodes
                        if isinstance(cn, cnodes.CUpsertIn))
            assert driver.ch.overflow_replays >= 1
            assert driver.ch.states[str(node.node.index)].cap == \
                node.caps["state"] > n
        return seen

    host = run(False)
    assert host[-1] == {(k, 10 * k + 2): 1 for k in range(n)}
    assert run(True) == host


# -- (2) the served q4 and q6: each entry is the tick's capacity and its ------
# requirement, and /metrics exports them


def _serve(cell: str, events: int, ticks: int) -> dict:
    """The cell's circuit behind ``Controller`` and ``CircuitServer`` as
    ``run.py`` serves it, with the pipeline's own registry; for each tick
    the capacities and requirements as validation left them, read when
    maintenance begins (before it may grow a trace's tail)."""
    from dbsp_tpu.compiled.compiler import node_scope
    from dbsp_tpu.compiled.driver import CompiledCircuitDriver
    from dbsp_tpu.io import Catalog
    from dbsp_tpu.io.controller import Controller, ControllerConfig
    from dbsp_tpu.io.server import CircuitServer
    from dbsp_tpu.nexmark import model as M
    from dbsp_tpu.obs import PipelineObs
    from dbsp_tpu.timeseries import counters

    import loadgen  # benchmark/loadgen.py: the bodies the cell pushes

    config = _config(cell, events)
    handle, (handles, out) = _build(config)
    driver = CompiledCircuitDriver(handle, validate_every=1)
    ch = driver.ch
    catalog = Catalog()
    for name, h, dts in (
            ("persons", handles[0], M.PERSON_KEY + M.PERSON_VALS),
            ("auctions", handles[1], M.AUCTION_KEY + M.AUCTION_VALS),
            ("bids", handles[2], M.BID_KEY + M.BID_VALS)):
        catalog.register_input(name, h, dts)
    catalog.register_output(config["view"], out, tuple(
        getattr(jnp, d) for d in config["view_dtypes"]))
    ctl = Controller(driver, catalog, ControllerConfig(
        min_batch_records=10 ** 9, flush_interval_s=3600.0))
    obs = PipelineObs(name=config["view"], max_trace_steps=4 * ticks)
    obs.attach_compiled(driver)
    obs.attach_controller(ctl)
    srv = CircuitServer(ctl, obs=obs)
    srv.start()
    base = f"http://127.0.0.1:{srv.port}"
    bodies = loadgen.make_bodies(config, SEED, ticks)
    seen = []
    maintain = ch.maintain

    def maintain_seen(*a, **kw):
        seen.append([(node_scope(cn), key, int(r), cn.caps[key])
                     for (cn, key), r in zip(ch._checks, ch.last_req)])
        return maintain(*a, **kw)

    ch.maintain = maintain_seen
    counters.VALIDATED_TICKS.clear()
    counters.CAPACITY_ROWS.clear()
    run = {"ticks": []}
    try:
        for k in range(ticks):
            for rel in generator.COLUMNS:
                req = urllib.request.Request(
                    f"{base}/input_endpoint/{rel}?format=json",
                    data=bodies[k][rel], method="POST")
                urllib.request.urlopen(req, timeout=120).read()
            urllib.request.urlopen(urllib.request.Request(
                base + "/step", data=b"", method="POST"), timeout=300).read()
            if k == 0:
                harness.presize(ch, config)
        run["records"] = list(counters.VALIDATED_TICKS)
        run["seen"] = seen
        run["first_settles"] = any(
            hasattr(cn, "_settled") for cn in ch.cnodes)
        run["metrics_text"] = urllib.request.urlopen(
            base + "/metrics", timeout=60).read().decode()
        run["rows"] = dict(counters.CAPACITY_ROWS)
        run["scopes"] = {node_scope(cn): cn.node.index for cn in ch.cnodes}
    finally:
        srv.stop()
        ctl.stop()
    return run


_SERVED: dict = {}


def _served_of(cell: str) -> dict:
    if cell not in _SERVED:
        _SERVED[cell] = _serve(cell, 2000, 6)
    return _SERVED[cell]


@pytest.mark.parametrize("cell", ("nexmark-q4.saturated",
                                  "nexmark-q6.saturated"))
def test_served_record_is_the_tick_s_capacities_and_requirements(cell):
    run = _served_of(cell)
    records, seen = run["records"], run["seen"]
    assert len(records) == len(seen) == 6
    for k, (rec, checks) in enumerate(zip(records, seen)):
        if k == 0 and run["first_settles"]:
            # a top-K's provisional capacities ran tick 0 and were set
            # from what it read when it validated: the record keeps what
            # the tick ran with
            assert [(s, key, r) for s, key, _, r, _ in rec["capacities"]] \
                == [(s, key, r) for s, key, r, cap in checks if cap]
            continue
        assert [(s, key, r, cap) for s, key, _, r, cap in
                rec["capacities"]] == [c for c in checks if c[3]], k


@pytest.mark.parametrize("cell", ("nexmark-q4.saturated",
                                  "nexmark-q6.saturated"))
def test_capacity_gauges_in_metrics(cell):
    run = _served_of(cell)
    text = run["metrics_text"]
    last = {}
    for scope, kind, _, required, capacity in run["records"][-1]["capacities"]:
        key = (run["scopes"][scope], kind)
        last[key] = max(last.get(key, (required, capacity)),
                        (required, capacity))
    assert {(n, k) for n, kinds in run["rows"].items() for k in kinds} == \
        set(last)
    for (node, kind), (required, capacity) in last.items():
        assert run["rows"][node][kind] == (required, capacity)
        for line in (f'dbsp_tpu_capacity_rows{{node="{node}",kind="{kind}"}}'
                     f' {capacity}',
                     f'dbsp_tpu_capacity_required_rows{{node="{node}",'
                     f'kind="{kind}"}} {required}'):
            assert line in text, line
    assert "dbsp_tpu_topk_gather_capacity_rows" not in text


# -- (3) an overflowed interval records nothing; its replay records once ------


def test_overflowed_interval_is_recorded_once_by_its_replay():
    from dbsp_tpu.compiled import cnodes
    from dbsp_tpu.compiled.compiler import node_scope
    from dbsp_tpu.timeseries import counters

    driver, _, push = _stepped("nexmark-q4.saturated", 600, 3)
    ch = driver.ch
    join = next(cn for cn in ch.cnodes if isinstance(cn, cnodes.CJoin))
    need = max(int(r) for (cn, key), r in zip(ch._checks, ch.last_req)
               if cn is join and key == "left")
    assert need > 64
    join.caps["left"] = 64  # narrower than a tick's fan-out: overflows
    ch._step_jit = None
    before, replays = len(counters.VALIDATED_TICKS), ch.overflow_replays
    grown = []
    grow = ch.grow

    def grow_noted(overflow, *a, **kw):
        # no record is written while the interval stands overflowed
        grown.append(len(counters.VALIDATED_TICKS))
        return grow(overflow, *a, **kw)

    ch.grow = grow_noted
    push(3)
    driver.step()
    assert ch.overflow_replays == replays + 1
    assert grown == [before]
    assert len(counters.VALIDATED_TICKS) == before + 1
    rec = counters.VALIDATED_TICKS[-1]
    left = [e for e in rec["capacities"]
            if e[0] == node_scope(join) and e[1] == "left"]
    assert len(left) == 1 and 64 < left[0][4] == join.caps["left"]
    assert left[0][3] <= left[0][4]


# -- (4) the readers ----------------------------------------------------------


def _ctx(window: int, total: int) -> dict:
    import measures  # benchmark/measures.py

    run = {"step_done": {str(k): 1.0 + k for k in range(total - window,
                                                        total)},
           "open": 0.0, "close": 1e9}
    assert len(measures.window_ticks(run)) == window
    return {"run": run, "measures": measures}


def test_readers_none_without_the_record():
    from dbsp_tpu.timeseries import counters

    _, records, _ = _stepped_of("nexmark-q4.saturated")
    readers = {n: _load(os.path.join(_BENCH, "metrics", n + ".py"),
                        "metric_" + n) for n in READERS}
    saved = list(counters.VALIDATED_TICKS)
    try:
        counters.VALIDATED_TICKS.clear()  # an empty ring
        for r in readers.values():
            assert r.read(_ctx(3, 4)) is None
        # the parent: records of a circuit with time or top-K nodes only,
        # and without the capacities
        counters.VALIDATED_TICKS.extend(
            {"topk_gathered_rows": 5, "topk_gather_capacity_rows": 8}
            for _ in records)
        for r in readers.values():
            assert r.read(_ctx(3, 4)) is None
        counters.VALIDATED_TICKS.clear()  # a ring shorter than the window
        counters.VALIDATED_TICKS.extend(records[:2])
        for r in readers.values():
            assert r.read(_ctx(3, 4)) is None
    finally:
        counters.VALIDATED_TICKS.clear()
        counters.VALIDATED_TICKS.extend(saved)


def test_readers_read_a_recorded_window(capsys):
    from dbsp_tpu.timeseries import counters

    _, records, _ = _stepped_of("nexmark-q4.saturated")
    window = records[1:]
    readers = {n: _load(os.path.join(_BENCH, "metrics", n + ".py"),
                        "metric_" + n) for n in READERS}
    saved = list(counters.VALIDATED_TICKS)
    try:
        counters.VALIDATED_TICKS.clear()
        counters.VALIDATED_TICKS.extend(records)
        fills = sorted(100.0 * t["tick_live_rows"] / t["tick_capacity_rows"]
                       for t in window)
        got = readers["delta_lane_fill_pct"].read(_ctx(3, 4))
        assert got == fills[1] and 0 < got < 100  # the median of three
        capsys.readouterr()
        peak = readers["capacity_peak_fill_pct"].read(_ctx(3, 4))
        want = max(100.0 * e[3] / e[4] for t in window
                   for e in t["capacities"])
        assert peak == want and 0 < peak <= 100
        lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
        assert len(lines) == 1 and lines[0]["phase"] == "capacity_fill"
        fact = lines[0]
        assert fact["window_ticks"] == 3
        last = fact["last_tick"]
        assert [c[:5] for c in last] == sorted(
            (list(e) for e in window[-1]["capacities"]),
            key=lambda c: (-c[4], c[0], c[1]))
        assert max(c[5] for c in last) == peak
        for c in last:
            assert c[5] == max(100.0 * e[3] / e[4] for t in window
                               for e in t["capacities"]
                               if (e[0], e[1]) == (c[0], c[1]))
    finally:
        counters.VALIDATED_TICKS.clear()
        counters.VALIDATED_TICKS.extend(saved)


def test_readers_are_entries_of_every_cell():
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        m = entries[name]
        assert sorted(m["workloads"]) == sorted(CELLS)
        assert (m["source"], m["layer"], m["moves"], m["unit"]) == (
            "program_counter", "step program", "events_per_s", "%")
        for cell in CELLS:
            assert name in [x["name"] for x in
                            harness.load_cell(cell)["per_layer"]]
    assert entries["delta_lane_fill_pct"]["better"] == "higher"
    assert entries["capacity_peak_fill_pct"]["better"] == "lower"
