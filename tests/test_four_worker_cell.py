"""The four-worker deployment ``nexmark-q4-4w`` and its cell (ISSUE 30), on
virtual CPU devices: the cell's rehearsal, the accelerator formulations
under ``shard_map``, the served path against the benchmark's plain
reference with a bucket that overflows, the five per-layer readers, and the
spans a worker mesh adds to a tick. Every test runs under a time limit."""

import importlib.util
import json
import os
import signal
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_ROOT, "benchmark")
for _p in (_ROOT, _BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import chip_smoke  # noqa: E402
import measures  # noqa: E402 — benchmark/measures.py
import span_measures as sm  # noqa: E402 — benchmark/span_measures.py
from dbsp_tpu.obs.tracing import SpanRecorder  # noqa: E402
from dbsp_tpu.parallel import exchange  # noqa: E402
from dbsp_tpu.zset import kernels  # noqa: E402
from dbsp_tpu.zset.batch import Batch  # noqa: E402

LIMIT_S = 400
CELL = "nexmark-q4-4w.saturated"
W = 4


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


# -- (a) the cell as data, and its rehearsal ---------------------------------


def test_configuration_is_q4_with_the_source_s_workers():
    """``nexmark-q4-4w.json`` holds every key of ``nexmark-q4.json`` with
    its value, but for the worker count and what states it."""
    with open(os.path.join(_BENCH, "configs", "nexmark-q4.json")) as f:
        q4 = json.load(f)
    with open(os.path.join(_BENCH, "configs", "nexmark-q4-4w.json")) as f:
        q4w = json.load(f)
    changed = {"name", "source", "workers", "guarantees", "reduced"}
    for k, v in q4.items():
        if k not in changed:
            assert q4w[k] == v, k
    assert q4w["workers"] == W and set(q4w) - set(q4) == {"deployment"}
    assert q4w["guarantees"][:len(q4["guarantees"])] == q4["guarantees"]
    assert q4w["reduced"]["events"] == q4["reduced"]["events"]
    assert set(q4w["reduced"]) == {"events", "workers"}
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nexmark-q4-4w", "saturated", W)
    entry = {c["name"]: c for c in bench["configs"]}["nexmark-q4-4w"]
    assert entry["reduced"] == ["events", "workers"]
    assert entry["source"] == q4w["source"] and len(entry["source"]) <= 200
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in mine) == sorted(READERS)
    for m in mine:
        assert m["moves"] == "events_per_s"
        assert os.path.isfile(os.path.join(_BENCH, "metrics",
                                           m["name"] + ".py"))


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_correct_on_four_virtual_devices(trace, monkeypatch):
    """``run.py --workload nexmark-q4-4w.saturated --rehearse-events 600``
    on four virtual CPU devices, as ``selfcheck.py`` runs every cell."""
    import selfcheck

    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")
    line = selfcheck.rehearse(CELL, trace, seed=3000000111, events=600)
    assert line["correct"] is True and line["rehearsal"], line
    assert line["metrics"] == {} and line["failed"] == 0, line
    assert line["device"]["count"] == W
    assert line["compared"]["rows_mismatched"] == {"value": 0, "limit": 0}


# -- (b) the accelerator formulations inside shard_map ------------------------


def _mesh():
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:W]), ("workers",))


def _sharded_batch(cap: int, live: int, sorted_runs: bool, seed: int = 5):
    """A [W, cap] batch: per worker ``live`` rows over few distinct keys
    (so rows meet across workers and weights net, some to zero), either
    each slice consolidated (one sorted run) or in arrival order."""
    rng = np.random.default_rng(seed)
    keys0, keys1, vals, ws = [], [], [], []
    for _ in range(W):
        rows = {}
        while len(rows) < live:
            rows[(int(rng.integers(0, live // 2)),
                  int(rng.integers(0, 3)), int(rng.integers(0, 2)))] = \
                int(rng.choice([-1, 1, 2]))
        order = sorted(rows) if sorted_runs else list(rows)
        pad = cap - live
        keys0.append([r[0] for r in order] + [np.iinfo(np.int64).max] * pad)
        keys1.append([r[1] for r in order] + [np.iinfo(np.int64).max] * pad)
        vals.append([r[2] for r in order] + [np.iinfo(np.int32).max] * pad)
        ws.append([rows[r] for r in order] + [0] * pad)
    return Batch((jnp.asarray(keys0, jnp.int64),
                  jnp.asarray(keys1, jnp.int64)),
                 (jnp.asarray(vals, jnp.int32),), jnp.asarray(ws, jnp.int64),
                 runs=(cap,) if sorted_runs else None)


def _rows(batch: Batch, w: int) -> list:
    """Worker ``w``'s slice as ``[(row, weight)]`` in slice order; its dead
    tail must be packed behind the live rows."""
    ws = np.asarray(batch.weights[w])
    n = int((ws != 0).sum())
    assert not ws[n:].any(), "live rows are not packed to the front"
    cols = [np.asarray(c[w])[:n].tolist() for c in batch.cols]
    return list(zip(zip(*cols), ws[:n].tolist()))


def _want_after_exchange(batch: Batch) -> list:
    """Plain Python: every live row lands on the worker its first key
    hashes to, equal rows net, zero rows go, each worker's rows sorted."""
    dest = np.asarray(exchange.worker_of(batch.keys[0].reshape(-1), W))
    ws = np.asarray(batch.weights).reshape(-1)
    cols = [np.asarray(c).reshape(-1).tolist() for c in batch.cols]
    per = [{} for _ in range(W)]
    for i, (d, w) in enumerate(zip(dest.tolist(), ws.tolist())):
        if w:
            row = tuple(c[i] for c in cols)
            per[d][row] = per[d].get(row, 0) + w
    return [sorted((r, w) for r, w in p.items() if w) for p in per]


EXCHANGE_CASES = {
    # name: (cap, live, slices consolidated, formulations the accelerator
    # dispatch must take)
    "sorted_runs_fold_merges": (4096, 3000, True,
                                {("merge", "xla_bitonic")}),
    "arrival_order_sorts": (4096, 3000, False,
                            {("sort_merge", "xla_bitonic"),
                             ("compact", "xla_shift")}),
    "small_bucket_one_chunk": (256, 100, False, {("compact", "xla_shift")}),
}


@pytest.mark.parametrize("name", EXCHANGE_CASES)
def test_exchange_accelerator_formulations_under_shard_map(
        name, accelerator_dispatch):
    """Bucketize + all_to_all + consolidate per worker on four virtual
    devices with the accelerator formulations (the bitonic merge network,
    the chunked sort's merge levels, the shift compaction): the rows the
    CPU formulations give, which are the plain recomputation's."""
    cap, live, sorted_runs, must_take = EXCHANGE_CASES[name]
    batch = _sharded_batch(cap, live, sorted_runs)
    want = _want_after_exchange(batch)
    before = dict(kernels.KERNEL_DISPATCH_COUNTS)
    got = jax.jit(exchange.spmd(
        _mesh(), lambda b: exchange.exchange_local(b, W)))(batch)
    took = {k for k, n in kernels.KERNEL_DISPATCH_COUNTS.items()
            if n > before.get(k, 0)}
    assert must_take <= took, took
    assert got.cap == W * cap
    for w in range(W):
        assert _rows(got, w) == want[w], (name, w)
    # the CPU formulations, on the same batch
    jax.clear_caches()
    jax.default_backend = lambda: "cpu"  # the fixture restores it
    cpu = jax.jit(exchange.spmd(
        _mesh(), lambda b: exchange.exchange_local(b, W)))(batch)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(cpu)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_probe_by_merge_under_shard_map(accelerator_dispatch):
    """The ladder probe of a sorted delta, each worker ranking its own
    slice in its own levels by the merge network inside ``shard_map``: lane
    for lane the binary search of that worker's slices."""
    from dbsp_tpu.zset import cursor

    delta = _sharded_batch(256, 150, True, seed=6)
    levels = [_sharded_batch(1024, 700, True, seed=7),
              _sharded_batch(128, 90, True, seed=8)]
    assert all(kernels.rank_by_merge(256, lvl.cap, 2) for lvl in levels)
    for side in ("left", "right"):
        before = dict(kernels.KERNEL_DISPATCH_COUNTS)
        got = np.asarray(jax.jit(exchange.spmd(
            _mesh(), lambda d, lv: cursor.lex_probe_ladder(
                [x.keys for x in lv], d.keys, side,
                sorted_queries=d.sorted_runs == 1)))(delta, levels))
        took = {k for k, n in kernels.KERNEL_DISPATCH_COUNTS.items()
                if n > before.get(k, 0) and k[0] == "probe_ladder"}
        assert took == {("probe_ladder", "xla_merge")}
        for w in range(W):
            for k, lvl in enumerate(levels):
                want = kernels._probe_search(
                    tuple(c[w] for c in lvl.keys),
                    tuple(c[w] for c in delta.keys), side)
                assert np.array_equal(got[w, k], np.asarray(want)), (w, k)


def test_gather_and_shard_batch_accelerator_formulations(accelerator_dispatch):
    """The other two boundaries of a sharded circuit under the same
    dispatch: ``shard_batch`` (input handle) places every row on the worker
    its key hashes to; ``gather_local`` (CUnshard) gives every worker the
    netted union."""
    flat = _sharded_batch(1024, 700, True)
    one = jax.tree_util.tree_map(lambda a: a[0], flat).tagged((1024,))
    sharded = exchange.shard_batch(one, _mesh())
    dest = np.asarray(exchange.worker_of(one.keys[0], W))
    live = np.asarray(one.weights) != 0
    for w in range(W):
        rows = _rows(sharded, w)
        assert len(rows) == int((live & (dest == w)).sum())
        assert rows == sorted(rows)
        assert all(int(exchange.worker_of(
            jnp.asarray([r[0][0]], jnp.int64), W)[0]) == w for r in rows)
    assert len({len(x.sharding.device_set)
                for x in jax.tree_util.tree_leaves(sharded)}) == 1
    union: dict = {}
    for w in range(W):
        for row, wt in _rows(flat, w):
            union[row] = union.get(row, 0) + wt
    want = sorted((r, wt) for r, wt in union.items() if wt)
    got = jax.jit(exchange.spmd(_mesh(), exchange.gather_local))(flat)
    for w in range(W):
        assert _rows(got, w) == want
    assert exchange.unshard_batch(sharded).to_dict() == one.to_dict()


# -- (c), (e) the served path on a worker mesh --------------------------------

INPUTS = (("persons", ("id", "name", "city", "state", "email", "date_time")),
          ("auctions", ("id", "item", "seller", "category", "initial_bid",
                        "reserve", "date_time", "expires")),
          ("bids", ("auction", "bidder", "price", "channel", "date_time")))


class _Served:
    """q4 behind ``CircuitServer`` as ``benchmark/run.py`` builds it, with
    ``workers`` workers; ticks are pushed as columns over HTTP."""

    def __init__(self, workers: int):
        import dbsp_tpu  # noqa: F401
        from dbsp_tpu.circuit import Runtime
        from dbsp_tpu.compiled.driver import CompiledCircuitDriver
        from dbsp_tpu.io import Catalog
        from dbsp_tpu.io.controller import Controller, ControllerConfig
        from dbsp_tpu.io.server import CircuitServer
        from dbsp_tpu.nexmark import build_inputs, model as M, queries

        def build(c):
            streams, handles = build_inputs(c)
            return handles, queries.q4(*streams).output()

        handle, (handles, out) = Runtime.init_circuit(workers, build)
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
        self.rec = SpanRecorder(max_steps=64)
        self.driver.spans = self.ctl.spans = self.srv.spans = self.rec
        self.srv.start()
        self.base = f"http://127.0.0.1:{self.srv.port}"
        self.acked = {rel: {c: [] for c in cols} for rel, cols in INPUTS}

    def tick(self, cols: dict) -> None:
        """``cols[relation][column]`` arrays: push (three POSTs), step."""
        for rel, names in INPUTS:
            if len(cols[rel][names[0]]) == 0:
                continue
            chip_smoke._http(
                f"{self.base}/input_endpoint/{rel}?format=json",
                data=chip_smoke._ndjson([cols[rel][c] for c in names]))
            for c in names:
                self.acked[rel][c].extend(np.asarray(cols[rel][c]).tolist())
        chip_smoke._http(self.base + "/step", data=b"")
        deadline = time.monotonic() + 10.0
        while self.rec.open_threads() and time.monotonic() < deadline:
            time.sleep(0.001)

    def view(self) -> dict:
        view = chip_smoke._http(self.base + "/view/q4")
        return {tuple(r[:-1]): r[-1] for r in view["rows"]}

    def close(self):
        self.srv.stop()
        self.ctl.stop()


def _skewed_tick(acked: dict, n_bids: int) -> dict:
    """A hand-built batch: ``n_bids`` distinct bids, every one on an
    auction whose id hashes to worker 0, all inside their auctions' time
    ranges; no person, no auction."""
    ids = np.asarray(acked["auctions"]["id"], np.int64)
    on0 = ids[np.asarray(exchange.worker_of(jnp.asarray(ids), W)) == 0]
    assert len(on0) >= 4, "the seeded ticks left worker 0 too few auctions"
    start = dict(zip(acked["auctions"]["id"], acked["auctions"]["date_time"]))
    i = np.arange(n_bids)
    auction = on0[i % len(on0)]
    return {
        "persons": {c: np.zeros((0,), np.int64) for c in INPUTS[0][1]},
        "auctions": {c: np.zeros((0,), np.int64) for c in INPUTS[1][1]},
        "bids": {"auction": auction,
                 "bidder": 1000 + i,
                 "price": 10_000_000 + 7 * i,
                 "channel": np.zeros(n_bids, np.int32),
                 "date_time": np.asarray([start[a] for a in auction.tolist()])
                 + 1},
    }


def test_served_four_workers_skewed_batch_overflows_and_replays():
    """Served q4 at four workers against the benchmark's plain reference:
    two seeded ticks, then a batch whose every bid lands on worker 0 and
    overflows its input bucket. The overflow is a replay, the view is
    exact, and the counters read what the batch implies."""
    import generator
    from dbsp_tpu.compiled import cnodes

    reference = _load(os.path.join(_BENCH, "references", "q4.py"), "ref_q4")
    gen = generator.NexmarkGenerator(generator.GeneratorConfig(seed=77))
    s = _Served(W)
    try:
        for k in range(2):
            s.tick(gen.generate(k * 600, (k + 1) * 600))
        assert s.view() == reference.recompute(s.acked)
        bids_node = next(cn.node.index for cn in s.driver.ch.cnodes
                         if isinstance(cn, cnodes.CInput)
                         and len(cn.op.key_dtypes) + len(cn.op.val_dtypes)
                         == len(INPUTS[2][1]))
        rows0, cap0 = exchange.EXCHANGE_SITE_ROWS[("input", bids_node)]
        assert 0 < rows0 <= cap0
        overflows0 = dict(exchange.EXCHANGE_OVERFLOW_COUNTS)
        replays0 = s.driver.ch.overflow_replays
        n_bids = 2 * cap0  # more than the bucket holds, all on one worker
        s.tick(_skewed_tick(s.acked, n_bids))
        assert s.view() == reference.recompute(s.acked)
        assert s.driver.ch.overflow_replays > replays0
        assert exchange.EXCHANGE_OVERFLOW_COUNTS["input"] \
            > overflows0.get("input", 0)
        rows, cap = exchange.EXCHANGE_SITE_ROWS[("input", bids_node)]
        assert rows == n_bids, "the worst worker holds the whole batch"
        assert cap >= n_bids and cap & (cap - 1) == 0 and cap > cap0
        # the reader's arithmetic over the same counters
        reader = _load(os.path.join(_BENCH, "metrics",
                                    "exchange_overflow_replays.py"), "m_eor")
        assert reader.read({"config": {"workers": W}}) == float(
            sum(exchange.EXCHANGE_OVERFLOW_COUNTS.values()))
        assert {k[0] for k in exchange.EXCHANGE_SITE_ROWS} == {
            "input", "exchange"}
        pad = _load(os.path.join(_BENCH, "metrics",
                                 "exchange_padding_pct.py"), "m_epp")
        assert 0.0 <= pad.read({}) < 100.0
    finally:
        s.close()


@pytest.mark.parametrize("workers", [1, W])
def test_mesh_spans_only_under_a_worker_mesh(workers):
    """``tick.shard_inputs`` (inside each ``tick.build_inputs``) and
    ``tick.unshard_outputs`` (inside ``tick.deliver``) are a worker mesh's:
    a one-worker tick records neither and pays for neither."""
    import generator

    gen = generator.NexmarkGenerator(generator.GeneratorConfig(seed=5))
    s = _Served(workers)
    try:
        s.tick(gen.generate(0, 400))
        s.tick(gen.generate(400, 800))
    finally:
        s.close()
    spans = sm.closed_spans(s.rec.events())
    ticks = [x for x in spans if x.name == "tick"]
    assert len(ticks) == 2
    for tick in ticks:
        shard = [x for x in tick.descendants()
                 if x.name == "tick.shard_inputs"]
        unshard = [x for x in tick.descendants()
                   if x.name == "tick.unshard_outputs"]
        if workers == 1:
            assert shard == [] and unshard == []
            continue
        assert len(shard) == 3 and len(unshard) == 1
        for x in shard:
            assert x.parent.name == "tick.build_inputs"
            assert x.args["workers"] == W and x.args["rows"] >= 8
            assert x.seconds <= x.parent.seconds
        assert unshard[0].parent.name == "tick.deliver"
        assert unshard[0].args["workers"] == W
        # no new phase directly under the tick: the fact line tick_phases
        # of a traced run lists the same names with any worker count
        assert not any(c.name in ("tick.shard_inputs", "tick.unshard_outputs")
                       for c in tick.children)


def test_step_program_has_one_input_placement():
    """A state leaf that a host-side program hands back under another
    sharding (on the chip a drain's emptied level came back replicated) is
    put back on its workers before the step: no new SPMD step program, the
    same view. (That a drain returns both levels one slice a worker is
    asked of the TPU's compiler in tests/test_tpu_compile.py.)"""
    import generator
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.compiled import cnodes
    from dbsp_tpu.compiled.driver import CompiledCircuitDriver
    from dbsp_tpu.nexmark import build_inputs, queries
    from dbsp_tpu.testing import retrace

    def build(c):
        streams, handles = build_inputs(c)
        return handles, queries.q4(*streams).output()

    handle, (handles, out) = Runtime.init_circuit(W, build)
    driver = CompiledCircuitDriver(handle, validate_every=1)
    cols = generator.NexmarkGenerator(
        generator.GeneratorConfig(seed=9)).generate(0, 800)

    def tick() -> int:
        """The same batch again (weights add, no new row: the shapes
        stay); step programs asked of the compiler meanwhile."""
        before = retrace.compile_counts().get("step_fn", 0)
        for h, (rel, names) in zip(handles, INPUTS):
            h.extend([(r, 1) for r in zip(*(cols[rel][c].tolist()
                                            for c in names))])
        driver.step()
        return retrace.compile_counts().get("step_fn", 0) - before

    ch = driver.ch
    with retrace.session():
        traced = [tick()]
        while traced[-1] and len(traced) < 12:  # capacities settle
            traced.append(tick())
        assert traced[0] >= 1 and traced[-1] == 0, traced
        view, n = out.to_dict(), len(traced)
        key, st = next((k, v) for k, v in ch.states.items() if isinstance(
            ch.by_index[int(k)], cnodes._Leveled) and len(v[0]) > 1)
        replicated = NamedSharding(ch.mesh, P())
        levels, base = st
        ch.states[key] = ((jax.device_put(levels[0], replicated),
                           *levels[1:]), base)
        assert all(x.sharding.is_fully_replicated for x in
                   jax.tree_util.tree_leaves(ch.states[key][0][0]))
        assert tick() == 0, "a replicated leaf cost a new step program"
    assert not any(x.sharding.is_fully_replicated
                   for x in jax.tree_util.tree_leaves(ch.states))
    assert view == out.to_dict(), "the same rows, whatever their weights"
    assert n < 12


# -- (d) the five readers on a ctx written by hand ----------------------------

READERS = ("exchange_device_ms", "shard_inputs_ms", "exchange_padding_pct",
           "exchange_overflow_replays", "state_balance_pct")

OPS = [["%fusion.12 fusion u32[131072]", 0.5],
       ["%all-to-all.3 all-to-all u32[4,4096]", 0.004],
       ["%all-gather-start.7 all-gather-start u32[64]", 0.0015],
       ["%all-gather-done.7 all-gather-done u32[64]", 0.0005],
       ["%while.5 while u32[4096]", 2.0],
       ["bench.trace_begin", 0.1]]


def _span_events(with_shard: bool) -> list:
    """Two window ticks (3, 4) written by hand, as the ring holds them:
    tick k's three ``tick.build_inputs`` with a ``tick.shard_inputs`` of
    (10 + k) ms inside each."""
    ev, t = [], 0.0

    def span(name, dur_ms, args=None, children=()):
        nonlocal t
        ev.append({"name": name, "ph": "B", "ts": t * 1e3, "tid": 1,
                   "args": args or {}})
        start = t
        for c in children:
            span(*c)
        t = max(t, start + dur_ms)
        ev.append({"name": name, "ph": "E", "ts": t * 1e3, "tid": 1})

    for k in (3, 4):
        span("ingest", 5.0, {"trace": f"b{k}"})
        inputs = [("tick.build_inputs", 40.0, {}, (
            [("tick.shard_inputs", 10.0 + k, {"workers": 4})]
            if with_shard else [])) for _ in range(3)]
        span("step_request", 200.0, {}, [
            ("tick", 190.0, {"tick": k, "batches": [f"b{k}"]}, inputs)])
    return ev


def _ctx(devices: int = 4, ops=OPS, workers: int = 4,
         with_shard: bool = True) -> dict:
    run = {"open": 0.0, "close": 10.0,
           "step_done": {"3": 1.0, "4": 2.0}, "step_sent": {}, "push": {},
           "visible": {}, "ops": [], "reads": []}
    ctx = {"run": run, "measures": measures, "config": {"workers": workers},
           "traffic": {"trace_ticks": 2}, "memory_peak_bytes": 1,
           "trace": {"devices": devices, "ops": ops}}
    ctx["span_window"] = sm.window(_span_events(with_shard), run, measures)
    return ctx


READER_CASES = [
    # (reader, ctx, program counters, what it reads)
    ("exchange_device_ms", _ctx(devices=4), None,
     1e3 * (0.004 + 0.0015 + 0.0005) / 4 / 2),
    ("exchange_device_ms", _ctx(devices=1), None,
     1e3 * (0.004 + 0.0015 + 0.0005) / 1 / 2),
    ("exchange_device_ms", _ctx(devices=1, ops=OPS[:1] + OPS[4:]), None,
     None),
    ("exchange_device_ms", {**_ctx(), "trace": None}, None, None),
    ("shard_inputs_ms", _ctx(), None, 3 * 13.0),  # nearest-rank median
    ("shard_inputs_ms", _ctx(with_shard=False), None, None),
    ("shard_inputs_ms", {**_ctx(), "span_window": None}, None, None),
    ("exchange_padding_pct", _ctx(),
     {"sites": {("input", 2): (9000, 32768), ("exchange", 12): (1000, 4096)}},
     100.0 * (1 - 10000 / 36864)),
    ("exchange_padding_pct", _ctx(), {"sites": {}}, None),
    ("exchange_overflow_replays", _ctx(),
     {"overflows": {"input": 3, "exchange": 1}}, 4.0),
    ("exchange_overflow_replays", _ctx(), {"overflows": {}}, 0.0),
    ("exchange_overflow_replays", _ctx(workers=1),
     {"overflows": {"input": 3}}, None),
    ("state_balance_pct", _ctx(workers=1), None, None),
    # the CPU backend reports no memory statistics: nothing to read
    ("state_balance_pct", _ctx(workers=4), None, None),
]


@pytest.mark.parametrize(
    "reader,ctx,counters,want", READER_CASES,
    ids=[f"{c[0]}-{i}" for i, c in enumerate(READER_CASES)])
def test_new_readers_on_a_synthetic_ctx(reader, ctx, counters, want,
                                        monkeypatch):
    if counters is not None:
        monkeypatch.setattr(exchange, "EXCHANGE_SITE_ROWS",
                            dict(counters.get("sites", {})))
        monkeypatch.setattr(exchange, "EXCHANGE_OVERFLOW_COUNTS",
                            dict(counters.get("overflows", {})))
    mod = _load(os.path.join(_BENCH, "metrics", reader + ".py"),
                "metric_" + reader)
    got = mod.read(ctx)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-12)


def test_state_balance_arithmetic():
    mod = _load(os.path.join(_BENCH, "metrics", "state_balance_pct.py"),
                "metric_state_balance_pct")
    assert mod.balance_pct([400, 300, 350, 380]) == pytest.approx(75.0)
    assert mod.balance_pct([400, 0, 350, 380]) is None
    assert mod.balance_pct([400, None, 350, 380]) is None
    assert mod.balance_pct([400]) is None
