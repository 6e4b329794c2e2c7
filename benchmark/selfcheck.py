#!/usr/bin/env python3
"""The benchmark's rehearsals, which need no chip (``on-chip-measurement``
guide, section 2). Run here, on the CPU, before a chip call:

    JAX_PLATFORMS=cpu python benchmark/selfcheck.py            # 1, 2, 3
    JAX_PLATFORMS=cpu python benchmark/selfcheck.py --compile nexmark-q3.saturated

1. ``generator``: the benchmark's copy of the NEXmark generator equals the
   program's ``NexmarkGenerator.generate`` at two seeds.
2. ``cells``: every cell of ``BENCHMARK.json`` end to end at a tiny tick
   (``run.py --rehearse-events``): the line printed says ``correct`` and
   carries no metric.
3. ``trace``: the reduction of ``trace_reduce.py`` over the small trace
   recorded on the chip (``testdata/trace_small.xplane.pb``) gives the
   numbers written beside it (``testdata/trace_small.expected.json``).
4. ``--compile <cell>``: the cell's step program, at the capacities its
   full-size ticks and presize give, compiled for a *described* v5e chip.
   Slow (it runs two full-size ticks on the CPU first); nothing runs on a
   device, so it says nothing about results or times.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)


def check_generator() -> None:
    import numpy as np

    import generator as G
    from dbsp_tpu.nexmark import GeneratorConfig, NexmarkGenerator

    for seed in (1, 2**31 + 12345):
        mine = G.NexmarkGenerator(G.GeneratorConfig(seed=seed)).generate(
            39_990, 81_234)
        theirs = NexmarkGenerator(GeneratorConfig(seed=seed)).generate(
            39_990, 81_234)
        for rel, names in G.COLUMNS.items():
            assert tuple(mine[rel]) == tuple(theirs[rel]) == names, rel
            for c in names:
                assert mine[rel][c].dtype == theirs[rel][c].dtype, (rel, c)
                assert np.array_equal(mine[rel][c], theirs[rel][c]), (rel, c)
    print("selfcheck generator: copy equals the program's at 2 seeds")


def rehearse(workload: str, trace: int, seed: int = 5,
             events: int = 500) -> dict:
    """One tiny run of a cell in a process of its own; its result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         workload, "--seed", str(seed), "--seconds", "2", "--trace",
         str(trace), "--rehearse-events", str(events)],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    if p.returncode != 0:
        raise AssertionError(f"{workload}: rc {p.returncode}\n"
                             f"{p.stderr[-3000:]}")
    return json.loads(p.stdout.strip().splitlines()[-1])


def check_cells() -> None:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        for trace in (0, 1):
            line = rehearse(w["name"], trace)
            assert line["correct"] is True, (w["name"], line)
            assert line["metrics"] == {} and line["rehearsal"], line
            assert line["failed"] == 0 and line["attempted"] > 0, line
        print(f"selfcheck cells: {w['name']} rehearsed, correct, no metric")


def check_trace() -> None:
    import trace_reduce

    path = os.path.join(HERE, "testdata", "trace_small.xplane.pb")
    with open(os.path.join(HERE, "testdata",
                           "trace_small.expected.json")) as f:
        want = json.load(f)
    got = trace_reduce.reduce_trace(path)
    assert got["devices"] == want["devices"], got["devices"]
    for key in ("window_s", "busy_s"):
        assert abs(got[key] - want[key]) <= 1e-9 * max(1.0, want[key]), (
            key, got[key], want[key])
    assert 0 < got["busy_s"] < got["window_s"]
    for name, (n, s) in want["modules"].items():
        assert got["modules"][name][0] == n, (name, got["modules"])
        assert abs(got["modules"][name][1] - s) <= 1e-9, name
    assert got["ops"][0][0] == want["top_op"], got["ops"][:3]
    assert got["whole_modules"] == want["whole_modules"]
    assert "bench.trace_begin" in got["marks"]
    # the arithmetic itself, on intervals written out by hand
    merged, total = trace_reduce.union_ns(
        [(0, 10), (5, 12), (20, 30), (22, 25), (30, 31)])
    assert merged == [[0, 12], [20, 31]] and total == 23
    print("selfcheck trace: reduction of the recorded trace as expected")


def compile_step_for_v5e(workload: str) -> None:
    """Rehearsal 3 of the guide for one cell's step program."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["DBSP_TPU_NATIVE"] = "0"
    import math
    import time

    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    import run as harness

    spec = harness.load_cell(workload)
    config = spec["config"]
    # steer the backend-keyed dispatch to its accelerator branches, as
    # tests/test_tpu_compile.py does (never an option of the program)
    jax.default_backend = lambda: "tpu"

    import dbsp_tpu  # noqa: F401
    import generator
    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.compiled.driver import CompiledCircuitDriver
    from dbsp_tpu.nexmark import build_inputs, queries

    def build(c):
        streams, handles = build_inputs(c)
        return handles, getattr(queries, config["query"])(*streams).output()

    handle, (handles, _) = Runtime.init_circuit(1, build)
    driver = CompiledCircuitDriver(handle, validate_every=1)
    gen = generator.from_config(config, seed=1)
    n = config["events_per_tick"]
    def feed(k: int) -> None:
        cols = gen.generate(k * n, (k + 1) * n)
        for h, (rel, names) in zip(handles, generator.COLUMNS.items()):
            h.extend([(r, 1) for r in zip(*(cols[rel][c].tolist()
                                            for c in names))])

    t0 = time.monotonic()
    for k in range(2):
        feed(k)
        driver.step()
        if k == 0:
            driver.ch.presize(ratio=config["assumed"]["presize_ratio"])
    print(f"selfcheck compile: two full-size ticks on the CPU in "
          f"{time.monotonic() - t0:.1f} s; capturing the step's arguments")
    ch = driver.ch
    captured = {}
    real = ch._step_jit

    def record(*args):
        captured["args"] = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        return real(*args)

    ch._step_jit = record
    feed(2)
    driver.step()
    ch._step_jit = real

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    shapes = jax.tree_util.tree_map(
        lambda sd: jax.ShapeDtypeStruct(sd.shape, sd.dtype,
                                        sharding=one_chip),
        captured["args"])
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    t0 = time.monotonic()
    compiled = ch._make_step().lower(*shapes).compile()
    state_bytes = sum(
        sd.dtype.itemsize * math.prod(sd.shape)
        for sd in jax.tree_util.tree_leaves(captured["args"][0]))
    print(f"selfcheck compile: {workload} step program compiled for a "
          f"described v5e chip in {time.monotonic() - t0:.1f} s; state "
          f"{state_bytes} bytes; {compiled.memory_analysis()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--only", choices=("generator", "cells", "trace"))
    ap.add_argument("--compile", metavar="CELL")
    args = ap.parse_args(argv)
    if args.compile:
        compile_step_for_v5e(args.compile)
        return 0
    for name, fn in (("generator", check_generator), ("cells", check_cells),
                     ("trace", check_trace)):
        if args.only in (None, name):
            fn()
    print("selfcheck: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
