#!/usr/bin/env python3
"""A benchmark configuration served on the CPU as ``benchmark/run.py`` serves
it, with what the capacity planning did recorded tick by tick: the
capacities before and after the harness's presize and at the end, the
overflow replays and step programs traced in every tick, each leveled
trace's slot decision, the validated requirements of every tick and the
program's ``VALIDATED_TICKS`` records. Two trees compared key for key is how
a change to ``compiled/`` shows that it leaves a cell's planning alone
(PR 36, PR 38).

    JAX_PLATFORMS=cpu python tools/rehearse_capacities.py --config nexmark-q4 \\
        --events 6000 --ticks 12 --seed 3 --out q4.json [--tree <checkout>]

``--tree`` serves another checkout's program and harness (a parent made by
``git archive``); four workers need
``XLA_FLAGS=--xla_force_host_platform_device_count=4``. ``--rate`` sets the
generator's ``first_event_rate`` (q5 at a small tick). No chip: the numbers
are counts and capacities, never times of a device.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.request


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True,
                    help="a file of benchmark/configs, without .json")
    ap.add_argument("--events", type=int, help="events a tick")
    ap.add_argument("--rate", type=int, help="first_event_rate")
    ap.add_argument("--ticks", type=int, default=43)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--tree", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [args.tree, os.path.join(args.tree, "benchmark")]

    import generator
    import loadgen
    import run as harness

    with open(os.path.join(args.tree, "benchmark", "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    if args.events:
        config["events_per_tick"] = args.events
    if args.rate:
        config["generator"]["first_event_rate"] = args.rate

    import jax.numpy as jnp

    import dbsp_tpu  # noqa: F401
    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.compiled.driver import CompiledCircuitDriver
    from dbsp_tpu.io import Catalog
    from dbsp_tpu.io.controller import Controller, ControllerConfig
    from dbsp_tpu.io.server import CircuitServer
    from dbsp_tpu.nexmark import build_inputs, model as M, queries
    from dbsp_tpu.testing import retrace

    def build(c):
        streams, handles = build_inputs(c)
        return handles, getattr(queries, config["query"])(*streams).output()

    def http(url, data=None):
        req = urllib.request.Request(
            url, data=data, method="GET" if data is None else "POST")
        with urllib.request.urlopen(req, timeout=3000) as r:
            return json.loads(r.read())

    def name(cn):
        return f"{cn.node.index}.{type(cn).__name__}"

    def caps(ch):
        return {f"{name(cn)}.{k}": v for cn in ch.cnodes
                for k, v in sorted(cn.caps.items())}

    def slots(ch):
        return {name(cn): [getattr(cn, "_slot_cap", None),
                           bool(getattr(cn, "_no_slots", False))]
                for cn in ch.cnodes if hasattr(cn, "level_keys")}

    def reqs(ch):
        return {f"{name(cn)}.{k}": int(r)
                for (cn, k), r in zip(ch._checks, ch.last_req)}

    handle, (handles, out) = Runtime.init_circuit(config["workers"], build)
    driver = CompiledCircuitDriver(
        handle, validate_every=config["controller"]["validate_every"])
    ch = driver.ch
    catalog = Catalog()
    for rel, h, dts in (
            ("persons", handles[0], M.PERSON_KEY + M.PERSON_VALS),
            ("auctions", handles[1], M.AUCTION_KEY + M.AUCTION_VALS),
            ("bids", handles[2], M.BID_KEY + M.BID_VALS)):
        catalog.register_input(rel, h, dts)
    catalog.register_output(
        config["view"], out,
        tuple(getattr(jnp, d) for d in config["view_dtypes"]))
    ctl = Controller(driver, catalog, ControllerConfig(
        min_batch_records=config["controller"]["min_batch_records"],
        flush_interval_s=config["controller"]["flush_interval_s"]))
    srv = CircuitServer(ctl)
    srv.start()
    base = f"http://127.0.0.1:{srv.port}"
    bodies = loadgen.make_bodies(config, args.seed, args.ticks)
    rec: dict = {"config": args.config, "events": config["events_per_tick"],
                 "seed": args.seed, "ticks": []}
    try:
        with retrace.session():
            for k in range(args.ticks):
                for rel in generator.COLUMNS:
                    http(f"{base}/input_endpoint/{rel}?format=json",
                         bodies[k][rel])
                r0 = ch.overflow_replays
                p0 = retrace.compile_counts().get("step_fn", 0)
                t0 = time.monotonic()
                http(base + "/step", b"")
                tick = {"k": k, "step_s": time.monotonic() - t0,
                        "replays": ch.overflow_replays - r0,
                        "traced": retrace.compile_counts().get(
                            "step_fn", 0) - p0}
                if k == 0:
                    rec["caps_before_presize"] = caps(ch)
                    harness.presize(ch, config)
                    rec["caps_after_presize"] = caps(ch)
                    rec["slots_after_presize"] = slots(ch)
                tick["reqs"] = reqs(ch)
                rec["ticks"].append(tick)
                print(json.dumps({key: tick[key] for key in (
                    "k", "step_s", "replays", "traced")}), flush=True)
            rec["caps_end"] = caps(ch)
            rec["slots_end"] = slots(ch)
            try:
                from dbsp_tpu.timeseries.counters import VALIDATED_TICKS
                rec["validated_ticks"] = list(VALIDATED_TICKS)
            except ImportError:  # a tree from before the ring
                pass
            rec["view_rows"] = len(
                http(f"{base}/view/{config['view']}")["rows"])
    finally:
        srv.stop()
        ctl.stop()
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
