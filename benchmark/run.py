#!/usr/bin/env python3
"""One run of one cell of the benchmark, on the chip.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The harness has no list of names of its own. It reads ``BENCHMARK.json`` at
the root of the checkout and finds by name: the configuration's file
(``configs/``), the traffic mix (``traffic/<traffic>.json``), each per-layer
metric's reader (``metrics/<metric>.py``), the plain reference
(``references/<reference>.py``) and the device's peaks (``peaks.json``).

This process holds the chip and serves: it builds the query with
``Runtime.init_circuit``, runs it under ``CompiledCircuitDriver`` and
``Controller`` behind ``CircuitServer`` (as ``chip_smoke.run_served`` does).
The load generator is a child process (``loadgen.py``: no JAX, no
``dbsp_tpu``, HTTP only) that makes its events from ``--seed``.

Set-up (``setup_s``: process start to window open) = load, build, and the
set-up ticks with their compiles and the one presize after tick 0 (its
validated requirements feed it; tick 1 still replays, CHANGES.md, PR 26).
The window is the traffic file's; nothing the harness can warm is left to
compile inside it. After the window: peak
memory is read, the served view is fetched, the program's state is freed,
and the plain reference recomputes the view from every acknowledged event.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result. ``--rehearse-events N`` is the rehearsal on the CPU at a
tiny tick: it prints a line with no metric in it.

Last line of stdout: one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (``--trace 1``: ``breakdown`` too) and,
last, ``compared``: each number compared beside its limit. Earlier lines
are facts of the run, one JSON object each.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import queue
import shutil
import subprocess
import sys
import threading
import time

T_START = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import measures  # noqa: E402
import trace_reduce  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_trace")
CHILD_REPLY_TIMEOUT_S = 1180.0


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str) -> dict:
    """Everything the cell names, found through ``BENCHMARK.json``."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in "
                         f"BENCHMARK.json; have {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    traffic_path = os.path.join(HERE, "traffic", cell["traffic"] + ".json")

    def applies(m):
        return workload in m.get("workloads", [workload])

    return {
        "cell": cell,
        "config_path": os.path.join(ROOT, entry["file"]),
        "config": load_json(os.path.join(ROOT, entry["file"])),
        "traffic_path": traffic_path,
        "traffic": load_json(traffic_path),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


class Child:
    """The load generator process and its line protocol."""

    def __init__(self, spec: dict, seed: int, rehearse_events: int | None):
        cmd = [sys.executable, os.path.join(HERE, "loadgen.py"),
               "--config", spec["config_path"],
               "--traffic", spec["traffic_path"], "--seed", str(seed)]
        if rehearse_events:
            cmd += ["--events-per-tick", str(rehearse_events)]
        self.proc = subprocess.Popen(cmd, stdin=subprocess.PIPE,
                                     stdout=subprocess.PIPE, text=True,
                                     bufsize=1)
        self.events: queue.Queue = queue.Queue()
        self._reader = threading.Thread(target=self._read, daemon=True,
                                        name="loadgen-stdout")
        self._reader.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            line = line.strip()
            if line:
                self.events.put(json.loads(line))
        self.events.put({"ev": "eof"})

    def send(self, **cmd) -> None:
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()

    def wait_for(self, ev: str, on_event=None) -> dict:
        """The next event named ``ev``; others go to ``on_event``."""
        deadline = time.monotonic() + CHILD_REPLY_TIMEOUT_S
        while True:
            try:
                obj = self.events.get(timeout=max(
                    0.1, deadline - time.monotonic()))
            except queue.Empty:
                raise RuntimeError(f"load generator: no {ev!r} in time")
            if obj["ev"] == ev:
                return obj
            if obj["ev"] == "eof":
                raise RuntimeError(f"load generator ended before {ev!r}")
            if on_event is not None:
                on_event(obj)

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.send(cmd="exit")
                self.proc.stdin.close()
                self.proc.wait(timeout=10)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
        self.proc.wait()
        self._reader.join(timeout=5)


class CompileMeter:
    """What JAX asks of the compiler, from its own monitoring events
    (copied from ``chip_smoke._CompileMeter``), with the time of each:
    ``events`` rows are ``(monotonic time, seconds)``."""

    def __init__(self):
        from jax import monitoring

        self.events: list = []
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.monotonic(), secs))

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def requests(self) -> int:
        return len(self.events)

    @property
    def seconds(self) -> float:
        return sum(s for _, s in self.events)

    def close(self) -> None:
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)


class Tracer:
    """The profiler around a stretch of the run, bracketed by two
    annotations that also tie the trace's clock to ``time.monotonic``."""

    def __init__(self, subdir: str):
        self.dir = os.path.join(TRACE_DIR, subdir)
        self.begin_mono = self.end_mono = None
        self.active = False
        self.reduced: dict | None = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # no Python frames: the trace of a
        opts.host_tracer_level = 1     # whole tick must reduce in seconds
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.active = True
        with jax.profiler.TraceAnnotation("bench.trace_begin"):
            self.begin_mono = time.monotonic()

    def stop(self) -> None:
        """End the trace, reduce it into ``reduced`` and delete the file."""
        import jax

        if not self.active:
            return
        with jax.profiler.TraceAnnotation("bench.trace_end"):
            self.end_mono = time.monotonic()
        jax.profiler.stop_trace()
        self.active = False
        path = trace_reduce.find_xplane(self.dir)
        if path is not None:
            t0 = time.monotonic()
            self.reduced = trace_reduce.reduce_trace(path)
            self.reduced["file_bytes"] = os.path.getsize(path)
            self.reduced["reduce_seconds"] = time.monotonic() - t0
        shutil.rmtree(self.dir, ignore_errors=True)


def idle_gaps_by_span(trace: dict, tracer: Tracer, run: dict) -> list:
    """The longest idle gaps of the traced window, each named by the load
    generator's span (``bench.step``, ``bench.push``, ``bench.read``: its
    clock around the calls into the server) that covers most of it."""
    if not trace["gaps"] or "bench.trace_begin" not in trace["marks"]:
        return []
    # the begin annotation was opened at begin_mono on the host's clock
    off = tracer.begin_mono - trace["marks"]["bench.trace_begin"][0][0] / 1e9
    spans = [("bench.step", run["step_sent"][k], run["step_done"][k])
             for k in run["step_sent"] if k in run["step_done"]]
    spans += [("bench.push", a, b) for a, b in run["push"].values()]
    out = []
    for g0, g1 in trace["gaps"]:
        a, b = g0 / 1e9 + off, g1 / 1e9 + off
        cover: dict = {}
        for name, s, e in spans:
            ov = min(b, e) - max(a, s)
            if ov > 0:
                cover[name] = cover.get(name, 0.0) + ov
        name = max(cover, key=cover.get) if cover else "between_calls"
        out.append([name, (g1 - g0) / 1e9])
    return out


def compare_view(view: dict, want: dict, ticks: int, acked: int,
                 expected_events: int) -> dict:
    """The numbers that decide ``correct``, each beside its limit. All are
    exact comparisons: the limit is 0."""
    got: dict = {}
    for r in view.get("rows") or []:
        got[tuple(r[:-1])] = got.get(tuple(r[:-1]), 0) + r[-1]
    keys = set(got) | set(want)
    mismatched = sum(1 for k in keys if got.get(k, 0) != want.get(k, 0))
    step = view.get("step")
    return {
        "rows_mismatched": {"value": mismatched, "limit": 0},
        "view_steps_behind": {
            "value": ticks - step if step is not None else ticks,
            "limit": 0},
        "acked_events_missing": {"value": expected_events - acked,
                                 "limit": 0},
        "reference_rows": {"value": len(want), "at_least": 1},
    }


def is_correct(compared: dict) -> bool:
    ok = True
    for c in compared.values():
        if "limit" in c:
            ok = ok and c["value"] == c["limit"]
        if "at_least" in c:
            ok = ok and c["value"] >= c["at_least"]
    return ok


def acked_events(spec: dict, seed: int, ticks: int,
                 drop_last_batch: bool = False) -> dict:
    """Every event of ticks [0, ticks) as plain Python lists, made again
    from the seed by the benchmark's generator: nothing the program made."""
    import generator

    n = spec["config"]["events_per_tick"]
    last = ticks - 1 if drop_last_batch else ticks
    cols = generator.from_config(spec["config"], seed).generate(0, last * n)
    return {rel: {c: cols[rel][c].tolist() for c in names}
            for rel, names in generator.COLUMNS.items()}


def presize(ch, config: dict) -> None:
    """The run's one presize, after the first validated tick, from what the
    configuration states: ``assumed.presize_ratio`` (how many times tick
    0's state the monotone capacities must hold), ``assumed.
    presize_interval`` (ticks of inflow a trace's level 0 must hold) and,
    where a deployment states it, ``deployment.per_delta_headroom``:
    ``{"<CNode class>.<capacity>": n}`` gives that per-delta capacity room
    for n times tick 0's requirement, where ``presize`` gives twice. (q4's
    aggregate touches 623 groups a worker in tick 0 and ~2,000 from tick
    25 on: twice is a capacity of 2,048, which two four-chip runs of six
    overflowed inside their windows; PERF.md 6, PR 33.)"""
    headroom = config.get("deployment", {}).get("per_delta_headroom", {})
    if headroom:
        from dbsp_tpu.zset.batch import bucket_cap

        for (cn, key), r in zip(ch._checks, ch.last_req):
            n = headroom.get(f"{type(cn).__name__}.{key}")
            if n and int(r) > 0:
                cn.caps[key] = max(cn.caps[key], bucket_cap(n * int(r)))
    ch.presize(ratio=config["assumed"]["presize_ratio"],
               interval=config["assumed"].get("presize_interval", 1))


def run_cell(spec: dict, args, child: Child) -> dict:
    """Serve the cell's query and drive one run; returns the result line.
    The look for a chip is ``main``'s."""
    import jax
    import jax.numpy as jnp

    import dbsp_tpu  # noqa: F401 — turns x64 on before any array exists
    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.compiled.driver import (CompiledCircuitDriver,
                                          enable_compile_cache)
    from dbsp_tpu.io import Catalog
    from dbsp_tpu.io.controller import Controller, ControllerConfig
    from dbsp_tpu.io.server import CircuitServer
    from dbsp_tpu.nexmark import build_inputs, model as M, queries
    from dbsp_tpu.testing import retrace
    from dbsp_tpu.zset import kernels

    config, traffic = spec["config"], spec["traffic"]
    cache_dir = enable_compile_cache()  # before the first compile
    meter = CompileMeter()
    devices = jax.devices()
    emit({"phase": "start", "workload": spec["cell"]["name"],
          "seed": args.seed, "seconds": args.seconds, "trace": args.trace,
          "events_per_tick": config["events_per_tick"],
          "platform": devices[0].platform, "compile_cache_dir": cache_dir,
          "since_start_s": time.monotonic() - T_START})

    def build(c):
        streams, handles = build_inputs(c)
        return handles, getattr(queries, config["query"])(*streams).output()

    handle, (handles, out) = Runtime.init_circuit(config["workers"], build)
    driver = CompiledCircuitDriver(
        handle, validate_every=config["controller"]["validate_every"])
    if driver.mode != "compiled":
        raise RuntimeError(f"driver mode {driver.mode!r}, not compiled")
    tracer = Tracer("window")
    setup = traffic["setup_ticks"]
    with retrace.session() as compiles:
        catalog = Catalog()
        for name, h, dts in (
                ("persons", handles[0], M.PERSON_KEY + M.PERSON_VALS),
                ("auctions", handles[1], M.AUCTION_KEY + M.AUCTION_VALS),
                ("bids", handles[2], M.BID_KEY + M.BID_VALS)):
            catalog.register_input(name, h, dts)
        catalog.register_output(
            config["view"], out,
            tuple(getattr(jnp, d) for d in config["view_dtypes"]))
        # never started: the circuit steps only on an explicit POST /step
        ctl = Controller(driver, catalog, ControllerConfig(
            min_batch_records=config["controller"]["min_batch_records"],
            flush_interval_s=config["controller"]["flush_interval_s"]))
        srv = CircuitServer(ctl)
        srv.start()
        emit({"phase": "serving", "since_start_s": time.monotonic() - T_START,
              "compile_requests": meter.requests,
              "backend_compile_seconds": meter.seconds})
        try:
            gen = child.wait_for("generated")
            emit({"phase": "generated", **{k: gen[k] for k in (
                "ticks", "seconds", "bytes")}})
            child.send(cmd="connect", base=f"http://127.0.0.1:{srv.port}")
            child.wait_for("connected")
            for k in range(setup):
                r0, c0 = meter.requests, meter.seconds
                p0 = retrace.compile_counts().get("step_fn", 0)
                child.send(cmd="tick", k=k)
                t = child.wait_for("tick")
                if not t["ok"]:
                    raise RuntimeError(f"set-up tick {k} failed")
                if k == 0:
                    # one projected re-trace now instead of a grow/replay
                    # ladder over the run
                    presize(driver.ch, config)
                emit({"phase": "tick", "tick": k, "push_s": t["push_s"],
                      "step_s": t["step_s"],
                      "step_programs_traced":
                          retrace.compile_counts().get("step_fn", 0) - p0,
                      "compile_requests": meter.requests - r0,
                      "backend_compile_seconds": meter.seconds - c0,
                      "overflow_replays": driver.ch.overflow_replays,
                      "since_start_s": time.monotonic() - T_START})

            def on_event(ev: dict) -> None:
                if ev["ev"] != "step":
                    return
                emit({"phase": "tick", "tick": ev["k"],
                      "step_s": ev["done"] - ev["sent"],
                      "compile_requests_so_far": meter.requests,
                      "since_start_s": ev["done"] - T_START})
                # the window's first trace_ticks ticks run under the profiler
                if ev["k"] + 1 == setup + traffic["trace_ticks"]:
                    tracer.stop()

            if args.trace:
                tracer.start()
            traced_before = retrace.compile_counts().get("step_fn", 0)
            replays_before = driver.ch.overflow_replays
            child.send(cmd="run", seconds=args.seconds)
            run = child.wait_for("run", on_event)
            traced_in_window = retrace.compile_counts().get(
                "step_fn", 0) - traced_before
            replays_in_window = driver.ch.overflow_replays - replays_before
            tracer.stop()   # if the window closed before the trace did
            # the peak on the fullest chip, before any reference runs
            memory_peak = max((d.memory_stats() or {}).get(
                "peak_bytes_in_use") or 0
                for d in devices[:spec["cell"]["chips"]])
            child.send(cmd="view")
            view = child.wait_for("view")
        finally:
            srv.stop()
            ctl.stop()
    meter.close()
    trace = tracer.reduced
    setup_s = (run["open"] - T_START) if run["open"] is not None else None
    ticks = (run["last_tick"] or 0) + 1
    n_window = len(measures.window_ticks(run))
    reads_ms = measures.read_latencies_ms(run)
    ages = measures.delta_ages(run)
    emit({"phase": "summary", "mode": driver.mode, "ticks": ticks,
          "window_ticks": n_window,
          # the sample behind each tail: a p95 wants ten beyond it
          "ticks_beyond_p95": measures.beyond(n_window, 95),
          "window_reads": len(reads_ms),
          "reads_beyond_p95": measures.beyond(len(reads_ms), 95),
          "reads_meeting_push": measures.reads_meeting_push(run),
          "read_ms": measures.tails(reads_ms),
          "delta_age_s": measures.tails(ages),
          "window_seconds": measures.window_seconds(run)
          if run["close"] is not None else None,
          "tick_seconds": measures.tick_seconds(run),
          "push_seconds": measures.push_seconds(run),
          "setup_s": setup_s, "compile_requests": meter.requests,
          "persistent_cache_hits": meter.cache_hits,
          "programs_compiled": meter.requests - meter.cache_hits,
          "backend_compile_seconds": meter.seconds,
          "step_programs_traced": compiles.compiles.get("step_fn", 0),
          "step_programs_traced_in_window": traced_in_window,
          "compiles_in_window": measures.compiles_in_window(
              run, meter.events),
          "overflow_replays": driver.ch.overflow_replays,
          "overflow_replays_in_window": replays_in_window,
          "host_overhead_s": {k: sum(v) / 1e9 for k, v in
                              driver.ch.host_overhead_ns.items()},
          "kernel_dispatch": {f"{k}/{b}": n for (k, b), n in sorted(
              kernels.KERNEL_DISPATCH_COUNTS.items())},
          "consolidate_paths": dict(kernels.CONSOLIDATE_COUNTS),
          "peak_device_bytes": memory_peak,
          "view_rows": len(view.get("rows") or [])})

    # per-layer probes (kernels measured alone) run under a trace of their
    # own, after the window and after the peak was read
    ctx = {"run": run, "measures": measures, "config": config,
           "traffic": traffic, "trace": trace, "probe_trace": None,
           "compile_events": meter.events, "seed": args.seed,
           "memory_peak_bytes": memory_peak, "peaks": None}
    readers = {}
    if args.trace:
        for m in spec["per_layer"]:
            readers[m["name"]] = load_module(
                os.path.join(HERE, "metrics", m["name"] + ".py"),
                "metric_" + m["name"].replace(".", "_"))
        if not args.rehearse_events:
            table = load_json(os.path.join(HERE, "peaks.json"))
            if devices[0].device_kind not in table:
                raise SystemExit(f"run.py: no peaks for device kind "
                                 f"{devices[0].device_kind!r} in peaks.json")
            ctx["peaks"] = table[devices[0].device_kind]
            probes = [(r, r.prepare(ctx)) for r in readers.values()
                      if hasattr(r, "probe")]
            if probes:
                ptracer = Tracer("probe")
                ptracer.start()
                for r, prepared in probes:
                    r.probe(ctx, prepared)
                ptracer.stop()
                ctx["probe_trace"] = ptracer.reduced
            del probes

    # free the program's state, then the plain reference
    del srv, ctl, driver, handle, handles, out, catalog
    gc.collect()
    t_ref = time.monotonic()
    reference = load_module(
        os.path.join(HERE, "references", config["reference"] + ".py"),
        "reference_" + config["reference"])
    want = reference.recompute(acked_events(spec, args.seed, ticks))
    compared = compare_view(view, want, ticks, run["acked_total"],
                            ticks * config["events_per_tick"])
    if args.control:
        # the control: the reference put in the program's place with one
        # guarantee broken; each must come out as not correct
        controls = {"lost_batch": reference.recompute(
            acked_events(spec, args.seed, ticks, drop_last_batch=True))}
        for name in reference.CONTROLS:
            controls[name] = reference.recompute(
                acked_events(spec, args.seed, ticks), control=name)
        for name, rows in controls.items():
            as_view = {"rows": [[*k, w] for k, w in rows.items()],
                       "step": view.get("step")}
            c = compare_view(as_view, want, ticks, ticks, ticks)
            emit({"phase": "control", "control": name,
                  "rows_mismatched": c["rows_mismatched"]["value"],
                  "correct": is_correct(c)})
    emit({"phase": "reference", "seconds": time.monotonic() - t_ref,
          "rows": len(want)})

    attempted, failed = measures.window_ops(run)
    # a read that fails or times out is late, not wrong: it counts in
    # ``failed``. A push or a step that fails breaks the account of what
    # was acknowledged, and a window with no tick measured nothing.
    broken = sum(1 for kind, _, _, ok in run["ops"]
                 if kind in ("push", "step") and not ok)
    compared["push_or_step_failed"] = {"value": broken, "limit": 0}
    compared["window_ticks"] = {"value": n_window, "at_least": 1}
    # a published tick that no /changefeed or /view response ever showed
    # is an answer that never came
    compared["ticks_never_visible"] = {
        "value": sum(1 for k in measures.window_ticks(run)
                     if str(k + 1) not in run["visible"]), "limit": 0}
    correct = is_correct(compared)
    metrics: dict = {}
    if args.rehearse_events:
        pass  # a rehearsal's line carries no metric
    elif args.trace:
        for m in spec["per_layer"]:
            v = readers[m["name"]].read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = {
            "events_per_s": measures.events_per_s(
                run, config["events_per_tick"]),
            "delta_age_p95_s": measures.percentile(ages, 95)
            if ages else None,
            "read_p90_ms": measures.percentile(reads_ms, 90),
            "setup_s": setup_s,
        }
        for m in spec["end_to_end"]:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": ctx["memory_peak_bytes"]}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if args.rehearse_events:
        result["rehearsal"] = True
    if args.trace and trace and trace["busy_s"] is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {
            "device_ops": trace["ops"][:10],
            "idle_gaps": idle_gaps_by_span(trace, tracer, run)}
        emit({"phase": "trace", "file_bytes": trace["file_bytes"],
              "reduce_seconds": trace["reduce_seconds"],
              "device_ops_in_window": trace["n_ops"],
              "modules": trace["modules"]})
    result["compared"] = compared
    for name, c in compared.items():
        print(f"compared {name}: {json.dumps(c)}", file=sys.stderr)
    print(f"correct: {correct} (failed operations {failed} of {attempted})",
          file=sys.stderr, flush=True)
    emit(result)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also print what the controls read (not a metric)")
    ap.add_argument("--rehearse-events", type=int, default=None,
                    help="rehearsal on the CPU: events per tick; the line "
                         "printed carries no metric")
    args = ap.parse_args(argv)
    spec = load_cell(args.workload)
    if args.rehearse_events:
        spec["config"]["events_per_tick"] = args.rehearse_events
    if not os.path.isdir(os.path.join(ROOT, "dbsp_tpu")):
        print("run.py: the system under test (dbsp_tpu/) is not in this "
              "checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    # the child makes its events while this process loads JAX
    child = Child(spec, args.seed, args.rehearse_events)
    try:
        import jax

        if not args.rehearse_events:
            devices = jax.devices()
            need = spec["cell"]["chips"]
            if devices[0].platform != "tpu" or len(devices) < need:
                print(f"run.py: needs {need} TPU chip(s); JAX found "
                      f"{len(devices)} {devices[0].platform!r} device(s) — "
                      f"no CPU fallback", file=sys.stderr)
                return 2
        run_cell(spec, args, child)
    finally:
        child.close()
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
