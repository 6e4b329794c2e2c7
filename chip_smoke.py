#!/usr/bin/env python3
"""chip_smoke.py — the served NEXmark q4 pipeline, once, on the chip.

The quickest proof that the system still starts on a TPU: q4 built with
``Runtime.init_circuit``, run by ``CompiledCircuitDriver`` under
``Controller`` behind ``CircuitServer``; events pushed through the HTTP
ingest route, one ``/step`` per tick, the accumulated view read back from
``/view/q4`` and compared with a from-scratch recomputation of q4 over all
pushed events (plain Python below — it shares no code with the engine).

    python chip_smoke.py              # one chip, 40,000-event ticks
    python chip_smoke.py --chips 4    # the key-hash-sharded path, 4 workers
    python chip_smoke.py --profile-dir DIR --profile-ticks 2   # keeps a trace

``--profile-dir DIR --profile-ticks N`` runs the last N ticks (warm ones)
under a ``jax.profiler`` session and keeps its trace in DIR, with the span
ring's Chrome trace beside it (``DIR/spans.json``):
``tools/trace_scopes.py DIR --spans DIR/spans.json`` reads both. Scopes show
only in programs compiled by this tree: use a fresh compile cache directory.

One process; it starts no child. Without a TPU it exits non-zero and never
prints ``"ok": true`` — there is no CPU fallback (tests call
:func:`run_served` small on the CPU, ``tests/test_chip_smoke.py``). Earlier
output lines are one JSON object each (facts of the run); the last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.

Size: the reference CI's 40,000 events per input batch (BASELINE.md),
generator defaults, seed from ``--seed``. ``--ticks`` is the cut of scale:
``--ticks 25`` (1,000,000 events, 1 % of the reference's 100 M) passes on a
v5e chip but a cold run of it took 1,125 s, ~820 s of them compiling the
first two ticks' programs (CHANGES.md, PR 26); the default is cut to the
8 ticks (320,000 events) that keep a cold run near 15 minutes.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.request

EVENTS_PER_TICK = 40_000
DEFAULT_TICKS = 8
HTTP_TIMEOUT_S = 1100.0  # one /step can hold a whole step-program compile


def _emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def _http(url: str, data: bytes | None = None):
    req = urllib.request.Request(
        url, data=data, method="GET" if data is None else "POST")
    with urllib.request.urlopen(req, timeout=HTTP_TIMEOUT_S) as r:
        return json.loads(r.read())


def _ndjson(cols) -> bytes:
    """Columns -> the ingest route's NDJSON insert envelopes."""
    return "\n".join(
        json.dumps({"insert": row})
        for row in zip(*(c.tolist() for c in cols))).encode()


def q4_recompute(auctions: dict, bids: dict) -> dict:
    """q4 from scratch: average, per category, of each auction's highest
    bid placed within [auction.date_time, auction.expires]. ``auctions`` /
    ``bids`` map column name -> list over ALL pushed events. Returns
    ``{(category, average): 1}`` — the accumulated view's rows."""
    info = {aid: (cat, d0, d1) for aid, cat, d0, d1 in zip(
        auctions["id"], auctions["category"], auctions["date_time"],
        auctions["expires"])}
    best: dict = {}
    for aid, ts, price in zip(bids["auction"], bids["date_time"],
                              bids["price"]):
        a = info.get(aid)
        if a is not None and a[1] <= ts <= a[2]:
            k = (aid, a[0])
            if price > best.get(k, 0):
                best[k] = price
    per_cat: dict = {}
    for (_, cat), price in best.items():
        per_cat.setdefault(cat, []).append(price)
    return {(cat, sum(ps) // len(ps)): 1 for cat, ps in per_cat.items()}


class _CompileMeter:
    """Counts what JAX compiles, from its own monitoring events: every
    ``backend_compile`` is one program asked of the compiler; a persistent
    cache hit is one that was loaded instead of compiled."""

    def __init__(self):
        from jax import monitoring

        self.requests = 0
        self.seconds = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def close(self) -> None:
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)


def run_served(ticks: int, events_per_tick: int, seed: int,
               workers: int = 1, emit=_emit, profile_dir: str | None = None,
               profile_ticks: int = 0) -> dict:
    """Serve q4 for ``ticks`` ticks of ``events_per_tick`` events and check
    the served view against :func:`q4_recompute`. Runs on whatever backend
    JAX has (the device check is ``main``'s). Emits fact lines through
    ``emit`` and returns the summary (``ok`` is the verdict); raises on any
    phase's failure. With ``profile_dir`` the last ``profile_ticks`` ticks
    run under a profiler session whose trace is kept there."""
    import jax
    import jax.numpy as jnp

    import dbsp_tpu  # noqa: F401 — turns x64 on before any array exists
    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.compiled.driver import (CompiledCircuitDriver,
                                          enable_compile_cache)
    from dbsp_tpu.io import Catalog
    from dbsp_tpu.io.controller import Controller, ControllerConfig
    from dbsp_tpu.io.server import CircuitServer
    from dbsp_tpu.nexmark import (GeneratorConfig, NexmarkGenerator,
                                  build_inputs, model as M, queries)
    from dbsp_tpu.testing import retrace
    from dbsp_tpu.zset import kernels

    t_start = time.perf_counter()
    cache_dir = enable_compile_cache()  # before the first compile
    meter = _CompileMeter()
    devices = jax.devices()
    emit({"phase": "start", "events_per_tick": events_per_tick,
          "ticks": ticks, "events": ticks * events_per_tick, "seed": seed,
          "workers": workers, "platform": devices[0].platform,
          "compile_cache_dir": cache_dir})

    def build(c):
        streams, handles = build_inputs(c)
        return handles, queries.q4(*streams).output()

    handle, (handles, out) = Runtime.init_circuit(workers, build)
    driver = CompiledCircuitDriver(handle, validate_every=1)
    if driver.mode != "compiled":
        raise RuntimeError(f"driver mode {driver.mode!r}, not compiled")
    # opened after the driver exists: it counts compiles by program name
    # without arming the sentinel's transfer guard on this handle
    with retrace.session() as compiles:
        catalog = Catalog()
        for name, h, dts in (
                ("persons", handles[0], M.PERSON_KEY + M.PERSON_VALS),
                ("auctions", handles[1], M.AUCTION_KEY + M.AUCTION_VALS),
                ("bids", handles[2], M.BID_KEY + M.BID_VALS)):
            catalog.register_input(name, h, dts)
        catalog.register_output("q4", out, (jnp.int64, jnp.int64))
        # never started: the circuit steps only on an explicit POST /step
        ctl = Controller(driver, catalog, ControllerConfig(
            min_batch_records=10 ** 9, flush_interval_s=3600.0))
        srv = CircuitServer(ctl)
        srv.start()
        base = f"http://127.0.0.1:{srv.port}"
        try:
            status = _http(base + "/status")
            if status["mode"] != "compiled":
                raise RuntimeError(f"/status mode {status['mode']!r}")
            # the NumPy generator: nothing built elsewhere is loaded
            gen = NexmarkGenerator(GeneratorConfig(seed=seed))
            pushed = {"auctions": {k: [] for k in (
                "id", "category", "date_time", "expires")},
                "bids": {k: [] for k in ("auction", "date_time", "price")}}
            tick_s, tick_step_programs = [], []
            presized = False
            for t in range(ticks):
                if profile_dir and t == ticks - profile_ticks:
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0  # no Python frames: small
                    opts.host_tracer_level = 1    # annotations only
                    jax.profiler.start_trace(profile_dir,
                                             profiler_options=opts)
                cols = gen.generate(t * events_per_tick,
                                    (t + 1) * events_per_tick)
                p, a, b = cols["persons"], cols["auctions"], cols["bids"]
                for name, side in (("auctions", a), ("bids", b)):
                    for k, acc in pushed[name].items():
                        acc.extend(side[k].tolist())
                r0, c0 = meter.requests, meter.seconds
                t0 = time.perf_counter()
                p0 = retrace.compile_counts().get("step_fn", 0)
                for name, body in (
                        ("persons", _ndjson([p[k] for k in (
                            "id", "name", "city", "state", "email",
                            "date_time")])),
                        ("auctions", _ndjson([a[k] for k in (
                            "id", "item", "seller", "category",
                            "initial_bid", "reserve", "date_time",
                            "expires")])),
                        ("bids", _ndjson([b[k] for k in (
                            "auction", "bidder", "price", "channel",
                            "date_time")]))):
                    if body:
                        _http(f"{base}/input_endpoint/{name}?format=json",
                              data=body)
                _http(base + "/step", data=b"")
                if t == 0 and ticks > 1:
                    # one projected re-trace now instead of a grow/replay
                    # ladder over the run (every growth recompiles the
                    # whole step program)
                    driver.ch.presize(ratio=ticks)
                    presized = True
                tick_s.append(time.perf_counter() - t0)
                tick_step_programs.append(
                    retrace.compile_counts().get("step_fn", 0) - p0)
                emit({"phase": "tick", "tick": t,
                      "seconds": tick_s[-1],
                      "step_programs_traced": tick_step_programs[-1],
                      "compile_requests": meter.requests - r0,
                      "backend_compile_seconds": meter.seconds - c0,
                      "overflow_replays": driver.ch.overflow_replays})
            view = _http(base + "/view/q4")
            got = {(r[0], r[1]): r[2] for r in view["rows"]}
        finally:
            if profile_dir and 0 < profile_ticks <= ticks:
                jax.profiler.stop_trace()
                with open(os.path.join(profile_dir, "spans.json"),
                          "w") as f:
                    f.write(srv.spans.to_json())
            srv.stop()
            ctl.stop()
            parsed = ctl.stats()["parsed_records"]
    meter.close()
    want = q4_recompute(pushed["auctions"], pushed["bids"])
    ok = bool(want) and got == want and view["step"] == ticks

    # a steady tick traced no step program (it may still compile a few
    # small eagerly dispatched programs: see its compile_requests)
    steady = [s for s, n in zip(tick_s, tick_step_programs) if n == 0]
    total_s = time.perf_counter() - t_start
    sharding = None
    if workers > 1:
        from dbsp_tpu.parallel import exchange

        # what one chip cannot show: the state really spans the workers
        leaves = jax.tree_util.tree_leaves(driver.ch.states)
        spans = sorted({len(x.sharding.device_set) for x in leaves
                        if hasattr(x, "sharding")})
        # spanning the mesh is not enough: a leaf a program returned
        # replicated holds every worker's slice on every chip
        replicated = sum(1 for x in leaves if hasattr(x, "sharding")
                         and x.sharding.is_fully_replicated)

        def per_chip(stat):
            return [(d.memory_stats() or {}).get(stat)
                    for d in devices[:workers]]

        in_use = per_chip("bytes_in_use")
        # per exchange site [worst worker's live rows, bucket capacity] at
        # the last validation, and the bucket overflows replayed, by kind
        sharding = {"state_leaves": len(leaves),
                    "devices_per_leaf": spans,
                    "replicated_leaves": replicated, "bytes_in_use": in_use,
                    "peak_bytes_in_use": per_chip("peak_bytes_in_use"),
                    "exchange_sites": {
                        f"{kind}:n{node}": list(v) for (kind, node), v
                        in sorted(exchange.EXCHANGE_SITE_ROWS.items())},
                    "exchange_overflows":
                        dict(exchange.EXCHANGE_OVERFLOW_COUNTS)}
        # (the CPU backend, where tests rehearse this, reports no stats)
        ok = ok and spans == [workers] and not replicated and (
            devices[0].platform == "cpu" or all(in_use))
    stats = devices[0].memory_stats() or {}
    summary = {
        "phase": "summary", "ok": ok, "mode": driver.mode,
        "events": ticks * events_per_tick, "ticks": ticks,
        "events_per_tick": events_per_tick, "workers": workers,
        "view_rows": len(got), "view_equals_recompute": got == want,
        "total_seconds": total_s,
        "steady_ticks": len(steady),
        "steady_tick_seconds": steady,
        "setup_and_compile_seconds": total_s - sum(steady),
        "compile_requests": meter.requests,
        "persistent_cache_hits": meter.cache_hits,
        "programs_compiled": meter.requests - meter.cache_hits,
        "backend_compile_seconds": meter.seconds,
        "step_programs_traced": compiles.compiles.get("step_fn", 0),
        "overflow_replays": driver.ch.overflow_replays,
        "presize_used": presized,
        # pushed rows by the parser path that took them (io/format.py)
        "parsed_records": parsed,
        "kernel_dispatch": {f"{k}/{b}": n for (k, b), n in sorted(
            kernels.KERNEL_DISPATCH_COUNTS.items())},
        "consolidate_paths": dict(kernels.CONSOLIDATE_COUNTS),
        "peak_device_bytes": stats.get("peak_bytes_in_use"),
        "sharding": sharding,
    }
    emit(summary)
    if not ok:
        emit({"phase": "mismatch", "got": sorted(got.items()),
              "want": sorted(want.items()), "view_step": view["step"]})
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = only the key-hash-sharded path on four chips")
    ap.add_argument("--ticks", type=int, default=DEFAULT_TICKS)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--profile-dir", default=None,
                    help="keep a profiler trace of the last ticks here")
    ap.add_argument("--profile-ticks", type=int, default=2)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform!r} "
              f"devices only ({len(devices)}) — no CPU fallback",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    summary = run_served(args.ticks, EVENTS_PER_TICK, args.seed,
                         workers=args.chips, profile_dir=args.profile_dir,
                         profile_ticks=args.profile_ticks)
    if not summary["ok"]:
        print("chip_smoke: served view != recomputation", file=sys.stderr)
        return 1
    _emit({"ok": True, "device": {"platform": devices[0].platform,
                                  "kind": devices[0].device_kind,
                                  "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
