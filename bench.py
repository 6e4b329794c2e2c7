#!/usr/bin/env python
"""Nexmark benchmark harness.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...detail}
— on a failure (no accelerator, backend init crash, mid-run exception) it
still emits the line, with the error in "detail" and whatever partial
measurement exists, and exits NONZERO. ``--slo`` (or env BENCH_SLO=1)
evaluates the obs SLO watchdog (dbsp_tpu.obs.slo, env-configured via
DBSP_TPU_SLO_*) over each query's flight-recorded tick stream, embeds an
"slo" summary (status/breaches/incidents) in the JSON, and exits NONZERO
on any breach — the CI gate form of the serving stack's watchdog.

Protocol (BASELINE.md): the reference measures elapsed wall-clock ->
events/sec on Nexmark; its CI config streams 100M events at a 10M/s
first-event rate. This harness streams generated events through the headline
incremental query (q4: join + per-auction max + per-category average) in
large per-tick batches, after a warmup phase that lets capacity buckets and
XLA compilation stabilize, and reports steady-state events/sec plus p50/p99
per-step latency (the latency metric BASELINE.md notes the reference lacks).

Platform selection: the harness runs as a SUPERVISOR that never imports jax
(a parent that has touched JAX holds the chip); the real measurement runs in
ONE child process on the accelerator JAX finds. A run that found no chip
exits non-zero — there is no CPU fallback (BENCH_PLATFORM=cpu asks for a
host run outright). The supervisor polices an init heartbeat and a hard
deadline from outside and kills a stuck child.

vs_baseline is events/sec divided by the reference protocol's 10M events/s
offered rate (the closest in-tree number; BASELINE.json publishes no absolute
reference results).

Execution mode: BENCH_MODE=compiled (default) runs the circuit through
``dbsp_tpu.compiled`` — the whole tick is ONE jitted XLA program including
device-side event generation, so the hot loop does zero host<->device
transfers and validates capacity requirements every BENCH_VALIDATE_EVERY ticks
with snapshot/replay on overflow. BENCH_MODE=host uses the host-driven
scheduler path (the general-purpose mode).

Latency protocol: on CPU the measured run blocks per tick (scan=False), so
step_times_ns holds >= 100 true per-tick samples and p50/p99 are a real
distribution. Off the CPU the run keeps the scanned-chunk mode (one
dispatch per validation interval) and latency granularity degrades to
chunk-level — reported as such.

Durability: each compiled query measurement also times one cold and one
warm (incremental) checkpoint save of the final engine state and embeds
``checkpoint_overhead`` — the fraction of elapsed a periodic checkpoint at
``DBSP_TPU_CHECKPOINT_EVERY_TICKS`` (default 64) would cost (README
§Durability; gated < 10% by tests/test_checkpoint.py).

Multi-query: BENCH_QUERIES (default "q3,q4,q8" — the north-star set) runs
each query through its own circuit; the headline metric/value is q4's (or
the first measured query's), with every query's numbers under
detail["queries"]. A query that exceeds the remaining time budget is
skipped and marked.

Multi-worker: BENCH_WORKERS=N runs each compiled measurement as an
N-worker SPMD circuit (virtual CPU devices or real chips); the bench JSON
gains ``workers`` plus an ``exchange`` block (per-exchange worst-worker
occupancy vs bucket capacity, process-wide overflow counts).
``--workers-sweep 1,2,4,8`` is the MULTICHIP protocol: one child process
per worker count over a mesh sized for the largest W, aggregated into one
JSON object with per-query speedup/efficiency (``--sweep-out PATH``
writes it to a file — MULTICHIP_r*.json).

Growth proof: BENCH_GROWTH=1 records a throughput-vs-accumulated-trace-
size sample per validated interval plus a ``growth_summary`` decay figure
(early/late interval throughput); BENCH_SCAN=1 forces scanned-chunk
dispatch on CPU (one dispatch per validation interval — the 10M-event
growth run uses both with a coarse BENCH_VALIDATE_EVERY).

Attribution: ``--profile`` (env BENCH_PROFILE=1) runs a segmented
operator profile of each query's final steady state (dbsp_tpu.obs
.opprofile — per-node wall time + rows, asserted bit-identical to the
fused step program, engine rewound) and embeds the top-operator table as
detail["profile"]; BENCH_PROFILE_TICKS sizes the run (default 4),
BENCH_PROFILE_OUT writes each full report JSON (``%q`` expands to the
query name — tools/roofline.py --per-node consumes it).

Env knobs: BENCH_EVENTS (per query; default 750_000 on CPU — >=100 ticks
at the CPU batch — 2_000_000 on TPU), BENCH_BATCH (events/tick, default
7_500 on CPU / 100_000 on TPU), BENCH_QUERIES, BENCH_QUERY (headline
override), BENCH_WARM_TICKS (default 4), BENCH_PLATFORM (cpu = a host run;
default: the accelerator), BENCH_PROBE_TIMEOUT_S (default 150), BENCH_MODE
(compiled|host), BENCH_VALIDATE_EVERY (default 8), BENCH_WORKERS,
BENCH_SCAN, BENCH_GROWTH, BENCH_PROFILE / --profile, BENCH_SLO / --slo
(SLO gate; thresholds from DBSP_TPU_SLO_P99_TICK_MS /
_TICK_P50_MULTIPLE / _WATERMARK_LAG / _OVERFLOW_REPLAYS),
BENCH_READ_LOAD / --read-load (served read-storm protocol: reader
threads hammer the snapshot routes while ingest runs; read QPS /
latency / staleness / epoch swaps land in detail.readpath),
BENCH_READERS (read-load reader thread count, default 2).
"""

import json
import os
import re
import signal
import subprocess
import sys
import time


class _Deadline(BaseException):
    """Raised by the SIGTERM/SIGALRM handlers so an external kill or the
    internal time budget still flows through the emit-partial-JSON path."""


def _arm_deadline() -> None:
    def _raise(signum, frame):
        raise _Deadline(f"signal {signum}")

    signal.signal(signal.SIGTERM, _raise)
    signal.signal(signal.SIGALRM, _raise)
    budget = float(os.environ.get("BENCH_TIME_BUDGET_S", 1080))
    if budget > 0:
        signal.alarm(int(budget))


def _debug(msg: str) -> None:
    if os.environ.get("BENCH_DEBUG"):
        print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
              flush=True)

# Persistent compile cache: TPU compiles are tens of seconds to minutes;
# cache programs across bench invocations. JAX_COMPILATION_CACHE_DIR, where
# set, wins; otherwise the fixed per-checkout directory (the same rule as
# dbsp_tpu.compiled.driver.enable_compile_cache).
_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".jax_bench_cache")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", _COMPILE_CACHE_DIR)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")


def _cache_entries() -> int:
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR", _COMPILE_CACHE_DIR)
    try:
        return sum(1 for _ in os.scandir(d))
    except OSError:
        return 0


# Cold-vs-warm attribution is PROCESS-level: the first query of a run
# against an empty cache directory populates it, so a per-query entry
# count would mislabel later queries' (still cold-compiling) warmups as
# warm. Captured once at import, before any measurement compiles.
_CACHE_COLD_AT_START = _cache_entries() == 0


def _compile_cache_state() -> dict:
    """Cold-vs-warm attribution for warmup_s: whether the cache directory
    was empty when THIS PROCESS started (a cold run pays every
    trace+compile inside warmup_s; a warm rerun deserializes), plus the
    entry count when the query began."""
    return {"dir": os.environ.get("JAX_COMPILATION_CACHE_DIR",
                                  _COMPILE_CACHE_DIR),
            "entries_before": _cache_entries(),
            "cold": _CACHE_COLD_AT_START}


def _emit(metric: str, value: float, detail: dict) -> None:
    print(json.dumps({
        "metric": metric,
        "value": round(value, 1),
        "unit": "events/s",
        "vs_baseline": round(value / 10_000_000, 4),
        "detail": detail,
    }))
    sys.stdout.flush()


def _supervise() -> int:
    """Parent mode: run the real measurement in ONE child process on the
    accelerator and police it from outside. The parent never imports jax
    (it would hold the chip); a child stuck INSIDE a C call (backend init,
    a compile) runs no Python signal handler, so the only robust recovery
    is an external kill: the parent kills a child that misses its init
    heartbeat or the hard deadline, and forwards the child's single JSON
    line. A run that found no chip, or failed on it, exits non-zero."""
    import queue
    import threading

    up_timeout = float(os.environ.get("BENCH_PROBE_TIMEOUT_S", 150))
    budget = float(os.environ.get("BENCH_TIME_BUDGET_S", 1080))
    deadline = time.time() + budget
    notes = []

    env = dict(os.environ, BENCH_CHILD="1", BENCH_PLATFORM="accel")
    t0 = time.time()
    p = subprocess.Popen([sys.executable, os.path.abspath(__file__)],
                         env=env, stdout=subprocess.PIPE, text=True)
    q: "queue.Queue" = queue.Queue()

    def _reader():
        for line in p.stdout:
            q.put(line)
        q.put(None)

    threading.Thread(target=_reader, daemon=True).start()
    up, json_line, eof = False, None, False
    while not eof:
        try:
            line = q.get(timeout=2)
        except queue.Empty:
            now = time.time()
            if not up and now - t0 > up_timeout:
                notes.append(f"no init heartbeat in {up_timeout:.0f}s")
                p.kill()
                break
            if now > deadline + 120:
                # child's own SIGALRM budget should have fired; it is
                # stuck in a C call — kill from outside
                notes.append("hard deadline, killed")
                p.kill()
                break
            continue
        if line is None:
            eof = True
        elif line.startswith("BENCH_UP="):
            up = True
        elif line.lstrip().startswith("{"):
            json_line = line.strip()
            break  # result in hand — don't wait out a stuck teardown
    try:
        p.wait(timeout=10)
    except subprocess.TimeoutExpired:
        p.kill()
    parsed = None
    if json_line:
        try:
            parsed = json.loads(json_line)
        except ValueError:
            notes.append(f"unparseable line {json_line[:160]!r}")
    d = (parsed or {}).get("detail", {})
    if parsed is not None:
        print(json_line)
        sys.stdout.flush()
        if d.get("events") and not d.get("error"):
            return _slo_exit_code(parsed)
        return 1
    # the child produced no line — emit one here so the capture is never
    # empty
    qname = os.environ.get("BENCH_QUERY", "q4")
    _emit(f"nexmark_{qname}_throughput", 0.0,
          {"error": "accelerator run failed", "attempts": notes})
    return 1


def _eval_slo(rec) -> dict:
    """Evaluate the env-configured SLOs (DBSP_TPU_SLO_*) over a flight
    recorder's event stream; returns the embeddable summary."""
    from dbsp_tpu.obs.slo import SLOConfig, SLOWatchdog

    wd = SLOWatchdog(rec, SLOConfig.from_env())
    wd.evaluate()
    incs = wd.incidents(with_window=False)
    return {"status": wd.status(), "breaches": len(incs),
            "config": wd.config.enabled(),
            "incidents": [{k: i[k] for k in ("slo", "cause", "causes",
                                             "observed", "threshold",
                                             "breach_count")}
                          for i in incs]}


def _slo_exit_code(obj) -> int:
    """Nonzero when --slo/BENCH_SLO is armed and any query breached.
    ``obj`` is the emitted JSON object (or its line)."""
    if not os.environ.get("BENCH_SLO"):
        return 0
    try:
        if isinstance(obj, (str, bytes)):
            obj = json.loads(obj)
    except ValueError:
        return 0
    d = (obj or {}).get("detail", {})
    qs = d.get("queries")
    if qs:  # per-query summaries (the headline copy would double count)
        n = sum((q.get("slo") or {}).get("breaches", 0)
                for q in qs.values())
    else:
        n = (d.get("slo") or {}).get("breaches", 0)
    return 1 if n else 0


def _knobs(platform: str):
    """Env-knob parsing shared by both execution modes."""
    cpu = platform == "cpu"
    default_events = 750_000 if cpu else 2_000_000
    default_batch = 7_500 if cpu else 100_000
    return (int(os.environ.get("BENCH_EVENTS", default_events)),
            int(os.environ.get("BENCH_BATCH", default_batch)),
            os.environ.get("BENCH_QUERY", "q4"),
            int(os.environ.get("BENCH_WARM_TICKS", 4)))


def _bench_workers() -> int:
    """BENCH_WORKERS=N runs each compiled measurement as an N-worker SPMD
    circuit over the visible device mesh (virtual CPU devices via
    XLA_FLAGS=--xla_force_host_platform_device_count, or real chips). The
    --workers-sweep supervisor sets this per child."""
    return max(1, int(os.environ.get("BENCH_WORKERS", "1")))


def _exchange_detail(ch, workers: int, before: dict) -> dict:
    """Exchange efficiency observables for the bench JSON: per-exchange
    worst-worker occupancy vs static bucket (skew), overflow counts and
    exchange-attributed replays — both WINDOWED to the measured run via
    the ``before`` snapshot (warmup capacity discovery overflows by
    design; attributing those to the measured window would misread benign
    growth as skew)."""
    from dbsp_tpu.compiled import cnodes
    from dbsp_tpu.parallel.exchange import EXCHANGE_OVERFLOW_COUNTS

    nodes = {}
    for cn in ch.cnodes:
        if isinstance(cn, cnodes.CExchange):
            cap = cn.caps.get("exchange", 0)
            nodes[str(cn.node.index)] = {
                "required": int(cn.last_required),
                "cap": cap,
                "occupancy": round(cn.last_required / cap, 4) if cap
                else None,
            }
    counts = before.get("counts", {})
    counts0 = before.get("counts0", {})
    return {"workers": workers, "nodes": nodes,
            "overflows": {k: int(v - counts.get(k, 0))
                          for k, v in EXCHANGE_OVERFLOW_COUNTS.items()
                          if v - counts.get(k, 0)},
            # THIS query's warmup window only (counts0 is snapshotted at
            # query start): the process-global counter also carries earlier
            # queries' overflows in a multi-query run
            "warmup_overflows": {k: int(v - counts0.get(k, 0))
                                 for k, v in counts.items()
                                 if v - counts0.get(k, 0)},
            "exchange_replays": ch.exchange_overflows
            - before.get("replays", 0)}


def _exchange_snapshot(ch) -> dict:
    from dbsp_tpu.parallel.exchange import EXCHANGE_OVERFLOW_COUNTS

    return {"counts": dict(EXCHANGE_OVERFLOW_COUNTS),
            "replays": ch.exchange_overflows}


def _measure_compiled_query(qname: str, platform: str, detail: dict) -> float:
    """Measure one query in compiled mode (one XLA program per tick,
    device-side generation, periodic validation — see module doc).
    Fills ``detail`` incrementally so a mid-run failure reports progress."""
    import time as _time

    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.compiled import compile_circuit
    from dbsp_tpu.nexmark import (GeneratorConfig, build_inputs, device_gen,
                                  queries)

    total, batch, _, warm_ticks = _knobs(platform)
    # CPU: a validation is one cheap host fetch, and frequent validations
    # keep trace level-0 small (it only drains at validation points —
    # maintenance). That trade only pays on state-heavy queries (q4's
    # per-tick l0 merge scales with l0 capacity); small-state queries run
    # sub-2ms ticks where even a ~1ms validation is measurable overhead,
    # so they keep a long cadence. Off the CPU a fetch stalls the device
    # queue: long cadence everywhere.
    big_state = qname in ("q4", "q5", "q6", "q7", "q9")
    validate_every = int(os.environ.get(
        "BENCH_VALIDATE_EVERY",
        2 if platform == "cpu" and big_state else 8))
    query = getattr(queries, qname)
    workers = _bench_workers()
    # device generation needs whole 50-event epochs; warmup needs >= 1 tick
    # for capacity discovery + presize
    batch = max(batch // 50, 1) * 50
    warm_ticks = max(warm_ticks, 1)
    ept = batch // 50  # epochs (50-event groups) per tick
    # per-tick blocking gives a true latency distribution; off the CPU the
    # run uses the scanned-chunk mode. BENCH_SCAN=1 forces scanned chunks on CPU too (the growth run
    # uses it: one dispatch per coarse validation interval).
    scan = platform != "cpu" or os.environ.get("BENCH_SCAN") == "1"
    growth = os.environ.get("BENCH_GROWTH") == "1"

    detail.update(query=qname, batch_per_tick=batch, events=0,
                  workers=workers)
    # cold-vs-warm warmup attribution: warmup_s is dominated by
    # trace+compile on a cold cache and by deserialization on a warm one
    cache_state = _compile_cache_state()
    detail["compile_cache"] = cache_state
    detail["warmup_cold"] = cache_state["cold"]
    from dbsp_tpu.zset import kernels as _zk

    consolidate_before = dict(_zk.CONSOLIDATE_COUNTS)
    kernel_paths_before = dict(_zk.KERNEL_DISPATCH_COUNTS)
    cfg = GeneratorConfig(seed=1)

    def build(c):
        streams, handles = build_inputs(c)
        return handles, query(*streams).output()

    handle, (handles, out) = Runtime.init_circuit(workers, build)
    hp, ha, hb = handles

    def gen_fn(tick):
        p, a, b = device_gen.generate_tick(cfg, tick * ept, ept)
        return {hp: p, ha: a, hb: b}

    # round the measured run to whole validation intervals so the scanned
    # program compiles for exactly ONE chunk length
    ticks = max(total // batch // validate_every, 1) * validate_every
    run_len = warm_ticks + ticks
    # pick the trace level count for THIS run length (short runs want a
    # shallow ladder, long runs a deep one — see cnodes.levels_for_run);
    # an explicit env override wins
    from dbsp_tpu.compiled import cnodes

    if "DBSP_TPU_TRACE_LEVELS" not in os.environ:
        cnodes.TRACE_LEVELS = cnodes.levels_for_run(ticks)
    detail["trace_levels"] = cnodes.TRACE_LEVELS

    ch = compile_circuit(handle, gen_fn=gen_fn)
    from dbsp_tpu.parallel.exchange import EXCHANGE_OVERFLOW_COUNTS

    exchange_query_start = dict(EXCHANGE_OVERFLOW_COUNTS)
    # Warmup protocol tuned for accelerator compile costs (minutes per
    # program): validate every tick, and on the FIRST overflow jump monotone
    # capacities straight to their projected end-of-run size
    # (project_ratio) — 2 compiles instead of a doubling ladder of them.
    t0 = _time.perf_counter()

    def warm_progress(next_tick):
        _debug(f"[{qname}] warmup tick {next_tick - 1} done "
               f"({_time.perf_counter() - t0:.1f}s)")

    # moderate projection during warmup: a big jump from tick-0 requirements
    # overshoots end-of-run caps several-fold, and per-tick merge/sort cost
    # scales with capacity — presize() below re-projects from all warm
    # ticks' calibrated requirements instead
    ch.run_ticks(0, warm_ticks, validate_every=1,
                 on_validated=warm_progress, project_ratio=4.0)
    # residual projection from the last warm tick's validated requirements
    ch.presize(run_len / warm_ticks, interval=validate_every)
    # one post-presize tick so the measured run starts on a compiled program
    ch.run_ticks(warm_ticks, 1, validate_every=1, project_ratio=4.0)
    detail["warmup_s"] = round(_time.perf_counter() - t0, 3)
    _debug(f"[{qname}] warmup total {detail['warmup_s']}s (caps: "
           f"{ {cn.op.name: dict(cn.caps) for cn in ch.cnodes if cn.caps} })")

    # Measured run. CPU: depth-1 pipelined ticks (tick t+1's host work
    # overlaps tick t's device compute; samples are completion-to-
    # completion wall times — a true per-tick latency distribution).
    # TPU: each validation interval is ONE scanned dispatch (lax.scan over
    # the tick index) — per-tick dispatch overhead amortizes
    # across the chunk; the first chunk's compile counts toward elapsed
    # (reported separately as scan_compile_s).
    ch.reset_timing()
    exchange_before = _exchange_snapshot(ch)
    exchange_before["counts0"] = exchange_query_start
    t0 = _time.perf_counter()
    m0 = warm_ticks + 1
    growth_log: list = []
    growth_prev = {"events": 0, "t": 0.0}

    def progress(next_tick):
        ev = (next_tick - m0) * batch
        el = _time.perf_counter() - t0
        detail.update(events=ev, elapsed_s=round(el, 3))
        if growth:
            # throughput-vs-accumulated-trace-size curve: one sample per
            # validated interval (BENCH_GROWTH=1; the 10M-event growth
            # proof reads decay off this log)
            from dbsp_tpu.compiled import cnodes as _cnodes

            rows = sum(cn.caps[k] for cn in ch.cnodes
                       if isinstance(cn, _cnodes._Leveled)
                       for k in cn.level_keys)
            seg_ev = ev - growth_prev["events"]
            seg_s = el - growth_prev["t"]
            if seg_ev > 0 and seg_s > 0:
                sample = {
                    "events": ev,
                    "elapsed_s": round(el, 3),
                    "trace_cap_rows": int(rows),
                    "interval_events_per_s": round(seg_ev / seg_s, 1)}
                # tiered residency: per-tier resident rows per interval —
                # with a budget set this is the evidence that decay is
                # attributable to the cold tiers (the per-cause transition
                # log rides detail["residency"] below)
                tiers = ch.tier_rows()
                if tiers.get("host") or tiers.get("disk"):
                    sample["tier_rows"] = {k: int(v)
                                           for k, v in tiers.items()}
                growth_log.append(sample)
            growth_prev.update(events=ev, t=el)
        if getattr(ch.residency_cfg, "active", False):
            # per-TRACE max device residency (the budget is per trace,
            # matching the host spine's semantics; level 0 is exempt) —
            # sampled at EVERY validated interval, growth mode or not,
            # so device_bound_ok below is never a vacuous claim. One
            # walk: the levels and tier map are in hand per trace, so
            # never re-walk via device_resident_rows(key) per key.
            mx = growth_prev.setdefault("max_dev", {})
            for _cn, _key, _st in ch._leveled_nodes():
                _tiers = ch._tiers.get(_key)
                dev = sum(
                    l.cap for j, l in enumerate(_st[0])
                    if j > 0 and (_tiers is None or _tiers[j] == "device"))
                mx[_key] = max(mx.get(_key, 0), dev)
        _debug(f"[{qname}] measured through tick {next_tick - 1} "
               f"({detail['elapsed_s']}s, {detail['events']} events)")

    # snapshots copy the full state (donated buffers) and the copy lands
    # in the next tick's latency — take ~2 per measured run; a (rare,
    # post-presize) overflow replays up to half the run, exactly
    snap_every = max(1, ticks // validate_every // 2)
    # compilation sentinel over the measured run: every recompile must
    # carry a declared cause and the steady state must stay free of
    # implicit host<->device transfers (jax.transfer_guard armed) — the
    # per-query evidence lands in detail["retrace"] below
    from dbsp_tpu.testing import retrace as _retrace_mod

    with _retrace_mod.session(ch) as retrace_report:
        ch.run_ticks(m0, ticks, validate_every=validate_every,
                     on_validated=progress, block_each=True, scan=scan,
                     project_ratio=4.0, snapshot_every=snap_every)
        ch.block()
        elapsed = _time.perf_counter() - t0
    detail["retrace"] = retrace_report.summary()
    measured = ticks * batch

    eps = measured / elapsed
    samples = list(ch.step_times_ns)
    if samples and scan:
        # first chunk carries the scan-program compile; report it apart and
        # exclude it from the steady-state latency stats when possible
        csort = sorted(samples)
        detail["scan_compile_s"] = round((samples[0] - csort[0]) / 1e9, 2) \
            if len(samples) > 1 else 0.0
        steady = samples[1:] or samples
        per_tick = sorted(c / validate_every for c in steady)
        gran = f"chunk/{validate_every}"
        steady_ns = sum(steady)
        steady_events = len(steady) * validate_every * batch
    elif samples:
        # overflow replays re-run ticks: extra samples carry real time but
        # re-deliver the same events — count DISTINCT events over all time
        per_tick = sorted(samples)
        gran = "tick"
        steady_ns = sum(samples)
        steady_events = min(len(samples), ticks) * batch
    if samples:
        p50_ns = per_tick[len(per_tick) // 2]
        p99_ns = per_tick[min(len(per_tick) - 1, int(len(per_tick) * 0.99))]
        detail.update(
            p50_tick_ms=round(p50_ns / 1e6, 2),
            p99_tick_ms=round(p99_ns / 1e6, 2),
            p99_over_p50=round(p99_ns / max(p50_ns, 1), 2),
            latency_samples=len(per_tick),
            latency_granularity=gran,
            steady_state_events_per_s=round(steady_events
                                            / (steady_ns / 1e9), 1))
        # Tail attribution: a spike (> 3x p50) tick is explained by the
        # causes the handle annotated against its sample index (maintain
        # drain / snapshot copy / program retrace) — BENCH_r06 can show
        # the tail is attributed, not guessed. The bookkeeping is the
        # flight recorder's (dbsp_tpu.obs.flight — the same machinery the
        # serving stack's /flight and /incidents run on), not a private
        # copy. Raw samples are CHUNK times in scan mode while p50_ns is
        # per-tick: scale the threshold back to chunk units there.
        from dbsp_tpu.obs.flight import (CompiledFlightSource,
                                         FlightRecorder, spike_causes)

        # one poll emits ticks PLUS every phase sample (validate/maintain/
        # snapshot), replay, maintain, and consolidate event — size the
        # ring for all of them or the deque evicts the earliest ticks
        # before spike_causes/_eval_slo read them
        n_phase = sum(len(v) for v in ch.host_overhead_ns.values())
        rec = FlightRecorder(capacity=2 * (len(samples) + n_phase) + 256)
        CompiledFlightSource(ch, rec).poll()
        spike_ns = 3 * p50_ns * (validate_every if scan else 1)
        detail["spike_causes"] = spike_causes(
            rec.events(kinds=("tick",)), spike_ns)
        # EXPLAIN SPIKE (obs/timeline.py): the served-pipeline attribution
        # pass over the same flight stream — outlier ticks against the
        # robust rolling baseline, each with ranked co-timed evidence.
        # Embedded per query so BENCH rows carry the serving stack's
        # answer to "which ticks spiked and why", not only the 3x-p50
        # histogram above.
        from dbsp_tpu.obs.timeline import Timeline

        tl = Timeline(capacity=2 * (len(samples) + n_phase) + 256,
                      enabled=True)
        tl.ingest_flight(rec)
        sp = tl.explain_spikes()
        detail["timeline"] = {
            "ticks_seen": sp["ticks_seen"],
            "spikes": [{"tick": s["tick"],
                        "latency_ms": round(s["latency_ns"] / 1e6, 2),
                        "baseline_ms": round(s["baseline_ns"] / 1e6, 2),
                        "cause": s["cause"],
                        "evidence": [{"cause": e["cause"],
                                      "score_ms": round(
                                          e["score_ns"] / 1e6, 2),
                                      "count": e["count"]}
                                     for e in s["evidence"][:3]]}
                       for s in sp["spikes"][-16:]],
        }
        if os.environ.get("BENCH_SLO"):
            detail["slo"] = _eval_slo(rec)
        detail["host_overhead_ms"] = {
            phase: round(sum(v) / 1e6, 2)
            for phase, v in ch.host_overhead_ns.items()}
        detail["maintain"] = {
            k: int(v) for k, v in ch.maintain_stats.items()}
    # Durability cost (README §Durability): measure one cold (full) and a
    # few warm (incremental, hard-linked deep levels) checkpoint saves of
    # the final state and report the steady-state overhead fraction at the
    # default periodic cadence — the quantity the <10%-of-elapsed bound in
    # tests/test_checkpoint.py gates on the mini protocol.
    if samples:
        import shutil as _sh
        import tempfile as _tf

        from dbsp_tpu import checkpoint as _ckpt

        every = int(os.environ.get("DBSP_TPU_CHECKPOINT_EVERY_TICKS",
                                   str(_ckpt.DEFAULT_EVERY_TICKS)))
        ckdir = _tf.mkdtemp(prefix="bench-ckpt-")
        try:
            t0 = _time.perf_counter()
            _ckpt.save(ch, ckdir, tick=ticks)
            cold_s = _time.perf_counter() - t0
            warm = []
            for _ in range(3):
                t0 = _time.perf_counter()
                info = _ckpt.save(ch, ckdir, tick=ticks)
                warm.append(_time.perf_counter() - t0)
            warm_s = sorted(warm)[1]
            per_tick_s = elapsed / ticks
            detail["checkpoint_overhead"] = {
                "every_ticks": every,
                "save_cold_ms": round(cold_s * 1e3, 2),
                "save_warm_ms": round(warm_s * 1e3, 2),
                "linked_arrays": info["linked_arrays"],
                "arrays": info["arrays"],
                "bytes": info["bytes"],
                "fraction_of_elapsed": round(
                    warm_s / (warm_s + every * per_tick_s), 4),
            }
        except Exception as e:  # noqa: BLE001 — overhead is best-effort
            detail["checkpoint_overhead"] = {"error": f"{type(e).__name__}:"
                                                      f" {e}"[:200]}
        finally:
            _sh.rmtree(ckdir, ignore_errors=True)
    # Operator attribution (dbsp_tpu.obs.opprofile — EXPLAIN ANALYZE for
    # the compiled engine): --profile / BENCH_PROFILE=1 runs a segmented
    # measured profile of the final steady state — per-node wall time +
    # rows asserted bit-identical to the fused program, engine rewound —
    # and embeds the top-operator table per query. BENCH_PROFILE_OUT
    # writes the full report JSON (%q -> query name) for
    # tools/roofline.py --per-node. Opt-in: segmentation compiles one
    # program per node and runs ~overhead x the fused tick.
    if os.environ.get("BENCH_PROFILE") and samples:
        from dbsp_tpu.obs import opprofile

        try:
            n_prof = int(os.environ.get("BENCH_PROFILE_TICKS", "4"))
            report = opprofile.measured_profile(ch, n=n_prof, t0=m0 + ticks)
            detail["profile"] = opprofile.summarize_for_bench(report)
            # NOT named `out`: that is the circuit's output handle, which
            # the final-output digest below still needs
            prof_out = os.environ.get("BENCH_PROFILE_OUT")
            if prof_out:
                with open(prof_out.replace("%q", qname), "w") as f:
                    json.dump(report, f, indent=1)
        except opprofile.ProfileDivergence:
            raise  # segmented != fused: a real engine bug, never swallowed
        except opprofile.ProfileError as e:
            # profiling-unsupported here (sharded mesh) — note it, keep
            # the measurement
            detail["profile"] = {"error": f"{type(e).__name__}: {e}"[:300]}
    expected = (ticks // validate_every + (1 if ticks % validate_every else 0)
                ) if scan else ticks
    # consolidation-regime dispatch decisions this query exercised (see
    # zset/kernels.py CONSOLIDATE_COUNTS — traced calls count per trace)
    detail["consolidate_paths"] = {
        k: int(v - consolidate_before.get(k, 0))
        for k, v in _zk.CONSOLIDATE_COUNTS.items()}
    # kernel-dispatch decisions (zset/kernels.py KERNEL_DISPATCH_COUNTS):
    # which backend (native/xla) each kernel entry point selected during
    # this query — the A/B evidence for DBSP_TPU_NATIVE force-off runs
    detail["kernel_paths"] = {
        f"{kern}:{backend}": int(v - kernel_paths_before.get((kern, backend),
                                                            0))
        for (kern, backend), v in sorted(_zk.KERNEL_DISPATCH_COUNTS.items())
        if v - kernel_paths_before.get((kern, backend), 0)}
    if workers > 1:
        detail["exchange"] = _exchange_detail(ch, workers, exchange_before)
    if growth and growth_log:
        # decay = median of the first-quarter interval throughputs /
        # median of the last quarter — the quantity the growth acceptance
        # bound (<= 2x) gates; per-interval causes are flight-recorded
        # above
        q = max(1, len(growth_log) // 4)
        early = sorted(g["interval_events_per_s"]
                       for g in growth_log[:q])[q // 2]
        late_w = growth_log[-q:]
        late = sorted(g["interval_events_per_s"] for g in late_w)[
            len(late_w) // 2]
        detail["growth"] = growth_log
        detail["growth_summary"] = {
            "intervals": len(growth_log),
            "early_events_per_s": early,
            "late_events_per_s": late,
            "decay": round(early / late, 3) if late else None,
            "final_trace_cap_rows": growth_log[-1]["trace_cap_rows"]}
    # tiered residency evidence (BENCH_GROWTH A/B pairs under
    # DBSP_TPU_DEVICE_ROWS/_HOST_ROWS): final per-tier rows, every
    # transition attributed by (from, to, cause), and the hard-cap
    # observation — device-resident rows vs the configured budget
    rstats = getattr(ch, "residency_stats", None)
    if rstats:
        cfg_r = ch.residency_cfg
        detail["residency"] = {
            "device_rows_budget": cfg_r.device_rows,
            "host_rows_budget": cfg_r.host_rows,
            "final_tier_rows": {k: int(v)
                                for k, v in ch.tier_rows().items()},
            # per-trace maxima EXCLUDING the always-hot level 0 — the
            # quantity the per-trace budget bounds; bound_ok is the
            # whole-run hard-cap observation
            "max_device_rows_by_trace": {
                k: int(v)
                for k, v in sorted(growth_prev.get("max_dev",
                                                   {}).items())},
            # None (not True) when no interval samples exist — a bound
            # claim with zero observations would be vacuous evidence
            "device_bound_ok": (
                None if not growth_prev.get("max_dev")
                else bool(cfg_r.device_rows is None or all(
                    v <= cfg_r.device_rows
                    for v in growth_prev["max_dev"].values()))),
            "transitions": {f"{frm}>{to}:{cause}": int(n)
                            for (frm, to, cause), n in
                            sorted(rstats.items())},
            "cold_blob_events": len(getattr(ch, "cold_events", ()))}
    # final-output digest: the A/B bit-identity evidence for budgeted
    # residency pairs (same protocol + same seed -> the digests of the
    # final validated output batch must MATCH across the pair)
    try:
        import hashlib as _hashlib

        import numpy as _np

        fin = ch.output(out)
        if fin is not None:
            h = _hashlib.sha256()
            for c in (*fin.keys, *fin.vals, fin.weights):
                h.update(_np.asarray(c).tobytes())
            detail["final_output_sha256"] = h.hexdigest()
    except Exception:  # noqa: BLE001 — evidence is best-effort
        pass
    detail.update(elapsed_s=round(elapsed, 3), events=measured, ticks=ticks,
                  replayed_intervals=max(0, len(samples) - expected))
    return eps


def run_compiled(platform: str, detail: dict) -> float:
    """Compiled-mode driver: measure every query in BENCH_QUERIES, headline
    the BENCH_QUERY one (default q4). Queries that would overrun the time
    budget are skipped and marked."""
    import time as _time

    import jax

    platform = jax.devices()[0].platform
    budget = float(os.environ.get("BENCH_TIME_BUDGET_S", 1080))
    started = _time.perf_counter()
    qnames = [q.strip() for q in
              os.environ.get("BENCH_QUERIES", "q3,q4,q8").split(",")
              if q.strip()]
    headline = os.environ.get("BENCH_QUERY", "q4")
    if headline not in qnames:
        qnames.insert(0, headline)
    # measure the headline query FIRST so a budget overrun still reports it
    qnames.sort(key=lambda q: q != headline)

    detail.update(platform=platform, mode="compiled", queries={})
    eps = 0.0
    for qn in qnames:
        left = budget - (_time.perf_counter() - started)
        d: dict = {}
        detail["queries"][qn] = d
        if qn != headline and left < 180:
            d["skipped"] = f"time budget ({left:.0f}s left)"
            continue
        try:
            q_eps = _measure_compiled_query(qn, platform, d)
            d["events_per_s"] = round(q_eps, 1)
        except NotImplementedError as e:
            if qn == headline:
                raise  # headline falls back to host mode
            d["compiled_fallback"] = str(e)[:160]
        except _Deadline:
            raise
        except Exception as e:  # noqa: BLE001 — other queries still report
            if qn == headline:
                raise  # a broken headline must FAIL the bench, not emit 0.0
            d["error"] = f"{type(e).__name__}: {e}"[:300]
        if qn == headline:
            eps = d.get("events_per_s", 0.0)
            detail.update({k: v for k, v in d.items()
                           if k != "queries"})  # headline fields top-level
        jax.clear_caches()  # bound live executables between circuits
    return eps


def _run_read_load(platform: str, detail: dict) -> float:
    """``--read-load`` / ``BENCH_READ_LOAD=1``: the SERVED read-path
    protocol — the headline query behind Runtime + Catalog + Controller +
    CircuitServer (host engine) with reader threads storming the
    snapshot routes (``/view`` point/range/scan + ``/output_endpoint``)
    WHILE ingest ticks run. Fills ``detail["readpath"]`` with read QPS,
    read p50/p99 latency, a staleness histogram (published-snapshot step
    lag observed by readers, in validation intervals) and the plane's
    epoch swap count, plus ``detail["e2e"]`` with per-stage delta-age
    percentiles from ``dbsp_tpu_e2e_stage_seconds`` (queue_wait / tick /
    publish / serve here — transport/apply need a replica); the returned
    metric value stays ingest events/s so the headline is comparable to
    the plain runs."""
    import threading
    import urllib.request

    import jax

    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.io.catalog import Catalog
    from dbsp_tpu.io.controller import Controller, ControllerConfig
    from dbsp_tpu.io.server import CircuitServer
    from dbsp_tpu.nexmark import (GeneratorConfig, NexmarkGenerator,
                                  build_inputs, queries)
    from dbsp_tpu.nexmark import model as M
    from dbsp_tpu.obs import PipelineObs

    _, batch, qname, warm_ticks = _knobs(platform)
    query = getattr(queries, qname)
    platform = jax.devices()[0].platform
    # the served loop pays HTTP + publication per tick; default to a
    # shorter run than the raw engine protocol (env still wins)
    total = int(os.environ.get("BENCH_EVENTS",
                               75_000 if platform == "cpu" else 750_000))
    detail.update(platform=platform, query=qname, batch_per_tick=batch,
                  mode="host-served-readload", events=0)

    def build(c):
        streams, handles = build_inputs(c)
        return handles, query(*streams).output()

    handle, (handles, out) = Runtime.init_circuit(1, build)
    catalog = Catalog()
    for name, h, key, vals in (("persons", handles[0], M.PERSON_KEY,
                                M.PERSON_VALS),
                               ("auctions", handles[1], M.AUCTION_KEY,
                                M.AUCTION_VALS),
                               ("bids", handles[2], M.BID_KEY, M.BID_VALS)):
        catalog.register_input(name, h, key + vals)
    catalog.register_output(qname, out, ())
    ctl = Controller(handle, catalog, ControllerConfig(
        min_batch_records=10**9, flush_interval_s=3600.0))
    plane = ctl.read_plane
    if not plane.enabled:
        raise RuntimeError("--read-load needs the read plane "
                           "(DBSP_TPU_READPLANE=0 is set)")
    # the deployed serving plane carries PipelineObs, so the read-load
    # protocol does too: this binds the e2e stage histogram the
    # detail["e2e"] section below reads (tracing itself stays governed
    # by DBSP_TPU_TRACE_E2E)
    obs = PipelineObs(name="bench-readload")
    obs.attach_controller(ctl)
    srv = CircuitServer(ctl, obs=obs)
    srv.start()
    base = f"http://127.0.0.1:{srv.port}"
    gen = NexmarkGenerator(GeneratorConfig(seed=1))
    stop = threading.Event()
    lat_ns: list = []
    lag_hist: dict = {}
    lock = threading.Lock()

    def storm():
        paths = (f"/view/{qname}?key=1", f"/view/{qname}?lo=0&hi=50",
                 f"/view/{qname}", f"/output_endpoint/{qname}?format=json")
        i, local_lat, local_lag = 0, [], {}
        while not stop.is_set():
            path = paths[i % len(paths)]
            pre = ctl.steps
            t0 = time.perf_counter_ns()
            try:
                with urllib.request.urlopen(base + path, timeout=30) as r:
                    r.read()
                    ep_step = r.headers.get("X-Dbsp-Step")
            except OSError:
                break  # server shutting down
            local_lat.append(time.perf_counter_ns() - t0)
            if path.startswith("/output_endpoint/") and ep_step:
                # snapshot step lag vs the steps counter sampled BEFORE
                # the request: an upper bound on observed staleness
                lag = max(0, pre - int(ep_step))
                local_lag[lag] = local_lag.get(lag, 0) + 1
            i += 1
        with lock:
            lat_ns.extend(local_lat)
            for k, v in local_lag.items():
                lag_hist[k] = lag_hist.get(k, 0) + v

    n = 0
    try:
        for _ in range(warm_ticks):
            gen.feed(handles, n, n + batch)
            ctl.note_pushed(batch)
            ctl.step()
            n += batch
        readers = [threading.Thread(target=storm, name=f"bench-reader-{i}",
                                    daemon=True)
                   for i in range(int(os.environ.get("BENCH_READERS", 2)))]
        for r in readers:
            r.start()
        t0 = time.perf_counter()
        measured = 0
        while measured < total:
            gen.feed(handles, n, n + batch)
            ctl.note_pushed(batch)
            ctl.step()
            n += batch
            measured += batch
            detail.update(events=measured,
                          elapsed_s=round(time.perf_counter() - t0, 3))
        elapsed = time.perf_counter() - t0
        stop.set()
        for r in readers:
            r.join(timeout=60)
    finally:
        stop.set()
        srv.stop()

    eps = measured / elapsed
    lat = sorted(lat_ns)
    stats = plane.stats()
    detail.update(elapsed_s=round(elapsed, 3), ticks=measured // batch)
    detail["readpath"] = {
        "readers": len(readers),
        "reads": len(lat),
        "read_qps": round(len(lat) / elapsed, 1),
        "read_p50_ms": round(lat[len(lat) // 2] / 1e6, 3) if lat else None,
        "read_p99_ms": round(
            lat[min(len(lat) - 1, int(len(lat) * 0.99))] / 1e6, 3)
        if lat else None,
        # staleness in validation intervals: 0 = read the current epoch,
        # 1 = one publish behind (the contract's bound on the host engine)
        "staleness_intervals": {str(k): lag_hist[k]
                                for k in sorted(lag_hist)},
        "epoch_swaps": stats["publishes"],
        "epoch": stats["epoch"],
    }
    # end-to-end delta-age decomposition: per-stage latency percentiles
    # from dbsp_tpu_e2e_stage_seconds (the replica-side transport/apply
    # stages stay absent here — this protocol runs no replica)
    from dbsp_tpu.obs.tracing import E2E_STAGES

    hist = obs.registry.get("dbsp_tpu_e2e_stage_seconds")
    by_stage = {}
    for key, child in (hist.samples() if hist is not None else ()):
        stage = key[0] if key else "?"
        if child.count:
            by_stage[stage] = {
                "count": child.count,
                "p50_ms": round(hist.quantile_of(child, 0.5) * 1e3, 3),
                "p99_ms": round(hist.quantile_of(child, 0.99) * 1e3, 3),
            }
    detail["e2e"] = {
        "enabled": bool(ctl.e2e is not None and ctl.e2e.enabled),
        "stages": {s: by_stage[s] for s in E2E_STAGES if s in by_stage},
        "tracer": ctl.e2e.stats() if ctl.e2e is not None else None,
    }
    return eps


def run(platform: str, detail: dict) -> float:
    """Measure; fills ``detail`` as it goes so a mid-run crash still reports
    platform + progress in the JSON line."""
    import jax

    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.nexmark import (GeneratorConfig, NexmarkGenerator,
                                  build_inputs, queries)

    if os.environ.get("BENCH_READ_LOAD"):
        return _run_read_load(platform, detail)
    if os.environ.get("BENCH_MODE", "compiled") == "compiled":
        try:
            return run_compiled(platform, detail)
        except NotImplementedError as e:
            # query uses operators outside the compiled set — host path
            detail["compiled_fallback"] = str(e)[:160]

    total, batch, qname, warm_ticks = _knobs(platform)
    query = getattr(queries, qname)

    platform = jax.devices()[0].platform  # actual backend that came up
    detail.update(platform=platform, query=qname, batch_per_tick=batch,
                  mode="host", events=0)
    gen = NexmarkGenerator(GeneratorConfig(seed=1))

    def build(c):
        streams, handles = build_inputs(c)
        return handles, query(*streams).output()

    handle, (handles, out) = Runtime.init_circuit(1, build)

    # Warmup: compile shapes along the trace-growth curve.
    n = 0
    for _ in range(warm_ticks):
        gen.feed(handles, n, n + batch)
        handle.step()
        out.take()
        n += batch
    handle.step_times_ns.clear()

    # Measured run.
    t0 = time.perf_counter()
    measured = 0
    while measured < total:
        gen.feed(handles, n, n + batch)
        handle.step()
        out.take()
        n += batch
        measured += batch
        detail.update(events=measured,
                      elapsed_s=round(time.perf_counter() - t0, 3))
    elapsed = time.perf_counter() - t0

    eps = measured / elapsed
    lat = sorted(handle.step_times_ns)
    p50 = lat[len(lat) // 2] / 1e6
    p99 = lat[min(len(lat) - 1, int(len(lat) * 0.99))] / 1e6
    detail.update(elapsed_s=round(elapsed, 3), p50_step_ms=round(p50, 2),
                  p99_step_ms=round(p99, 2), ticks=len(lat))
    if os.environ.get("BENCH_SLO"):
        # host path has no cause annotations; the latency SLOs still apply
        from dbsp_tpu.obs.flight import FlightRecorder, ticks_from_samples

        rec = FlightRecorder(capacity=2 * len(handle.step_times_ns) + 64)
        ticks_from_samples(rec, handle.step_times_ns)
        detail["slo"] = _eval_slo(rec)
    return eps


def _child_platform() -> tuple[str, dict]:
    """Child mode: initialize the backend BENCH_PLATFORM asks for and emit
    the init heartbeat the supervisor watches for."""
    want = os.environ.get("BENCH_PLATFORM", "accel")
    info: dict = {}
    if "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS",
                                                                ""):
        want = "cpu"  # virtual-CPU-mesh convention (see __graft_entry__)
        info["forced"] = "virtual-device XLA_FLAGS"
    import jax

    if want == "cpu":
        jax.config.update("jax_platforms", "cpu")
        platform = "cpu"
    else:
        platform = jax.devices()[0].platform
        if platform == "cpu":
            raise RuntimeError(
                "no accelerator: JAX found CPU devices only "
                "(BENCH_PLATFORM=cpu asks for a host run)")
    if os.environ.get("BENCH_CHILD"):
        print(f"BENCH_UP={platform}", flush=True)
    return platform, info


def last_json_object(text: str):
    """Last parseable ``{``-prefixed stdout line, or None — the child
    protocol shared by the sweep supervisor and tools/lint_all.py's
    multichip front (one copy: a protocol change lands in both)."""
    parsed = None
    for line in text.splitlines():
        if line.lstrip().startswith("{"):
            try:
                parsed = json.loads(line)
            except ValueError:
                pass
    return parsed


def _workers_sweep(workers_list, out_path=None) -> int:
    """``--workers-sweep 1,2,4,8``: run the compiled measurement once per
    worker count (each in a fresh child process over a virtual CPU device
    mesh sized for the largest W) and emit ONE JSON object with per-query
    scaling efficiency plus the exchange skew/overflow observables — the
    MULTICHIP_r* protocol. ``--sweep-out PATH`` also writes it to a file.

    Children run the normal bench protocol (BENCH_QUERIES/BENCH_EVENTS/
    BENCH_BATCH knobs apply), so per-W numbers are directly comparable to
    the single-worker BENCH_r* lines."""
    maxw = max(workers_list)
    budget = float(os.environ.get("BENCH_TIME_BUDGET_S", 1080))
    started = time.time()
    runs: dict = {}
    for w in workers_list:
        flags = os.environ.get("XLA_FLAGS", "")
        # force the mesh to max(W) even when the env already carries the
        # flag: an inherited smaller value would cap the device count below
        # the largest swept W and kill those children at make_mesh
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       flags)
        flags = (flags.strip() +
                 f" --xla_force_host_platform_device_count={maxw}").strip()
        env = dict(os.environ, BENCH_CHILD="1", BENCH_PLATFORM="cpu",
                   BENCH_WORKERS=str(w), JAX_PLATFORMS="cpu",
                   XLA_FLAGS=flags)
        child_budget = max(120.0, (budget - (time.time() - started)) /
                           max(1, len(workers_list) - len(runs)))
        env["BENCH_TIME_BUDGET_S"] = str(child_budget)
        # hard backstop past the child's own SIGALRM budget (which can't
        # fire inside a wedged C call): the REMAINING budget plus compile
        # slack, not the sweep's full initial budget — a second wedged
        # child must not wait out another full-budget window
        try:
            p = subprocess.run([sys.executable, os.path.abspath(__file__)],
                               env=env, capture_output=True, text=True,
                               timeout=child_budget + 120)
        except subprocess.TimeoutExpired as e:
            # a wedged child (stuck XLA compile) must not discard the
            # already-completed per-W runs — record and move on
            runs[str(w)] = {"error": "child timed out after "
                            f"{child_budget + 120:.0f}s",
                            "stdout": (e.stdout or "")[-300:] if
                            isinstance(e.stdout, str) else None}
            continue
        parsed = last_json_object(p.stdout)
        runs[str(w)] = (parsed if parsed is not None
                        else {"error": "no JSON line",
                              "stderr": p.stderr[-500:]})
    # per-query scaling efficiency vs the smallest swept worker count
    base_w = str(min(workers_list))
    base_q = ((runs.get(base_w) or {}).get("detail", {}) or {}).get(
        "queries", {})
    scaling: dict = {}
    for w in workers_list:
        d = (runs.get(str(w)) or {}).get("detail", {}) or {}
        for qn, qd in (d.get("queries") or {}).items():
            eps = qd.get("events_per_s")
            base = (base_q.get(qn) or {}).get("events_per_s")
            if eps and base:
                scaling.setdefault(qn, {})[str(w)] = {
                    "events_per_s": eps,
                    "speedup": round(eps / base, 3),
                    "efficiency": round(eps / base / (w / min(workers_list)),
                                        3)}
    obj = {
        "protocol": "workers-sweep",
        "workers": workers_list,
        "host_cores": os.cpu_count(),
        "queries": os.environ.get("BENCH_QUERIES", "q3,q4,q8"),
        "events_per_query": os.environ.get("BENCH_EVENTS", "default"),
        "scaling": scaling,
        "runs": runs,
    }
    line = json.dumps(obj)
    print(line)
    sys.stdout.flush()
    if out_path:
        with open(out_path, "w") as f:
            f.write(json.dumps(obj, indent=1) + "\n")
    return 0


def _flag_operand(flag: str) -> str:
    """The operand after ``flag`` in argv, with a usage error (not an
    IndexError, and not a silently-swallowed next flag) when missing."""
    i = sys.argv.index(flag)
    if i + 1 >= len(sys.argv) or sys.argv[i + 1].startswith("--"):
        print(f"bench.py: {flag} needs a value "
              f"(e.g. {flag} {'1,2,4,8' if 'workers' in flag else 'F.json'})",
              file=sys.stderr)
        raise SystemExit(2)
    return sys.argv[i + 1]


def main() -> int:
    if "--slo" in sys.argv:  # env form so child processes inherit it
        os.environ["BENCH_SLO"] = "1"
    if "--profile" in sys.argv:  # env form so child processes inherit it
        os.environ["BENCH_PROFILE"] = "1"
    if "--read-load" in sys.argv:  # env form so child processes inherit it
        os.environ["BENCH_READ_LOAD"] = "1"
    if "--workers-sweep" in sys.argv:
        ws = sorted({int(x)
                     for x in _flag_operand("--workers-sweep").split(",")
                     if x})
        out_path = None
        if "--sweep-out" in sys.argv:
            out_path = _flag_operand("--sweep-out")
        return _workers_sweep(ws, out_path)
    inline_cpu = (os.environ.get("BENCH_PLATFORM") == "cpu" or
                  "xla_force_host_platform_device_count"
                  in os.environ.get("XLA_FLAGS", ""))
    if not os.environ.get("BENCH_CHILD") and not inline_cpu:
        return _supervise()
    qname = os.environ.get("BENCH_QUERY", "q4")
    metric = f"nexmark_{qname}_throughput"
    detail: dict = {}
    _arm_deadline()
    try:
        platform, info = _child_platform()
        detail.update(info)
        eps = run(platform, detail)
        _emit(metric, eps, detail)
    except BaseException as e:  # noqa: BLE001 — the JSON line must happen
        detail["error"] = f"{type(e).__name__}: {e}"
        partial = detail.get("events", 0) / detail["elapsed_s"] \
            if detail.get("elapsed_s") else 0.0
        _emit(metric, partial, detail)
        return 1
    return _slo_exit_code({"detail": detail})


if __name__ == "__main__":
    sys.exit(main())
