"""Span recorder + fleet-wide delta tracing: Chrome-trace JSON rings and
the end-to-end stage attribution that rides the serving plane.

Load any export in Perfetto (https://ui.perfetto.dev) or chrome://tracing.
Spans nest step -> operator eval -> exchange on the host path (driven by
:class:`~dbsp_tpu.obs.instrument.CircuitInstrumentation` from the
scheduler-event stream). The served path names every phase of a request
with fixed strings (the tick index, table, record and byte counts are
``args``): ``ingest`` > ``ingest.read_body`` / ``ingest.parse`` /
``ingest.push_rows`` (io/server.py), ``step_request`` > ``step.lock_wait``,
``tick`` > ``tick.drain_endpoints`` / ``tick.build_inputs`` (on a worker
mesh > ``tick.shard_inputs``) / ``tick.snapshot`` / ``tick.dispatch`` /
``tick.validate`` (> ``tick.device_wait``, ``tick.grow``, ``tick.replay``)
/ ``tick.maintain`` / ``tick.deliver`` (on a worker mesh >
``tick.unshard_outputs``) / ``tick.emit_outputs`` / ``tick.publish`` /
``tick.checkpoint`` / ``tick.monitors`` (io/controller.py,
compiled/driver.py, operators/io_handles.py), ``read`` > ``read.query`` /
``read.respond``, and a closed ``compile`` child for every program asked
of the compiler inside any of them. :func:`default_recorder` is the ring
they land in unless a ``PipelineObs`` hands its own.

Format: the JSON-object flavor of the Trace Event Format — ``B``/``E``
duration events with microsecond timestamps, so nesting is explicit and a
consumer (or test) can check balance. Events carry the real ``os.getpid()``
and ``threading.get_native_id()`` so the serving plane's thread fan-out
(HTTP handlers, circuit loop, replica feed loops) lands in distinct lanes,
with ``M`` metadata events naming each process and thread. The window is
bounded: per kind of top-level span (its ``cat``) only the most recent
``max_steps`` completed ones are retained (a serving pipeline runs
forever; the trace buffer must not), so reads never evict ticks;
evictions are counted in ``dropped_steps`` and exported as
``dbsp_tpu_obs_trace_dropped_total{pipeline}`` once :meth:`SpanRecorder.bind`
has run.

The second half of this module is the fleet-wide delta path. Every ingested
batch gets a trace context (id + stage timestamps) that flows

    push -> Controller._step_locked tick -> ReadPlane.publish
         -> changefeed record -> ReplicaServer._apply -> read response

so an end-to-end "delta age" decomposes exactly into the closed stage set
:data:`E2E_STAGES`:

``queue_wait``
    ingest wall-time to the start of the tick that drained the batch.
``tick``
    the draining tick's wall-clock (step + output emission).
``publish``
    tick end to the validation publish that made the delta readable —
    includes the deferred-validation dwell on the compiled path.
``transport``
    publish to changefeed receipt at a replica (HTTP long-poll hop).
``apply``
    the replica's fold of the changefeed records into its view state.
``serve``
    the read handler's own latency (snapshot/index lookup + encode).

The writer-side stages use one wall-clock (``time.time``) timeline, so
``queue_wait + tick + publish == publish_ts - ingest_ts`` exactly; replica
stages extend the same timeline across the (same-host) process boundary.
Stage latencies land in ``dbsp_tpu_e2e_stage_seconds{stage}``, in span
rings (as ``e2e`` category spans carrying the trace ids), in the timeline
(``e2e_stage`` records EXPLAIN SPIKE attributes outliers to), and on every
``/view`` response as ``age_s`` + ``stages``. Kill switch:
``DBSP_TPU_TRACE_E2E=0`` (default on, like the read plane's).
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from collections import OrderedDict, deque
from typing import Deque, Dict, List, Optional, Sequence, Tuple

from jax.profiler import TraceAnnotation

from dbsp_tpu.testing.tsan import maybe_instrument as _tsan_hook

__all__ = [
    "SpanRecorder", "default_recorder", "child_span", "E2ETracer",
    "E2E_STAGES", "trace_e2e_enabled",
    "merge_chrome_traces",
]

#: closed stage taxonomy of the end-to-end delta path, in path order.
#: ``dbsp_tpu_e2e_stage_seconds{stage}`` only ever carries these values.
E2E_STAGES = ("queue_wait", "tick", "publish", "transport", "apply", "serve")

#: trace ids carried per published epoch are capped (a firehose tick can
#: drain thousands of batches; the annotation rides every feed record)
_MAX_IDS_PER_EPOCH = 16


def trace_e2e_enabled(env: Optional[dict] = None) -> bool:
    """Kill switch for end-to-end delta tracing: ``DBSP_TPU_TRACE_E2E=0``
    disables it (default on, mirroring ``readplane_enabled``)."""
    env = os.environ if env is None else env
    return str(env.get("DBSP_TPU_TRACE_E2E", "1")).lower() not in (
        "0", "false", "no", "off")


#: top-level spans kept per kind by :func:`default_recorder`: a served
#: pipeline read every 100 ms over a ~50 s window leaves ~500 ``read``
#: entries, and the window's reads are all wanted back afterwards
DEFAULT_STEPS_PER_KIND = 1024

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

#: per-thread: ``rec`` = the recorder that last opened a span here (the
#: compile listener's target), ``cache_hit`` = the compile in flight on this
#: thread was a persistent-cache load
_tls = threading.local()
_default: Optional["SpanRecorder"] = None
_module_lock = threading.RLock()  # default_recorder builds under it
_listening = False


def _on_compile_event(event: str, **_) -> None:
    if event == _CACHE_HIT_EVENT:
        _tls.cache_hit = True


def _on_compile_duration(event: str, secs: float, **kw) -> None:
    """One backend compile request (a persistent-cache load included) ended
    on this thread: a closed ``compile`` child of whatever span is open
    here, else a top-level span of the default recorder."""
    if event != _COMPILE_EVENT:
        return
    hit = getattr(_tls, "cache_hit", False)
    _tls.cache_hit = False
    rec = getattr(_tls, "rec", None) or _default
    if rec is None:
        return
    t1 = time.perf_counter_ns()
    rec.span_at("compile", t1 - int(secs * 1e9), t1, cat="compile",
                args={"seconds": secs, "cache_hit": hit,
                      "fun": kw.get("fun_name")}, nest=True)


def _listen_for_compiles() -> None:
    """Register the ``jax.monitoring`` listeners once per process (they
    route by thread, so one pair serves every recorder)."""
    global _listening
    with _module_lock:
        if _listening:
            return
        from jax import monitoring

        monitoring.register_event_listener(_on_compile_event)
        monitoring.register_event_duration_secs_listener(_on_compile_duration)
        _listening = True


def child_span(name: str, cat: str = "tick", args: Optional[dict] = None):
    """A span under whatever span this thread has open, in that span's
    recorder — for code below the served path's phases that is handed no
    recorder (an input handle sharding its batch inside
    ``tick.build_inputs``). Where the thread has no span open (the host
    engine, a test calling the operator directly) it records nothing."""
    rec = getattr(_tls, "rec", None)
    if rec is None:
        return contextlib.nullcontext()
    return rec.span(name, cat, args)


def default_recorder() -> "SpanRecorder":
    """The process's span ring: the served path (server, controller,
    compiled driver) records into it unless handed another. It outlives
    the objects it observed, so a harness reads it after they are gone."""
    global _default
    if _default is None:
        with _module_lock:
            if _default is None:
                _default = SpanRecorder(max_steps=DEFAULT_STEPS_PER_KIND)
    return _default


class _OpenStack:
    """One thread's in-flight top-level span: its events so far and, per
    open span, the profiler annotation to leave at its end."""

    __slots__ = ("thread", "events", "marks")

    def __init__(self, thread: str):
        self.thread = thread
        self.events: List[dict] = []
        self.marks: List[TraceAnnotation] = []


class SpanRecorder:
    """Accumulates B/E span events; one bounded ring per kind of top-level
    span (its ``cat``), so a flood of one kind (``read``) never evicts
    another (``step``).

    Events are stamped with the recorder's process id and the *real* native
    thread id of the caller, with per-thread open-span stacks so concurrent
    serving-plane threads (circuit loop, HTTP handlers, replica feed loop)
    nest correctly in their own lanes instead of interleaving into one. A
    thread's stack exists only while it has a span open. Timestamps are
    ``time.perf_counter_ns`` (``CLOCK_MONOTONIC`` on Linux, the clock of
    ``time.monotonic``). Every ``begin``/``end`` also enters/leaves a
    ``jax.profiler.TraceAnnotation`` named ``dbsp.<span name>``: under a
    profiler session the host phases sit on the device trace's own clock;
    without one the annotation is inert.
    """

    def __init__(self, max_steps: int = 64, process: str = "dbsp_tpu"):
        self.pid = os.getpid()
        self.process = process
        self.max_steps = max_steps
        # kind -> ring of (thread name, events of one top-level span)
        self._steps: Dict[str, Deque[Tuple[str, List[dict]]]] = {}
        self._open: Dict[int, _OpenStack] = {}   # tid -> in-flight stack
        self._lock = threading.Lock()
        self.dropped_steps = 0
        self._dropped_counter = None  # wired once by bind()
        self._pipeline = ""
        _listen_for_compiles()
        _tsan_hook(self)

    # -- recording ----------------------------------------------------------
    def _push_step_locked(self, thread: str,
                          events: List[dict]) -> None:  # holds: _lock
        kind = events[0].get("cat", "")
        ring = self._steps.get(kind)
        if ring is None:
            ring = self._steps[kind] = deque(maxlen=self.max_steps)
        if len(ring) == ring.maxlen:
            self.dropped_steps += 1
        ring.append((thread, events))

    def begin(self, name: str, cat: str = "operator",
              ts_ns: Optional[int] = None,
              args: Optional[dict] = None) -> int:
        """Open a span on this thread; returns its start (ns)."""
        mark = TraceAnnotation("dbsp." + name)
        mark.__enter__()
        ts_ns = ts_ns if ts_ns else time.perf_counter_ns()
        tid = threading.get_native_id()
        ev = {"name": name, "cat": cat, "ph": "B",
              "ts": ts_ns / 1e3, "pid": self.pid, "tid": tid}
        if args:
            ev["args"] = args
        _tls.rec = self
        with self._lock:
            stack = self._open.get(tid)
            if stack is None:
                stack = self._open[tid] = _OpenStack(
                    threading.current_thread().name)
            stack.events.append(ev)
            stack.marks.append(mark)
        return ts_ns

    def end(self, name: str, ts_ns: Optional[int] = None,
            args: Optional[dict] = None) -> int:
        """Close this thread's innermost span; ``args`` (what was only
        known at the end) ride the ``E`` event. Returns its end (ns)."""
        ts_ns = ts_ns if ts_ns else time.perf_counter_ns()
        tid = threading.get_native_id()
        ev = {"name": name, "ph": "E", "ts": ts_ns / 1e3,
              "pid": self.pid, "tid": tid}
        if args:
            ev["args"] = args
        with self._lock:
            stack = self._open.get(tid)
            if stack is None:
                return ts_ns  # unbalanced end (attached mid-step): drop
            stack.events.append(ev)
            mark = stack.marks.pop()
            if not stack.marks:
                del self._open[tid]
                self._push_step_locked(stack.thread, stack.events)
                _tls.rec = None
        mark.__exit__(None, None, None)
        return ts_ns

    def instant(self, name: str, cat: str = "event",
                ts_ns: Optional[int] = None,
                args: Optional[dict] = None) -> None:
        """A zero-duration marker (overflow replays, re-traces, ...)."""
        ts = (ts_ns if ts_ns else time.perf_counter_ns()) / 1e3
        tid = threading.get_native_id()
        ev = {"name": name, "cat": cat, "ph": "i", "ts": ts,
              "pid": self.pid, "tid": tid, "s": "t"}
        if args:
            ev["args"] = args
        with self._lock:
            stack = self._open.get(tid)
            if stack is not None:
                stack.events.append(ev)
            else:
                self._push_step_locked(
                    threading.current_thread().name, [ev])

    def span_at(self, name: str, t0_ns: int, t1_ns: int,
                cat: str = "e2e", args: Optional[dict] = None,
                nest: bool = False) -> None:
        """Append one already-completed span as a balanced ``[B, E]`` pair.
        By default it is a ring entry of its own — the e2e stage spans use
        this, so a trace snapshot taken mid-tick can never observe them
        half-open (and they may start before the tick that reports them).
        ``nest=True`` makes it a child of the span open on this thread, if
        any, its start clipped to the events already there."""
        tid = threading.get_native_id()
        bev = {"name": name, "cat": cat, "ph": "B", "ts": t0_ns / 1e3,
               "pid": self.pid, "tid": tid}
        if args:
            bev["args"] = args
        eev = {"name": name, "ph": "E", "ts": max(t0_ns, t1_ns) / 1e3,
               "pid": self.pid, "tid": tid}
        with self._lock:
            stack = self._open.get(tid) if nest else None
            if stack is not None:
                bev["ts"] = max(bev["ts"], stack.events[-1]["ts"])
                eev["ts"] = max(eev["ts"], bev["ts"])
                stack.events += (bev, eev)
            else:
                self._push_step_locked(
                    threading.current_thread().name, [bev, eev])

    class _Span:
        """``with rec.span(...) as sp``: ``sp.note(k=v)`` adds args known
        only at the end; ``sp.t0`` / ``sp.t1`` are the two clock readings
        the ring holds, for callers that keep a duration beside it."""

        __slots__ = ("rec", "name", "cat", "args", "t0", "t1")

        def __init__(self, rec, name, cat, args):
            self.rec, self.name, self.cat, self.args = rec, name, cat, args
            self.t0 = self.t1 = 0

        def __enter__(self):
            self.t0 = self.rec.begin(self.name, self.cat, args=self.args)
            self.args = None
            return self

        def note(self, **args) -> None:
            self.args = {**(self.args or {}), **args}

        def __exit__(self, *exc):
            self.t1 = self.rec.end(self.name, args=self.args)
            return False

        @property
        def elapsed_ns(self) -> int:
            return self.t1 - self.t0

    def span(self, name: str, cat: str = "operator",
             args: Optional[dict] = None) -> "_Span":
        """Context-manager convenience for host-driven span pairs."""
        return SpanRecorder._Span(self, name, cat, args)

    # -- export -------------------------------------------------------------
    def bind(self, registry=None, pipeline: str = "") -> None:
        """Export drop accounting: mirrors ``dropped_steps`` into
        ``dbsp_tpu_obs_trace_dropped_total{pipeline}`` at scrape time (the
        flight recorder got exactly this in its PR; the span ring never
        did). Idempotent; called once at obs attach, before traffic."""
        if registry is None or self._dropped_counter is not None:
            return
        counter = registry.counter(
            "dbsp_tpu_obs_trace_dropped_total",
            "Completed top-level spans evicted from the bounded span ring "
            "(/trace is truncated history once this grows)",
            labels=("pipeline",))
        self._pipeline = pipeline
        self._dropped_counter = counter
        registry.register_collector(self._export)

    def _export(self) -> None:
        self._dropped_counter.labels(pipeline=self._pipeline).set_total(
            float(self.dropped_steps))

    def _retained_locked(self) -> List[Tuple[str, List[dict]]]:  # holds: _lock
        """Every ring's entries, in order of their start."""
        return sorted((step for ring in self._steps.values()
                       for step in ring), key=lambda s: s[1][0]["ts"])

    def events(self) -> List[dict]:
        with self._lock:
            return [ev for _, evs in self._retained_locked() for ev in evs]

    def open_threads(self) -> int:
        """Threads with a span in flight (the per-thread map's size)."""
        with self._lock:
            return len(self._open)

    def to_chrome_trace(self) -> dict:
        with self._lock:
            steps = self._retained_locked()
            threads = {tid: st.thread for tid, st in self._open.items()}
            dropped = self.dropped_steps
        evs = [ev for _, es in steps for ev in es]
        threads.update((es[0]["tid"], thread) for thread, es in steps)
        meta = [{"name": "process_name", "ph": "M", "pid": self.pid,
                 "tid": 0, "args": {"name": self.process}}]
        for tid in sorted(threads):
            meta.append({"name": "thread_name", "ph": "M", "pid": self.pid,
                         "tid": tid, "args": {"name": threads[tid]}})
        return {"traceEvents": meta + evs, "displayTimeUnit": "ms",
                "otherData": {"dropped_steps": dropped,
                              "truncated": dropped > 0,
                              "process": self.process, "pid": self.pid}}

    def to_json(self) -> str:
        return json.dumps(self.to_chrome_trace())

    def clear(self) -> None:
        with self._lock:
            self._steps.clear()
            self._open = {}


def merge_chrome_traces(traces: Sequence[dict]) -> dict:
    """Merge per-process Chrome-trace exports into one Perfetto-loadable
    fleet trace: concatenates ``traceEvents`` (each ring already carries
    its own real pid lanes), dedups identical ``M`` metadata events, and
    folds the per-ring drop accounting into ``otherData``."""
    events: List[dict] = []
    seen_meta = set()
    processes: List[dict] = []
    dropped = 0
    for doc in traces:
        if not doc:
            continue
        for ev in doc.get("traceEvents", ()):
            if ev.get("ph") == "M":
                key = (ev.get("name"), ev.get("pid"), ev.get("tid"),
                       str(ev.get("args")))
                if key in seen_meta:
                    continue
                seen_meta.add(key)
            events.append(ev)
        other = doc.get("otherData", {})
        dropped += int(other.get("dropped_steps", 0) or 0)
        if "process" in other:
            processes.append({"process": other.get("process"),
                              "pid": other.get("pid"),
                              "dropped_steps": other.get("dropped_steps", 0)})
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"dropped_steps": dropped,
                          "truncated": dropped > 0,
                          "processes": processes}}


class E2ETracer:
    """Per-process end-to-end delta tracker: batch trace contexts move
    through three pools as the delta path advances —

    ``_pending``  (note_ingest)   arrived, awaiting a tick
    ``_in_tick``  (tick_begin)    drained into the in-flight tick
    ``_awaiting`` (tick_end)      ticked, awaiting validation publish

    — and are sealed per epoch by :meth:`note_publish` into ``_by_epoch``,
    the bounded annotation map read routes and changefeed records resolve
    stage breakdowns from. The annotation dict is JSON-safe and rides
    ``rec["trace"]`` on every changefeed record, which is how the context
    crosses to replicas (same-host wall clock makes the transport stage a
    plain subtraction).

    Everything mutable sits behind one leaf lock (``_lock``); the metric/
    span/timeline side effects happen outside it via the two-phase
    ``note_publish`` / ``flush_publish`` split so the read plane never
    holds its own lock across an observation.
    """

    def __init__(self, enabled: Optional[bool] = None,
                 max_pending: int = 4096, max_epochs: int = 256):
        self.enabled = trace_e2e_enabled() if enabled is None else bool(enabled)
        self.max_pending = max_pending
        self.max_epochs = max_epochs
        self._lock = threading.Lock()
        self._seq = 0
        self._pending: List[dict] = []
        self._in_tick: List[dict] = []
        self._awaiting: List[dict] = []
        self._tick_t0: Optional[float] = None
        self._by_epoch: "OrderedDict[int, dict]" = OrderedDict()
        self.dropped = 0
        self._hist = None      # wired once by bind()
        self._spans = None
        self._timeline = None
        _tsan_hook(self)

    # -- wiring -------------------------------------------------------------
    def bind(self, registry=None, spans=None, timeline=None) -> None:
        """Wire export surfaces (idempotent for the registry; called once
        at obs attach, before traffic)."""
        if spans is not None:
            self._spans = spans
        if timeline is not None:
            self._timeline = timeline
        if registry is not None and self._hist is None:
            from dbsp_tpu.obs.registry import default_latency_buckets
            self._hist = registry.histogram(
                "dbsp_tpu_e2e_stage_seconds",
                "Per-stage latency of the end-to-end delta path "
                "ingest->tick->publish->changefeed->replica->read (closed "
                "stage set: obs.tracing.E2E_STAGES; writer stages sampled "
                "once per published epoch, replica stages once per applied "
                "changefeed batch, serve once per read)",
                labels=("stage",), buckets=default_latency_buckets())

    # -- writer-side path ---------------------------------------------------
    def note_ingest(self, rows: int, ts: Optional[float] = None,
                    trace_id: Optional[str] = None) -> Optional[str]:
        """Stamp one arrived batch; returns its trace id (caller-supplied
        via the ``X-Dbsp-Trace`` header, or freshly minted)."""
        if not self.enabled or rows <= 0:
            return None
        now = time.time() if ts is None else ts
        with self._lock:
            if len(self._pending) >= self.max_pending:
                self.dropped += 1
                return None
            if trace_id is None:
                self._seq += 1
                trace_id = "%x-%d" % (os.getpid(), self._seq)
            self._pending.append(
                {"id": trace_id, "ingest_ts": now, "rows": rows})
        return trace_id

    def tick_begin(self) -> None:
        """The tick that is about to drain the input queues starts: every
        pending context's queue_wait ends here. Called by the controller
        *before* it drains ``_pushed``/endpoint rows, so any context
        stamped earlier has its rows included in this tick."""
        if not self.enabled:
            return
        now = time.time()
        with self._lock:
            batch, self._pending = self._pending, []
            self._tick_t0 = now
            for ctx in batch:
                ctx["queue_wait_s"] = max(0.0, now - ctx["ingest_ts"])
            self._in_tick.extend(batch)

    def tick_end(self) -> List[str]:
        """The tick finished (step + output emission): contexts move to
        the awaiting-publish pool. Returns the batch trace ids so the
        controller can link its timeline tick record to them."""
        if not self.enabled:
            return []
        now = time.time()
        with self._lock:
            t0, self._tick_t0 = self._tick_t0, None
            moved, self._in_tick = self._in_tick, []
            tick_s = max(0.0, now - t0) if t0 is not None else 0.0
            for ctx in moved:
                ctx["tick_s"] = tick_s
                ctx["tick_end_ts"] = now
            self._awaiting.extend(moved)
            return [ctx["id"] for ctx in moved[:_MAX_IDS_PER_EPOCH]]

    def note_publish(self, epoch: int,
                     ts: Optional[float] = None) -> Optional[dict]:
        """Seal every awaiting context into epoch ``epoch``'s annotation
        (called by ``ReadPlane.publish`` under the plane lock — state move
        only; pass the result to :meth:`flush_publish` after the plane
        lock is released for the metric/span/timeline effects).

        Stage arithmetic is exact for the oldest batch: queue_wait + tick
        + publish sum to ``publish_ts - ingest_ts`` on one wall clock.
        """
        if not self.enabled:
            return None
        now = time.time() if ts is None else ts
        with self._lock:
            moved, self._awaiting = self._awaiting, []
            if not moved:
                return None
            oldest = min(moved, key=lambda c: c["ingest_ts"])
            ann = {
                "ids": [c["id"] for c in moved[:_MAX_IDS_PER_EPOCH]],
                "n": len(moved),
                "rows": sum(c["rows"] for c in moved),
                "epoch": epoch,
                "ingest_ts": oldest["ingest_ts"],
                "publish_ts": now,
                "stages": {
                    "queue_wait": oldest["queue_wait_s"],
                    "tick": oldest["tick_s"],
                    "publish": max(0.0, now - oldest["tick_end_ts"]),
                },
            }
            self._by_epoch[epoch] = ann
            while len(self._by_epoch) > self.max_epochs:
                self._by_epoch.popitem(last=False)
        return ann

    def flush_publish(self, ann: Optional[dict]) -> None:
        """Record the sealed epoch's writer stages: histogram samples, one
        ``e2e`` span per stage in the writer's ring, and timeline
        ``e2e_stage`` records for EXPLAIN SPIKE's stage detector."""
        if ann is None:
            return
        for stage in ("queue_wait", "tick", "publish"):
            self._record_stage(stage, ann["stages"][stage], ann["ids"],
                               spans=self._spans)

    def _record_stage(self, stage: str, seconds: float,
                      ids: List[str], spans=None) -> None:
        hist = self._hist
        if hist is not None:
            hist.labels(stage=stage).observe(seconds)
        if spans is not None:
            t1 = time.perf_counter_ns()
            spans.span_at("e2e:" + stage, t1 - int(seconds * 1e9), t1,
                          args={"trace": ids, "stage": stage,
                                "seconds": round(seconds, 6)})
        tl = self._timeline
        if tl is not None:
            tl.note_e2e_stage(stage, seconds, ids)

    # -- lookups ------------------------------------------------------------
    def for_epoch(self, epoch) -> Optional[dict]:
        """The sealed annotation for one published epoch (None once it has
        aged out of the bounded map, or for pre-tracing epochs)."""
        if not self.enabled or epoch is None:
            return None
        with self._lock:
            return self._by_epoch.get(epoch)

    def annotate_read(self, resp: dict, t0_perf: float) -> dict:
        """Attach ``age_s`` + per-stage breakdown to a primary ``/view``
        response (resolved from the response's epoch); observes the serve
        stage. Mutates and returns ``resp``."""
        if not self.enabled:
            return resp
        serve_s = max(0.0, time.perf_counter() - t0_perf)
        hist = self._hist
        if hist is not None:
            hist.labels(stage="serve").observe(serve_s)
        ann = self.for_epoch(resp.get("epoch"))
        if ann is not None:
            stages = dict(ann["stages"])
            stages["serve"] = serve_s
            resp["age_s"] = max(0.0, time.time() - ann["ingest_ts"])
            resp["stages"] = stages
            resp["trace"] = {"ids": list(ann["ids"])}
        return resp

    # -- replica-side path --------------------------------------------------
    def note_apply(self, ann: Optional[dict], recv_ts: float,
                   apply_s: float, spans=None) -> Optional[dict]:
        """Replica-side stage stamps for one applied changefeed record:
        extends the writer annotation (same trace ids) with transport =
        receipt - publish and the measured apply fold. ``spans`` is the
        *replica's* ring, so the same delta shows up in both processes'
        traces under identical ids."""
        if not self.enabled or ann is None:
            return None
        transport_s = max(0.0, recv_ts - ann.get("publish_ts", recv_ts))
        apply_s = max(0.0, apply_s)
        ids = list(ann.get("ids", ()))
        ext = dict(ann)
        stages = dict(ann.get("stages", {}))
        stages["transport"] = transport_s
        stages["apply"] = apply_s
        ext["stages"] = stages
        ext["applied_ts"] = recv_ts + apply_s
        # the stage spans go to the *replica's* ring, not the writer's
        self._record_stage("transport", transport_s, ids, spans=spans)
        self._record_stage("apply", apply_s, ids, spans=spans)
        return ext

    def annotate_replica_read(self, resp: dict, ext: Optional[dict],
                              t0_perf: float) -> dict:
        """Replica flavor of :meth:`annotate_read`: the stage breakdown
        comes from the stored applied annotation (which already includes
        transport/apply)."""
        if not self.enabled:
            return resp
        serve_s = max(0.0, time.perf_counter() - t0_perf)
        hist = self._hist
        if hist is not None:
            hist.labels(stage="serve").observe(serve_s)
        # epoch gate: a fold can land between the table snapshot and this
        # annotation — never label one epoch's rows with another's trace
        if ext is not None and ext.get("epoch") == resp.get("epoch"):
            stages = dict(ext["stages"])
            stages["serve"] = serve_s
            resp["age_s"] = max(0.0, time.time() - ext["ingest_ts"])
            resp["stages"] = stages
            resp["trace"] = {"ids": list(ext["ids"])}
        return resp

    def stats(self) -> dict:
        with self._lock:
            return {"enabled": self.enabled, "seq": self._seq,
                    "pending": len(self._pending),
                    "in_tick": len(self._in_tick),
                    "awaiting_publish": len(self._awaiting),
                    "epochs": len(self._by_epoch),
                    "dropped": self.dropped}
