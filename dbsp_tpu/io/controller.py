"""Controller: drives a circuit from transport endpoints with backpressure.

Reference: ``adapters/src/controller/mod.rs`` — ``Controller::with_config``
(:119), the circuit thread ("calls dbsp.step() when input buffered", :1-14),
the backpressure thread (pauses endpoints over threshold, :11-15),
``start/pause/stop`` (:196-246) — and the stats module
(``controller/stats.rs:129``: per-endpoint + global atomic counters).

One difference by design: the reference needs a separate backpressure thread
because endpoints buffer inside foreign-threaded callbacks; here endpoint
buffers are checked on the same circuit loop that drains them (pause/resume
transitions happen at drain points), which keeps the protocol identical
(pause over threshold, resume at half) with one fewer moving thread.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
import time
from typing import Dict, List, Optional

from dbsp_tpu.circuit.runtime import CircuitHandle
from dbsp_tpu.io.catalog import Catalog
from dbsp_tpu.io.format import INPUT_FORMATS, OUTPUT_FORMATS
from dbsp_tpu.io.transport import InputTransport, OutputTransport
from dbsp_tpu.testing.tsan import maybe_instrument as _tsan_hook


@dataclasses.dataclass
class ControllerConfig:
    """Reference: ``PipelineConfig`` (controller/config.rs:28-131)."""

    min_batch_records: int = 1_000     # step as soon as this many buffered
    max_buffered_records: int = 100_000  # pause endpoint above this
    flush_interval_s: float = 0.25     # step at least this often when idle
    # durability (dbsp_tpu.checkpoint): directory for periodic checkpoint
    # generations and the cadence in controller ticks. 0/None defer to the
    # env knobs DBSP_TPU_CHECKPOINT_EVERY_TICKS / DBSP_TPU_CHECKPOINT_DIR;
    # a configured directory with no interval uses the default cadence.
    checkpoint_dir: Optional[str] = None
    checkpoint_every_ticks: int = 0
    # transport hardening (io/minikafka.py): connect/read timeout, retry
    # attempts, and exponential-backoff base for broker-backed endpoints
    transport_timeout_s: float = 10.0
    transport_retries: int = 5
    transport_backoff_s: float = 0.05
    # tiered trace residency (dbsp_tpu/residency.py) — the per-pipeline
    # override of the DBSP_TPU_DEVICE_ROWS / _HOST_ROWS / _COLD_DIR env
    # knobs, honored by BOTH engines (compiled leveled traces and host
    # spines). None = env default; <= 0 = explicitly unbounded; cold_dir
    # unset defaults to <checkpoint_dir>/cold when checkpointing is on.
    device_rows: Optional[int] = None
    host_rows: Optional[int] = None
    cold_dir: Optional[str] = None


class _InputEndpoint:
    def __init__(self, name: str, collection, transport: InputTransport,
                 parser, notify_arrival=None):
        self.name = name
        self.collection = collection
        self.transport = transport
        self.parser = parser
        # freshness stamp hook (Controller._note_arrival): called with the
        # row count of each arriving chunk, outside the endpoint lock
        self.notify_arrival = notify_arrival
        self.lock = threading.Lock()
        self.rows: List = []
        self.eoi = False
        self.paused = False
        self.error = None
        self.total_records = 0
        self.total_bytes = 0
        # rows to DROP before feeding the circuit: restore-on-deploy sets
        # this to the checkpointed consumed count for transports that
        # replay their stream from the beginning, so replayed rows the
        # restored state already contains are not double-applied
        self.skip_rows = 0
        _tsan_hook(self)

    def on_chunk(self, chunk: bytes) -> None:
        n_new = 0
        with self.lock:
            self.total_bytes += len(chunk)
            try:
                self.parser.feed(chunk)
                taken = self.parser.take()
                self.rows.extend(taken)
                n_new = len(taken)
            except Exception as e:  # bad data must not kill the reader
                # record, surface via stats, and terminate the endpoint so
                # eoi_reached() cannot hang on a dead feed
                self.error = f"{type(e).__name__}: {e}"
                taken = self.parser.take()
                self.rows.extend(taken)
                n_new = len(taken)
                self.eoi = True
                self.transport.stop()
        # arrival wall-time stamp for freshness tracking — OUTSIDE the
        # endpoint lock (the timeline has its own guard; no nesting)
        if n_new and self.notify_arrival is not None:
            self.notify_arrival(n_new)

    def on_eoi(self) -> None:
        with self.lock:
            try:
                self.parser.eoi()
                self.rows.extend(self.parser.take())
            except Exception as e:
                self.error = f"{type(e).__name__}: {e}"
            self.eoi = True

    def drain(self) -> List:
        with self.lock:
            rows, self.rows = self.rows, []
            if self.skip_rows:
                k = min(self.skip_rows, len(rows))
                self.skip_rows -= k
                rows = rows[k:]  # already counted in the restored totals
            self.total_records += len(rows)
            return rows

    def buffered(self) -> int:
        with self.lock:
            return len(self.rows)


class _OutputEndpoint:
    def __init__(self, name: str, collection, transport: OutputTransport,
                 encoder):
        self.name = name
        self.collection = collection
        self.transport = transport
        self.encoder = encoder
        self.total_records = 0
        self.total_bytes = 0
        self.error = None  # terminal sink failure (dead output broker)
        self.pending = None  # batch whose write failed, awaiting retry
        # private delta queue: endpoints never race other handle consumers
        self.cursor = collection.handle.register_consumer()
        _tsan_hook(self)


class Controller:
    """Owns the circuit thread; endpoints feed it, outputs drain from it."""

    def __init__(self, handle: CircuitHandle, catalog: Catalog,
                 config: ControllerConfig = ControllerConfig()):
        self.handle = handle
        self.catalog = catalog
        self.config = config
        self.inputs: Dict[str, _InputEndpoint] = {}
        self.outputs: Dict[str, _OutputEndpoint] = {}
        self.state = "initializing"  # reference PipelineState
        self.steps = 0
        self._stop = threading.Event()
        self._pushed = 0              # host-pushed rows awaiting a step
        self.total_pushed = 0         # lifetime counter (stats)
        # of the pushed rows that a parser made (HTTP POSTs): how many its
        # columnar bulk path took, how many its line parser
        self.parsed_columnar = 0
        self.parsed_fallback = 0
        self._pushed_lock = threading.Lock()
        self._running = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._step_lock = threading.Lock()
        self._lifecycle_lock = threading.Lock()  # stop()/pause() idempotency
        # monitor hooks (the SLO watchdog's evaluation site): run after
        # every step and on idle loop passes, on the circuit thread
        self._monitors: List = []
        # durability: periodic checkpointing into a generation store
        # (dbsp_tpu.checkpoint). Enabled when a directory is configured
        # (config field or DBSP_TPU_CHECKPOINT_DIR); the cadence defaults
        # to checkpoint.DEFAULT_EVERY_TICKS when unset.
        from dbsp_tpu import checkpoint as _ckpt

        self.checkpoint_dir = config.checkpoint_dir or \
            os.environ.get("DBSP_TPU_CHECKPOINT_DIR") or None
        every = config.checkpoint_every_ticks or \
            int(os.environ.get("DBSP_TPU_CHECKPOINT_EVERY_TICKS", "0"))
        self.checkpoint_every = every or (
            _ckpt.DEFAULT_EVERY_TICKS if self.checkpoint_dir else 0)
        self.last_checkpoint_tick: Optional[int] = None
        self.checkpoints = 0
        self.checkpoint_error: Optional[str] = None
        self._last_ckpt_step = 0
        # optional obs.FlightRecorder (PipelineObs.attach_controller wires
        # it) — checkpoint/restore events become SLO-visible through it
        self.flight = None
        # optional obs.Timeline (same wiring site): per-tick latency /
        # rows / queue-depth records plus freshness stamps (arrival at
        # push sites, visibility at validation publish)
        self.timeline = None
        # tiered trace residency: route the unified budgets into whichever
        # engine this controller drives (compiled handle or host spines).
        # Applying HERE — not only on the manager deploy path — is what
        # makes the allowlist-accepted config keys honored everywhere a
        # controller is built (an accepted-but-ignored key is the silent
        # failure the allowlist exists to prevent).
        from dbsp_tpu import residency as _res

        rcfg = _res.resolve(
            device_rows=config.device_rows, host_rows=config.host_rows,
            cold_dir=config.cold_dir or (
                os.path.join(self.checkpoint_dir, "cold")
                if self.checkpoint_dir else None))
        # applied UNCONDITIONALLY: an explicit <= 0 config key resolves to
        # an INACTIVE config that must still reach the engine to DISABLE
        # the env budget it read at construction (gating on rcfg.active
        # here would be the accepted-but-ignored key again, in reverse).
        # Kept on the controller: restore_from re-applies it — a host
        # restore rebuilds spines from decoded state, which would
        # otherwise silently drop the per-pipeline budgets.
        self._residency_cfg = rcfg
        _res.apply_to_driver(handle, rcfg)
        # lock-free read serving plane (dbsp_tpu/serving.py): every
        # catalog output becomes a served view; the step path publishes
        # immutable snapshots at each validation publish and readers
        # never touch _step_lock. DBSP_TPU_READPLANE=0 disables
        # publication (reads fall back to the quiesced control path).
        from dbsp_tpu.serving import ReadPlane

        self.read_plane = ReadPlane()
        for vname, vcol in self.catalog.outputs.items():
            self.read_plane.add_view(vname, vcol.handle)
        # fleet-wide delta tracing (obs/tracing.py): every ingested batch
        # gets a trace context that flows push -> tick -> publish ->
        # changefeed -> replica -> read; DBSP_TPU_TRACE_E2E=0 disables.
        from dbsp_tpu.obs.tracing import E2ETracer, default_recorder

        self.e2e = E2ETracer()
        # the ``tick`` span and its controller-side phases land here
        # (PipelineObs.attach_controller hands its own ring)
        self.spans = default_recorder()
        labels = getattr(handle, "input_labels", None)
        if labels is not None:
            # the compiled driver names its per-input spans by node; the
            # catalog knows the tables
            for name, col in self.catalog.inputs.items():
                op = getattr(col.handle, "_op", None)
                if op in labels:
                    labels[op] = name
        _tsan_hook(self)

    # -- endpoint wiring ----------------------------------------------------
    def add_input_endpoint(self, name: str, collection: str,
                           transport: InputTransport,
                           fmt: str = "csv") -> None:
        col = self.catalog.input(collection)
        parser = INPUT_FORMATS[fmt](col.dtypes)
        ep = _InputEndpoint(name, col, transport, parser,
                            notify_arrival=self._note_arrival)
        self.inputs[name] = ep
        configure = getattr(transport, "configure_retry", None)
        if configure is not None:  # broker-backed transports honor the
            configure(timeout_s=self.config.transport_timeout_s,  # knobs
                      retries=self.config.transport_retries,
                      backoff_s=self.config.transport_backoff_s)
        transport.start(ep.on_chunk, ep.on_eoi)

    def add_output_endpoint(self, name: str, collection: str,
                            transport: OutputTransport,
                            fmt: str = "csv") -> None:
        col = self.catalog.output(collection)
        configure = getattr(transport, "configure_retry", None)
        if configure is not None:
            # sinks retry SYNCHRONOUSLY on the circuit thread (the parked
            # pending batch re-sends next step), so the retry budget here
            # bounds per-step stall time under a dead output broker
            configure(timeout_s=self.config.transport_timeout_s,
                      retries=self.config.transport_retries,
                      backoff_s=self.config.transport_backoff_s)
        self.outputs[name] = _OutputEndpoint(name, col, transport,
                                             OUTPUT_FORMATS[fmt]())

    def add_monitor(self, fn) -> None:
        """Register a zero-arg callable run by the circuit loop after each
        step and while idling (obs.PipelineObs.watch registers here — the
        controller loop is where SLOs evaluate). Exceptions are swallowed:
        a watchdog must never take the pipeline down."""
        self._monitors.append(fn)

    def _run_monitors(self) -> None:
        for fn in self._monitors:
            try:
                fn()
            except Exception:  # noqa: BLE001 — monitoring is best-effort
                pass

    # push-style input (HTTP endpoints on the server use this)
    def push(self, collection: str, rows) -> int:
        col = self.catalog.input(collection)
        n = col.push_rows(rows)
        self.note_pushed(n)
        return n

    def _note_arrival(self, n: int,
                      trace_id: Optional[str] = None) -> Optional[str]:
        """Freshness: stamp the wall-time a batch of rows reached this
        controller (push sites and transport chunk callbacks both land
        here). Visibility is stamped when the batch's results publish —
        the gap is the freshness sample. Also mints (or adopts, when the
        pusher sent ``X-Dbsp-Trace``) the batch's e2e trace context;
        returns its id."""
        tl = self.timeline
        if n and tl is not None:
            tl.note_arrival(n)
        if n:
            return self.e2e.note_ingest(n, trace_id=trace_id)
        return None

    def note_pushed(self, n: int, trace_id: Optional[str] = None,
                    columnar: Optional[int] = None) -> Optional[str]:
        """Record host-pushed rows (HTTP endpoints / client API) so the
        circuit loop's batching sees them alongside transport buffers —
        without this, pushed rows waited for an explicit /step. Rows out
        of a parser say how many of them its bulk path took (``columnar``;
        the line parser took the rest). Returns the batch's e2e trace id
        (None when tracing is off)."""
        with self._pushed_lock:
            self._pushed += int(n)
            self.total_pushed += int(n)
        if columnar is not None:
            # (a block of its own: tests/test_concurrency.py seeds its
            # defect by unguarding exactly the two writes above)
            with self._pushed_lock:
                self.parsed_columnar += int(columnar)
                self.parsed_fallback += int(n) - int(columnar)
        return self._note_arrival(n, trace_id=trace_id)

    # -- durability (dbsp_tpu.checkpoint) -----------------------------------
    def _controller_state(self) -> dict:
        """The controller-side section of a checkpoint manifest: the step
        counter plus each input endpoint's consumed high-water mark — the
        replay position recovery resumes feeds from (exactly-once: rows
        counted here were fully stepped; rows past them must be re-fed)."""
        return {
            "steps": self.steps,
            "pushed_records": self.total_pushed,
            # read-plane epoch at checkpoint time: restore republishes the
            # checkpointed view state under this epoch, so changefeed
            # cursors from before the restore resume exactly (older
            # cursors get a synthesized snapshot record)
            "read_epoch": self.read_plane.epoch,
            "inputs": {name: {"total_records": ep.total_records,
                              "total_bytes": ep.total_bytes}
                       for name, ep in self.inputs.items()},
        }

    def checkpoint(self, path: Optional[str] = None) -> dict:
        """Write one checkpoint generation (quiesced under the step lock).
        Uses the configured directory when ``path`` is omitted."""
        with self._step_lock:
            return self._checkpoint_locked(path)

    def _checkpoint_locked(self, path=None) -> dict:  # holds: _step_lock
        from dbsp_tpu import checkpoint as _ckpt

        path = path or self.checkpoint_dir
        if not path:
            raise ValueError(
                "no checkpoint directory configured (set checkpoint_dir "
                "in the pipeline config or DBSP_TPU_CHECKPOINT_DIR)")
        tick = getattr(self.handle, "_tick", None)
        info = _ckpt.save(self.handle, path,
                          controller=self._controller_state(),
                          tick=self.steps if tick is None else None,
                          output_pending={
                              name: out.pending
                              for name, out in self.outputs.items()
                              if out.pending is not None},
                          read_plane=(self.read_plane.state_batches()
                                      if self.read_plane.enabled else None))
        self.checkpoints += 1
        self.last_checkpoint_tick = info["tick"]
        self.checkpoint_error = None
        self._last_ckpt_step = self.steps
        if self.flight is not None:
            self.flight.record("checkpoint", tick=info["tick"],
                               generation=info["generation"],
                               linked=info["linked_arrays"],
                               bytes=info["bytes"])
        return info

    def _checkpoint_due(self) -> bool:
        return bool(self.checkpoint_every and self.checkpoint_dir) and \
            self.steps - self._last_ckpt_step >= self.checkpoint_every

    def _periodic_checkpoint_locked(self) -> None:  # holds: _step_lock
        """Periodic-cadence hook on the circuit thread, run when
        :meth:`_checkpoint_due`: a checkpoint failure is recorded (flight +
        stats) but never takes the pipeline down — serving continues at
        reduced durability."""
        try:
            self._checkpoint_locked()
        except Exception as e:  # noqa: BLE001 — durability is best-effort
            self.checkpoint_error = f"{type(e).__name__}: {e}"
            self._last_ckpt_step = self.steps  # back off a full interval
            if self.flight is not None:
                self.flight.record("checkpoint",
                                   error=self.checkpoint_error[:200])

    def restore_from(self, path: Optional[str] = None) -> dict:
        """Restore the newest valid generation into this controller's
        driver and adopt the checkpointed controller counters. Call before
        :meth:`start` (deploy-time recovery).

        Input replay position: each endpoint's checkpointed consumed-row
        count becomes its SKIP prefix when the transport replays its
        stream from the beginning (``transport.replays_from_start`` —
        file inputs), so replayed rows the restored state already
        contains are dropped, not double-applied. Broker-backed inputs
        own their position server-side (consumer-group offsets) and
        resume there; rows fetched-but-unstepped at a crash follow the
        transport's own at-most-once auto-commit contract."""
        from dbsp_tpu import checkpoint as _ckpt

        path = path or self.checkpoint_dir
        if not path:
            raise ValueError("no checkpoint directory configured")
        with self._step_lock:
            info = _ckpt.restore(self.handle, path)
            # a HOST restore rebuilds spines from decoded state (fresh
            # Spine objects, module-default budgets) — re-apply the
            # pipeline's resolved residency config so the budgets survive
            # recovery; no-op for the compiled driver (its handle keeps
            # residency_cfg across restore)
            from dbsp_tpu import residency as _res

            _res.apply_to_driver(self.handle, self._residency_cfg)
            c = info.get("controller") or {}
            self.steps = int(c.get("steps", info["tick"]))
            with self._pushed_lock:  # writes join note_pushed's guard
                self.total_pushed = int(c.get("pushed_records", 0))
            for name, d in (c.get("inputs") or {}).items():
                ep = self.inputs.get(name)
                if ep is not None:
                    with ep.lock:  # counters share the endpoint's guard
                        ep.total_records = int(d.get("total_records", 0))
                        ep.total_bytes = int(d.get("total_bytes", 0))
                        if getattr(ep.transport, "replays_from_start",
                                   False):
                            ep.skip_rows = ep.total_records
            for name, batch in (info.get("output_pending") or {}).items():
                out = self.outputs.get(name)
                if out is not None:  # undelivered sink deltas re-send on
                    out.pending = batch  # the first post-restore emission
            if self.read_plane.enabled:
                # republish the checkpointed view state under the
                # checkpointed epoch; pre-restore changefeed cursors
                # resume via a synthesized snapshot record
                self.read_plane.restore(
                    int(c.get("read_epoch", 0)),
                    info.get("read_plane") or {})
            self.last_checkpoint_tick = info["tick"]
            self._last_ckpt_step = self.steps
        return info

    # -- lifecycle (reference: start/pause/stop, controller/mod.rs:196-246) -
    def start(self) -> None:
        # under the lifecycle lock like pause()/stop(): a start() racing
        # a stop() must not resurrect "running" state or spawn a second
        # circuit thread (found by tools/check_concurrency.py C001 —
        # state/_thread are claimed writelock(_lifecycle_lock))
        with self._lifecycle_lock:
            if self.state == "shutdown":
                return
            self.state = "running"
            self._running.set()
            if self._thread is None:
                self._thread = threading.Thread(target=self._circuit_loop,
                                                daemon=True, name="circuit")
                self._thread.start()

    def pause(self) -> None:
        with self._lifecycle_lock:
            if self.state in ("paused", "shutdown"):
                return  # idempotent under double-call
            self.state = "paused"
            # clear the run gate INSIDE the lifecycle lock: a racing
            # start() otherwise interleaves its _running.set() before
            # this clear, leaving state=="running" with the gate down —
            # a healthy-looking pipeline that never steps
            self._running.clear()
        with self._step_lock:  # quiesce: wait out any in-flight step
            self._flush_driver_locked()

    def stop(self) -> None:
        with self._lifecycle_lock:
            already = self.state == "shutdown"
            self.state = "shutdown"
            self._stop.set()
            self._running.set()  # unblock
            if already:
                # second stop(): the first one owns teardown — just wait
                # it out instead of racing the circuit thread join and
                # re-running the flush/checkpoint sequence
                if self._thread:
                    self._thread.join(timeout=10)
                return
        for ep in self.inputs.values():
            ep.transport.stop()
        if self._thread:
            self._thread.join(timeout=10)
        with self._step_lock:
            # graceful shutdown: flush any open deferred-validation
            # interval, then persist a final checkpoint so a clean stop
            # is always resumable from its exact last tick. ONLY when
            # there is progress past the last checkpoint: a no-progress
            # save would be a redundant generation, and on an
            # aborted/refused deploy it would overwrite a store the
            # operator may still want to inspect with FRESH-EMPTY state
            # (turning a strict-mode refusal into a silent reset).
            self._flush_driver_locked()
            if self.checkpoint_dir and self.steps > self._last_ckpt_step:
                try:
                    self._checkpoint_locked()
                except Exception as e:  # noqa: BLE001 — still shut down
                    self.checkpoint_error = f"{type(e).__name__}: {e}"

    def _flush_driver_locked(self) -> None:  # holds: _step_lock
        """Validate + deliver a compiled driver's open interval (no-op for
        host handles and at the default serve cadence of 1). Called with
        the step lock held, at quiesce points and when the loop idles, so
        a validation cadence > 1 never strands buffered outputs."""
        flush = getattr(self.handle, "flush", None)
        if flush is not None:
            was_open = getattr(self.handle, "interval_open", False)
            flush()
            self._emit_outputs()
            # snapshot publication rides every validation publish (cheap
            # no-op when no output's step_id advanced)
            self.read_plane.publish(tracer=self.e2e)
            tl = self.timeline
            if was_open and tl is not None:
                # a deferred-validation interval just closed: its buffered
                # ticks' results became visible now, not at their steps
                tl.note_visible(list(self.catalog.outputs))

    @contextlib.contextmanager
    def quiesce(self):
        """Public quiesce point: hold the step lock (no serving tick in
        flight) with any open deferred-validation interval flushed, for
        the duration of the ``with`` block. The sanctioned way for other
        components (the HTTP server's ``/lineage`` and ``/profile``
        handlers) to get a consistent, non-advancing view of the engine —
        reaching through to ``_step_lock`` directly is a C003 lint
        violation (tools/check_concurrency.py).

        Lock order: ``_step_lock`` is the OUTERMOST engine lock; nested
        inside it are ``_pushed_lock`` (static C002 graph) and the
        per-endpoint ``_InputEndpoint.lock`` (drain/restore — a
        cross-class edge the static graph does not model; the runtime
        sanitizer's lock-order tracking covers it). Never acquire an
        endpoint lock and THEN call into a step-lock-taking controller
        method — that is the ABBA inversion. Do not call ``step()``,
        ``checkpoint()`` or another ``quiesce()`` from inside the block —
        the step lock is not reentrant."""
        with self._step_lock:
            self._flush_driver_locked()
            yield self

    def eoi_reached(self) -> bool:
        """All inputs exhausted AND fully processed.

        Buffers drain at the START of a step, so emptiness alone races with
        an in-flight step (its results aren't visible yet); taking the step
        lock serializes against it.
        """
        if not all(ep.eoi and ep.buffered() == 0
                   for ep in self.inputs.values()):
            return False
        with self._step_lock:
            # "fully processed" includes a compiled driver's open deferred-
            # validation interval — validate + deliver it before answering,
            # or a cadence > 1 strands the final ticks' outputs
            self._flush_driver_locked()
            return all(ep.eoi and ep.buffered() == 0
                       for ep in self.inputs.values())

    # -- the circuit thread ---------------------------------------------------
    def _circuit_loop(self) -> None:
        last_flush = time.monotonic()
        while not self._stop.is_set():
            if not self._running.wait(timeout=0.1):
                continue
            if self._stop.is_set():
                break
            stepped = False
            # the running re-check happens UNDER the step lock: once pause()
            # holds the lock, no new step can slip in after it returns
            with self._step_lock:
                if self._running.is_set():
                    buffered = sum(ep.buffered()
                                   for ep in self.inputs.values())
                    with self._pushed_lock:
                        buffered += self._pushed
                    now = time.monotonic()
                    if buffered >= self.config.min_batch_records or (
                            buffered > 0 and
                            now - last_flush >= self.config.flush_interval_s):
                        self._step_locked()
                        last_flush = now
                        stepped = True
            if not stepped:
                with self._step_lock:
                    self._flush_driver_locked()
                self._run_monitors()
                time.sleep(0.005)
            self._backpressure()

    def step(self) -> None:
        """One controller-driven tick: drain buffers -> step -> emit outputs."""
        self.spans.begin("step.lock_wait", "step")
        with self._step_lock:
            self.spans.end("step.lock_wait")
            self._step_locked()

    def _step_locked(self) -> None:  # holds: _step_lock
        """The ``tick`` span: opened before anything is drained, closed
        when everything a client of ``/step`` waits on is done. Its start
        is also the timeline's tick start — one clock reading for both."""
        spans = self.spans
        with spans.span("tick", "step", args={"tick": self.steps}) as tick:
            # queue_wait ends for every batch stamped so far: contexts
            # noted BEFORE this point have their rows in the buffers
            # drained below (push sites append rows before stamping the
            # context)
            self.e2e.tick_begin()
            with spans.span("tick.drain_endpoints", "tick"):
                with self._pushed_lock:
                    rows_in = self._pushed
                    self._pushed = 0  # this step consumes all pushed rows
                for ep in self.inputs.values():
                    rows = ep.drain()
                    if rows:
                        ep.collection.push_rows(rows)
                        rows_in += len(rows)
            self.handle.step()
            self.steps += 1
            with spans.span("tick.emit_outputs", "tick"):
                rows_out = self._emit_outputs()
            trace_ids = self.e2e.tick_end()
            if not getattr(self.handle, "interval_open", False):
                # validation publish: swap in immutable read-plane
                # snapshots (host engine: every step; compiled: when the
                # deferred-validation interval closed this tick). BEFORE
                # the periodic checkpoint so a checkpoint captures this
                # tick's publication.
                with spans.span("tick.publish", "tick"):
                    self.read_plane.publish(tracer=self.e2e)
            if self._checkpoint_due():
                with spans.span("tick.checkpoint", "tick"):
                    self._periodic_checkpoint_locked()
            if self._monitors:
                with spans.span("tick.monitors", "tick"):
                    self._run_monitors()
            tick.note(rows_in=rows_in, rows_out=rows_out,
                      batches=trace_ids)
            # the tick record is stamped LAST so checkpoint writes and
            # in-tick monitor work (everything inside the step lock) count
            # toward the tick's wall latency — that is what a serving
            # client waits on
            tl = self.timeline
            if tl is not None:
                tl.note_tick(self.steps, time.perf_counter_ns() - tick.t0,
                             rows_in=rows_in, rows_out=rows_out,
                             queue_depth=sum(ep.buffered()
                                             for ep in self.inputs.values()),
                             trace_ids=trace_ids)
                if not getattr(self.handle, "interval_open", False):
                    # this step's results validated and published (host
                    # engine: every step; compiled: when no deferred
                    # interval remains)
                    tl.note_visible(list(self.catalog.outputs))

    def _emit_outputs(self) -> int:
        from dbsp_tpu.zset.batch import concat_batches

        emitted = 0
        for out in self.outputs.values():
            # per-consumer queue: the HTTP server's /read peeks the same
            # handle, so a destructive take() here would race it
            batch = out.collection.handle.read_consumer(out.cursor)
            if out.pending is not None:
                # deltas whose write failed fold into this emission (Z-set
                # sum — exactly what the consumer queue does for laggards)
                batch = out.pending if batch is None else concat_batches(
                    [out.pending, batch]).consolidate().shrink_to_fit()
                out.pending = None
            if batch is not None and int(batch.live_count()) > 0:
                data = out.encoder.encode(batch)
                try:
                    out.transport.write(data)
                    out.transport.flush()
                except Exception as e:  # noqa: BLE001 — a dead SINK must
                    # not kill the circuit thread: record the failure (the
                    # flight source latches it as degraded), retain the
                    # batch for the next emission, and keep serving — a
                    # recovered sink misses nothing
                    out.error = f"{type(e).__name__}: {e}"
                    out.pending = batch
                    continue
                out.error = None
                out.total_bytes += len(data)
                n = len(batch.to_dict())
                out.total_records += n
                emitted += n
        return emitted

    def _backpressure(self) -> None:
        for ep in self.inputs.values():
            n = ep.buffered()
            if not ep.paused and n > self.config.max_buffered_records:
                ep.paused = True
                ep.transport.pause()
            elif ep.paused and n < self.config.max_buffered_records // 2:
                ep.paused = False
                ep.transport.resume()

    def input_queue_depths(self) -> Dict[str, int]:
        """Rows buffered per input endpoint, awaiting the next drain —
        the /status queue-depth section. Each read takes only the
        endpoint's own lock; never the step lock."""
        return {name: ep.buffered() for name, ep in self.inputs.items()}

    # -- stats (reference: ControllerStatus, controller/stats.rs) -----------
    def stats(self) -> dict:
        return {
            "state": self.state,
            "steps": self.steps,
            "pushed_records": self.total_pushed,
            "parsed_records": {"columnar": self.parsed_columnar,
                               "fallback": self.parsed_fallback},
            "checkpoints": self.checkpoints,
            "last_checkpoint_tick": self.last_checkpoint_tick,
            "checkpoint_error": self.checkpoint_error,
            "read_plane": self.read_plane.stats(),
            "e2e": self.e2e.stats(),
            "inputs": {
                name: {
                    "total_records": ep.total_records,
                    "total_bytes": ep.total_bytes,
                    "buffered_records": ep.buffered(),
                    "paused": ep.paused,
                    "eoi": ep.eoi,
                    # a transport's terminal failure (dead broker past the
                    # retry budget) surfaces as the endpoint's error too
                    "error": ep.error or getattr(ep.transport, "error",
                                                 None),
                    "transport_retries": getattr(ep.transport, "retries",
                                                 0),
                } for name, ep in self.inputs.items()
            },
            "outputs": {
                name: {
                    "total_records": out.total_records,
                    "total_bytes": out.total_bytes,
                    "error": out.error,
                } for name, out in self.outputs.items()
            },
        }
