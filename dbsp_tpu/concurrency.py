"""Concurrency schema: declared guards for every shared mutable field of
the serving plane.

Sibling of :data:`dbsp_tpu.checkpoint.STATE_SCHEMA` — where that registry
claims each serving-state field's *persistence* disposition so restore can
never silently drop state, this one claims each field's *guard* so a data
race can never silently appear. Both registries are linted in BOTH
directions (unclaimed field / stale claim) through the shared walker in
``tools/schema_walk.py``; the consumers are:

* ``tools/check_concurrency.py`` — the STATIC pass: verifies lock-guarded
  fields are only touched under their declared lock (rule C001), builds
  the static lock-order graph from nested ``with`` acquisitions and
  reports cycles (C002), and flags cross-class private-lock reach-through
  (C003);
* ``dbsp_tpu.testing.tsan`` — the RUNTIME sanitizer (``DBSP_TPU_TSAN=1``):
  instruments locks and attribute access on the classes registered here,
  enforcing the declared guards plus Eraser-style lockset inference
  (Savage et al., TOCS'97) and lock-order inversion detection.

Guard taxonomy (the value strings in :data:`CONCURRENCY_SCHEMA`):

``immutable``
    Bound once during construction (``__init__`` or a class-level
    default) and never rebound. Method calls on the object are fine —
    ``threading.Event``/``queue.Queue`` fields are ``immutable`` bindings
    of internally-synchronized objects.
``lock(<attr>)``
    Every read AND write must hold ``self.<attr>``: inside a
    ``with self.<attr>:`` block, or in a method whose signature line
    carries a ``# holds: <attr>`` marker (callers own the acquisition —
    the ``*_locked`` idiom). The strictest claim; use it when lock-free
    reads would observe torn multi-field state.
``writelock(<attr>)``
    Writes (assignment, augmented assignment, subscript stores, mutating
    container calls) must hold ``self.<attr>``; bare reads are allowed by
    declared invariant — single GIL-atomic loads of a monotone or latched
    value (the pervasive locked-writes/lock-free-stats idiom).
``owner``
    Thread-confined: after construction exactly one thread touches the
    field. Statically exempt; the runtime sanitizer records the first
    accessing thread and flags any second thread.
``lockset``
    Externally synchronized — the protecting lock belongs to another
    object (e.g. fields only mutated on paths serialized by the owning
    controller's step lock). Statically exempt; the runtime sanitizer
    runs pure Eraser inference over WRITES: once a second thread writes,
    the intersection of lock sets held across all writes must stay
    non-empty.
``gil-atomic: <why>``
    Exempt by declared invariant; the rationale is REQUIRED and the lint
    rejects a bare ``gil-atomic``. For single reference assignments whose
    races are benign by design (last-write-wins caches, wiring that
    happens strictly before the threads exist).

Every guard may carry a trailing ``: <note>``; for ``gil-atomic`` the
note is the load-bearing invariant. Static findings are waivable with a
``# concurrency: ok`` comment on the flagged line; runtime findings are
not waivable — fix the race or change the claim.
"""

from __future__ import annotations

import re
from typing import Dict, NamedTuple, Optional, Tuple

#: marker comment on a ``def`` line documenting that callers invoke this
#: method with the named lock(s) held (comma-separated attr names)
HOLDS_MARKER = "# holds:"

#: waiver comment suppressing a static finding on its line
WAIVER = "# concurrency: ok"

#: (file relative to repo root, class name) pairs under schema control —
#: the serving plane's shared-state surface. tools/check_concurrency.py
#: walks these; dbsp_tpu.testing.tsan instruments their instances.
CONCURRENCY_CLASSES: Tuple[Tuple[str, str], ...] = (
    ("dbsp_tpu/io/controller.py", "Controller"),
    ("dbsp_tpu/io/controller.py", "_InputEndpoint"),
    ("dbsp_tpu/io/controller.py", "_OutputEndpoint"),
    ("dbsp_tpu/io/server.py", "CircuitServer"),
    ("dbsp_tpu/manager.py", "PipelineManager"),
    ("dbsp_tpu/manager.py", "Pipeline"),
    ("dbsp_tpu/manager.py", "_CompilerService"),
    ("dbsp_tpu/io/transport.py", "FileInputTransport"),
    ("dbsp_tpu/io/transport.py", "FileOutputTransport"),
    ("dbsp_tpu/io/transport.py", "KafkaInputTransport"),
    ("dbsp_tpu/io/transport.py", "KafkaOutputTransport"),
    ("dbsp_tpu/io/minikafka.py", "MiniKafkaBroker"),
    ("dbsp_tpu/io/minikafka.py", "_Conn"),
    ("dbsp_tpu/io/minikafka.py", "MiniConsumer"),
    ("dbsp_tpu/io/minikafka.py", "MiniProducer"),
    ("dbsp_tpu/obs/flight.py", "FlightRecorder"),
    ("dbsp_tpu/obs/flight.py", "CompiledFlightSource"),
    ("dbsp_tpu/obs/flight.py", "ControllerFlightSource"),
    ("dbsp_tpu/obs/flight.py", "HostFlightSource"),
    ("dbsp_tpu/obs/timeline.py", "Timeline"),
    ("dbsp_tpu/obs/tracing.py", "SpanRecorder"),
    ("dbsp_tpu/obs/tracing.py", "E2ETracer"),
    ("dbsp_tpu/obs/slo.py", "SLOConfig"),
    ("dbsp_tpu/obs/slo.py", "SLOWatchdog"),
    ("dbsp_tpu/obs/registry.py", "MetricsRegistry"),
    ("dbsp_tpu/obs/registry.py", "Metric"),
    ("dbsp_tpu/obs/registry.py", "Counter"),
    ("dbsp_tpu/obs/registry.py", "Gauge"),
    ("dbsp_tpu/obs/registry.py", "Histogram"),
    ("dbsp_tpu/obs/registry.py", "Summary"),
    ("dbsp_tpu/serving.py", "ReadPlane"),
    ("dbsp_tpu/serving.py", "_ViewState"),
    ("dbsp_tpu/serving.py", "ReplicaServer"),
)

#: extra modules swept for C003 (private-lock reach-through) beyond the
#: ones CONCURRENCY_CLASSES already names
REACH_THROUGH_MODULES: Tuple[str, ...] = (
    "dbsp_tpu/obs/instrument.py",
    "dbsp_tpu/io/config.py",
)

# Deliberately NOT schema'd (documented, not forgotten):
#   * obs/registry.py ``_Child``/``_Bound`` — per-label-set value cells,
#     guarded by the owning Metric's ``_lock``; they have no methods of
#     their own and every mutation goes through Metric._inc/_set/_observe
#     under that lock.
#   * the per-request ``Handler`` classes nested in the HTTP servers —
#     one instance per request, no shared state of their own.
#   * the engine layer (CompiledHandle/CircuitHandle/Spines) — serialized
#     by the controller step lock by design; its fields are the
#     *checkpoint* schema's concern, and every serving-path entry point
#     is covered by the controller/server claims here.
#   * serving.py ``_Run``/``ViewSnapshot`` — immutable value objects
#     (``__slots__``, every field bound once in ``__init__``); the
#     lock-free read contract depends on them never mutating, which the
#     ``_ViewState.snap`` claim below pins at the pointer swap.

CONCURRENCY_SCHEMA: Dict[str, Dict[str, str]] = {
    "Controller": {
        "handle": "immutable",
        "catalog": "immutable",
        "config": "immutable",
        "checkpoint_dir": "immutable",
        "checkpoint_every": "immutable",
        "_residency_cfg": "immutable",
        "inputs": "gil-atomic: endpoint wiring is single-threaded deploy "
                  "work before start(); post-start the dicts are only read",
        "outputs": "gil-atomic: endpoint wiring is single-threaded deploy "
                   "work before start(); post-start the dicts are only read",
        "state": "writelock(_lifecycle_lock)",
        "steps": "writelock(_step_lock)",
        "_stop": "immutable",
        "_running": "immutable",
        "_pushed_lock": "immutable",
        "_step_lock": "immutable",
        "_lifecycle_lock": "immutable",
        "_pushed": "lock(_pushed_lock)",
        "total_pushed": "writelock(_pushed_lock)",
        "parsed_columnar": "writelock(_pushed_lock)",
        "parsed_fallback": "writelock(_pushed_lock)",
        "_thread": "writelock(_lifecycle_lock)",
        "_monitors": "gil-atomic: append-only list appended at deploy "
                     "time; the circuit loop's iteration tolerates a "
                     "mid-append snapshot under the GIL",
        "last_checkpoint_tick": "writelock(_step_lock)",
        "checkpoints": "writelock(_step_lock)",
        "checkpoint_error": "writelock(_step_lock)",
        "_last_ckpt_step": "writelock(_step_lock)",
        "flight": "gil-atomic: wired once by PipelineObs.attach_controller "
                  "before start(); read-only afterwards",
        "timeline": "gil-atomic: wired once by PipelineObs."
                    "attach_controller before start(); read-only "
                    "afterwards (note_* calls go through the timeline's "
                    "own lock)",
        "read_plane": "immutable",
        "e2e": "immutable",
        "spans": "gil-atomic: wired once by PipelineObs.attach_controller "
                 "before start(); read-only afterwards (begin/end go "
                 "through the recorder's own lock)",
    },
    "_InputEndpoint": {
        "name": "immutable",
        "collection": "immutable",
        "transport": "immutable",
        "parser": "immutable",
        "notify_arrival": "immutable",
        "lock": "immutable",
        "rows": "lock(lock)",
        "skip_rows": "lock(lock)",
        "eoi": "writelock(lock)",
        "error": "writelock(lock)",
        "total_records": "writelock(lock)",
        "total_bytes": "writelock(lock)",
        "paused": "lockset: single writer — the circuit loop's "
                  "backpressure pass; stats() reads tolerate staleness",
    },
    "_OutputEndpoint": {
        "name": "immutable",
        "collection": "immutable",
        "transport": "immutable",
        "encoder": "immutable",
        "cursor": "immutable",
        "total_records": "lockset: mutated only on paths serialized by "
                         "the owning controller's step lock",
        "total_bytes": "lockset: mutated only on paths serialized by "
                       "the owning controller's step lock",
        "error": "lockset: mutated only on paths serialized by the "
                 "owning controller's step lock; stats() reads tolerate "
                 "staleness",
        "pending": "lockset: mutated only on paths serialized by the "
                   "owning controller's step lock (emission, restore, "
                   "checkpoint)",
    },
    "CircuitServer": {
        "controller": "immutable",
        "profiler": "immutable",
        "obs": "immutable",
        "spans": "immutable",
        "analysis_findings": "immutable",
        "httpd": "immutable",
        "port": "immutable",
        "_thread": "gil-atomic: wired once by start()",
        "_last_profile": "gil-atomic: last-served-report cache — one "
                         "reference assignment per query; /debug's read "
                         "is last-write-wins by design",
        "_last_lineage": "gil-atomic: last-served-report cache — one "
                         "reference assignment per query; /debug's read "
                         "is last-write-wins by design",
    },
    "PipelineManager": {
        "programs": "lock(lock)",
        "pipelines": "lock(lock)",
        "storage_path": "immutable",
        "lock": "immutable",
        "compiler": "immutable",
        "httpd": "immutable",
        "port": "immutable",
        "_thread": "gil-atomic: wired once by start()",
    },
    "Pipeline": {
        "name": "immutable",
        "program": "immutable",
        "config": "immutable",
        "status": "gil-atomic: one deploying writer, then the shutdown "
                  "caller — ordered by the observed status transition; "
                  "reference assignments, describe() reads tolerate "
                  "in-progress values",
        "controller": "gil-atomic: see status",
        "server": "gil-atomic: see status",
        "port": "gil-atomic: see status",
        "error": "gil-atomic: see status",
        "mode": "gil-atomic: see status",
        "obs": "gil-atomic: see status",
        "fallback_reason": "gil-atomic: see status",
        "restored_tick": "gil-atomic: see status",
        "replicas": "gil-atomic: scaled by operator actions (replica "
                    "routes); list append/replace are single GIL-atomic "
                    "ops and fanout_read snapshots the list reference "
                    "before indexing",
        "_fanout_rr": "gil-atomic: racy round-robin counter — concurrent "
                      "increments may collide, costing distribution "
                      "fairness, never correctness",
        "_replica_gauge": "gil-atomic: wired once on the first "
                          "add_replicas; one reference assignment",
        "_replica_breached": "gil-atomic: per-replica breach latch keyed "
                             "by name; writers (scrape collector, "
                             "replicas route) are last-write-wins on a "
                             "boolean by design",
    },
    "_CompilerService": {
        "mgr": "immutable",
        "q": "immutable",
        "thread": "immutable",
    },
    "FileInputTransport": {
        "name": "immutable",
        "replays_from_start": "immutable",
        "path": "immutable",
        "chunk_size": "immutable",
        "follow": "immutable",
        "_paused": "immutable",
        "_stop": "immutable",
        "_thread": "gil-atomic: wired once by start(); join() only reads",
    },
    "FileOutputTransport": {
        "name": "immutable",
        "_lock": "immutable",
        "_f": "lock(_lock)",
    },
    "KafkaInputTransport": {
        "name": "immutable",
        "_kind": "immutable",
        "_mod": "immutable",
        "brokers": "immutable",
        "topics": "immutable",
        "group_id": "immutable",
        "poll_timeout": "immutable",
        "_stop": "immutable",
        "_paused": "immutable",
        "_consumer": "gil-atomic: assigned once by start() before the "
                     "reader thread exists; configure_retry/retries "
                     "reads tolerate None pre-start",
        "_retry_cfg": "gil-atomic: written at endpoint wiring "
                      "(configure_retry) before start()",
        "error": "gil-atomic: single writer (the reader thread), "
                 "monotone None->str; stats() reads tolerate staleness",
    },
    "KafkaOutputTransport": {
        "name": "immutable",
        "_kind": "immutable",
        "_mod": "immutable",
        "topic": "immutable",
        "_producer": "immutable",
    },
    "MiniKafkaBroker": {
        "lock": "immutable",
        "server": "immutable",
        "host": "immutable",
        "port": "immutable",
        "address": "immutable",
        "_thread": "immutable",
        "topics": "lock(lock)",
        "offsets": "lock(lock)",
        "_conns": "lock(lock)",
    },
    "_Conn": {
        "addr": "immutable",
        "lock": "immutable",
        "timeout_s": "writelock(lock)",
        "max_retries": "writelock(lock)",
        "backoff_s": "writelock(lock)",
        "retries": "writelock(lock)",
        "sock": "lock(lock)",
        "rfile": "lock(lock)",
    },
    "MiniConsumer": {
        "topics": "immutable",
        "group": "immutable",
        "conn": "immutable",
    },
    "MiniProducer": {
        "conn": "immutable",
        "lock": "immutable",
        "_pending": "lock(lock)",
    },
    "FlightRecorder": {
        "capacity": "immutable",
        "_lock": "immutable",
        "_ring": "lock(_lock)",
        "_seq": "lock(_lock)",
        "dropped": "writelock(_lock)",
        "dropped_by_source": "lock(_lock)",
    },
    "Timeline": {
        "capacity": "immutable",
        "enabled": "immutable",
        "pipeline": "immutable",
        "_lock": "immutable",
        "_records": "lock(_lock)",
        "_seq": "lock(_lock)",
        "dropped": "writelock(_lock)",
        "_flight_seen": "lock(_lock)",
        "_pending_rows": "lock(_lock)",
        "_oldest_pending_ts": "lock(_lock)",
        "_last_visible_ts": "lock(_lock)",
        "_freshness": "lock(_lock)",
        "_spike_metric_seen": "lock(_lock)",
        "_fresh_hist": "immutable",
        "_stale_gauge": "immutable",
        "_spike_counter": "immutable",
    },
    "SpanRecorder": {
        "process": "immutable",
        "pid": "immutable",
        "max_steps": "immutable",
        "_lock": "immutable",
        "_steps": "lock(_lock)",
        "_open": "lock(_lock)",
        "dropped_steps": "writelock(_lock)",
        "_dropped_counter": "gil-atomic: wired once by bind() during obs "
                            "attach, before any traffic; read-only "
                            "afterwards",
        "_pipeline": "gil-atomic: wired once by bind() during obs attach, "
                     "before any traffic; read-only afterwards",
    },
    "E2ETracer": {
        "enabled": "gil-atomic: boolean kill-switch latch resolved at "
                   "construction from DBSP_TPU_TRACE_E2E and toggled only "
                   "by A/B harnesses between blocks; a racy read costs at "
                   "most one stray sample",
        "max_pending": "immutable",
        "max_epochs": "immutable",
        "_lock": "immutable",
        "_seq": "lock(_lock)",
        "_pending": "lock(_lock)",
        "_in_tick": "lock(_lock)",
        "_awaiting": "lock(_lock)",
        "_tick_t0": "lock(_lock)",
        "_by_epoch": "lock(_lock)",
        "dropped": "writelock(_lock)",
        "_hist": "gil-atomic: wired once by bind() during obs attach, "
                 "before any traffic; read-only afterwards",
        "_spans": "gil-atomic: wired once by bind() during obs attach, "
                  "before any traffic; read-only afterwards",
        "_timeline": "gil-atomic: wired once by bind() during obs attach, "
                     "before any traffic; read-only afterwards",
    },
    "CompiledFlightSource": {
        "ch": "immutable",
        "flight": "immutable",
        "_lock": "immutable",
        "_lat_seen": "lock(_lock)",
        "_cause_seen": "lock(_lock)",
        "_overhead_seen": "lock(_lock)",
        "_replays_seen": "lock(_lock)",
        "_rows_moved_seen": "lock(_lock)",
        "_consolidate_seen": "lock(_lock)",
        "_residency_seen": "lock(_lock)",
        "_cold_seen": "lock(_lock)",
        "_clock_ns": "lock(_lock)",
    },
    "ControllerFlightSource": {
        "controller": "immutable",
        "flight": "immutable",
        "_lock": "immutable",
        "_errors_seen": "lock(_lock)",
    },
    "HostFlightSource": {
        "circuit": "immutable",
        "flight": "immutable",
        "_spines": "immutable",
        "_spine_nids": "immutable",
        "_exchanges": "immutable",
        "_wm_ops": "immutable",
        "_depth": "lockset: mutated only by scheduler-event callbacks, "
                  "serialized by whatever drives step() — the "
                  "controller's step lock on the serving path",
        "_step_t0": "lockset: see _depth",
        "_tick": "lockset: see _depth",
        "_merged_seen": "lockset: see _depth",
        "_exch_seen": "lockset: see _depth",
        "_wm_lag_seen": "lockset: see _depth",
        "_res_seen": "lockset: see _depth",
    },
    "SLOConfig": {
        "p99_tick_seconds": "immutable",
        "tick_p50_multiple": "immutable",
        "watermark_lag": "immutable",
        "fallback_to_host": "immutable",
        "overflow_replays": "immutable",
        "window_ticks": "immutable",
        "window_s": "immutable",
    },
    "SLOWatchdog": {
        "flight": "immutable",
        "config": "immutable",
        "pipeline": "immutable",
        "freeze_window": "immutable",
        "_lock": "immutable",
        "_breach_counter": "immutable",
        "_incidents_counter": "immutable",
        "_seen_seq": "lock(_lock)",
        "_ticks": "lock(_lock)",
        "_replay_ts": "lock(_lock)",
        "_wm_lag": "lock(_lock)",
        "_fallback": "lock(_lock)",
        "_transport": "lock(_lock)",
        "_restore_failed": "lock(_lock)",
        "_restores": "lock(_lock)",
        "_active": "lock(_lock)",
        "_incidents": "lock(_lock)",
        "_ids": "lock(_lock)",
    },
    "MetricsRegistry": {
        "_lock": "immutable",
        "_metrics": "lock(_lock)",
        "_collectors": "lock(_lock)",
    },
    "Metric": {
        "kind": "immutable",
        "name": "immutable",
        "help": "immutable",
        "label_names": "immutable",
        "_lock": "immutable",
        "_children": "lock(_lock)",
    },
    "ReadPlane": {
        "enabled": "immutable",
        "capacity": "immutable",
        "compact_after": "immutable",
        "_lock": "immutable",
        "_wakeup": "immutable",
        "_views": "writelock(_lock): registered at controller "
                  "construction (add_view); reader routes do one "
                  "GIL-atomic dict lookup",
        "epoch": "writelock(_lock): monotone int; changefeed/stats reads "
                 "are single loads",
        "publishes": "writelock(_lock)",
        "last_publish_ts": "writelock(_lock)",
        "flight": "gil-atomic: wired once by bind() before traffic; one "
                  "reference assignment",
        "_read_qps": "gil-atomic: wired once by bind() before traffic; "
                     "the idempotence guard's read tolerates None",
        "_read_seconds": "gil-atomic: see _read_qps",
        "_publish_total": "gil-atomic: see _read_qps",
    },
    "_ViewState": {
        "name": "immutable",
        "handle": "immutable",
        "mode": "immutable",
        "nkeys": "lockset: written only under the owning plane's _lock "
                 "(publish/restore); monotone None->int, lock-free reads "
                 "are single loads",
        "cid": "lockset: rebound only under the owning plane's _lock "
               "(restore re-registration)",
        "snap": "lockset: the lock-free read contract — publication "
                "swaps this pointer under the owning plane's _lock; "
                "readers resolve it with ONE GIL-atomic load and then "
                "touch only the immutable ViewSnapshot",
        "prev_rows": "lockset: publisher-only diff base, mutated under "
                     "the owning plane's _lock",
        "feed": "lockset: appended/cleared under the owning plane's "
                "_lock; changefeed reads snapshot it via list(feed) — "
                "atomic under the GIL on a deque",
        "dropped_epoch": "lockset: written under the owning plane's "
                         "_lock; monotone int, lock-free reads are "
                         "single loads",
        "seen_step": "lockset: publisher-only cursor, mutated under the "
                     "owning plane's _lock",
    },
    "ReplicaServer": {
        "primary": "immutable",
        "views_served": "immutable",
        "name": "immutable",
        "poll_timeout_s": "immutable",
        "_lock": "immutable",
        "_state": "writelock(_lock)",
        "_cursor": "writelock(_lock)",
        "_nkeys": "writelock(_lock)",
        "_applied_ts": "writelock(_lock)",
        "_sorted": "writelock(_lock): per-view cache cell — readers do "
                   "one GIL-atomic load and rebuild under the lock on "
                   "miss; a racy extra rebuild is benign",
        "applied": "writelock(_lock)",
        "stalled": "gil-atomic: boolean latch toggled by the "
                   "stall()/resume() caller; the feed loop's read is a "
                   "benign race (one extra poll)",
        "_stop": "immutable",
        "_httpd": "immutable",
        "port": "immutable",
        "_serve_thread": "immutable",
        "_feed_thread": "immutable",
        "e2e": "immutable",
        "spans": "immutable",
        "_trace": "writelock(_lock)",
    },
    "Counter": {},
    "Gauge": {},
    "Histogram": {
        "bounds": "immutable",
    },
    "Summary": {
        "quantiles": "immutable",
    },
}


class Guard(NamedTuple):
    kind: str                 # immutable|lock|writelock|owner|lockset|
    lock: Optional[str]       # gil-atomic; attr name for lock/writelock
    note: Optional[str]


_GUARD_RE = re.compile(
    r"^(immutable|owner|lockset|gil-atomic"
    r"|(?:lock|writelock)\(([A-Za-z_][A-Za-z0-9_]*)\))"
    r"(?::\s*(.+))?$", re.S)


class GuardError(ValueError):
    pass


def parse_guard(value: str) -> Guard:
    """Parse one schema guard string; raises :class:`GuardError` on a
    malformed guard or a ``gil-atomic`` without its rationale."""
    m = _GUARD_RE.match(value.strip())
    if m is None:
        raise GuardError(
            f"malformed guard {value!r} (expected immutable | lock(X) | "
            "writelock(X) | owner | lockset | gil-atomic: <why>)")
    head, lock, note = m.group(1), m.group(2), m.group(3)
    kind = head.split("(")[0]
    if kind == "gil-atomic" and not (note and note.strip()):
        raise GuardError(
            "gil-atomic claims must state their invariant: "
            f"'gil-atomic: <why>' (got {value!r})")
    return Guard(kind, lock, note.strip() if note else None)


def effective_schema(class_name: str,
                     bases: Dict[str, Tuple[str, ...]],
                     schema_map: Optional[Dict[str, Dict[str, str]]] = None,
                     ) -> Dict[str, str]:
    """The merged guard dict for ``class_name``: its own entry layered
    over its (transitive) base classes' entries. ``bases`` maps class
    name -> direct base names (the static pass derives it from the AST;
    the runtime derives it from the MRO). ``schema_map`` defaults to
    :data:`CONCURRENCY_SCHEMA` (tests layer gallery classes over it)."""
    schema_map = CONCURRENCY_SCHEMA if schema_map is None else schema_map
    out: Dict[str, str] = {}

    def fold(name: str) -> None:
        for b in bases.get(name, ()):
            fold(b)
        out.update(schema_map.get(name, {}))

    fold(class_name)
    return out
