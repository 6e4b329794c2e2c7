"""Metric primitives + the registry that owns them.

Reference: the per-pipeline metric surface of ``server/prometheus.rs`` and
``controller/stats.rs:129`` (global + per-endpoint atomic counters). Here
the primitives are host-side and lock-protected — they sit on control-plane
paths (scheduler event handlers, scrape-time collectors), never inside
jitted kernels.

Types:
  Counter    — monotone; ``_total`` names.
  Gauge      — set/inc/dec; scrape-time collectors usually drive these.
  Histogram  — log-bucketed (geometric bucket bounds); renders cumulative
               ``_bucket{le=...}`` series plus ``_sum``/``_count`` and can
               answer :meth:`Histogram.quantile` host-side.
  Summary    — same sketch as Histogram but renders ``{quantile=...}``
               lines (p50/p95/p99) — for step latency, where operators want
               the quantiles directly in the scrape.

Every metric is labeled: ``metric.labels(worker="0").inc()``. An empty
label set is the common case and needs no ``labels()`` call.

Naming convention (enforced at registration): metric names look like
``dbsp_tpu_<subsystem>_<name>_<unit>`` — lowercase snake_case, prefix
``dbsp_tpu_``, final segment one of the allowed units. Counters must end in
``_total``. ``tools/check_metrics.py`` re-checks the convention over the
tree as a tier-1 lint.
"""

from __future__ import annotations

import bisect
import math
import re
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from dbsp_tpu.testing.tsan import maybe_instrument as _tsan_hook

# final name segment must be a unit (prometheus naming conventions; "total"
# is the counter suffix, "info" the build-info idiom; "timestamp" covers
# event-time domains whose unit the engine cannot know; "ms" is event time
# where it can: the time nodes' watermarks, milliseconds by the contract
# of ``timeseries/`` — never a duration, those are "seconds")
ALLOWED_UNITS = ("total", "seconds", "rows", "bytes", "count", "ratio",
                 "info", "timestamp", "ms")

_NAME_RE = re.compile(r"^dbsp_tpu_[a-z0-9]+(_[a-z0-9]+)+$")
_LABEL_RE = re.compile(r"^[a-z_][a-z0-9_]*$")

# The closed label-name allowlist for engine metrics. Label VALUES drive
# time-series cardinality, so label names are restricted to dimensions
# with enumerable value sets (operators, nodes, phases, causes, ...) —
# never per-key, per-row, or per-tick identities. tools/check_metrics.py
# lints every in-tree registration against this list (tier-1 via
# tests/test_obs.py); grow it deliberately, with the value set in mind.
# ("le"/"quantile" are exposition-internal, reserved for obs/export.py.)
ALLOWED_LABEL_NAMES = frozenset((
    "operator", "node", "endpoint", "phase", "cause", "reason", "path",
    "rule", "severity", "slo", "pipeline", "worker", "mode", "state",
    "query", "kind",
    # kernel dispatch attribution: "kernel" names a Z-set kernel entry
    # point (merge/probe/expand/...), "backend" the implementation it
    # dispatched to (native, xla, or an accelerator's xla_* formulation)
    # — both closed, enumerable sets (zset/native_merge.py::KERNELS x the
    # backends kernels.count_kernel_dispatch is called with)
    "kernel", "backend",
    # tiered trace residency (dbsp_tpu/residency.py): "tier" and the
    # transition endpoints draw from the closed {device, host, disk} set
    "tier", "tier_from", "tier_to",
    # freshness tracking (obs/timeline.py): "view" names a registered
    # output view of the pipeline's catalog — the value set is the
    # pipeline's declared views, fixed at program deploy time
    "view",
    # flight-recorder drop accounting (obs/flight.py): "source" is the
    # event kind group that was evicted from the bounded ring — drawn
    # from the closed FlightRecorder event-kind vocabulary
    "source",
    # read serving plane (dbsp_tpu/serving.py): "route" is the read API
    # surface served (closed set: serving.READ_ROUTES); "replica" names
    # a manager-orchestrated read replica — the value set is the
    # deployment's replica topology, fixed at orchestration time like
    # "pipeline"/"worker"
    "route", "replica",
    # end-to-end delta tracing (obs/tracing.py): "stage" is one hop of
    # the ingest→tick→publish→changefeed→replica→read path — the closed
    # set obs.tracing.E2E_STAGES (queue_wait, tick, publish, transport,
    # apply, serve)
    "stage",
    # time nodes (timeseries/counters.py): "dir" is the way a row crossed
    # a window's bounds — the closed set {out, in}
    "dir",
))


class MetricNameError(ValueError):
    pass


def validate_metric_name(name: str, kind: Optional[str] = None) -> None:
    """Raise :class:`MetricNameError` unless ``name`` follows the
    ``dbsp_tpu_<subsystem>_<name>_<unit>`` convention (and, for counters,
    ends in ``_total``)."""
    if not _NAME_RE.match(name):
        raise MetricNameError(
            f"metric name {name!r} must match "
            "dbsp_tpu_<subsystem>_<name>_<unit> (lowercase snake_case)")
    if kind == "counter" and not name.endswith("_total"):
        raise MetricNameError(
            f"counter {name!r} must end in '_total'")
    unit = name.rsplit("_", 1)[1]
    if unit not in ALLOWED_UNITS:
        raise MetricNameError(
            f"metric name {name!r} must end in a unit suffix "
            f"{ALLOWED_UNITS}, got {unit!r}")
    if kind in ("histogram", "summary") and name.endswith("_total"):
        raise MetricNameError(
            f"{kind} {name!r} must not end in '_total' (reserved for "
            "counters)")


def default_latency_buckets() -> Tuple[float, ...]:
    """Geometric (log-spaced) latency bounds: 100us .. ~100s, x2 per
    bucket — 21 buckets, enough resolution for p50/p95/p99 over anything
    from a fused XLA tick to a whole-step TPU compile."""
    return tuple(100e-6 * 2 ** i for i in range(21))


class _Child:
    """One label-set instance of a metric; holds the actual value(s)."""

    __slots__ = ("value", "sum", "count", "buckets")

    def __init__(self, nbuckets: int = 0):
        self.value = 0.0
        self.sum = 0.0
        self.count = 0
        self.buckets = [0] * nbuckets if nbuckets else None

    def snapshot(self) -> "_Child":
        """Deep-enough copy for consistent reads; take under the owning
        metric's lock (samples()/quantile() both go through this — one
        copy site, so a new field cannot be copied in one and torn in
        the other)."""
        s = _Child()
        s.value, s.sum, s.count = self.value, self.sum, self.count
        s.buckets = list(self.buckets) if self.buckets is not None else None
        return s


class Metric:
    """Base: a named family of children keyed by label values."""

    kind = "untyped"

    def __init__(self, name: str, help: str, labels: Sequence[str] = ()):
        validate_metric_name(name, self.kind)
        for ln in labels:
            if not _LABEL_RE.match(ln):
                raise MetricNameError(f"bad label name {ln!r} on {name!r}")
        self.name = name
        self.help = help
        self.label_names = tuple(labels)
        self._children: Dict[Tuple[str, ...], _Child] = {}
        self._lock = threading.Lock()

    def _child(self, key: Tuple[str, ...]) -> _Child:
        # fully under the lock — no lock-free fast path. The old
        # check-then-act (a naked dict read before a locked setdefault)
        # could hand out a child that clear_children() had just detached,
        # silently dropping updates into a dead cell; the schema claims
        # _children as lock(_lock), and these are control-plane metrics
        # where an uncontended acquire costs nothing measurable.
        with self._lock:
            c = self._children.get(key)
            if c is None:
                c = self._children[key] = self._new_child()
            return c

    def _new_child(self) -> _Child:
        return _Child()

    def labels(self, **labels: str) -> "_Bound":
        if set(labels) != set(self.label_names):
            raise ValueError(
                f"{self.name} expects labels {self.label_names}, "
                f"got {tuple(labels)}")
        key = tuple(str(labels[n]) for n in self.label_names)
        return _Bound(self, self._child(key))

    @property
    def _default(self) -> _Child:
        return self._child(())

    def clear_children(self) -> None:
        """Drop every label-set child. For gauge families whose HELP
        contract is "the LAST <event>" (e.g. the per-node profile
        gauges): re-exporting without clearing would leave children from
        the previous event serving stale values next to fresh ones.
        Never call on counters — monotone families must not regress."""
        with self._lock:
            self._children.clear()

    def samples(self) -> List[Tuple[Tuple[str, ...], _Child]]:
        """(label values, child-SNAPSHOT) pairs in insertion order. Copies
        are taken under the metric lock so a scrape concurrent with
        observe()/inc() renders internally consistent values (sum/count/
        buckets from one moment), never torn mid-update state."""
        with self._lock:
            return [(key, c.snapshot())
                    for key, c in self._children.items()]


class _Bound:
    """A metric bound to one label set; forwards the value API."""

    __slots__ = ("_metric", "_c")

    def __init__(self, metric: Metric, child: _Child):
        self._metric = metric
        self._c = child

    def inc(self, amount: float = 1.0) -> None:
        self._metric._inc(self._c, amount)

    def dec(self, amount: float = 1.0) -> None:
        self._metric._inc(self._c, -amount)

    def set(self, value: float) -> None:
        self._metric._set(self._c, value)

    def set_total(self, value: float) -> None:
        # collector API (counters): mirror an external monotone total
        self._metric._set(self._c, value)

    def observe(self, value: float) -> None:
        self._metric._observe(self._c, value)

    @property
    def value(self) -> float:
        return self._c.value


class Counter(Metric):
    kind = "counter"

    def inc(self, amount: float = 1.0) -> None:
        self._inc(self._default, amount)

    def _inc(self, c: _Child, amount: float) -> None:
        if amount < 0:
            raise ValueError(f"counter {self.name} cannot decrease")
        with self._lock:
            c.value += amount

    def _set(self, c: _Child, value: float) -> None:
        """Collector API: mirror an externally-accumulated monotone total
        (endpoint counters owned by the controller). Never regresses."""
        with self._lock:
            c.value = max(c.value, value)

    def set_total(self, value: float) -> None:
        self._set(self._default, value)

    def _observe(self, c, value):  # pragma: no cover
        raise TypeError(f"counter {self.name} has no observe()")

    @property
    def value(self) -> float:
        return self._default.value


class Gauge(Metric):
    kind = "gauge"

    def set(self, value: float) -> None:
        self._set(self._default, value)

    def inc(self, amount: float = 1.0) -> None:
        self._inc(self._default, amount)

    def dec(self, amount: float = 1.0) -> None:
        self._inc(self._default, -amount)

    def _inc(self, c: _Child, amount: float) -> None:
        with self._lock:
            c.value += amount

    def _set(self, c: _Child, value: float) -> None:
        with self._lock:
            c.value = value

    def _observe(self, c, value):  # pragma: no cover
        raise TypeError(f"gauge {self.name} has no observe()")

    @property
    def value(self) -> float:
        return self._default.value


class Histogram(Metric):
    kind = "histogram"

    def __init__(self, name: str, help: str, labels: Sequence[str] = (),
                 buckets: Optional[Sequence[float]] = None):
        super().__init__(name, help, labels)
        bounds = tuple(buckets) if buckets else default_latency_buckets()
        if list(bounds) != sorted(bounds) or len(set(bounds)) != len(bounds):
            raise ValueError(f"{name}: bucket bounds must strictly increase")
        self.bounds = bounds

    def _new_child(self) -> _Child:
        return _Child(nbuckets=len(self.bounds) + 1)  # + overflow

    def observe(self, value: float) -> None:
        self._observe(self._default, value)

    def _observe(self, c: _Child, value: float) -> None:
        i = bisect.bisect_left(self.bounds, value)
        with self._lock:
            c.buckets[i] += 1
            c.sum += value
            c.count += 1

    def _inc(self, c, amount=1.0):  # pragma: no cover
        raise TypeError(f"histogram {self.name} has no inc()")

    def _set(self, c, value):  # pragma: no cover
        raise TypeError(f"histogram {self.name} has no set()")

    # -- host-side quantile estimate (bucket upper-bound interpolation) ----
    def quantile(self, q: float, labels: Tuple[str, ...] = ()) -> float:
        """Estimated q-quantile (0..1) from the bucket sketch: linear
        interpolation inside the containing bucket (log buckets make the
        relative error bounded by the bucket growth factor). Computed
        over a snapshot taken under the lock, like :meth:`samples` — a
        live child mid-observe() would yield a torn count/bucket pair."""
        with self._lock:
            c = self._children.get(labels)
            if c is not None:
                c = c.snapshot()
        return self.quantile_of(c, q)

    def quantile_of(self, c: Optional[_Child], q: float) -> float:
        """Quantile over one child/snapshot (export.py renders summaries
        from :meth:`samples` snapshots through this)."""
        if c is None or c.count == 0:
            return float("nan")
        rank = q * c.count
        seen = 0
        lo = 0.0
        for i, n in enumerate(c.buckets):
            if n == 0:
                if i < len(self.bounds):
                    lo = self.bounds[i]
                continue
            if seen + n >= rank:
                hi = self.bounds[i] if i < len(self.bounds) else lo * 2
                frac = (rank - seen) / n
                return lo + (hi - lo) * min(1.0, max(0.0, frac))
            seen += n
            lo = self.bounds[i] if i < len(self.bounds) else lo
        return self.bounds[-1]


class Summary(Histogram):
    """Quantile summary over the same log-bucket sketch (the exposition
    differs: ``{quantile="0.5"}`` lines instead of cumulative buckets)."""

    kind = "summary"
    quantiles = (0.5, 0.95, 0.99)


class MetricsRegistry:
    """Owns metrics + scrape-time collectors; one per pipeline.

    ``counter``/``gauge``/``histogram``/``summary`` are get-or-create (same
    name must keep the same type and label names). ``register_collector``
    adds a zero-arg callable run before every exposition — the idiom for
    gauges mirroring engine state (spine residency, buffered rows) without
    per-tick bookkeeping."""

    def __init__(self):
        self._metrics: Dict[str, Metric] = {}
        self._collectors: List[Callable[[], None]] = []
        self._lock = threading.Lock()
        _tsan_hook(self)

    def _get_or_create(self, cls, name, help, labels, **kw) -> Metric:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, help, labels, **kw)
                # the construction chokepoint for every metric family:
                # instrumenting here (not in Metric.__init__) lets the
                # whole subclass __init__ chain finish first, so the
                # sanitizer never misreads construction as mutation
                _tsan_hook(m)
                return m
        # under tsan the stored instance's class is the traced subclass;
        # compare against the ORIGINAL class it instruments
        if getattr(type(m), "__tsan_base__", type(m)) is not cls or \
                m.label_names != tuple(labels):
            raise ValueError(
                f"metric {name!r} re-registered as {cls.__name__}"
                f"{tuple(labels)} but exists as {type(m).__name__}"
                f"{m.label_names}")
        return m

    def counter(self, name: str, help: str = "",
                labels: Sequence[str] = ()) -> Counter:
        return self._get_or_create(Counter, name, help, labels)

    def gauge(self, name: str, help: str = "",
              labels: Sequence[str] = ()) -> Gauge:
        return self._get_or_create(Gauge, name, help, labels)

    def histogram(self, name: str, help: str = "",
                  labels: Sequence[str] = (),
                  buckets: Optional[Sequence[float]] = None) -> Histogram:
        return self._get_or_create(Histogram, name, help, labels,
                                   buckets=buckets)

    def summary(self, name: str, help: str = "",
                labels: Sequence[str] = (),
                buckets: Optional[Sequence[float]] = None) -> Summary:
        return self._get_or_create(Summary, name, help, labels,
                                   buckets=buckets)

    def register_collector(self, fn: Callable[[], None]) -> None:
        with self._lock:
            self._collectors.append(fn)

    def collect(self) -> List[Metric]:
        """Run collectors, then return all metrics sorted by name."""
        with self._lock:
            collectors = list(self._collectors)
        for fn in collectors:
            fn()
        with self._lock:
            return [self._metrics[n] for n in sorted(self._metrics)]

    # -- test/introspection helpers -----------------------------------------
    def get(self, name: str) -> Optional[Metric]:
        with self._lock:
            return self._metrics.get(name)

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._metrics)

    def value(self, name: str, **labels: str) -> float:
        """Current value of a counter/gauge child (tests). Goes through
        the metric's snapshotting :meth:`Metric.samples` instead of
        reaching into its private child dict — reading another object's
        lock-guarded state directly is exactly what the concurrency lint
        exists to stop."""
        m = self.get(name)
        if m is None:
            raise KeyError(name)
        key = tuple(str(labels[n]) for n in m.label_names)
        for k, c in m.samples():
            if k == key:
                return c.value
        return 0.0


def fmt_value(v: float) -> str:
    """Canonical Prometheus float formatting (ints render bare)."""
    if math.isnan(v):
        return "NaN"  # a quantile of an empty summary child; int(v) raises
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(v)
