"""The arithmetic from the program's span ring to numbers: pure functions
over a list of Chrome-trace ``B``/``E`` events (what
``dbsp_tpu.obs.tracing.default_recorder().events()`` returns), shared by the
``program_span`` readers in ``metrics/`` as ``measures.py`` is by the others.

Event times are microseconds of ``time.perf_counter_ns``, which on Linux is
``CLOCK_MONOTONIC``: the clock of the load generator's ``time.monotonic``.
A span here carries seconds on that clock. The span names are the
program's contract (``dbsp_tpu/obs/tracing.py``): ``ingest`` >
``ingest.*``, ``step_request`` > ``step.lock_wait``, ``tick`` > ``tick.*``,
``read`` > ``read.*``, and a closed ``compile`` child wherever a program was
asked of the compiler.

A reader that cannot see every window tick (the program has no recorder, as
the parent of the PR that added these has not, or the ring evicted one)
gets ``None`` from :func:`window` and reports nothing: never a partial
number.
"""

from __future__ import annotations

CONTAINERS = ("step_request", "tick", "ingest", "read")


class Span:
    __slots__ = ("name", "t0", "t1", "tid", "args", "parent", "children")

    def __init__(self, name, t0, tid, args, parent):
        self.name, self.t0, self.t1, self.tid = name, t0, t0, tid
        self.args = dict(args or {})
        self.parent = parent
        self.children: list = []

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    @property
    def self_seconds(self) -> float:
        """What no child covers."""
        return self.seconds - sum(c.seconds for c in self.children)

    def descendants(self):
        for c in self.children:
            yield c
            yield from c.descendants()

    def total(self, *names: str) -> float:
        """Seconds of the descendants named so, summed."""
        return sum(s.seconds for s in self.descendants() if s.name in names)

    def self_intervals(self) -> list:
        """``[(start, end)]`` of this span that no child covers."""
        out, at = [], self.t0
        for c in sorted(self.children, key=lambda c: c.t0):
            if c.t0 > at:
                out.append((at, c.t0))
            at = max(at, c.t1)
        if self.t1 > at:
            out.append((at, self.t1))
        return out


def recorder_events():
    """The process-wide ring's events, or None where the program has no
    such recorder."""
    try:
        from dbsp_tpu.obs.tracing import default_recorder
    except ImportError:
        return None
    return default_recorder().events()


def closed_spans(events) -> list:
    """Pair ``B``/``E`` events (per thread, innermost first) into spans
    with parent, children and merged args; a ``B`` with no ``E`` is left
    out. Top-level and nested spans alike, in order of their start."""
    stacks: dict = {}
    out = []
    for ev in events:
        stack = stacks.setdefault(ev["tid"], [])
        if ev["ph"] == "B":
            stack.append(Span(ev["name"], ev["ts"] / 1e6, ev["tid"],
                              ev.get("args"), stack[-1] if stack else None))
        elif ev["ph"] == "E" and stack and stack[-1].name == ev["name"]:
            span = stack.pop()
            span.t1 = ev["ts"] / 1e6
            span.args.update(ev.get("args") or {})
            if span.parent is not None:
                span.parent.children.append(span)
            out.append(span)
    closed = {id(s) for s in out}
    out = [s for s in out if s.parent is None or id(s.parent) in closed]
    return sorted(out, key=lambda s: s.t0)


class Window:
    """The window ticks of a run as spans: ``ticks[k]`` the ``tick`` span
    of window tick k, ``requests[k]`` its ``step_request``, ``pushes[k]``
    the ``ingest`` spans of the batches it drained."""

    def __init__(self, spans, ticks, requests, pushes):
        self.spans, self.ticks = spans, ticks
        self.requests, self.pushes = requests, pushes


def window(events, run: dict, measures) -> Window | None:
    """None unless every window tick, its request and its pushes are in
    the ring."""
    if events is None:
        return None
    want = measures.window_ticks(run)
    spans = closed_spans(events)
    by_tick = {s.args.get("tick"): s for s in spans if s.name == "tick"}
    by_trace = {s.args.get("trace"): s for s in spans if s.name == "ingest"}
    ticks, requests, pushes = {}, {}, {}
    for k in want:
        tick = by_tick.get(k)
        if tick is None or tick.parent is None \
                or tick.parent.name != "step_request":
            return None
        batches = tick.args.get("batches") or []
        if not batches or any(b not in by_trace for b in batches):
            return None
        ticks[k], requests[k] = tick, tick.parent
        pushes[k] = [by_trace[b] for b in batches]
    return Window(spans, ticks, requests, pushes) if want else None


def window_of(ctx: dict) -> Window | None:
    """The run's :class:`Window` over the process-wide ring, worked out
    once per run and kept in ``ctx``."""
    if "span_window" not in ctx:
        ctx["span_window"] = window(recorder_events(), ctx["run"],
                                    ctx["measures"])
    return ctx["span_window"]


def per_tick_ms(ctx: dict, seconds_of) -> float | None:
    """Median (``measures.percentile``, as ``tick_p50_s`` takes it) over
    the window ticks of ``seconds_of(tick span)``, ms."""
    win = window_of(ctx)
    if win is None:
        return None
    return 1e3 * ctx["measures"].percentile(
        [seconds_of(win.ticks[k]) for k in sorted(win.ticks)], 50)


def phase_table(win: Window) -> list:
    """Per window tick: the request's seconds and each phase's, by name
    (children of ``tick`` and ``step_request``; ``compile`` summed over
    every depth), and the compiles of 50 ms or more as ``[phase that
    asked, program, seconds, persistent-cache hit]``."""
    rows = []
    for k in sorted(win.ticks):
        tick, req = win.ticks[k], win.requests[k]
        phases: dict = {}
        for c in (*req.children, *tick.children):
            if c.name != "tick":
                phases[c.name] = phases.get(c.name, 0.0) + c.seconds
        phases["compile"] = req.total("compile")
        phases["ingest"] = sum(p.seconds for p in win.pushes[k])
        rows.append({"tick": k, "step_request_s": req.seconds,
                     "unnamed_s": req.self_seconds + tick.self_seconds,
                     "phases_s": phases,
                     "compiles": [[c.parent.name, c.args.get("fun"),
                                   c.seconds, c.args.get("cache_hit")]
                                  for c in req.descendants()
                                  if c.name == "compile"
                                  and c.seconds >= 0.05]})
    return rows


def window_read_spans(spans: list, run: dict, measures) -> list | None:
    """The ``read`` span of every window read that was answered, matched
    by its start lying inside the client's call; None if one is missing."""
    reads = sorted((s for s in spans if s.name == "read"),
                   key=lambda s: s.t0)
    out, i = [], 0
    for _, sent, got, ok in measures.window_reads(run):
        if not ok:
            continue
        while i < len(reads) and reads[i].t0 < sent:
            i += 1
        if i == len(reads) or reads[i].t0 > got:
            return None
        out.append(reads[i])
        i += 1
    return out


def read_handler_ms(ctx: dict, q: float) -> float | None:
    """Percentile ``q`` of the ``read`` spans of the window's answered
    reads, ms; None unless every one of them is in the ring."""
    win = window_of(ctx)
    if win is None:
        return None
    reads = window_read_spans(win.spans, ctx["run"], ctx["measures"])
    if not reads:
        return None
    return ctx["measures"].percentile([s.seconds * 1e3 for s in reads], q)


def name_gaps(spans: list, gaps: list) -> list:
    """For each ``(start, end)`` gap in seconds on the spans' clock: the
    seconds of it by innermost open span, ``{name: seconds}``. Where a
    phase of a step or an ingest is open it names the instant; else a
    phase of a read; else ``unnamed`` (only a container is open); else
    ``no_request``."""
    pieces = []  # (start, end, rank, name): each span's own time
    for s in spans:
        if s.name in CONTAINERS:
            rank, name = 2, "unnamed"
        else:
            top = s
            while top.parent is not None:
                top = top.parent
            rank, name = (1 if top.name == "read" else 0), s.name
        pieces += [(a, b, rank, name) for a, b in s.self_intervals()]
    out = []
    for g0, g1 in gaps:
        inside = [(max(a, g0), min(b, g1), rank, name)
                  for a, b, rank, name in pieces if b > g0 and a < g1]
        edges = sorted({g0, g1, *(x for a, b, _, _ in inside
                                  for x in (a, b))})
        named: dict = {}
        for a, b in zip(edges, edges[1:]):
            over = [(rank, name) for s, e, rank, name in inside
                    if s <= a and e >= b]
            name = min(over)[1] if over else "no_request"
            named[name] = named.get(name, 0.0) + (b - a)
        out.append(named)
    return out
