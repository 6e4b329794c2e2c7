"""The arithmetic from a run's record (what ``loadgen.py`` returns for a
``run`` command) to numbers: pure functions, shared by the end-to-end
metrics in ``run.py`` and the per-layer readers in ``metrics/``.

Tick index k is step number k + 1 of the controller. A window tick is one
whose ``/step`` returned after the open and no later than the close.
"""

from __future__ import annotations

import math


def percentile(values, q: float):
    """Nearest-rank percentile (q in 0..100) of all values; None if empty."""
    vs = sorted(values)
    if not vs:
        return None
    return vs[max(0, math.ceil(q / 100.0 * len(vs)) - 1)]


def beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie beyond their nearest-rank percentile
    ``q``: what the tail rests on (a percentile wants ten or more)."""
    return n - max(1, math.ceil(q / 100.0 * n)) if n else 0


def tails(values) -> dict | None:
    """The percentiles a run's fact line states beside each tail metric."""
    if not values:
        return None
    return {"p50": percentile(values, 50), "p90": percentile(values, 90),
            "p95": percentile(values, 95), "max": max(values)}


def window_ticks(run: dict) -> list:
    """Tick indices published inside the window, in order."""
    if run["open"] is None or run["close"] is None:
        return []
    return sorted(int(k) for k, t in run["step_done"].items()
                  if run["open"] < t <= run["close"])


def window_seconds(run: dict) -> float:
    return run["close"] - run["open"]


def events_per_s(run: dict, events_per_tick: int):
    ticks = window_ticks(run)
    if not ticks:
        return None
    return len(ticks) * events_per_tick / window_seconds(run)


def tick_seconds(run: dict) -> list:
    """Client clock around ``/step`` for each window tick."""
    return [run["step_done"][str(k)] - run["step_sent"][str(k)]
            for k in window_ticks(run)]


def push_seconds(run: dict) -> list:
    """Client clock around one batch's POSTs, for each window tick."""
    return [run["push"][str(k)][1] - run["push"][str(k)][0]
            for k in window_ticks(run) if str(k) in run["push"]]


def delta_ages(run: dict) -> list | None:
    """Age at first visibility of the window's events, one value per window
    tick: every event of batch k was created when its push began, and every
    batch holds the same number of events, so a percentile over ticks is
    the percentile over events. None where a window tick never became
    visible (that is a failure, not an age)."""
    ages = []
    for k in window_ticks(run):
        seen = run["visible"].get(str(k + 1))
        if seen is None:
            return None
        ages.append(seen - run["push"][str(k)][0])
    return ages


def window_reads(run: dict) -> list:
    """``(due, sent, received, ok)`` of the reads due inside the window."""
    return [r for r in run["reads"]
            if run["open"] <= r[0] <= run["close"]]


def reads_meeting_push(run: dict) -> int:
    """Window reads that were waiting (due to received) while a batch was
    being pushed: the reads whose latency holds a parse."""
    pushes = list(run["push"].values())
    return sum(1 for due, _, got, _ in window_reads(run)
               if any(a < got and b > due for a, b in pushes))


def read_latencies_ms(run: dict) -> list:
    """From when each read was due to its response."""
    return [(got - due) * 1e3 for due, _, got, ok in window_reads(run) if ok]


def reader_lateness_ms(run: dict) -> list:
    return [(sent - due) * 1e3 for due, sent, _, _ in window_reads(run)]


def window_ops(run: dict) -> tuple:
    """``(attempted, failed)`` HTTP operations that ended in the window,
    a window tick that never became visible counting as one failure."""
    ops = [o for o in run["ops"] if run["open"] < o[2] <= run["close"]]
    unseen = sum(1 for k in window_ticks(run)
                 if str(k + 1) not in run["visible"])
    return len(ops), sum(1 for o in ops if not o[3]) + unseen


def compiles_in_window(run: dict, compile_events) -> int | None:
    """Programs asked of the compiler inside the window: ``compile_events``
    rows are ``(monotonic time, seconds)``, cache loads included."""
    if run["open"] is None or run["close"] is None:
        return None
    return sum(1 for t, _ in compile_events
               if run["open"] < t <= run["close"])


def mean_module_seconds(trace: dict | None, name: str):
    """Mean device seconds of one run of the programs whose name holds
    ``name``, over the runs that lie wholly inside the traced window."""
    if not trace:
        return None
    hit = [(n, s) for mod, (n, s) in trace["whole_modules"].items()
           if name in mod]
    runs = sum(n for n, _ in hit)
    return sum(s for _, s in hit) / runs if runs else None
