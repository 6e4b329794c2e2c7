"""The window's ticks as the program's time nodes recorded them: shared by
the ``time windows`` readers in ``metrics/`` as ``span_measures.py`` is by
the span readers. The program keeps one record per validated tick in
``dbsp_tpu.timeseries.counters.VALIDATED_TICKS`` (a bounded ring); the
window's ticks are the run's last ones, so its records are the ring's
last ``window_ticks``. A program without the ring (the parent of the PR
that added it), a circuit without a time node (the ring stays empty) or a
ring shorter than the window gives None: never a partial number."""

from __future__ import annotations


def window_records(ctx: dict) -> list | None:
    """The records of the window's ticks, first to last, or None."""
    if "time_records" not in ctx:
        ctx["time_records"] = _window_records(ctx)
    return ctx["time_records"]


def _window_records(ctx: dict) -> list | None:
    try:
        from dbsp_tpu.timeseries.counters import VALIDATED_TICKS
    except ImportError:
        return None
    n = len(ctx["measures"].window_ticks(ctx["run"]))
    ticks = list(VALIDATED_TICKS)
    if not n or len(ticks) < n:
        return None
    return ticks[-n:]
