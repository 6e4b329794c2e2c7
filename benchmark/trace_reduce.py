"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

Kept with the benchmark so that every PR computes the same number in the
same way; ``selfcheck.py`` checks it against the small recorded trace in
``testdata/``. Reads the file with ``jax.profiler.ProfileData`` and nothing
else.

What a TPU trace holds (looked at by hand, PR 27): one plane per chip named
``/device:TPU:<n>`` with a line ``XLA Modules`` (one event per run of a
compiled program, named ``jit_<fn>(<fingerprint>)``) and a line ``XLA Ops``
(one event per HLO operation, nested where an operation has a body); host
threads are lines of the plane ``/host:CPU``, where
``jax.profiler.TraceAnnotation`` events appear under their own names.
"""

from __future__ import annotations

import glob
import math
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK_PREFIX = "bench."


def short_op_name(name: str) -> str:
    """``%while.278 = (u32[], u32[135168]{...}, ...) while(...)`` ->
    ``%while.278 while u32[135168]``: the trace names an operation by its
    whole HLO line; keep its result name, its opcode and its widest array."""
    left, _, rest = name.partition(" = ")
    if not rest:
        return name[:120]
    op = re.search(r" ([a-z][a-z0-9\-]*)\(", " " + rest)
    shapes = re.findall(r"[a-z]+[0-9]*\[([0-9,]*)\]", rest.split(op.group(0))[0]
                        if op else rest)
    widest = max(shapes, default="", key=lambda d: math.prod(
        int(x) for x in d.split(",") if x))
    dtype = re.search(r"([a-z]+[0-9]*)\[" + re.escape(widest) + r"\]", rest)
    return " ".join(x for x in (
        left, op.group(1) if op else "",
        f"{dtype.group(1)}[{widest}]" if dtype and widest else "") if x)


def find_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def union_ns(intervals: list) -> tuple:
    """Merged ``[(start, end)]`` of possibly nested or overlapping
    intervals, and their total length."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged, sum(e - s for s, e in merged)


def reduce_trace(path: str, begin_mark: str = MARK_PREFIX + "trace_begin",
                 end_mark: str = MARK_PREFIX + "trace_end") -> dict:
    """The reduction. Times in the result are seconds; ``*_ns`` keys are
    on the trace's own clock. The traced window runs from the end of the
    ``begin_mark`` annotation to the start of the ``end_mark`` one; where
    a mark is missing, from the first device event to the last.

    ``busy_s`` is the union of the device-operation intervals inside the
    window, averaged over the device planes; ``modules`` maps a program's
    name (fingerprint stripped) to ``[runs, device seconds]`` summed over
    chips, clipped to the window, and ``whole_modules`` the same over the
    runs that lie wholly inside it; ``ops`` lists ``[name, seconds]`` by
    total time (an operation with a body counts its body's time too);
    ``gaps`` are the longest idle stretches of the first chip as
    ``[start_ns, end_ns]``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    marks: dict = {}
    devices: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(e.name, int(e.start_ns),
                            int(e.start_ns + e.duration_ns))
                           for e in line.events]
                elif line.name == MODULES_LINE:
                    modules = [(e.name, int(e.start_ns),
                                int(e.start_ns + e.duration_ns))
                               for e in line.events]
            devices.append((plane.name, ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(MARK_PREFIX):
                        marks.setdefault(e.name, []).append(
                            (int(e.start_ns),
                             int(e.start_ns + e.duration_ns)))
    out = {"devices": len(devices), "marks": marks, "n_ops": 0,
           "window_s": None, "busy_s": None, "modules": {},
           "whole_modules": {}, "ops": [],
           "gaps": [], "window_ns": None}
    if not devices or not any(ops for _, ops, _ in devices):
        return out
    every = [iv for _, ops, _ in devices for iv in ops]
    w0 = marks[begin_mark][0][1] if begin_mark in marks \
        else min(s for _, s, _ in every)
    w1 = marks[end_mark][-1][0] if end_mark in marks \
        else max(e for _, _, e in every)
    out["window_ns"] = [w0, w1]
    out["window_s"] = (w1 - w0) / 1e9
    busy, by_op, by_module, by_whole = [], {}, {}, {}
    for i, (_, ops, modules) in enumerate(sorted(devices)):
        clipped = []
        for name, s, e in ops:
            if e > w0 and s < w1:
                clipped.append((max(s, w0), min(e, w1)))
                by_op[name] = by_op.get(name, 0) + (min(e, w1) - max(s, w0))
        merged, total = union_ns(clipped)
        busy.append(total / 1e9)
        out["n_ops"] += len(clipped)
        for name, s, e in modules:
            if e > w0 and s < w1:
                key = name.split("(")[0]
                n, t = by_module.get(key, (0, 0))
                by_module[key] = (n + 1, t + (min(e, w1) - max(s, w0)))
                if s >= w0 and e <= w1:
                    n, t = by_whole.get(key, (0, 0))
                    by_whole[key] = (n + 1, t + (e - s))
        if i == 0:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            gaps = [(edges[j], edges[j + 1])
                    for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
            out["gaps"] = [list(g) for g in sorted(
                gaps, key=lambda g: g[0] - g[1])[:10]]
    out["busy_s"] = sum(busy) / len(busy)
    out["modules"] = {k: [n, t / 1e9] for k, (n, t) in by_module.items()}
    out["whole_modules"] = {k: [n, t / 1e9]
                            for k, (n, t) in by_whole.items()}
    out["ops"] = [[short_op_name(k), t / 1e9] for k, t in sorted(
        by_op.items(), key=lambda kv: -kv[1])]
    return out


def describe(path: str, limit: int = 12) -> dict:
    """A trace's shape, for looking at one by hand: planes, their lines,
    event counts and the first few event names of each line."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            lines.append({"line": line.name, "events": len(events),
                          "first": [[e.name[:80], int(e.start_ns),
                                     int(e.duration_ns)]
                                    for e in events[:limit]]})
        planes.append({"plane": plane.name, "lines": lines})
    return {"bytes": os.path.getsize(path), "planes": planes}
