#!/usr/bin/env python3
"""Device time of a profiler trace by program, circuit node and kernel.

    python tools/trace_scopes.py <trace dir or .xplane.pb> [--spans spans.json]
                                 [--stats N] [--top N]

The programs carry their own names: ``CompiledHandle._run_nodes`` wraps each
node's eval in ``jax.named_scope("n<index>.<CNode class>")``, the public
kernels of ``zset/kernels.py`` and the ladder steps of ``zset/cursor.py``
(``k.lex_probe_ladder``, ``k.expand_ladder``, ``k._select_gather``) in
``k.<kernel>`` (the outermost one names an operation), the exchange's collectives
in ``x.all_to_all`` / ``x.all_gather`` (``parallel/exchange.py``; counted
with the kernels), the maintenance drains in ``maintain.drain``; XLA keeps
the scope path in each operation's metadata and the TPU's trace keeps it
in the metadata of the ``XLA Ops`` line's events — on a v5e in the
metadata's ``tf_op`` stat (my chip run, PR 28);
where is looked for, not assumed: ``scope_stats`` in the output counts the
places, ``--stats N`` prints the first N scoped operations. Eagerly
dispatched programs (``jit_scan``, ``jit_gather``) carry no scope: their
program name is all they have.

Every device nanosecond is counted once, by the outermost operation event
that covers it (an operation with a body — a ``while``, a fusion — covers
its body's events). An outermost operation with no scope in its path is
``unscoped``; ``by_op_s`` names the outermost operations themselves, each
with its node and kernel scope. The host side of the same trace holds the served path's phase
spans as ``dbsp.<span>`` annotations (``obs/tracing.py``): for each the
table gives its own host seconds (what no child annotation covers) and how
much of that the device was busy or idle.

``--spans`` takes the span ring's Chrome trace (``chip_smoke.py
--profile-dir`` writes it beside the trace) and ties the two clocks: per
span name the offset ring − trace and how far single spans stray from it.

The persistent compile cache's key leaves scope metadata out: a program
loaded from a cache that a tree without scopes filled has none. Read scopes
only from a run on a fresh cache directory. Prints one JSON object.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import re
import sys

DEVICE_PLANE_PREFIX = "/device:TPU:"
NODE = re.compile(r"(?:^|/)(n\d+\.C\w+|maintain\.drain)(?:/|$)")
KERNEL = re.compile(r"(?:^|/)([kx]\.\w+)(?:/|$)")
ANNOTATION_PREFIX = "dbsp."


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(
        path, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise SystemExit(f"trace_scopes: no .xplane.pb under {path!r}")
    return found[-1]


def _fields(buf, pos: int, end: int):
    """Protobuf wire format: yields ``(field, value)`` of the message in
    ``buf[pos:end]`` — an int for a varint or fixed field, ``(start, end)``
    for a length-delimited one."""
    while pos < end:
        key = shift = 0
        while True:
            b = buf[pos]
            pos += 1
            key |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                break
        field, wire = key >> 3, key & 7
        if wire == 0:
            value = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                value |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
            yield field, value
        elif wire == 2:
            n = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                n |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
            yield field, (pos, pos + n)
            pos += n
        else:  # fixed 64 / 32: skipped, no field read here has one
            pos += 8 if wire == 1 else 4
            yield field, None


def op_scopes(path: str) -> tuple:
    """``({operation name: scope path}, {where found: count})`` of the
    first TPU plane, read from the file's own bytes: the scope path is in
    the operations' *metadata* (``XEventMetadata`` name, display name or
    stats; ``tsl/profiler/protobuf/xplane.proto``), which
    ``jax.profiler.ProfileData`` does not hand out — an event's ``stats``
    there are its own three (``device_offset_ps``, ``device_duration_ps``,
    ``Time Scale Multiplier``; looked at on the chip, PR 28)."""
    with open(path, "rb") as f:
        buf = f.read()

    def text(span):
        return buf[span[0]:span[1]].decode("utf-8", "replace")

    for field, plane in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        parts = list(_fields(buf, *plane))
        name = next((text(v) for f, v in parts if f == 2), "")
        if name != DEVICE_PLANE_PREFIX + "0":
            continue
        stat_names = {}
        for f, entry in parts:
            if f == 5:   # map<int64, XStatMetadata>
                for ef, ev in _fields(buf, *entry):
                    if ef == 2:
                        meta = dict(_fields(buf, *ev))
                        stat_names[meta.get(1, 0)] = text(meta[2]) \
                            if 2 in meta else ""
        scopes, where = {}, {}
        for f, entry in parts:
            if f != 4:   # map<int64, XEventMetadata>
                continue
            for ef, ev in _fields(buf, *entry):
                if ef != 2:
                    continue
                op, found = "", None
                for mf, mv in _fields(buf, *ev):
                    if mf == 2:
                        op = text(mv)
                        cands = [("name", op)]
                    elif mf == 4:
                        cands = [("display_name", text(mv))]
                    elif mf == 5:   # XStat of the metadata
                        stat = dict(_fields(buf, *mv))
                        key = stat_names.get(stat.get(1), "?")
                        cands = []
                        if 5 in stat:
                            cands.append((key, text(stat[5])))
                        if 7 in stat:   # a reference to a stat's name
                            cands.append((key, stat_names.get(stat[7], "")))
                    else:
                        continue
                    for key, value in cands:
                        if found is None and (NODE.search(value)
                                              or KERNEL.search(value)):
                            found = (key, value)
                if found:
                    scopes[op] = found[1]
                    where[found[0]] = where.get(found[0], 0) + 1
        return scopes, where
    return {}, {}


def outermost(events: list) -> list:
    """Of ``(start, end, ...)`` tuples, those no earlier one covers."""
    out, cover = [], -1
    for ev in sorted(events, key=lambda e: (e[0], -e[1])):
        if ev[0] >= cover:
            out.append(ev)
            cover = ev[1]
    return out


def self_intervals(events: list) -> list:
    """``(name, start, end)`` events of one thread -> ``(name, start,
    end)`` pieces that no nested event covers."""
    pieces, stack = [], []   # stack rows: [name, end, own time counted to]

    def pop():
        name, end, cursor = stack.pop()
        if end > cursor:
            pieces.append((name, cursor, end))
        if stack:
            stack[-1][2] = max(stack[-1][2], end)

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            pop()
        if stack:
            if start > stack[-1][2]:
                pieces.append((stack[-1][0], stack[-1][2], start))
            stack[-1][2] = max(stack[-1][2], start)
        stack.append([name, end, start])
    while stack:
        pop()
    return pieces


def overlap(merged: list, starts: list, a: int, b: int) -> int:
    """Nanoseconds of ``[a, b)`` inside the sorted disjoint ``merged``."""
    total = 0
    i = max(0, bisect.bisect_right(starts, a) - 1)
    while i < len(merged) and merged[i][0] < b:
        total += max(0, min(b, merged[i][1]) - max(a, merged[i][0]))
        i += 1
    return total


def reduce(path: str, n_stats: int = 0) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, host = [], [], []   # host: one event list per thread
    scopes, stat_names = op_scopes(path)
    census = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            if plane.name != DEVICE_PLANE_PREFIX + "0":
                continue  # one chip's account; the others mirror it
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        scope = scopes.get(e.name, "")
                        if len(census) < n_stats and scope:
                            census.append({"name": e.name[:300],
                                           "scope": scope})
                        ops.append((int(e.start_ns),
                                    int(e.start_ns + e.duration_ns), scope,
                                    e.name))
                elif line.name == "XLA Modules":
                    modules = [(int(e.start_ns),
                                int(e.start_ns + e.duration_ns),
                                e.name.split("(")[0]) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name[len(ANNOTATION_PREFIX):], int(e.start_ns),
                        int(e.start_ns + e.duration_ns))
                       for e in line.events
                       if e.name.startswith(ANNOTATION_PREFIX)]
                if evs:
                    host.append(evs)
    top = outermost(ops)
    modules.sort()
    mod_starts = [m[0] for m in modules]
    by_program: dict = {}
    by_node: dict = {}
    by_kernel: dict = {}
    by_op: dict = {}
    for start, end, scope, op in top:
        i = bisect.bisect_right(mod_starts, start) - 1
        program = modules[i][2] if i >= 0 and start < modules[i][1] \
            else "no_module"
        node = NODE.search(scope)
        kernel = KERNEL.search(scope)
        ns = end - start
        by_program[program] = by_program.get(program, 0) + ns
        key = f"{program}/{node.group(1) if node else 'unscoped'}"
        by_node[key] = by_node.get(key, 0) + ns
        key = kernel.group(1) if kernel else "unscoped"
        by_kernel[key] = by_kernel.get(key, 0) + ns
        # the operation itself, so that ``unscoped`` has names: the leading
        # "%name = type[shape] opcode" of its text, and its node and kernel
        scoped = " ".join(m.group(1) for m in (node, kernel) if m)
        key = f"{program}/{op.split('(')[0].strip()[:100]} " \
              f"[{scoped or 'unscoped'}]"
        by_op[key] = by_op.get(key, 0) + ns
        if kernel:
            key = f"{kernel.group(1)} in {program}/" \
                  f"{node.group(1) if node else 'unscoped'}"
            by_kernel[key] = by_kernel.get(key, 0) + ns
    runs: dict = {}
    for _, _, name in modules:
        runs[name] = runs.get(name, 0) + 1
    busy = [(s, e) for s, e, *_ in top]
    busy_starts = [s for s, _ in busy]
    phases: dict = {}
    for evs in host:
        seen: dict = {}
        for name, _, _ in evs:
            seen[name] = seen.get(name, 0) + 1
        for name, a, b in self_intervals(evs):
            row = phases.setdefault(name, {"spans": 0, "host_s": 0.0,
                                           "device_busy_s": 0.0})
            inside = overlap(busy, busy_starts, a, b)
            row["host_s"] += (b - a) / 1e9
            row["device_busy_s"] += inside / 1e9
        for name, n in seen.items():
            phases[name]["spans"] += n
    for row in phases.values():
        row["device_idle_s"] = row["host_s"] - row["device_busy_s"]

    def table(d):
        return {k: v / 1e9 for k, v in sorted(d.items(),
                                              key=lambda kv: -kv[1])}

    return {
        "file": path, "bytes": os.path.getsize(path),
        "device_ops": len(ops), "outermost_ops": len(top),
        "device_busy_s": sum(e - s for s, e in busy) / 1e9,
        "scope_stats": stat_names,
        "program_runs": runs,
        "by_program_s": table(by_program), "by_node_s": table(by_node),
        "by_kernel_s": table(by_kernel), "by_op_s": table(by_op),
        "host_phases": dict(sorted(phases.items(),
                                   key=lambda kv: -kv[1]["host_s"])),
        "host_annotations": host,
        "stats_of_first_ops": census,
    }


def tie_clocks(annotations: list, spans_doc: dict) -> dict:
    """Ring start − trace start (seconds) over the spans both hold, how far
    single spans stray from its median, and the largest difference between
    a span's length in the ring and in the trace."""
    ring: dict = {}
    open_: dict = {}
    for ev in spans_doc["traceEvents"]:
        if ev.get("ph") == "B":
            open_.setdefault(ev["tid"], []).append(ev)
        elif ev.get("ph") == "E" and open_.get(ev["tid"]):
            b = open_[ev["tid"]].pop()
            ring.setdefault(b["name"], []).append(
                (b["ts"] / 1e6, ev["ts"] / 1e6))
    trace: dict = {}
    for evs in annotations:
        for name, a, b in evs:
            trace.setdefault(name, []).append((a / 1e9, b / 1e9))
    # a first guess from the longest span both hold once each or more,
    # paired by length; then every trace span takes the ring span of its
    # name that starts nearest under that guess
    guess = None
    for name in sorted(trace, key=lambda n: -max(b - a for a, b in trace[n])):
        if name in ring:
            ta, tb = max(trace[name], key=lambda ab: ab[1] - ab[0])
            ra, _ = min(ring[name], key=lambda ab: abs(
                (ab[1] - ab[0]) - (tb - ta)))
            guess = ra - ta
            break
    if guess is None:
        return {"spans": 0}
    offsets, lengths, unmatched = [], [], 0
    for name, pairs in trace.items():
        for ta, tb in pairs:
            near = min(ring.get(name, ()), default=None,
                       key=lambda ab: abs(ab[0] - ta - guess))
            if near is None or abs(near[0] - ta - guess) > 0.05:
                unmatched += 1
                continue
            offsets.append(near[0] - ta)
            lengths.append((near[1] - near[0]) - (tb - ta))
    if not offsets:
        return {"spans": 0, "unmatched": unmatched}
    mid = sorted(offsets)[len(offsets) // 2]
    return {"spans": len(offsets), "unmatched": unmatched, "offset_s": mid,
            "max_stray_ms": max(abs(o - mid) for o in offsets) * 1e3,
            "max_length_diff_ms": max(abs(x) for x in lengths) * 1e3}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a trace directory or an .xplane.pb")
    ap.add_argument("--spans", help="the span ring's Chrome trace (JSON)")
    ap.add_argument("--stats", type=int, default=0,
                    help="print the first N scoped operation events")
    ap.add_argument("--top", type=int, default=40,
                    help="rows kept of each table")
    args = ap.parse_args(argv)
    out = reduce(find_xplane(args.trace), n_stats=args.stats)
    annotations = out.pop("host_annotations")
    if args.spans:
        with open(args.spans) as f:
            out["clocks"] = tie_clocks(annotations, json.load(f))
    for key in ("by_node_s", "by_kernel_s", "by_program_s", "by_op_s"):
        out[key] = dict(list(out[key].items())[:args.top])
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
