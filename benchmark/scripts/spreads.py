#!/usr/bin/env python3
"""Medians and spreads of sets of runs, as the contract defines them:
spread = (third quartile - first quartile) / median, with the quartiles of
``statistics.quantiles(values, n=4)``.

    python3 benchmark/scripts/spreads.py chiprun_out q4A q4B

reads every ``<dir>/<label>_s<seed>.out`` and prints, for each metric of
the result lines and for the facts a run's ``summary`` line states beside
them (the sample behind each tail, the tails a bounded metric could be
taken at, what compiled in the window), every run's value in seed order,
each set's median and spread — also as the driver takes it for tightness,
without the run farthest from the median — the second median against the
first, and the spread of all the sets' runs pooled.
"""

from __future__ import annotations

import glob
import json
import os
import statistics
import sys

#: facts of the summary line printed beside the metrics; a dot goes one
#: level down
FACTS = (
    "window_seconds", "window_ticks", "window_reads", "reads_beyond_p95",
    "reads_meeting_push", "read_ms.p50", "read_ms.p90", "read_ms.p95",
    "read_ms.max", "delta_age_s.p50", "delta_age_s.p90", "delta_age_s.p95",
    "delta_age_s.max", "compiles_in_window",
    "step_programs_traced_in_window", "overflow_replays_in_window",
    "peak_device_bytes")


def read_run(path: str) -> dict:
    """One run's numbers by name: the result line's metrics, then the
    summary line's facts; ``correct`` under its own key."""
    with open(path) as f:
        lines = [json.loads(x) for x in f.read().splitlines()
                 if x.startswith("{")]
    result = [x for x in lines if "correct" in x and "metrics" in x][-1]
    out = {"correct": result["correct"], "failed": result["failed"]}
    for n, m in result["metrics"].items():
        out[n] = m["value"]
    summary = next((x for x in lines if x.get("phase") == "summary"), {})
    for name in FACTS:
        v = summary
        for k in name.split("."):
            v = v.get(k) if isinstance(v, dict) else None
        if v is not None:
            out["(" + name + ")"] = v
    return out


def spread(vs: list) -> float:
    if len(vs) < 2 or not statistics.median(vs):
        return float("nan")
    q = statistics.quantiles(vs, n=4)
    return (q[2] - q[0]) / statistics.median(vs)


def without_farthest(vs: list) -> list:
    med = statistics.median(vs)
    far = max(vs, key=lambda v: abs(v - med))
    rest = list(vs)
    rest.remove(far)
    return rest


def main(argv) -> int:
    directory, labels = argv[1], argv[2:]
    sets: dict = {}
    for label in labels:
        paths = sorted(glob.glob(os.path.join(directory, f"{label}_s*.out")))
        runs = [read_run(p) for p in paths]
        bad = [r for r in runs if not r["correct"] or r["failed"]]
        print(f"{label}: {len(runs)} runs, {len(bad)} not correct or with "
              f"failed operations")
        sets[label] = runs
    names = []
    for runs in sets.values():
        for r in runs:
            names += [n for n in r if n not in names
                      and n not in ("correct", "failed")]
    for n in names:
        print(n)
        medians, pooled = [], []
        for label, runs in sets.items():
            vs = [r[n] for r in runs if n in r]
            if not vs:
                continue
            pooled += vs
            med = statistics.median(vs)
            medians.append(med)
            trimmed = without_farthest(vs) if len(vs) > 2 else vs
            print(f"  {label}: {' '.join(f'{v:.6g}' for v in vs)} | median "
                  f"{med:.6g} spread {100 * spread(vs):.2f} % (without the "
                  f"farthest {100 * spread(trimmed):.2f} %)")
        if len(medians) == 2 and medians[0]:
            print(f"  second median against first "
                  f"{100 * (medians[1] / medians[0] - 1):+.2f} %")
        if len(medians) > 1:
            print(f"  pooled: median {statistics.median(pooled):.6g} spread "
                  f"{100 * spread(pooled):.2f} % n {len(pooled)}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
