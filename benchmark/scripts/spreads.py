#!/usr/bin/env python3
"""Medians and spreads of sets of runs, as the contract defines them:
spread = (third quartile - first quartile) / median, with the quartiles of
``statistics.quantiles(values, n=4)``.

    python3 benchmark/scripts/spreads.py chiprun_out q4A q4B

reads the last line of every ``<dir>/<label>_s<seed>.out`` and prints, for
each metric, each set's median and spread, and the second median against
the first.
"""

from __future__ import annotations

import glob
import json
import os
import statistics
import sys


def last_line(path: str) -> dict:
    with open(path) as f:
        return json.loads(f.read().strip().splitlines()[-1])


def main(argv) -> int:
    directory, labels = argv[1], argv[2:]
    medians: dict = {}
    for label in labels:
        runs = [last_line(p) for p in sorted(
            glob.glob(os.path.join(directory, f"{label}_s*.out")))]
        bad = [r for r in runs if not r["correct"]]
        print(f"{label}: {len(runs)} runs, {len(bad)} not correct")
        names = sorted({n for r in runs for n in r["metrics"]})
        for n in names:
            vs = [r["metrics"][n]["value"] for r in runs if n in r["metrics"]]
            med = statistics.median(vs)
            if len(vs) >= 2:
                q = statistics.quantiles(vs, n=4)
                spread = (q[2] - q[0]) / med
            else:
                spread = float("nan")
            medians.setdefault(n, []).append(med)
            print(f"  {n}: median {med:.6g} spread {100 * spread:.2f} % "
                  f"min {min(vs):.6g} max {max(vs):.6g} n {len(vs)}")
    for n, ms in medians.items():
        if len(ms) == 2:
            print(f"{n}: second median against first "
                  f"{100 * (ms[1] / ms[0] - 1):+.2f} %")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
