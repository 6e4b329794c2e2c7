#!/usr/bin/env python3
"""The two formulations of the ladder probe, each alone and warm, on the
device this process holds: what ``kernels.rank_by_merge`` decides from.

    python tools/probe_rates.py [--reps N] [--shapes m:cap:nk,...]

For every shape ``m`` sorted int64 queries are ranked in a sorted table of
``cap`` rows (``nk`` key columns) by the binary search
(``kernels._probe_search``, one gather a step a column) and by the merge
(``kernels.rank_sorted``); both answers are compared, both calls timed
over ``--reps`` dispatches in flight at once, and the rule's choice is
printed beside the two times with the rates they imply:
``gather_ns`` = search time / (steps x m x nk) and ``pass_ns`` = merge time
/ (stages x padded rows x (nk + 4)), the shapes of ``PROBE_GATHER_NS`` and
``PROBE_PASS_NS``. One JSON object a shape, then a summary line. Needs an
accelerator: a CPU's times say nothing about the rule.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SHAPES = ("64:1024:1,1024:16384:1,4096:65536:1,4096:2097152:1,"
          "16384:262144:1,65536:4096:1,65536:65536:1,65536:262144:1,"
          "65536:1048576:1,65536:2097152:1,262144:262144:1,262144:1048576:1,"
          "65536:262144:2,262144:262144:2,16384:4096:1,16384:2097152:1")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--shapes", default=SHAPES)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import dbsp_tpu  # noqa: F401  (x64)
    from dbsp_tpu.zset import kernels

    if not kernels.accelerator():
        raise SystemExit("probe_rates: no accelerator (a CPU's times say "
                         "nothing about the rule)")
    search = jax.jit(lambda t, q: kernels._probe_search(t, q, "left"))
    merge = jax.jit(lambda t, q: kernels.rank_sorted(t, q, "left"))

    def timed(fn, *ops):
        fn(*ops).block_until_ready()  # compile and warm
        t0 = time.perf_counter()
        outs = [fn(*ops) for _ in range(args.reps)]
        for o in outs:
            o.block_until_ready()
        return (time.perf_counter() - t0) / args.reps * 1e3

    rng = np.random.default_rng(37)
    agree = True
    for spec in args.shapes.split(","):
        m, cap, nk = (int(x) for x in spec.split(":"))

        def cols(n):
            rows = rng.integers(0, max(cap, m) * 2, (n, nk))
            rows = rows[np.lexsort(rows.T[::-1])]
            return tuple(jnp.asarray(rows[:, i].astype(np.int64))
                         for i in range(nk))

        table, query = cols(cap), cols(m)
        same = bool(jnp.all(search(table, query) == merge(table, query)))
        agree &= same
        search_ms = timed(search, table, query)
        merge_ms = timed(merge, table, query)
        total = 1 << (cap + m - 1).bit_length()
        stages = total.bit_length() - 1
        print(json.dumps({
            "m": m, "cap": cap, "nk": nk, "same": same,
            "search_ms": round(search_ms, 4), "merge_ms": round(merge_ms, 4),
            "rule_takes": "merge" if kernels.rank_by_merge(m, cap, nk)
            else "search",
            "faster": "merge" if merge_ms < search_ms else "search",
            "gather_ns": round(search_ms * 1e6 / (cap.bit_length() * m * nk),
                               3),
            "pass_ns": round(merge_ms * 1e6 / (stages * total * (nk + 4)),
                             4)}), flush=True)
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "all_same": agree,
                      "PROBE_GATHER_NS": kernels.PROBE_GATHER_NS,
                      "PROBE_PASS_NS": kernels.PROBE_PASS_NS}))
    return 0 if agree else 1


if __name__ == "__main__":
    sys.exit(main())
