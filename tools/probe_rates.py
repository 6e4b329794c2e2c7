#!/usr/bin/env python3
"""The two formulations of the ladder probe and of the ladder gather, each
alone and warm, on the device this process holds: what
``kernels.rank_by_merge`` and ``kernels.gather_flat`` decide from.

    python tools/probe_rates.py [--reps N] [--shapes m:cap:nk,...]
                                [--gathers out_cap:cap/cap/...:ncols,...]

For every shape ``m`` sorted int64 queries are ranked in a sorted table of
``cap`` rows (``nk`` key columns) by the binary search
(``kernels._probe_search``, one gather a step a column) and by the merge
(``kernels.rank_sorted``); both answers are compared, both calls timed
over ``--reps`` dispatches in flight at once, and the rule's choice is
printed beside the two times with the rates they imply:
``gather_ns`` = search time / (steps x m x nk) and ``pass_ns`` = merge time
/ (stages x padded rows x (nk + 4)), the shapes of ``PROBE_GATHER_NS`` and
``PROBE_PASS_NS``.

For every gather shape ``out_cap`` slots of ``ncols`` int64 columns are
read from a ladder of levels with the given capacities (slots level-major,
each level's sources ascending, as ``expand_ladder`` lays them out) by the
per-level form (``cursor._level_gather``: a clamped gather a level a
column and a select) and by the flat form (``cursor._flat_gather``: the
levels concatenated, one gather a column); both answers are compared and
the concatenation is also timed alone. ``level_gather_ns`` = per-level
time / (K x out_cap x ncols), ``flat_gather_ns`` = (flat time − the
concatenation's) / (out_cap x ncols) and ``copy_ns`` = the concatenation's
time / (sum of caps x ncols): the shapes of ``PROBE_GATHER_NS`` and of the
copy rate in ``kernels.gather_flat``. One JSON object a shape, then a
summary line. Needs an accelerator: a CPU's times say nothing about the
rules.
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
# q6's top-1 (262,144 slots from the joined bids' four levels), q4's join
# on either side, and narrower gathers from the same deep ladder down to a
# few dozen lanes, where the rule keeps the per-level form
GATHERS = ("262144:4194304/1048576/262144/65536:6,"
           "131072:262144/131072/32768/4096/4096/4096:5,"
           "65536:2097152/1048576/262144/65536:5,"
           "16384:4194304/1048576/262144/65536:6,"
           "4096:4194304/1048576/262144/65536:6,"
           "1024:4194304/1048576/262144/65536:6,"
           "64:4194304/1048576/262144/65536:6")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--shapes", default=SHAPES)
    ap.add_argument("--gathers", default=GATHERS)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import dbsp_tpu  # noqa: F401  (x64)
    from dbsp_tpu.zset import cursor, kernels

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
    for spec in filter(None, args.shapes.split(",")):
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
    for spec in filter(None, args.gathers.split(",")):
        out_cap, caps, ncols = spec.split(":")
        out_cap, ncols = int(out_cap), int(ncols)
        caps = [int(c) for c in caps.split("/")]
        levels = [tuple(jnp.asarray(rng.integers(0, 1 << 40, cap))
                        for _ in range(ncols)) for cap in caps]
        # level-major slots, each level's share in proportion to its rows
        share = np.asarray(caps) / sum(caps)
        level = np.sort(rng.choice(len(caps), out_cap, p=share))
        src = np.concatenate([np.sort(rng.integers(0, caps[k], n))
                              for k, n in enumerate(np.bincount(
                                  level, minlength=len(caps)))])
        level = jnp.asarray(level.astype(np.int32))
        src = jnp.asarray(src.astype(np.int32))
        per_level = jax.jit(cursor._level_gather)
        flat = jax.jit(cursor._flat_gather)
        concat = jax.jit(lambda lv: tuple(
            jnp.concatenate([c[ci] for c in lv]) for ci in range(ncols)))
        same = all(bool(jnp.all(a == b)) for a, b in zip(
            per_level(levels, level, src), flat(levels, level, src)))
        agree &= same

        def last(fn):
            return lambda *a: fn(*a)[-1]

        level_ms = timed(last(per_level), levels, level, src)
        flat_ms = timed(last(flat), levels, level, src)
        copy_ms = timed(last(concat), levels)
        print(json.dumps({
            "out_cap": out_cap, "caps": caps, "ncols": ncols, "same": same,
            "level_ms": round(level_ms, 4), "flat_ms": round(flat_ms, 4),
            "copy_ms": round(copy_ms, 4),
            "rule_takes": "flat" if kernels.gather_flat(out_cap, caps, ncols)
            else "level",
            "faster": "flat" if flat_ms < level_ms else "level",
            "level_gather_ns": round(
                level_ms * 1e6 / (len(caps) * out_cap * ncols), 3),
            "flat_gather_ns": round(
                (flat_ms - copy_ms) * 1e6 / (out_cap * ncols), 3),
            "copy_ns": round(copy_ms * 1e6 / (sum(caps) * ncols), 4)}),
            flush=True)
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "all_same": agree,
                      "PROBE_GATHER_NS": kernels.PROBE_GATHER_NS,
                      "PROBE_PASS_NS": kernels.PROBE_PASS_NS}))
    return 0 if agree else 1


if __name__ == "__main__":
    sys.exit(main())
