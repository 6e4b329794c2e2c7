#!/usr/bin/env python
"""LDBC-Graphalytics-style BFS and PageRank on the iterative engine.

Reference: ``crates/dbsp/benches/ldbc-graphalytics/{bfs,pagerank}.rs`` and
the CI protocol (``scripts/ci.bash:40-49``: graph500-22 / datagen-8_4-fb).
Those datasets are fetched from the LDBC servers at bench time; this
environment has no egress, so the harness generates a synthetic power-law
graph of configurable size instead — the circuit shapes match the
reference's:

* **BFS** (bfs.rs:23-80): an iterative child circuit whose feedback carries
  distance-improvement deltas — candidates = dists ⋈ edges (+1 hop), a Min
  aggregate keeps the per-vertex shortest, and the loop terminates when no
  vertex improves. Incremental join + incremental Min inside the iteration,
  exactly the reference shape.
* **PageRank** (pagerank.rs:21-160): a fixed-iteration child
  (iterate_with_condition with a step bound) over fixed-point int64 ranks
  (the engine's Z-weights are integers, so ranks live in value columns
  scaled by 1e9 — deterministic across worker counts, unlike f64 folds).

Env knobs: LDBC_VERTICES (default 400), LDBC_EDGE_FACTOR (default 8),
LDBC_PR_ITERS (default 10). Prints one JSON line per benchmark.
"""

import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(os.path.dirname(os.path.dirname(
                          os.path.abspath(__file__))), ".jax_bench_cache"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

SCALE = 1_000_000_000  # fixed-point rank scale


def synthetic_graph(n: int, edge_factor: int, seed: int = 7):
    """Power-law-ish directed graph: preferential attachment by squaring."""
    rng = random.Random(seed)
    edges = set()
    for _ in range(n * edge_factor):
        src = int((rng.random() ** 2) * n)
        dst = rng.randrange(n)
        if src != dst:
            edges.add((min(src, n - 1), dst))
    return sorted(edges)


# ---------------------------------------------------------------------------
# BFS
# ---------------------------------------------------------------------------


def build_bfs(c):
    import jax.numpy as jnp

    from dbsp_tpu.circuit.nested import subcircuit
    from dbsp_tpu.operators import add_input_zset
    from dbsp_tpu.operators.aggregate import Min
    from dbsp_tpu.operators.z1 import Z1
    from dbsp_tpu.zset.batch import Batch

    i64 = jnp.int64
    edges, he = add_input_zset(c, (i64,), (i64,))    # src -> dst
    roots, hr = add_input_zset(c, (i64,), (i64,))    # v -> dist 0
    full_edges = edges.integrate()
    full_roots = roots.integrate()
    schema = ((i64,), (i64,))

    def ctor(child):
        e = child.import_stream(full_edges)
        r = child.import_stream(full_roots)
        fb = child.add_feedback(Z1(lambda: Batch.empty(*schema)))
        fb.stream.schema = schema
        # candidates: every improved (v, d) proposes (u, d+1) along v->u
        cands = fb.stream.join_index(
            e, lambda k, dv, ev: ((ev[0],), (dv[0] + 1,)),
            (i64,), (i64,), name="bfs-expand").plus(r)
        cands.schema = schema
        best = cands.aggregate(Min(0), name="bfs-min")
        best.schema = schema
        fb.connect(best)
        child.add_condition(best)
        child.export(best.integrate())
        return None

    exports, _ = subcircuit(c, ctor, iterative=True)
    dists = exports.apply(lambda t: t[0], name="bfs-out")
    dists.schema = schema
    return (he, hr), dists.output()


def build_bfs_incremental(c):
    """BFS in the INCREMENTAL recursive scope (reference: bfs.rs over
    nested timestamps): edges/roots import as parent DELTAS, the Min
    aggregate runs inside the fixedpoint via the four-corner nested form
    (operators/nested_ops.NestedAggregateOp), and a later epoch's work is
    proportional to the graph change, not the accumulated relation."""
    import jax.numpy as jnp

    from dbsp_tpu.operators import add_input_zset
    from dbsp_tpu.operators.aggregate import Min

    i64 = jnp.int64
    edges, he = add_input_zset(c, (i64,), (i64,))    # src -> dst
    roots, hr = add_input_zset(c, (i64,), (i64,))    # v -> dist 0
    seed, _ = add_input_zset(c, (i64,), (i64,))      # recursion shell: empty

    def f(child, R):
        e = child.import_stream(edges)
        r = child.import_stream(roots)
        stepd = R.join_index(
            e, lambda k, dv, ev: ((ev[0],), (dv[0] + 1,)),
            (i64,), (i64,), name="bfs-step")
        cand = stepd.plus(r)
        cand.schema = stepd.schema
        return cand.aggregate(Min(0), name="bfs-min-nested")

    dists = seed.recurse(f)
    return (he, hr), dists.integrate().output()


def bfs_oracle(edges, root):
    from collections import deque

    adj = {}
    for s, d in edges:
        adj.setdefault(s, []).append(d)
    dist = {root: 0}
    dq = deque([root])
    while dq:
        v = dq.popleft()
        for u in adj.get(v, ()):  # noqa: B905
            if u not in dist:
                dist[u] = dist[v] + 1
                dq.append(u)
    return dist


# ---------------------------------------------------------------------------
# PageRank
# ---------------------------------------------------------------------------


def build_pagerank(c, iters: int, damping_pct: int = 85):
    import jax.numpy as jnp

    from dbsp_tpu.circuit.nested import subcircuit
    from dbsp_tpu.operators import add_input_zset
    from dbsp_tpu.operators.aggregate import Sum
    from dbsp_tpu.operators.z1 import Z1
    from dbsp_tpu.zset.batch import Batch

    i64 = jnp.int64
    # edges annotated with the source's out-degree (host-side precomputation,
    # like the reference's weighted_vertices)
    edges, he = add_input_zset(c, (i64,), (i64, i64))   # src -> (dst, outdeg)
    ranks0, h0 = add_input_zset(c, (i64,), (i64,))      # v -> SCALE/n
    tele, ht = add_input_zset(c, (i64,), (i64,))        # v -> (1-d)*SCALE/n
    full_edges = edges.integrate()
    full_ranks0 = ranks0.integrate()
    full_tele = tele.integrate()
    schema = ((i64,), (i64,))  # v -> fixed-point rank

    def ctor(child):
        child.run_exact = iters
        # constants re-emitted every iteration (per-tick operators consume
        # whole values, not deltas)
        e = child.import_stream(full_edges, hold=True)
        t = child.import_stream(full_tele, hold=True)
        zeros = child.import_stream(full_tele, hold=True).map_rows(
            lambda k, v: (k, (jnp.zeros_like(v[0]),)), (i64,), (i64,),
            name="pr-zero")
        seed = child.import_stream(full_ranks0)  # iteration 0 only
        fb = child.add_feedback(Z1(lambda: Batch.empty(*schema)))
        fb.stream.schema = schema
        ranks = fb.stream.plus(seed)
        ranks.schema = schema
        # contributions along edges: rank/outdeg to each destination; a
        # zero row per vertex keeps no-in-edge vertices in the aggregation
        contrib = ranks.stream_join(
            e, lambda k, rv, ev: ((ev[0],),
                                  (rv[0] // jnp.maximum(ev[1], 1),)),
            (i64,), (i64,), name="pr-contrib").plus(zeros)
        contrib.schema = schema
        sums = contrib.stream_aggregate(Sum(0), name="pr-sum")
        # new rank = teleport + d * sum(contribs)
        nxt = sums.stream_join(
            t, lambda k, sv, tv: (k, (tv[0] + sv[0] * damping_pct // 100,)),
            (i64,), (i64,), name="pr-next")
        nxt.schema = schema
        fb.connect(nxt)
        child.export(nxt)
        return None

    exports, _ = subcircuit(c, ctor, iterative=True)
    ranks = exports.apply(lambda t: t[0], name="pr-out")
    ranks.schema = schema
    return (he, h0, ht), ranks.output()


def pagerank_oracle(n, edges, iters, damping=0.85):
    out = {}
    deg = {}
    for s, d in edges:
        deg[s] = deg.get(s, 0) + 1
    ranks = {v: 1.0 / n for v in range(n)}
    for _ in range(iters):
        sums = {v: 0.0 for v in range(n)}
        for s, d in edges:
            sums[d] += ranks[s] / deg[s]
        ranks = {v: (1 - damping) / n + damping * sums[v] for v in range(n)}
    return ranks


# ---------------------------------------------------------------------------


def main():
    import jax

    # default to CPU; LDBC_PLATFORM=tpu opts into the accelerator.
    if os.environ.get("LDBC_PLATFORM", "cpu") == "cpu":
        jax.config.update("jax_platforms", "cpu")

    from dbsp_tpu.circuit import Runtime

    n = int(os.environ.get("LDBC_VERTICES", 400))
    ef = int(os.environ.get("LDBC_EDGE_FACTOR", 8))
    pr_iters = int(os.environ.get("LDBC_PR_ITERS", 10))
    edges = synthetic_graph(n, ef)

    # BFS
    handle, ((he, hr), out) = Runtime.init_circuit(1, build_bfs)
    he.extend([(e, 1) for e in edges])
    hr.push((0, 0), 1)
    t0 = time.perf_counter()
    handle.step()
    bfs_s = time.perf_counter() - t0
    reached = len(out.to_dict())
    print(json.dumps({
        "metric": "ldbc_bfs", "value": round(len(edges) / bfs_s, 1),
        "unit": "edges/s",
        "detail": {"vertices": n, "edges": len(edges),
                   "reached": reached, "elapsed_s": round(bfs_s, 3)}}))

    # Incremental BFS (nested scope): first epoch builds the relation; the
    # second applies a small edge delta — its cost must be delta-bound
    handle, ((he, hr), out) = Runtime.init_circuit(1, build_bfs_incremental)
    hr.push((0, 0), 1)
    he.extend([(e, 1) for e in edges])
    t0 = time.perf_counter()
    handle.step()
    epoch1_s = time.perf_counter() - t0
    want = bfs_oracle(edges, 0)
    got = {v: d for (v, d), w in out.to_dict().items() if w > 0}
    assert got == want, "incremental BFS epoch 1 diverges from oracle"
    # delta: retract one edge, add one fresh edge off vertex 0
    drop = edges[len(edges) // 2]
    he.push(drop, -1)
    he.push((0, n - 1), 1)
    edges2 = [e for e in edges if e != drop] + [(0, n - 1)]
    t0 = time.perf_counter()
    handle.step()
    epoch2_s = time.perf_counter() - t0
    got2 = {v: d for (v, d), w in out.to_dict().items() if w > 0}
    assert got2 == bfs_oracle(edges2, 0), \
        "incremental BFS epoch 2 diverges from oracle"
    print(json.dumps({
        "metric": "ldbc_bfs_incremental",
        "value": round(len(edges) / epoch1_s, 1), "unit": "edges/s",
        "detail": {"vertices": n, "edges": len(edges),
                   "epoch1_s": round(epoch1_s, 3),
                   "epoch2_delta_s": round(epoch2_s, 3),
                   "delta_speedup": round(epoch1_s / max(epoch2_s, 1e-9),
                                          1)}}))

    # PageRank
    deg = {}
    for s, d in edges:
        deg[s] = deg.get(s, 0) + 1
    handle, ((he, h0, ht), out) = Runtime.init_circuit(
        1, lambda c: build_pagerank(c, pr_iters))
    he.extend([((s, d, deg[s]), 1) for s, d in edges])
    base = (SCALE * 15 // 100) // n
    h0.extend([((v, SCALE // n), 1) for v in range(n)])
    ht.extend([((v, base), 1) for v in range(n)])
    t0 = time.perf_counter()
    handle.step()
    pr_s = time.perf_counter() - t0
    print(json.dumps({
        "metric": "ldbc_pagerank",
        "value": round(len(edges) * pr_iters / pr_s, 1),
        "unit": "edge-iters/s",
        "detail": {"vertices": n, "edges": len(edges), "iters": pr_iters,
                   "elapsed_s": round(pr_s, 3)}}))


if __name__ == "__main__":
    main()
