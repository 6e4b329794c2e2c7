"""``operators.topk.topk_rows`` alone, after the window, as a share of the
HBM roofline, %. NOT in situ: the top-K selection of one ``CTopK`` (the
sort of the re-read histories by group and value, the per-group ranks, the
compaction of the kept rows), under a jitted wrapper the benchmark names
(``bench_topk_rows``), on a seeded buffer at the shape the cell's widest
top-K ran in its last window tick.

Shape, from the program's own record of that tick
(``dbsp_tpu.timeseries.counters.TOPK_ROWS``, per ``CTopK``: its gather's
capacity, its ``queries`` capacity, k and its value columns), of the node
with the widest gather: in ``nexmark-q6.saturated`` the top-1 per auction,
262,144 rows of gather and 16,384 queries at k = 1, 5 value columns (PERF.md
3, PR 38). Every gathered row of the buffer is live (the cell's gather is
about 46 % full: ``topk_gather_fill_pct``), ``gather / queries`` rows a
group, so ``min(gather, k * queries)`` rows are kept.

Bytes are those any implementation must move: each gathered row read once
(an int32 group index, the int64 values, an int64 weight: 52 bytes at 5
values) and each kept row written once (an int64 key, the values, an int64
weight: 56 bytes). HBM bounds it (no FLOP to speak of): share = bytes / the
peak's bytes per second over the kernel's device time in the probe's
trace. None where the program keeps no such record (the parent of the PR
that added it) or the circuit has no ``CTopK`` (q3, q4, q4-4w, q5).
Layer: top-k (operators/topk.py)."""

NAME = "bench_topk_rows"
RUNS = 5


def shape():
    """(gather rows, queries, k, value columns) of the widest ``CTopK`` as
    the program recorded its last validated tick, or None."""
    try:
        from dbsp_tpu.timeseries.counters import TOPK_ROWS
    except ImportError:
        return None
    nodes = [e for e in TOPK_ROWS.values() if "queries" in e]
    if not nodes:
        return None
    e = max(nodes, key=lambda e: e["capacity"])
    return e["capacity"], e["queries"], e["k"], e["values"]


def needed_bytes(gather: int, queries: int, k: int, values: int) -> int:
    kept = min(gather, k * queries)
    return gather * (4 + 8 * values + 8) + kept * (8 + 8 * values + 8)


def prepare(ctx):
    """Compile and warm outside any trace; returns what ``probe`` runs, or
    None where the program recorded no top-K (run.py prepares every
    reader's probe in every traced run)."""
    import jax
    import jax.numpy as jnp

    dims = shape()
    if dims is None:
        return None
    from dbsp_tpu.operators.topk import topk_rows

    gather, queries, k, values = dims

    def bench_topk_rows(qrow, qkeys, vals, w):
        out = topk_rows(qrow, qkeys, vals, w, k=k, largest=True,
                        weight_sign=1, q_cap=queries)
        return out.keys, out.vals, out.weights

    bench_topk_rows.__name__ = NAME
    fn = jax.jit(bench_topk_rows)

    @jax.jit
    def make(key):
        ks = jax.random.split(key, values + 1)
        # every group holds gather / queries rows, in no order, as the
        # gather lays the levels' runs end to end
        qrow = jax.random.permutation(
            ks[0], jnp.arange(gather, dtype=jnp.int32) % queries)
        vals = tuple(jax.random.randint(kv, (gather,), 1, 1 << 40,
                                        dtype=jnp.int64) for kv in ks[1:])
        qkeys = (jnp.arange(queries, dtype=jnp.int64) + 1000,)
        return qrow, qkeys, vals, jnp.ones((gather,), jnp.int64)

    args = make(jax.random.PRNGKey(ctx["seed"] % (2 ** 31)))
    jax.block_until_ready(fn(*args))
    return fn, args


def probe(ctx, prepared):
    """Runs inside the probe's trace."""
    import jax

    if prepared is None:
        return
    fn, args = prepared
    for _ in range(RUNS):
        jax.block_until_ready(fn(*args))


def read(ctx):
    dims = shape()
    if dims is None:
        return None
    seconds = ctx["measures"].mean_module_seconds(ctx["probe_trace"], NAME)
    if seconds is None:
        return None
    peak = ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * (needed_bytes(*dims) / peak) / seconds
