"""``kernels.consolidate_cols`` alone, after the window, as a share of the
HBM roofline, %. NOT in situ: one kernel on a seeded batch, under a jitted
wrapper the benchmark names (``bench_consolidate``); the in-situ share needs
named scopes in the program (the ``tracing`` issue).

The batch is bids-shaped — 4 int64 + 1 int32 key columns and int64 weights
— at the capacity a tick's bids delta is padded to (the next power of two
over 46/50 of ``events_per_tick``: 65,536 rows for 40,000 events). Bytes
are what any implementation must move: every row read once and written
once. HBM bounds it (no FLOP to speak of): share = bytes / 819 GB/s over
the kernel's device time in the probe's trace.
Layer: kernels (zset/kernels.py)."""

NAME = "bench_consolidate"
ROW_BYTES = 4 * 8 + 4 + 8  # four int64 and one int32 key column, weights
RUNS = 5


def rows(config: dict) -> int:
    bids = config["events_per_tick"] * 46 // 50
    return 1 << max(1, (bids - 1).bit_length())


def needed_bytes(config: dict) -> int:
    return 2 * rows(config) * ROW_BYTES


def prepare(ctx):
    """Compile and warm outside any trace; returns what ``probe`` runs."""
    import jax
    import jax.numpy as jnp

    from dbsp_tpu.zset import kernels

    n = rows(ctx["config"])

    def bench_consolidate(cols, w):
        return kernels.consolidate_cols(cols, w)

    bench_consolidate.__name__ = NAME
    fn = jax.jit(bench_consolidate)

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 5)
        # few distinct auctions and bidders, as hot keys make them, so
        # that equal rows meet and weights sum
        cols = (jax.random.randint(ks[0], (n,), 1000, 1000 + n // 8,
                                   dtype=jnp.int64),
                jax.random.randint(ks[1], (n,), 1000, 1000 + n // 64,
                                   dtype=jnp.int64),
                jax.random.randint(ks[2], (n,), 1, 10_000_000,
                                   dtype=jnp.int64),
                jax.random.randint(ks[3], (n,), 0, 16, dtype=jnp.int32),
                jax.random.randint(ks[4], (n,), 0, 1 << 40,
                                   dtype=jnp.int64))
        return cols, jnp.ones((n,), jnp.int64)

    cols, w = make(jax.random.PRNGKey(ctx["seed"] % (2 ** 31)))
    jax.block_until_ready(fn(cols, w))
    return fn, cols, w


def probe(ctx, prepared):
    """Runs inside the probe's trace."""
    import jax

    fn, cols, w = prepared
    for _ in range(RUNS):
        jax.block_until_ready(fn(cols, w))


def read(ctx):
    seconds = ctx["measures"].mean_module_seconds(ctx["probe_trace"], NAME)
    if seconds is None:
        return None
    peak = ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * (needed_bytes(ctx["config"]) / peak) / seconds
