"""Ask the TPU's compiler, without a TPU: the main path's kernels compiled
for one DESCRIBED v5e chip (``jax.experimental.topologies``; nothing runs).

Guards what the CPU backend cannot: that the plain-XLA kernels of the
served q4 path lower at a 40,000-row delta (capacity bucket 65,536) and at
a maintenance drain's shapes (65,536 rows into 1,048,576), that no merge of
sorted runs gathers or scatters, that the large sort is the chunked merge
sort there, that the exchange and the output's gather compile as one SPMD
program for the four chips of a host, and that the maintenance programs
hand their levels back one slice a worker.

The topology is described inside a module-scoped fixture (never at import:
only one process at a time may load the TPU's library, and every xdist
worker imports this file); every compile runs in the test's own process.
The code under test picks its branch from ``kernels.accelerator()``, which
still sees the CPU here — conftest's ``accelerator_dispatch`` steers it.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from dbsp_tpu.zset import cursor, kernels
from dbsp_tpu.zset.batch import Batch

I64, I32 = jnp.int64, jnp.int32
CAP = 65_536  # bucket_cap of a 40,000-event tick's bid delta
# bids row: key (auction) + vals (bidder, price, channel, date_time)
BID = (I64, I64, I64, I32, I64)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip (it warns and recompiles)."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture
def compile_for(one_chip, no_persistent_cache, accelerator_dispatch):
    def shape(n, dtype=I64):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)

    def batch(n, key_dts, val_dts):
        return Batch(tuple(shape(n, d) for d in key_dts),
                     tuple(shape(n, d) for d in val_dts), shape(n),
                     runs=(n,))

    def compile_(fn, *args):
        return jax.jit(fn).lower(*args).compile()

    compile_.shape, compile_.batch = shape, batch
    return compile_


def _case(name, c):
    """(fn, args) of one main-path kernel at the 40,000-event tick's sizes."""
    rows = tuple(c.shape(CAP, d) for d in BID)
    ladder = (CAP, 4 * CAP, 16 * CAP)  # l0 / l1 / tail of a 1M-row trace
    level = tuple(c.shape(16 * CAP, d) for d in BID)  # a drain's target
    if name == "consolidate":
        return kernels.consolidate_cols, (rows, c.shape(CAP))
    if name == "consolidate_block":  # a tick's bids from host columns
        from dbsp_tpu.zset.batch import _consolidate_block
        return (lambda cols, w: _consolidate_block(cols, w, 1)), (
            rows, c.shape(CAP))
    if name == "consolidate_drain":  # the same rows, unsorted
        n = 17 * CAP
        return kernels.consolidate_cols, (
            tuple(c.shape(n, d) for d in BID), c.shape(n))
    if name == "merge_sorted":
        return kernels.merge_sorted_cols, (rows, c.shape(CAP),
                                           rows, c.shape(CAP))
    if name == "merge_sorted_drain":  # 65,536 rows into 1,048,576
        return kernels.merge_sorted_cols, (level, c.shape(16 * CAP),
                                           rows, c.shape(CAP))
    if name == "lex_probe":
        return (lambda t, q: kernels.lex_probe(t, q, "left")), (
            (c.shape(16 * CAP), c.shape(16 * CAP)),
            (c.shape(CAP), c.shape(CAP)))
    if name == "rank_sorted":  # a bids delta's keys in a 262,144-row level
        return (lambda t, q: kernels.rank_sorted(t, q, "right")), (
            (c.shape(4 * CAP),), (c.shape(CAP),))
    if name == "rank_q5_count":
        # q5's Count per (window, auction): its 131,072 sorted unique keys
        # in its 1,048,576-row accumulator, both ends of each key's range
        # (aggregate._gather_level_impl, stated sorted)
        def probes(t, q):
            return tuple(kernels.lex_probe(t, q, side, sorted_queries=True)
                         for side in ("left", "right"))
        return probes, ((c.shape(16 * CAP), c.shape(16 * CAP)),
                        (c.shape(2 * CAP), c.shape(2 * CAP)))
    if name == "join_ladder":  # q4-join: bids delta x auctions trace
        def fn(k, bv, av):
            return (k[0], av[0]), (bv[1], bv[3], av[1], av[2])
        return (lambda d, lv: cursor.join_ladder(d, lv, 1, fn, 2 * CAP)), (
            c.batch(CAP, (I64,), (I64, I64, I32, I64)),
            [c.batch(n, (I64,), (I64, I64, I64)) for n in ladder])
    if name == "gather_ladder":  # q4-max: group gather over its trace
        return (lambda qk, ql, lv: cursor.gather_ladder(
            qk, ql, lv, 2 * CAP)), (
            (c.shape(CAP), c.shape(CAP)), c.shape(CAP, jnp.bool_),
            [c.batch(n, (I64, I64), (I64,)) for n in ladder])
    raise AssertionError(name)


@pytest.mark.parametrize("name", [
    "consolidate", "consolidate_block", "consolidate_drain", "merge_sorted",
    "merge_sorted_drain", "lex_probe", "rank_sorted", "rank_q5_count",
    "join_ladder", "gather_ladder"])
def test_plain_xla_kernel_compiles_for_v5e(name, compile_for):
    fn, args = _case(name, compile_for)
    before = dict(kernels.KERNEL_DISPATCH_COUNTS)
    compiled = compile_for(fn, *args)
    assert "tpu_custom_call" not in compiled.as_text()  # no Mosaic kernel
    took = {k for k, n in kernels.KERNEL_DISPATCH_COUNTS.items()
            if n > before.get(k, 0)}
    assert (took or name == "rank_sorted") and {b for _, b in took} <= {
        "xla", "xla_bitonic", "xla_shift", "xla_merge", "xla_flat"}, took
    if name == "join_ladder":  # a sorted delta of its levels' order: merged
        assert ("probe_ladder", "xla_merge") in took
    if name == "rank_q5_count":  # both sides by the merge, none searched
        assert took == {("probe", "xla_merge")}, took
    if name in ("join_ladder", "gather_ladder"):
        # 131,072 slots from three levels: gathered from the levels laid
        # end to end, one gather a 32-bit half of each int64 column (the
        # weights and the level's vals) over one materialized buffer; a
        # K-way select of K reads fused back would gather three times as
        # often
        assert ("gather", "xla_flat") in took
        words = {"join_ladder": 2 * 4, "gather_ladder": 2 * 2}[name]
        gathers = [line for line in compiled.as_text().splitlines()
                   if " gather(" in line and "k._select_gather/" in line]
        assert len(gathers) == words, gathers


def test_topk_rows_compiles_for_v5e_at_the_q6_cells_capacities(compile_for):
    """The top-1 per auction's selection (``operators.topk.topk_rows``: the
    sort of the re-read histories by group and value, the per-group ranks,
    the compaction of the kept rows) at the capacities the cell
    ``nexmark-q6.saturated`` reaches: 262,144 gathered rows and 16,384
    queries (``benchmark/metrics/topk_rows_roofline.py``). Plain XLA, no
    Mosaic call; 11.4 s to compile here (PR 38)."""
    import time

    from dbsp_tpu.operators.topk import topk_rows

    gather, queries = 262_144, 16_384
    args = (compile_for.shape(gather, I32), (compile_for.shape(queries),),
            tuple(compile_for.shape(gather) for _ in range(5)),
            compile_for.shape(gather))
    t0 = time.monotonic()
    compiled = compile_for(lambda q, k, v, w: topk_rows(
        q, k, v, w, 1, True, 1, queries), *args)
    seconds = time.monotonic() - t0
    text = compiled.as_text()
    assert "tpu_custom_call" not in text  # no Mosaic kernel
    assert seconds < 240, seconds


def _sort_of(n):
    """The signature a sort of ``n`` bids rows prints after its comparator."""
    operands = ", ".join(f"tensor<{n}x{'i32' if d == I32 else 'i64'}>"
                         for d in (*BID, I64))
    return f"}}) : ({operands}) ->"


@pytest.mark.parametrize("name", ["merge_sorted", "merge_sorted_drain",
                                  "rank_sorted", "rank_q5_count",
                                  "consolidate", "consolidate_drain"])
def test_merges_of_sorted_runs_gather_nothing_on_tpu(name, compile_for):
    """Off the CPU a merge of sorted runs, the merge levels of a large
    sort, and the netting and compaction behind both are elementwise
    passes over contiguous rows: no gather and no scatter, in a loop or
    out of one (the chip gathers single int64 elements at 16.5 ns each —
    PERF.md 6, PR 29), and no sort but the consolidate's one SORT_CHUNK_ROWS
    chunk sort."""
    fn, args = _case(name, compile_for)
    text = jax.jit(fn).lower(*args).as_text()
    assert "stablehlo.gather" not in text
    assert "stablehlo.scatter" not in text
    if name.startswith(("merge", "rank")):
        assert "stablehlo.sort" not in text
    else:
        assert text.count("stablehlo.sort") == 1
        assert _sort_of(kernels.SORT_CHUNK_ROWS) in text


def test_large_sort_is_chunked_on_tpu(compile_for):
    """Off the CPU the consolidate's sort never hands XLA more than
    SORT_CHUNK_ROWS rows at once (a 65,536-row, 5-column int64 sort takes
    minutes to compile for this chip; the chunk takes seconds): one chunk
    sort in a ``lax.map``, and merge levels that sort and gather nothing."""
    cols = tuple(compile_for.shape(CAP, d) for d in BID)
    text = jax.jit(lambda c, w: kernels.sort_rows(c, (w,))).lower(
        cols, compile_for.shape(CAP)).as_text()
    assert text.count("stablehlo.sort") == 1
    assert _sort_of(kernels.SORT_CHUNK_ROWS) in text
    assert "stablehlo.gather" not in text
    assert "stablehlo.scatter" not in text


# -- the exchange, for the four chips of a v5e host ---------------------------

WORKERS = 4
EXCHANGES = {
    # name: (per-worker capacity, key dtypes, value dtypes, collective)
    # q4's one compiled exchange (re-key the per-auction maxima by
    # category) at the bucket a 40,000-event tick presizes it to
    "exchange_q4_maxima": (4_096, (I64,), (I64,), "all-to-all"),
    # a bids delta's per-worker share, as a query that exchanges bids would
    # route it
    "exchange_bids_share": (CAP // WORKERS, BID[:1], BID[1:], "all-to-all"),
    # the output's gather (CUnshard); at a view of a few dozen rows the
    # compiler folds the gather into fusions and no all-gather is left
    "gather_view": (4_096, (I64, I64), (), "all-gather"),
}


@pytest.fixture(scope="module")
def four_chips(topo):
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.asarray(topo.devices[:WORKERS]), ("workers",))


@pytest.mark.parametrize("name", EXCHANGES)
def test_exchange_compiles_for_four_v5e_chips(name, four_chips,
                                              no_persistent_cache,
                                              accelerator_dispatch):
    """Bucketize + ``all_to_all`` + per-worker consolidation (and the
    output's ``all_gather``) as ONE SPMD program over a described 2x2 mesh,
    with the accelerator formulations behind the consolidation."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dbsp_tpu.parallel import exchange

    cap, key_dts, val_dts, collective = EXCHANGES[name]
    sharded = NamedSharding(four_chips, P("workers"))

    def shape(dtype=I64):
        return jax.ShapeDtypeStruct((WORKERS, cap), dtype, sharding=sharded)

    batch = Batch(tuple(shape(d) for d in key_dts),
                  tuple(shape(d) for d in val_dts), shape(), runs=(cap,))
    body = exchange.gather_local if collective == "all-gather" else (
        lambda b: exchange.exchange_local(b, WORKERS))
    compiled = jax.jit(exchange.spmd(four_chips, body)).lower(batch).compile()
    text = compiled.as_text()
    ncols = len(key_dts) + len(val_dts) + 1
    assert text.count(f" {collective}(") + text.count(
        f" {collective}-start(") >= 1, f"no {collective} in the program"
    # every column and the weights cross the mesh: nothing stays behind
    out = jax.tree_util.tree_leaves(compiled.output_shardings)
    assert len(out) == ncols
    assert all(len(s.device_set) == WORKERS for s in out)


@pytest.mark.parametrize("name", ["drain_pair", "drain_slice", "copy_tree"])
def test_maintenance_keeps_levels_on_their_workers(name, four_chips,
                                                   no_persistent_cache,
                                                   accelerator_dispatch):
    """What maintenance hands back to the step program stays one slice a
    worker. Left to itself the v5e's compiler returned a drain's emptied
    level (all constants) replicated on the four chips, and every level
    pair's first drain cost the served path a new SPMD step program."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dbsp_tpu.circuit.runtime import Runtime
    from dbsp_tpu.compiled import compiler

    sharded = NamedSharding(four_chips, P("workers"))

    def level(cap):
        def shape(dtype=I64):
            return jax.ShapeDtypeStruct((WORKERS, cap), dtype,
                                        sharding=sharded)

        return Batch(tuple(shape(d) for d in BID[:1]),
                     tuple(shape(d) for d in BID[1:]), shape(), runs=(cap,))

    l0, l1 = level(CAP // WORKERS), level(CAP)
    prev = Runtime._swap(Runtime(WORKERS, mesh=four_chips))
    try:
        if name == "drain_pair":
            lowered = compiler._drain_pair.lower(l1, l0, CAP)
        elif name == "drain_slice":
            lowered = compiler._drain_slice.lower(
                l1, l0, jax.ShapeDtypeStruct((), I32), CAP)
        else:
            lowered = compiler._copy_tree.lower((l1, l0))
        compiled = lowered.compile()
    finally:
        Runtime._swap(prev)
    out = jax.tree_util.tree_leaves(compiled.output_shardings)
    assert len(out) == 2 * (len(BID) + 1)
    for s in out:
        assert not s.is_fully_replicated, s
        assert s.is_equivalent_to(sharded, 2), s


def test_flat_gather_stays_on_its_worker_on_four_chips(four_chips,
                                                       no_persistent_cache,
                                                       accelerator_dispatch):
    """q4-4w's join lifted per worker (a bids delta's share against a
    quarter of the auctions' trace): the levels are laid end to end on each
    chip, and the SPMD program holds no collective at all."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dbsp_tpu.parallel import exchange

    sharded = NamedSharding(four_chips, P("workers"))

    def batch(cap, key_dts, val_dts):
        def shape(dtype=I64):
            return jax.ShapeDtypeStruct((WORKERS, cap), dtype,
                                        sharding=sharded)

        return Batch(tuple(shape(d) for d in key_dts),
                     tuple(shape(d) for d in val_dts), shape(), runs=(cap,))

    def fn(k, bv, av):
        return (k[0], av[0]), (bv[1], bv[3], av[1], av[2])

    share = CAP // WORKERS
    before = dict(kernels.KERNEL_DISPATCH_COUNTS)
    text = jax.jit(exchange.spmd(four_chips, lambda d, lv: cursor.join_ladder(
        d, lv, 1, fn, 2 * share))).lower(
        batch(share, (I64,), (I64, I64, I32, I64)),
        [batch(n, (I64,), (I64, I64, I64)) for n in (share, CAP, 4 * CAP)],
    ).compile().as_text()
    assert kernels.KERNEL_DISPATCH_COUNTS.get(("gather", "xla_flat"), 0) > \
        before.get(("gather", "xla_flat"), 0)
    for collective in ("all-to-all", "all-gather", "all-reduce",
                       "collective-permute", "reduce-scatter"):
        assert f" {collective}" not in text, collective
