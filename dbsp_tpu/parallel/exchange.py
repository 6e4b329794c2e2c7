"""Shard / exchange / gather: worker parallelism as XLA collectives.

Reference components replaced (SURVEY.md §2.7, §5):
  * ``shard()`` — key-hash repartition across workers
    (``operator/communication/shard.rs:89``);
  * ``Exchange`` — the N²-mailbox shared-memory fabric with atomic
    ready-counters (``operator/communication/exchange.rs:45``);
  * ``gather()`` — all-to-one collection (``communication/gather.rs:41``).

TPU-native design: a sharded Z-set is a :class:`Batch` whose arrays carry a
leading ``[W, cap_local]`` worker axis laid out over the 1-D device mesh
(parallel/mesh.py). ``exchange`` runs INSIDE the jitted SPMD step as a bucket
+ ``lax.all_to_all`` over ICI — the reference's mailbox handshakes, ready
callbacks, and sender/receiver operator split all disappear because the
compiler schedules communication/compute overlap, and its per-step barrier
semantics (shard.rs:80-88) are exactly SPMD program semantics.

Routing invariant: rows are routed by a hash of the FIRST key column, so all
rows sharing a full key land on one worker (equal full keys share the first
column) — the same contract the reference's shard() gives join/aggregate/
distinct. Dead rows route nowhere (weight 0, dropped scatter).
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from dbsp_tpu.parallel.mesh import WORKER_AXIS, worker_sharding
from dbsp_tpu.zset import kernels
from dbsp_tpu.zset.batch import Batch


# Process-wide exchange bucket-overflow detections, by site kind
# ("exchange" = a CExchange's static per-worker output capacity, "input" =
# a sharded CInput's per-worker share capacity). The compiled step runs
# optimistically: a skewed tick can route more rows to one worker than the
# static bucket holds, and the surplus would silently fall off the
# ``with_cap`` slice — the requirement check catches it at the next
# validation, the overflow-replay machinery re-runs the interval at grown
# capacity, and THIS counter (exported as
# ``dbsp_tpu_exchange_overflow_total{kind}``, mirrored in bench detail)
# makes each such save visible instead of silent.
EXCHANGE_OVERFLOW_COUNTS: dict = {}


def count_exchange_overflow(kind: str, n: int = 1) -> None:
    EXCHANGE_OVERFLOW_COUNTS[kind] = EXCHANGE_OVERFLOW_COUNTS.get(kind, 0) + n


# Process-wide, per exchange site — keyed (kind, node index), kind as in
# EXCHANGE_OVERFLOW_COUNTS: (the worst worker's live rows, the static
# per-worker bucket capacity) at the last validation. Filled from the
# requirement levels validation fetches anyway (no device sync of its own);
# rows / capacity is the bucket's occupancy, the rest of the bucket is
# padding every worker sorts and merges for nothing. Exported as
# ``dbsp_tpu_exchange_site_live_rows{kind,node}`` and
# ``dbsp_tpu_exchange_site_capacity_rows{kind,node}``; empty with one worker.
EXCHANGE_SITE_ROWS: dict = {}


def note_exchange_site(kind: str, node: int, rows: int,
                       capacity: int) -> None:
    EXCHANGE_SITE_ROWS[(kind, node)] = (rows, capacity)


def _hash_key(col: jnp.ndarray) -> jnp.ndarray:
    """splitmix64-style mix of the first key column (any int dtype)."""
    z = col.astype(jnp.uint64) * jnp.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    z = z ^ (z >> jnp.uint64(27))
    return z


def worker_of(col: jnp.ndarray, nworkers: int) -> jnp.ndarray:
    return (_hash_key(col) % jnp.uint64(nworkers)).astype(jnp.int32)


@kernels._scoped
def exchange_bucketize(batch: Batch, nworkers: int) -> Batch:
    """Scatter local rows into [W, cap] bins by key hash (dead rows dropped).

    Rows keep their relative order within a bin; bins are zero-padded with
    sentinel keys so each bin is a valid (unconsolidated) batch slice.
    Scope ``k.exchange_bucketize`` (as the public kernels of
    ``zset/kernels.py`` carry ``k.<kernel>``).
    """
    cap = batch.cap
    dest = jnp.where(batch.weights != 0,
                     worker_of(batch.keys[0], nworkers),
                     jnp.int32(nworkers))  # out-of-range -> dropped scatter
    onehot = dest[None, :] == jnp.arange(nworkers, dtype=jnp.int32)[:, None]
    rank_by_worker = jnp.cumsum(onehot, axis=1) - 1        # [W, cap]
    rank = jnp.take_along_axis(
        rank_by_worker, jnp.clip(dest, 0, nworkers - 1)[None, :], axis=0)[0]

    def scatter(col, fill):
        out = jnp.full((nworkers, cap), fill, col.dtype)
        return out.at[dest, rank].set(col, mode="drop")

    keys = tuple(scatter(c, kernels.sentinel_for(c.dtype)) for c in batch.keys)
    vals = tuple(scatter(c, kernels.sentinel_for(c.dtype)) for c in batch.vals)
    w = scatter(batch.weights, jnp.zeros((), batch.weights.dtype))
    return Batch(keys, vals, w)


# ---------------------------------------------------------------------------
# In-SPMD-context primitives (call inside shard_map; axis name = "workers")
# ---------------------------------------------------------------------------


def exchange_local(batch: Batch, nworkers: int) -> Batch:
    """Repartition the local batch by key hash; per-worker view.

    Local [cap] rows are bucketed into ``nworkers`` bins of the full local
    capacity (worst-case skew = all rows to one peer), all_to_all'd over ICI,
    and consolidated. Output capacity is ``nworkers * cap``; callers
    re-bucket outside the jit boundary when they care (spine insert does).
    """
    binned = exchange_bucketize(batch, nworkers)

    def a2a(x):
        return lax.all_to_all(x, WORKER_AXIS, split_axis=0, concat_axis=0,
                              tiled=True).reshape(nworkers * batch.cap)

    nk = len(batch.keys)
    # the collectives are no loop bodies, so the scope stays on them
    with jax.named_scope("x.all_to_all"):
        cols = tuple(a2a(c) for c in binned.cols)
        w = a2a(binned.weights)
    # a consolidated input arrives as nworkers sorted runs (each peer's bin
    # keeps its relative order, live-packed with a sentinel tail) — the
    # regime dispatch folds sorted merges instead of re-sorting
    runs = (batch.cap,) * nworkers if batch.sorted_runs == 1 else None
    return Batch(cols[:nk], cols[nk:], w, runs).consolidate()


def gather_local(batch: Batch) -> Batch:
    """All-gather + consolidate: every worker ends with the full union
    (the reference's gather targets one worker; replication is the SPMD
    equivalent and what output handles consume). The peer group is the
    mesh axis itself — no worker count to pass (or get wrong)."""
    def ag(x):
        return lax.all_gather(x, WORKER_AXIS, tiled=True)

    nk = len(batch.keys)
    with jax.named_scope("x.all_gather"):
        cols = tuple(ag(c) for c in batch.cols)
        w = ag(batch.weights)
    # the gather stacks every worker's consolidated slice: W sorted runs
    # (W read off the gathered shape — no worker count to pass or get wrong)
    runs = None
    if batch.sorted_runs == 1 and w.shape[-1] % batch.cap == 0:
        runs = (batch.cap,) * (w.shape[-1] // batch.cap)
    return Batch(cols[:nk], cols[nk:], w, runs).consolidate()


# ---------------------------------------------------------------------------
# Host-level helpers (outside shard_map)
# ---------------------------------------------------------------------------


def spmd(mesh: Mesh, fn):
    """Lift a per-worker function over 1-D batches to [W, ...] sharded
    batches via shard_map (leading worker axis squeezed inside)."""
    from dbsp_tpu.parallel.mesh import shard_map

    def lifted(*args):
        def body(*local):
            sq = jax.tree.map(lambda a: a[0], local)
            out = fn(*sq)
            return jax.tree.map(lambda a: a[None], out)

        return shard_map(body, mesh=mesh, in_specs=P(WORKER_AXIS),
                         out_specs=P(WORKER_AXIS))(*args)

    return lifted


@partial(jax.jit, static_argnames=("nworkers",))
def _shard_kernel(batch: Batch, nworkers: int) -> Batch:
    return exchange_bucketize(batch, nworkers)


@lru_cache(maxsize=None)
def _sharded_consolidate(mesh: Mesh):
    return jax.jit(spmd(mesh, lambda b: b.consolidate()))


def shard_batch(batch: Batch, mesh: Mesh) -> Batch:
    """Distribute a 1-D batch into the [W, cap_local] sharded layout by key
    hash (the input-handle -> sharded-circuit boundary), consolidated
    per-worker."""
    nworkers = mesh.devices.size
    binned = _shard_kernel(batch, nworkers)
    binned = jax.device_put(binned, worker_sharding(mesh))
    return _sharded_consolidate(mesh)(binned)


def unshard_batch(sharded: Batch) -> Batch:
    """Collapse a [W, cap_local] sharded batch to one consolidated 1-D batch
    on the host driver (output-handle boundary).

    Run metadata must be RE-derived: tree-mapping the reshape would carry
    the per-worker tag onto the flattened rows, where a 1-run sharded batch
    is really W stacked per-worker runs (which is exactly the tag that lets
    the consolidate fold merges instead of sorting)."""
    flat = jax.tree.map(lambda a: a.reshape(-1), sharded)
    runs = (sharded.cap,) * sharded.weights.shape[0] \
        if sharded.sorted_runs == 1 else None
    return flat.tagged(runs).consolidate()
