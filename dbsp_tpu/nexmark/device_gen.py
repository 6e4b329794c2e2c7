"""Device-side Nexmark event generation — the input pipeline for compiled
(fully-jitted) benchmark runs.

The host generator (:mod:`dbsp_tpu.nexmark.generator`) is counter-based:
every column of event ``i`` is a pure function of ``(seed, i)`` via the
splitmix64 finalizer. That design pays off twice — it made the host path
batch-invariant and parallel, and it means the SAME arithmetic runs on the
TPU as a jitted kernel, so a benchmark tick needs **zero host→device
transfer** (the reference streams events over memory from generator threads,
``crates/nexmark/src/lib.rs:40-160``; a host batch pays a host→device
copy per tick instead).

Bit-compatibility with the host path is tested (``tests/test_device_gen.py``):
integer columns are identical arithmetic; the one transcendental (the
log-uniform bid price) is replaced on both paths' terms by an exact 65536-entry
lookup table computed once with numpy, so device and host prices agree bit
for bit.

Static shapes: a tick of ``n`` events with ``n % 50 == 0`` contains exactly
``n/50`` persons, ``3n/50`` auctions and ``46n/50`` bids (the spec's fixed
event mix), so every tick compiles to the same shapes and the whole run is
one XLA program.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dbsp_tpu.nexmark import model as M
from dbsp_tpu.nexmark.generator import GeneratorConfig
from dbsp_tpu.zset import kernels
from dbsp_tpu.zset.batch import WEIGHT_DTYPE, Batch


def _mix64(seed: int, x: jnp.ndarray) -> jnp.ndarray:
    """splitmix64 finalizer — same constants as the host/native paths."""
    z = x.astype(jnp.uint64) + jnp.uint64((seed * 0x9E3779B97F4A7C15) % 2**64)
    z = (z ^ (z >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return z ^ (z >> jnp.uint64(31))


def price_table() -> np.ndarray:
    """All 65536 possible bid prices, exactly as the host generator computes
    them (log-uniform in [1, 10^7)); numpy-evaluated once so host and device
    agree bit for bit."""
    r = np.arange(65536, dtype=np.float64)
    p = np.exp(np.log(10_000_000) * (r / 65536.0))
    return np.maximum(p.astype(np.int64), 1)


def _draws(seed: int, n: jnp.ndarray) -> Tuple[jnp.ndarray, ...]:
    """The five 31-bit draws for each absolute event index (int64)."""
    return tuple((_mix64(seed, n * 8 + j) >> jnp.uint64(33)).astype(jnp.int64)
                 for j in range(5))


def _timestamps(cfg: GeneratorConfig, n: jnp.ndarray) -> jnp.ndarray:
    step_ns = 1_000_000_000 // cfg.first_event_rate
    return cfg.base_time_ms + (n.astype(jnp.int64) * step_ns) // 1_000_000


@partial(jax.jit, static_argnames=("cfg", "epochs"))
def generate_tick(cfg: GeneratorConfig, e0: jnp.ndarray, epochs: int
                  ) -> Tuple[Batch, Batch, Batch]:
    """Device batches for epochs [e0, e0+epochs) == events [50*e0, 50*(e0+epochs)).

    ``e0`` is a traced scalar, ``epochs`` static — every tick of a run reuses
    one compiled program. Returns consolidated (persons, auctions, bids)
    batches at their natural capacities (epochs, 3*epochs, 46*epochs).
    """
    e0 = jnp.asarray(e0, jnp.int64)
    ep = e0 + jnp.arange(epochs, dtype=jnp.int64)

    # -- persons: event n = 50*ep --------------------------------------------
    n_p = ep * M.PROPORTION_DENOMINATOR
    r = _draws(cfg.seed, n_p)
    persons = Batch(
        keys=(M.FIRST_PERSON_ID + ep,),
        vals=((r[0] % cfg.num_name_codes).astype(jnp.int32),
              (r[1] % cfg.num_city_codes).astype(jnp.int32),
              (r[2] % cfg.num_state_codes).astype(jnp.int32),
              (r[3] % cfg.num_name_codes).astype(jnp.int32),
              _timestamps(cfg, n_p)),
        weights=jnp.ones((epochs,), WEIGHT_DTYPE),
        runs=(epochs,))

    # -- auctions: events n = 50*ep + 1 + i, i in 0..3 -----------------------
    epa = jnp.repeat(ep, M.AUCTION_PROPORTION)
    off = jnp.tile(jnp.arange(M.AUCTION_PROPORTION, dtype=jnp.int64), epochs)
    n_a = epa * M.PROPORTION_DENOMINATOR + M.PERSON_PROPORTION + off
    ts = _timestamps(cfg, n_a)
    r = _draws(cfg.seed, n_a)
    aid = M.FIRST_AUCTION_ID + epa * M.AUCTION_PROPORTION + off
    max_person = jnp.maximum(epa, 0)
    hot = (r[0] % 1000) < int(cfg.hot_bidder_ratio * 1000)
    recent = jnp.maximum(max_person - cfg.hot_window, 0)
    seller_idx = jnp.where(
        hot, recent + r[1] % jnp.maximum(max_person - recent + 1, 1),
        r[1] % jnp.maximum(max_person + 1, 1))
    price0 = 1 + (r[2] % 10_000)
    span = cfg.auction_expire_max_ms - cfg.auction_expire_min_ms
    auctions = Batch(
        keys=(aid,),
        vals=((r[3] % cfg.num_name_codes).astype(jnp.int32),
              M.FIRST_PERSON_ID + seller_idx,
              M.FIRST_CATEGORY_ID + r[4] % M.NUM_CATEGORIES,
              price0,
              price0 + (r[2] >> 16) % 10_000,
              ts,
              ts + cfg.auction_expire_min_ms + r[0] % span),
        weights=jnp.ones((epochs * M.AUCTION_PROPORTION,), WEIGHT_DTYPE),
        runs=(epochs * M.AUCTION_PROPORTION,))

    # -- bids: events n = 50*ep + 4 + i, i in 0..46 --------------------------
    epb = jnp.repeat(ep, M.BID_PROPORTION)
    offb = jnp.tile(jnp.arange(M.BID_PROPORTION, dtype=jnp.int64), epochs)
    n_b = (epb * M.PROPORTION_DENOMINATOR + M.PERSON_PROPORTION +
           M.AUCTION_PROPORTION + offb)
    ts = _timestamps(cfg, n_b)
    r = _draws(cfg.seed, n_b)
    max_auction = jnp.maximum((epb + 1) * M.AUCTION_PROPORTION - 1, 0)
    max_person = epb
    hot_a = (r[0] % 1000) < int(cfg.hot_auction_ratio * 1000)
    recent_a = jnp.maximum(max_auction - cfg.hot_window, 0)
    auction_idx = jnp.where(
        hot_a, recent_a + r[1] % jnp.maximum(max_auction - recent_a + 1, 1),
        r[1] % jnp.maximum(max_auction + 1, 1))
    hot_b = (r[2] % 1000) < int(cfg.hot_bidder_ratio * 1000)
    recent_b = jnp.maximum(max_person - cfg.hot_window, 0)
    bidder_idx = jnp.where(
        hot_b, recent_b + r[3] % jnp.maximum(max_person - recent_b + 1, 1),
        r[3] % jnp.maximum(max_person + 1, 1))
    prices = jnp.asarray(price_table())[r[4] % 65536]
    bids = Batch(
        keys=(M.FIRST_AUCTION_ID + auction_idx,),
        vals=(M.FIRST_PERSON_ID + bidder_idx,
              prices,
              (r[0] % cfg.num_channels).astype(jnp.int32),
              ts),
        weights=jnp.ones((epochs * M.BID_PROPORTION,), WEIGHT_DTYPE))

    # persons/auctions arrive sorted by their dense ids (consolidated);
    # bids are keyed by a random auction id and need the one sort
    return persons, auctions, bids.consolidate()
