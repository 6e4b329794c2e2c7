"""NEXmark event generator — the benchmark's own copy of the yardstick.

Copied from ``dbsp_tpu/nexmark/generator.py`` (``NexmarkGenerator.generate``,
NumPy, splitmix64 keyed by seed and absolute event index) with the model
constants of ``dbsp_tpu/nexmark/model.py`` inlined, so that a later change to
the program cannot move the traffic. Imports NumPy only: the load generator
(a process that holds no chip) and the plain references use it.
``selfcheck.py`` shows it equals the program's generator at two seeds.

Event mix per 50 consecutive events: 1 person, 3 auctions, 46 bids; dense
monotone ids; event time advances at ``first_event_rate`` events per second;
bids prefer the last ``hot_window`` auctions and bidders with the configured
probabilities. Any [n0, n1) partitioning yields identical events.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

FIRST_PERSON_ID = 1000
FIRST_AUCTION_ID = 1000
FIRST_CATEGORY_ID = 10
NUM_CATEGORIES = 5
PERSON_PROPORTION = 1
AUCTION_PROPORTION = 3
PROPORTION_DENOMINATOR = 50  # 1 person + 3 auctions + 46 bids

#: column order of each relation on the wire (the ingest route's schema)
COLUMNS = {
    "persons": ("id", "name", "city", "state", "email", "date_time"),
    "auctions": ("id", "item", "seller", "category", "initial_bid",
                 "reserve", "date_time", "expires"),
    "bids": ("auction", "bidder", "price", "channel", "date_time"),
}


def _mix64(seed: int, x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer over uint64 counters — the per-event RNG."""
    z = x.astype(np.uint64) + np.uint64((seed * 0x9E3779B97F4A7C15) % 2**64)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


@dataclasses.dataclass(frozen=True)
class GeneratorConfig:
    seed: int = 1
    base_time_ms: int = 1_651_000_000_000
    first_event_rate: int = 10_000_000     # events/sec of *event time*
    hot_auction_ratio: float = 0.85
    hot_bidder_ratio: float = 0.85
    hot_window: int = 100
    num_channels: int = 16
    num_name_codes: int = 512
    num_city_codes: int = 64
    num_state_codes: int = 50
    auction_expire_min_ms: int = 1_000
    auction_expire_max_ms: int = 60_000


class NexmarkGenerator:
    """Columnar batch generator over a half-open event-index range."""

    def __init__(self, cfg: GeneratorConfig = GeneratorConfig()):
        self.cfg = cfg

    def timestamps(self, n: np.ndarray) -> np.ndarray:
        step_ns = 1_000_000_000 // self.cfg.first_event_rate
        return (self.cfg.base_time_ms
                + (n.astype(np.int64) * step_ns) // 1_000_000)

    def generate(self, n0: int, n1: int) -> Dict[str, Dict[str, np.ndarray]]:
        """Columns for events [n0, n1), split per relation:
        ``{"persons": {...}, "auctions": {...}, "bids": {...}}``."""
        n = np.arange(n0, n1, dtype=np.int64)
        ep, off = n // PROPORTION_DENOMINATOR, n % PROPORTION_DENOMINATOR
        ts = self.timestamps(n)
        is_person = off < PERSON_PROPORTION
        is_auction = (~is_person) & (off < PERSON_PROPORTION +
                                     AUCTION_PROPORTION)
        is_bid = ~is_person & ~is_auction
        # draw j of absolute event i is splitmix64(seed, i*8+j)
        r32 = np.stack([_mix64(self.cfg.seed, n * 8 + j) >> np.uint64(33)
                        for j in range(5)]).astype(np.int64)
        return {
            "persons": self._persons(ep[is_person], ts[is_person],
                                     r32[:, is_person]),
            "auctions": self._auctions(ep[is_auction], off[is_auction],
                                       ts[is_auction], r32[:, is_auction]),
            "bids": self._bids(n[is_bid], ts[is_bid], r32[:, is_bid]),
        }

    def _persons(self, ep, ts, r):
        c = self.cfg
        return {
            "id": FIRST_PERSON_ID + ep,
            "name": (r[0] % c.num_name_codes).astype(np.int32),
            "city": (r[1] % c.num_city_codes).astype(np.int32),
            "state": (r[2] % c.num_state_codes).astype(np.int32),
            "email": (r[3] % c.num_name_codes).astype(np.int32),
            "date_time": ts,
        }

    def _auctions(self, ep, off, ts, r):
        c = self.cfg
        aid = (FIRST_AUCTION_ID + ep * AUCTION_PROPORTION +
               (off - PERSON_PROPORTION))
        max_person = np.maximum(ep, 0)
        hot = (r[0] % 1000) < int(c.hot_bidder_ratio * 1000)
        recent = np.maximum(max_person - c.hot_window, 0)
        seller_idx = np.where(
            hot, recent + r[1] % np.maximum(max_person - recent + 1, 1),
            r[1] % np.maximum(max_person + 1, 1))
        price0 = 1 + (r[2] % 10_000)
        span = c.auction_expire_max_ms - c.auction_expire_min_ms
        return {
            "id": aid,
            "item": (r[3] % c.num_name_codes).astype(np.int32),
            "seller": FIRST_PERSON_ID + seller_idx,
            "category": FIRST_CATEGORY_ID + r[4] % NUM_CATEGORIES,
            "initial_bid": price0,
            "reserve": price0 + (r[2] >> 16) % 10_000,
            "date_time": ts,
            "expires": ts + c.auction_expire_min_ms + r[0] % span,
        }

    def _bids(self, n, ts, r):
        c = self.cfg
        ep = n // PROPORTION_DENOMINATOR
        max_auction = np.maximum((ep + 1) * AUCTION_PROPORTION - 1, 0)
        max_person = ep
        hot_a = (r[0] % 1000) < int(c.hot_auction_ratio * 1000)
        recent_a = np.maximum(max_auction - c.hot_window, 0)
        auction_idx = np.where(
            hot_a, recent_a + r[1] % np.maximum(max_auction - recent_a + 1, 1),
            r[1] % np.maximum(max_auction + 1, 1))
        hot_b = (r[2] % 1000) < int(c.hot_bidder_ratio * 1000)
        recent_b = np.maximum(max_person - c.hot_window, 0)
        bidder_idx = np.where(
            hot_b, recent_b + r[3] % np.maximum(max_person - recent_b + 1, 1),
            r[3] % np.maximum(max_person + 1, 1))
        # log-uniform price in [1, 10^7)
        price = np.exp(np.log(10_000_000) * ((r[4] % 65536) / 65536.0))
        return {
            "auction": FIRST_AUCTION_ID + auction_idx,
            "bidder": FIRST_PERSON_ID + bidder_idx,
            "price": np.maximum(price.astype(np.int64), 1),
            "channel": (r[0] % c.num_channels).astype(np.int32),
            "date_time": ts,
        }


def from_config(config: dict, seed: int) -> NexmarkGenerator:
    """The generator a configuration file describes (``generator`` holds
    the source's settings; the seed is the run's)."""
    return NexmarkGenerator(GeneratorConfig(seed=seed, **config["generator"]))
