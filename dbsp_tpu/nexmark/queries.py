"""Nexmark queries as circuit builders.

Reference: ``crates/nexmark/src/queries/*.rs`` (hand-built on the Stream
API, q0-q9 + q12-q22). Each builder takes the three relation streams
(persons, auctions, bids — see model.py schemas) and returns the query's
output stream. Queries are added here stage by stage as the operator
library grows; q3+ use incremental join/aggregate (operators/join.py,
operators/aggregate.py).
"""

from __future__ import annotations

import jax.numpy as jnp

from dbsp_tpu.circuit.builder import Stream
from dbsp_tpu.nexmark import model as M
from dbsp_tpu.operators.aggregate import Max, Min  # noqa: F401
# Count/Average take the linear fast path (delta segment-sums, no input
# trace); Min/Max need the general group-gather path
from dbsp_tpu.operators.aggregate_linear import (  # noqa: F401
    LinearAverage as Average, LinearCount as Count)


def q0(persons: Stream, auctions: Stream, bids: Stream) -> Stream:
    """Passthrough — measures raw engine overhead (queries/q0.rs)."""
    return bids.map_rows(lambda k, v: (k, v), M.BID_KEY, M.BID_VALS,
                         name="q0", preserves_order=True)


def q1(persons: Stream, auctions: Stream, bids: Stream) -> Stream:
    """Currency conversion: price dollars -> euros * 0.908 (queries/q1.rs).

    Integer semantics: price * 908 / 1000 (the reference uses f32; integer
    milli-euros keep the Z-set exactly comparable across backends).
    """
    def conv(k, v):
        bidder, price, channel, ts = v
        return k, (bidder, price * 908 // 1000, channel, ts)

    return bids.map_rows(conv, M.BID_KEY, M.BID_VALS, name="q1",
                         preserves_order=True)


def q2(persons: Stream, auctions: Stream, bids: Stream) -> Stream:
    """Bids on a sampled set of auctions: auction % 123 == 0, project
    (auction, price) (queries/q2.rs)."""
    filt = bids.filter_rows(lambda k, v: k[0] % 123 == 0, name="q2-filter")
    return filt.map_rows(lambda k, v: (k, (v[M.B_PRICE],)),
                         M.BID_KEY, (jnp.int64,), name="q2-project")


# State codes standing in for the reference's 'OR','ID','CA' literals
# (states are dictionary-encoded, generator.py).
Q3_STATES = (0, 1, 2)
Q3_CATEGORY = 10


def q3(persons: Stream, auctions: Stream, bids: Stream) -> Stream:
    """Who is selling in OR/ID/CA in category 10? (queries/q3.rs:35)

    filter(persons by state) ⋈ filter(auctions by category) on seller ->
    (name, city, state, auction id), keyed by auction id. Incremental
    equi-join (operators/join.py).
    """
    sellers = persons.filter_rows(
        lambda k, v: (v[M.P_STATE] == Q3_STATES[0])
        | (v[M.P_STATE] == Q3_STATES[1]) | (v[M.P_STATE] == Q3_STATES[2]),
        name="q3-sellers")
    cat = auctions.filter_rows(
        lambda k, v: v[M.A_CATEGORY] == Q3_CATEGORY, name="q3-category")
    # re-key auctions by seller (person id)
    by_seller = cat.index_by(
        lambda k, v: (v[M.A_SELLER],), M.PERSON_KEY,
        val_fn=lambda k, v: (k[0],), val_dtypes=(jnp.int64,),
        name="q3-by-seller")
    return sellers.join_index(
        by_seller,
        lambda k, pv, av: ((av[0],), (pv[0], pv[1], pv[2])),
        [jnp.int64], [jnp.int32, jnp.int32, jnp.int32], name="q3-join")


Q5_WINDOW_MS = 10_000
Q5_HOP_MS = 2_000
Q5_RETAIN_MS = 4 * Q5_WINDOW_MS  # completed windows linger this long


def q5(persons: Stream, auctions: Stream, bids: Stream) -> Stream:
    """Hot items: auctions with the most bids per hopping window
    (10s window, 2s hop — queries/q5.rs). Hopping windows are expressed
    TPU-style as a static flat_map: each bid belongs to exactly
    window/hop = 5 windows, so fan-out is a fixed [5, cap] expansion instead
    of a data-dependent iterator. Output: (window_start, auction) for
    auctions whose bid count equals the window maximum."""
    fanout = Q5_WINDOW_MS // Q5_HOP_MS

    def assign(k, v):
        ts = v[M.B_DATE]
        first = (ts // Q5_HOP_MS) * Q5_HOP_MS - (fanout - 1) * Q5_HOP_MS
        starts = jnp.stack([first + i * Q5_HOP_MS for i in range(fanout)])
        auction = jnp.broadcast_to(k[0], starts.shape)
        keep = jnp.ones(starts.shape, bool)
        return (starts, auction), (), keep

    per_window = bids.flat_map_rows(
        assign, fanout, (jnp.int64, jnp.int64), (), name="q5-windows")
    # retire old windows (queries/q5.rs keeps state bounded the same way):
    # a watermark on bid time drives monotone bounds; windows whose start
    # falls below wm - retention are retracted AND their trace state GC'd
    wm = bids.watermark_monotonic(lambda k, v: v[M.B_DATE], lateness=0)
    bounds = wm.apply(
        lambda w: None if w is None else (w - Q5_RETAIN_MS, 1 << 62),
        name="q5-bounds")
    per_window = per_window.window(bounds, gc=True)
    counts = per_window.aggregate(Count(), name="q5-count")
    # counts: key=(window, auction) val=(n). Max n per window:
    by_window = counts.index_by(
        lambda k, v: (k[0],), (jnp.int64,),
        val_fn=lambda k, v: (k[1], v[0]), val_dtypes=(jnp.int64, jnp.int64),
        name="q5-by-window", preserves_first_key=True)
    maxes = by_window.aggregate(Max(1), name="q5-max")
    hot = by_window.join_index(
        maxes,
        lambda k, cv, mv: (k, (cv[0], cv[1], mv[0])),
        (jnp.int64,), (jnp.int64, jnp.int64, jnp.int64), name="q5-join",
        preserves_first_key=True)
    winners = hot.filter_rows(lambda k, v: v[1] == v[2], name="q5-winners")
    return winners.map_rows(lambda k, v: ((k[0], v[0]), ()),
                            (jnp.int64, jnp.int64), (), name="q5-project",
                            preserves_first_key=True)


#: q5 under NEXmark's own name for it ("Hot Items"). The served deployment
#: ``nexmark-q5`` (benchmark/configs) asks for the query by this name, new
#: in PR 36: a tree from before it can build q5 but cannot serve that
#: deployment — a stale slot pin in the by_window trace makes each of its
#: step programs take ~530 s to compile for a v5e, tick 0 alone 1,600 s
#: (PERF.md 6, PR 36) — and, asked for a name it lacks, fails at once
#: instead of running for an hour.
hot_items = q5


Q7_WINDOW_MS = 10_000


def q7(persons: Stream, auctions: Stream, bids: Stream) -> Stream:
    """Highest bid of the latest completed tumbling window (queries/q7.rs):
    a watermark on bid event time drives monotone window bounds; the window
    operator maintains the bids of the last complete period, and a Max
    aggregate reduces them. Output: (window_end, max_price)."""
    wm = bids.watermark_monotonic(lambda k, v: v[M.B_DATE], lateness=0)

    def to_bounds(w):
        if w is None:
            return None
        end = (w // Q7_WINDOW_MS) * Q7_WINDOW_MS
        return (end - Q7_WINDOW_MS, end)

    bounds = wm.apply(to_bounds, name="q7-bounds")
    by_time = bids.index_by(
        lambda k, v: (v[M.B_DATE],), (jnp.int64,),
        val_fn=lambda k, v: (v[M.B_PRICE],), val_dtypes=(jnp.int64,),
        name="q7-by-time")
    windowed = by_time.window(bounds)
    # all rows of the (single-period) window share a window end — key by it
    keyed = windowed.map_rows(
        lambda k, v: (((k[0] // Q7_WINDOW_MS) * Q7_WINDOW_MS + Q7_WINDOW_MS,),
                      (v[0],)),
        (jnp.int64,), (jnp.int64,), name="q7-rekey")
    return keyed.aggregate(Max(0), name="q7-max")


Q8_WINDOW_MS = 10_000


def q8(persons: Stream, auctions: Stream, bids: Stream) -> Stream:
    """Monitor new users (queries/q8.rs:48-70): persons who created an
    auction in the same tumbling 10s window they registered in. The
    reference builds this from watermark_monotonic + window + join; the
    tumbling-window equality is expressed by making the window start a join
    key component. Output: (person_id, window_start, name)."""
    p_keyed = persons.index_by(
        lambda k, v: (k[0], (v[M.P_DATE] // Q8_WINDOW_MS) * Q8_WINDOW_MS),
        (jnp.int64, jnp.int64),
        val_fn=lambda k, v: (v[M.P_NAME],), val_dtypes=(jnp.int32,),
        name="q8-persons", preserves_first_key=True)
    a_keyed = auctions.index_by(
        lambda k, v: (v[M.A_SELLER],
                      (v[M.A_DATE] // Q8_WINDOW_MS) * Q8_WINDOW_MS),
        (jnp.int64, jnp.int64),
        val_fn=lambda k, v: (), val_dtypes=(),
        name="q8-auctions")
    joined = p_keyed.join_index(
        a_keyed, lambda k, pv, av: (k, (pv[0],)),
        (jnp.int64, jnp.int64), (jnp.int32,), name="q8-join",
        preserves_first_key=True)
    return joined.distinct()


def q4(persons: Stream, auctions: Stream, bids: Stream) -> Stream:
    """Average final (max) bid price per category over closed auctions
    (queries/q4.rs:43): bids within [auction.date_time, auction.expires]
    joined on auction id -> max price per (auction, category) -> average per
    category. Exercises join + two incremental aggregates."""
    by_auction = auctions.index_by(
        lambda k, v: (k[0],), M.AUCTION_KEY,
        val_fn=lambda k, v: (v[M.A_CATEGORY], v[M.A_DATE], v[M.A_EXPIRES]),
        val_dtypes=(jnp.int64, jnp.int64, jnp.int64), name="q4-auctions",
        preserves_first_key=True)
    joined = bids.join_index(
        by_auction,
        lambda k, bv, av: (
            (k[0], av[0]),
            (bv[M.B_PRICE], bv[M.B_DATE], av[1], av[2])),
        [jnp.int64, jnp.int64], [jnp.int64, jnp.int64, jnp.int64, jnp.int64],
        name="q4-join", preserves_first_key=True)
    in_window = joined.filter_rows(
        lambda k, v: (v[1] >= v[2]) & (v[1] <= v[3]), name="q4-window")
    # max price per (auction, category)
    per_auction = in_window.map_rows(
        lambda k, v: (k, (v[0],)), (jnp.int64, jnp.int64), (jnp.int64,),
        name="q4-price", preserves_first_key=True).aggregate(Max(0), name="q4-max")
    # average of those maxima per category
    by_category = per_auction.index_by(
        lambda k, v: (k[1],), (jnp.int64,),
        val_fn=lambda k, v: (v[0],), val_dtypes=(jnp.int64,),
        name="q4-by-category")
    return by_category.aggregate(Average(0), name="q4-avg")


# ---------------------------------------------------------------------------
# q6 / q9: winning bids (join + in-window max with tie-break) and rolling
# per-seller averages (top-K by close time)
# ---------------------------------------------------------------------------


def _winning_bids(auctions: Stream, bids: Stream) -> Stream:
    """(auction) -> (price, neg_ts, bidder, seller, expires) for the winning
    (highest-price, earliest-time) in-window bid of each auction — the core
    of q9/q6 (queries/q9.rs). Tie-break encoded by ranking on
    (price, -ts): lexicographic top-1 picks max price then min ts."""
    by_auction = auctions.index_by(
        lambda k, v: (k[0],), M.AUCTION_KEY,
        val_fn=lambda k, v: (v[M.A_SELLER], v[M.A_DATE], v[M.A_EXPIRES]),
        val_dtypes=(jnp.int64, jnp.int64, jnp.int64), name="q9-auctions",
        preserves_first_key=True)
    joined = bids.join_index(
        by_auction,
        lambda k, bv, av: (
            (k[0],),
            (bv[M.B_PRICE], -bv[M.B_DATE], bv[M.B_BIDDER], av[0],
             bv[M.B_DATE], av[1], av[2])),
        (jnp.int64,),
        (jnp.int64, jnp.int64, jnp.int64, jnp.int64, jnp.int64, jnp.int64,
         jnp.int64), name="q9-join", preserves_first_key=True)
    in_window = joined.filter_rows(
        lambda k, v: (v[4] >= v[5]) & (v[4] <= v[6]), name="q9-window")
    ranked = in_window.map_rows(
        lambda k, v: (k, (v[0], v[1], v[2], v[3], v[6])),
        (jnp.int64,), (jnp.int64, jnp.int64, jnp.int64, jnp.int64, jnp.int64),
        name="q9-rank")
    return ranked.topk(1, largest=True, name="q9-top1")


def q9(persons: Stream, auctions: Stream, bids: Stream) -> Stream:
    """Winning bid of each auction: (auction, price, ts, bidder)."""
    return _winning_bids(auctions, bids).map_rows(
        lambda k, v: (k, (v[0], -v[1], v[2])),
        (jnp.int64,), (jnp.int64, jnp.int64, jnp.int64), name="q9-project",
        preserves_first_key=True)


def q6(persons: Stream, auctions: Stream, bids: Stream) -> Stream:
    """Average winning price of each seller's last 10 closed auctions
    (queries/q6.rs): winning bids -> per-seller top-10 by expiry -> average.
    Output: (seller, avg_price)."""
    winners = _winning_bids(auctions, bids)
    by_seller = winners.map_rows(
        lambda k, v: ((v[3],), (v[4], k[0], v[0])),
        (jnp.int64,), (jnp.int64, jnp.int64, jnp.int64), name="q6-by-seller")
    last10 = by_seller.topk(10, largest=True, name="q6-last10")
    prices = last10.map_rows(lambda k, v: (k, (v[2],)),
                             (jnp.int64,), (jnp.int64,), name="q6-prices")
    return prices.aggregate(Average(0), name="q6-avg")


#: q6 under NEXmark's own name for it ("Average Selling Price by Seller").
#: The served deployment ``nexmark-q6`` (benchmark/configs) asks for the
#: query by this name, new in PR 38: a tree from before it can build q6 but
#: sizes its top-K nodes' buffers from a projection of the whole stream
#: (PERF.md 6, PR 38) and, asked for a name it lacks, fails at once instead.
average_selling_price_by_seller = q6


# ---------------------------------------------------------------------------
# q12-q22
# ---------------------------------------------------------------------------

Q12_WINDOW_TICKS = 10


def q12(persons: Stream, auctions: Stream, bids: Stream) -> Stream:
    """Bid count per bidder per PROCESSING-time window (queries/q12.rs).

    Processing time on a deterministic engine is the tick index: each
    circuit step is one processing unit, windows span 10 ticks. The tick
    counter is a stream_fold (no wall clock — reproducible runs)."""
    import jax.numpy as _jnp

    from dbsp_tpu.operators.basic import Apply2
    from dbsp_tpu.zset.batch import Batch

    tick = bids.stream_fold(0, lambda acc, b: acc + 1)

    def attach(batch: Batch, t: int) -> Batch:
        win = (t - 1) // Q12_WINDOW_TICKS
        bidder = batch.vals[M.B_BIDDER]
        wcol = _jnp.full((batch.cap,), win, _jnp.int64)
        return Batch((bidder, wcol), (), batch.weights).consolidate()

    keyed = bids.circuit.add_binary_operator(
        Apply2(attach, "q12-procwin"), bids, tick)
    keyed.schema = ((jnp.int64, jnp.int64), ())
    return keyed.aggregate(Count(), name="q12-count")


def q13(persons: Stream, auctions: Stream, bids: Stream,
        side: Stream = None) -> Stream:
    """Bounded side-input join (queries/q13.rs): enrich bids from a static
    keyed table. Default side input: channel -> boosted id table."""
    from dbsp_tpu.operators.basic import Generator
    from dbsp_tpu.zset.batch import Batch

    c = bids.circuit
    if side is None:
        table = Batch.from_tuples(
            [((ch, 1000 + ch), 1) for ch in range(16)],
            (jnp.int64,), (jnp.int64,))
        side = c.add_source(Generator(
            [table], default=Batch.empty((jnp.int64,), (jnp.int64,))))
        side.schema = ((jnp.int64,), (jnp.int64,))
    by_channel = bids.index_by(
        lambda k, v: (v[M.B_CHANNEL].astype(jnp.int64),), (jnp.int64,),
        val_fn=lambda k, v: (k[0], v[M.B_BIDDER], v[M.B_PRICE], v[M.B_DATE]),
        val_dtypes=(jnp.int64, jnp.int64, jnp.int64, jnp.int64),
        name="q13-by-channel")
    return by_channel.join_index(
        side, lambda k, bv, sv: ((bv[0],), (bv[1], bv[2], bv[3], sv[0])),
        (jnp.int64,), (jnp.int64, jnp.int64, jnp.int64, jnp.int64),
        name="q13-join")


def q14(persons: Stream, auctions: Stream, bids: Stream) -> Stream:
    """Calculation + filter (queries/q14.rs): euro price > 1M, bucketed
    bid-time-of-day. Output key (auction), vals (bidder, eur, timetype, ts);
    timetype: 0=day [8,18), 1=night [0,6)|[20,24), 2=other."""
    def conv(k, v):
        eur = v[M.B_PRICE] * 908 // 1000
        hour = (v[M.B_DATE] // 3_600_000) % 24
        night = ((hour < 6) | (hour >= 20)).astype(jnp.int64)
        day = ((hour >= 8) & (hour < 18)).astype(jnp.int64)
        timetype = jnp.where(day == 1, 0, jnp.where(night == 1, 1, 2))
        return k, (v[M.B_BIDDER], eur, timetype, v[M.B_DATE])

    mapped = bids.map_rows(conv, M.BID_KEY,
                           (jnp.int64, jnp.int64, jnp.int64, jnp.int64),
                           name="q14-calc")
    return mapped.filter_rows(lambda k, v: v[1] > 1_000_000, name="q14-filter")


DAY_MS = 86_400_000


def q15(persons: Stream, auctions: Stream, bids: Stream) -> Stream:
    """Distinct bidders per day (queries/q15.rs): (day, n_distinct)."""
    day_bidder = bids.map_rows(
        lambda k, v: ((v[M.B_DATE] // DAY_MS, v[M.B_BIDDER]), ()),
        (jnp.int64, jnp.int64), (), name="q15-daybidder")
    uniq = day_bidder.distinct()
    by_day = uniq.index_by(lambda k, v: (k[0],), (jnp.int64,),
                           val_fn=lambda k, v: (k[1],),
                           val_dtypes=(jnp.int64,), name="q15-by-day")
    return by_day.aggregate(Count(), name="q15-count")


Q16_RANK1 = 10_000
Q16_RANK2 = 1_000_000
Q16_NSTATS = 12


import dataclasses as _dc

from dbsp_tpu.operators.aggregate_linear import LinearAggregator


@_dc.dataclass(frozen=True)
class _Q16Stats(LinearAggregator):
    """12-column linear sum: each input row is a one-hot stat contribution;
    summing per (channel, day) assembles the full stat row with zeros for
    absent ranks — the left-join-with-default-0 the reference's SQL
    `count(*) filter (...)` columns imply."""

    acc_dtypes = (jnp.int64,) * Q16_NSTATS
    out_dtypes = (jnp.int64,) * Q16_NSTATS
    name = "q16stats"

    def weigh(self, val_cols):
        return tuple(val_cols[:Q16_NSTATS])

    def finalize(self, acc_cols, count):
        return acc_cols


def q16(persons: Stream, auctions: Stream, bids: Stream) -> Stream:
    """Channel statistics per day (queries/q16.rs, the FULL stat set):
    (channel, day) -> (total_bids, rank1/2/3_bids, total_bidders,
    rank1/2/3_bidders, total_auctions, rank1/2/3_auctions), where rank
    buckets split on price < 10_000 / < 1_000_000 / >= (q16.rs:55-66).

    Shape: one Count per bid rank (4 streams), one distinct+Count per
    (bidder x rank) and (auction x rank) (8 streams); each stat maps to a
    one-hot 12-column row and a single 12-column linear sum per
    (channel, day) assembles the output with 0 for empty buckets."""
    def rank_of(price):
        return jnp.where(price < Q16_RANK1, 1,
                         jnp.where(price < Q16_RANK2, 2, 3))

    base = bids.map_rows(
        lambda k, v: ((v[M.B_CHANNEL].astype(jnp.int64),
                       v[M.B_DATE] // DAY_MS),
                      (k[0], v[M.B_BIDDER], rank_of(v[M.B_PRICE]))),
        (jnp.int64, jnp.int64), (jnp.int64, jnp.int64, jnp.int64),
        name="q16-base")  # (channel, day) -> (auction, bidder, rank)

    def rank_filter(s, r, name):
        return s if r == 0 else s.filter_rows(
            lambda k, v, _r=r: v[2] == _r, name=name)

    stats = []  # (slot, stream of (channel, day) -> count)
    for r in range(4):  # bids counts: slots 0..3
        stats.append((r, rank_filter(base, r, f"q16-bids-r{r}")
                      .aggregate(Count(), name=f"q16-nbids-r{r}")))
    for col, what in ((1, "bidder"), (0, "auction")):
        for r in range(4):  # bidders: slots 4..7; auctions: slots 8..11
            slot = (4 if what == "bidder" else 8) + r
            uniq = rank_filter(base, r, f"q16-{what}-r{r}-f").map_rows(
                lambda k, v, _c=col: ((k[0], k[1], v[_c]), ()),
                (jnp.int64, jnp.int64, jnp.int64), (),
                name=f"q16-{what}-r{r}-key").distinct()
            cnt = uniq.index_by(
                lambda k, v: (k[0], k[1]), (jnp.int64, jnp.int64),
                val_fn=lambda k, v: (k[2],), val_dtypes=(jnp.int64,),
                name=f"q16-{what}-r{r}-by").aggregate(
                    Count(), name=f"q16-n{what}-r{r}")
            stats.append((slot, cnt))

    # one-hot each stat into the 12-column layout and sum
    onehot = []
    for slot, s in stats:
        def mk(slot):
            def f(k, v):
                z = jnp.zeros_like(v[0])
                return k, tuple(v[0] if i == slot else z
                                for i in range(Q16_NSTATS))
            return f

        oh = s.map_rows(mk(slot), (jnp.int64, jnp.int64),
                        (jnp.int64,) * Q16_NSTATS, name=f"q16-oh{slot}")
        onehot.append(oh)
    combined = onehot[0].sum_with(onehot[1:])
    combined.schema = ((jnp.int64, jnp.int64), (jnp.int64,) * Q16_NSTATS)
    return combined.aggregate(_Q16Stats(), name="q16-stats")


def q17(persons: Stream, auctions: Stream, bids: Stream) -> Stream:
    """Auction bid statistics per day (queries/q17.rs): (auction, day) ->
    (count, min, max, avg price)."""
    keyed = bids.map_rows(
        lambda k, v: ((k[0], v[M.B_DATE] // DAY_MS), (v[M.B_PRICE],)),
        (jnp.int64, jnp.int64), (jnp.int64,), name="q17-key",
        preserves_first_key=True)
    cnt = keyed.aggregate(Count(), name="q17-count")
    mn = keyed.aggregate(Min(0), name="q17-min")
    mx = keyed.aggregate(Max(0), name="q17-max")
    avg = keyed.aggregate(Average(0), name="q17-avg")
    j1 = cnt.join_index(mn, lambda k, a, b: (k, (a[0], b[0])),
                        (jnp.int64, jnp.int64), (jnp.int64, jnp.int64),
                        name="q17-j1", preserves_first_key=True)
    j2 = j1.join_index(mx, lambda k, a, b: (k, (a[0], a[1], b[0])),
                       (jnp.int64, jnp.int64),
                       (jnp.int64, jnp.int64, jnp.int64), name="q17-j2", preserves_first_key=True)
    return j2.join_index(avg, lambda k, a, b: (k, (a[0], a[1], a[2], b[0])),
                         (jnp.int64, jnp.int64),
                         (jnp.int64, jnp.int64, jnp.int64, jnp.int64),
                         name="q17-j3", preserves_first_key=True)


def q18(persons: Stream, auctions: Stream, bids: Stream) -> Stream:
    """Last bid of each bidder (queries/q18.rs): (bidder, ts, auction, price)."""
    by_bidder = bids.index_by(
        lambda k, v: (v[M.B_BIDDER],), (jnp.int64,),
        val_fn=lambda k, v: (v[M.B_DATE], k[0], v[M.B_PRICE]),
        val_dtypes=(jnp.int64, jnp.int64, jnp.int64), name="q18-by-bidder")
    return by_bidder.topk(1, largest=True, name="q18-last")


def q19(persons: Stream, auctions: Stream, bids: Stream) -> Stream:
    """Top-10 bids by price per auction (queries/q19.rs): the window-function
    query; ranking = (price, ts) lexicographic."""
    ranked = bids.index_by(
        lambda k, v: (k[0],), M.BID_KEY,
        val_fn=lambda k, v: (v[M.B_PRICE], v[M.B_DATE], v[M.B_BIDDER]),
        val_dtypes=(jnp.int64, jnp.int64, jnp.int64), name="q19-rank",
        preserves_first_key=True)
    return ranked.topk(10, largest=True, name="q19-top10")


def q20(persons: Stream, auctions: Stream, bids: Stream) -> Stream:
    """Expand bids with their auction's info, category 10 only
    (queries/q20.rs): (auction) -> (bidder, price, item, seller)."""
    cat = auctions.filter_rows(lambda k, v: v[M.A_CATEGORY] == Q3_CATEGORY,
                               name="q20-cat")
    by_id = cat.index_by(
        lambda k, v: (k[0],), M.AUCTION_KEY,
        val_fn=lambda k, v: (v[M.A_ITEM].astype(jnp.int64), v[M.A_SELLER]),
        val_dtypes=(jnp.int64, jnp.int64), name="q20-auctions",
        preserves_first_key=True)
    return bids.join_index(
        by_id, lambda k, bv, av: (k, (bv[M.B_BIDDER], bv[M.B_PRICE],
                                      av[0], av[1])),
        (jnp.int64,), (jnp.int64, jnp.int64, jnp.int64, jnp.int64),
        name="q20-join", preserves_first_key=True)


def q21(persons: Stream, auctions: Stream, bids: Stream) -> Stream:
    """Channel id classification (queries/q21.rs): channels 0-3 map to fixed
    ids (the reference's apple/google/facebook/baidu CASE), others extract
    channel_id from the url. Strings are dictionary codes; the host-side
    dictionary (``nexmark/strings.py``) is constructed so this arithmetic
    EQUALS the CASE/regex over the decoded strings (fidelity-tested)."""
    def classify(k, v):
        ch = v[M.B_CHANNEL].astype(jnp.int64)
        chan_id = jnp.where(ch < 4, ch, 100 + ch)
        return k, (v[M.B_BIDDER], v[M.B_PRICE], ch, chan_id)

    return bids.map_rows(classify, M.BID_KEY,
                         (jnp.int64, jnp.int64, jnp.int64, jnp.int64),
                         name="q21")


def q22(persons: Stream, auctions: Stream, bids: Stream) -> Stream:
    """URL split (queries/q22.rs): dir1/dir2/dir3 of the bid url. URLs are
    dictionary-coded; ``nexmark/strings.py`` owns the real strings, built so
    this mod/div arithmetic EQUALS split_part over the decoded url
    (fidelity-tested)."""
    def split(k, v):
        url = v[M.B_CHANNEL].astype(jnp.int64)  # channel doubles as url code
        dir1 = url % 7
        dir2 = (url // 7) % 11
        dir3 = (url // 77) % 13
        return k, (v[M.B_BIDDER], v[M.B_PRICE], dir1, dir2, dir3)

    return bids.map_rows(split, M.BID_KEY,
                         (jnp.int64, jnp.int64, jnp.int64, jnp.int64,
                          jnp.int64), name="q22")
