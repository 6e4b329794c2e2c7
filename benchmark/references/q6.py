"""NEXmark q6, average selling price by seller, from scratch, in plain Python
(written anew for the benchmark; shares no code with ``dbsp_tpu``).

A bid counts for its auction when it lies within ``[auction.date_time,
auction.expires]``. The winner of an auction is its counted bid with the
highest price, then the earliest time, then the largest bidder. A seller's
auctions with a winner are ranked by ``(expires, auction)``; the view holds,
per seller, the average winning price of the 10 largest:
``(seller, sum // count)``. Prices are positive, so Python's floor division
equals the program's truncating one. Python integers: exact.

What no comparison here can check is 64-bit arithmetic: every number the
view depends on fits in 32 bits (prices below 10^7, a sum of at most 10 of
them, ids near 10^5; event times near 1.65e12 are only compared within an
auction's minute, which no 32-bit wrap crosses in a run). ``control="int32"``
computes the view so wrapped and reads ``correct: true`` at the cell's size
(PERF.md 4, PR 38), so it is no control of a guarantee and is left out of
``CONTROLS``; the configuration claims no int64 precision for that reason.
"""

from __future__ import annotations

LAST = 10  # auctions a seller's average is taken over


def _wrap32(x: int) -> int:
    return (x + 2**31) % 2**32 - 2**31

#: controls this reference can compute, each a guarantee broken: the winner
#: ranked by time and not by price, and a seller's last 9 auctions averaged
CONTROLS = ("latest_bid_wins", "last_9")


def recompute(events: dict, control: str | None = None) -> dict:
    """``events[relation][column]`` is a list over ALL acknowledged events.
    Returns ``{(seller, average): 1}``: the view after the last of them.

    ``control="latest_bid_wins"`` makes an auction's latest counted bid its
    winner (a top-1 ordered on the wrong column); ``control="last_9"``
    averages a seller's 9 latest auctions, not 10; ``control="int32"`` is
    the same arithmetic with every value, sum and comparison wrapped to 32
    bits (module doc: not a control)."""
    w = _wrap32 if control == "int32" else (lambda x: x)
    a, b = events["auctions"], events["bids"]
    info = {w(aid): (w(seller), w(d0), w(d1)) for aid, seller, d0, d1 in zip(
        a["id"], a["seller"], a["date_time"], a["expires"])}
    best: dict = {}
    for aid, bidder, price, ts in zip(b["auction"], b["bidder"], b["price"],
                                      b["date_time"]):
        aid, bidder, price, ts = w(aid), w(bidder), w(price), w(ts)
        au = info.get(aid)
        if au is None or not au[1] <= ts <= au[2]:
            continue
        rank = (ts, price, bidder) if control == "latest_bid_wins" \
            else (price, -ts, bidder)
        if aid not in best or rank > best[aid][0]:
            best[aid] = (rank, price)
    per_seller: dict = {}
    for aid, (_, price) in best.items():
        seller, _, expires = info[aid]
        per_seller.setdefault(seller, []).append((expires, aid, price))
    last = LAST - 1 if control == "last_9" else LAST
    out = {}
    for seller, rows in per_seller.items():
        kept = sorted(rows, reverse=True)[:last]
        out[(seller, w(sum(p for _, _, p in kept)) // len(kept))] = 1
    return out
