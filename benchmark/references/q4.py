"""NEXmark q4, from scratch, in plain Python (copied from
``chip_smoke.q4_recompute``; shares no code with ``dbsp_tpu``).

Average, per category, of each auction's highest bid placed within
``[auction.date_time, auction.expires]``. Python integers: exact, as the
configuration's int64 guarantee demands.
"""

from __future__ import annotations

#: lower-precision controls this reference can compute
CONTROLS = ("int32",)


def _wrap32(x: int) -> int:
    return (x + 2**31) % 2**32 - 2**31


def recompute(events: dict, control: str | None = None) -> dict:
    """``events[relation][column]`` is a list over ALL acknowledged events.
    Returns ``{(category, average): weight}`` — the accumulated view.

    ``control="int32"`` is the control of the int64 guarantee: the same
    arithmetic with every value, sum and comparison wrapped to 32 bits."""
    w = _wrap32 if control == "int32" else (lambda x: x)
    a, b = events["auctions"], events["bids"]
    info = {aid: (cat, w(d0), w(d1)) for aid, cat, d0, d1 in zip(
        a["id"], a["category"], a["date_time"], a["expires"])}
    best: dict = {}
    for aid, ts, price in zip(b["auction"], b["date_time"], b["price"]):
        au = info.get(aid)
        if au is not None and au[1] <= w(ts) <= au[2]:
            k = (aid, au[0])
            if price > best.get(k, 0):
                best[k] = price
    per_cat: dict = {}
    for (_, cat), price in best.items():
        s, n = per_cat.get(cat, (0, 0))
        per_cat[cat] = (w(s + price), n + 1)
    out = {}
    for cat, (s, n) in per_cat.items():
        # the engine's integer division truncates like Python's floor on
        # the non-negative sums of the exact path; the wrapped control
        # may go negative, where any rounding already differs
        out[(cat, s // n)] = 1
    return out
