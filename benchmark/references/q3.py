"""NEXmark q3, from scratch, in plain Python (shares no code with
``dbsp_tpu``): who is selling in OR, ID or CA in category 10?

Persons whose state code is 0, 1 or 2 joined with category-10 auctions on
``auction.seller == person.id``; one row ``(auction, name, city, state)``
per matching pair. Bids are read by nothing.
"""

from __future__ import annotations

#: lower-precision controls this reference can compute: none. Every id and
#: code of q3's rows fits 32 bits at the cell's scale, so q3 cannot witness
#: the configuration's int64 guarantee; only q4 can (PERF.md, section 7)
CONTROLS = ()

STATES = (0, 1, 2)   # the dictionary codes of OR, ID, CA
CATEGORY = 10


def recompute(events: dict, control: str | None = None) -> dict:
    """``events[relation][column]`` is a list over ALL acknowledged events.
    Returns ``{(auction, name, city, state): weight}``."""
    if control is not None:
        raise ValueError(f"q3 has no control {control!r}")
    p, a = events["persons"], events["auctions"]
    sellers: dict = {}
    for pid, name, city, state in zip(p["id"], p["name"], p["city"],
                                      p["state"]):
        if state in STATES:
            sellers.setdefault(pid, []).append((name, city, state))
    out: dict = {}
    for aid, seller, cat in zip(a["id"], a["seller"], a["category"]):
        if cat == CATEGORY:
            for name, city, state in sellers.get(seller, ()):
                row = (aid, name, city, state)
                out[row] = out.get(row, 0) + 1
    return out
