"""NEXmark q5, hot items, from scratch, in plain Python (written anew for
the benchmark; shares no code with ``dbsp_tpu``).

Every bid falls into the five windows of 10 s, hopping by 2 s, that cover
its time. The watermark is the latest bid time acknowledged; a window
whose start lies below the watermark less 40 s has been retired and is in
the view no longer. Of the windows that are left, the view holds, per
window, the auctions with the most bids: ``(window start, auction)``.
Python integers: exact, as the configuration's int64 guarantee demands.
"""

from __future__ import annotations

WINDOW_MS = 10_000
HOP_MS = 2_000
RETAIN_MS = 40_000  # how long a completed window lingers in the view

#: controls this reference can compute: a guarantee broken in each
CONTROLS = ("no_retire", "int32")


def _wrap32(x: int) -> int:
    return (x + 2**31) % 2**32 - 2**31


def recompute(events: dict, control: str | None = None) -> dict:
    """``events[relation][column]`` is a list over ALL acknowledged events.
    Returns ``{(window_start, auction): 1}`` — the view as it stands after
    the last of them.

    ``control="no_retire"`` never retracts a window (the fifth guarantee
    broken); ``control="int32"`` does the window arithmetic wrapped to 32
    bits (event times are about 1.65e12 ms)."""
    w = _wrap32 if control == "int32" else (lambda x: x)
    bids = events["bids"]
    if not bids["date_time"]:
        return {}
    watermark = max(w(ts) for ts in bids["date_time"])
    retired_below = None if control == "no_retire" \
        else w(watermark - RETAIN_MS)
    counts: dict = {}
    for auction, ts in zip(bids["auction"], bids["date_time"]):
        ts = w(ts)
        newest = w((ts // HOP_MS) * HOP_MS)
        for i in range(WINDOW_MS // HOP_MS):
            start = w(newest - i * HOP_MS)
            if retired_below is None or start >= retired_below:
                key = (start, auction)
                counts[key] = counts.get(key, 0) + 1
    most: dict = {}
    for (start, _), n in counts.items():
        if n > most.get(start, 0):
            most[start] = n
    return {key: 1 for key, n in counts.items() if n == most[key[0]]}
