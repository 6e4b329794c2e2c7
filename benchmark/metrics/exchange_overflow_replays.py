"""Bucket overflows a run paid for with a replay, count: the program's
``dbsp_tpu.parallel.exchange.EXCHANGE_OVERFLOW_COUNTS`` summed over its
kinds (``exchange``: a compiled exchange's per-worker bucket; ``input``: a
sharded input's share) — each is one validated interval re-run at a grown
capacity, set-up ticks included. None with one worker (no bucket exists).
Layer: exchange (parallel/exchange.py, compiled/compiler.py grow)."""


def read(ctx):
    if ctx["config"]["workers"] < 2:
        return None
    try:
        from dbsp_tpu.parallel.exchange import EXCHANGE_OVERFLOW_COUNTS
    except ImportError:
        return None
    return float(sum(EXCHANGE_OVERFLOW_COUNTS.values()))
