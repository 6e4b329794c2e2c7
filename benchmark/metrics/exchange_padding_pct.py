"""The padded rows every worker sorts and merges for nothing, %: over the
exchange sites (each compiled exchange and each sharded input), 100 x
(1 - worst worker's live rows / bucket capacity) at the last validation,
rows and capacities summed over the sites. From the program's counter
``dbsp_tpu.parallel.exchange.EXCHANGE_SITE_ROWS`` ({site: (rows,
capacity)}), which validation fills from the requirements it fetches
anyway. None where the program has no such counter or no site filled it
(one worker).
Layer: exchange (parallel/exchange.py, compiled/cnodes.py CExchange)."""


def padding_pct(sites: dict):
    rows = sum(r for r, _ in sites.values())
    cap = sum(c for _, c in sites.values())
    return None if not cap else 100.0 * (1.0 - rows / cap)


def read(ctx):
    try:
        from dbsp_tpu.parallel.exchange import EXCHANGE_SITE_ROWS
    except ImportError:
        return None
    return padding_pct(EXCHANGE_SITE_ROWS)
