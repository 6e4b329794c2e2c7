"""How evenly the state lies over the cell's chips, %: the smallest over
the largest ``memory_stats()["peak_bytes_in_use"]`` of the configuration's
``workers`` first devices, read after the window. Near 100 = every chip
held its share; near 0 = the state sat on one chip and the cell measured
nothing. None with one worker, or where the backend reports no statistics.
Layer: trace state (compiled/cnodes.py, compiler.py: [W]-leading states)."""


def balance_pct(peaks: list):
    if len(peaks) < 2 or not all(peaks):
        return None
    return 100.0 * min(peaks) / max(peaks)


def read(ctx):
    import jax

    workers = ctx["config"]["workers"]
    if workers < 2:
        return None
    return balance_pct([(d.memory_stats() or {}).get("peak_bytes_in_use")
                        for d in jax.devices()[:workers]])
