"""The ``read_p95_ms`` arithmetic (``/view`` reads timed from when each was
due, p95 over the window's answered reads), reported per layer because it
cannot be held to a bound in any cell: q4's runs spread by 8 % with 14
reads beyond the p95 (the bounded read tail is ``read_p90_ms``), q3 has ~100
reads in a window and five beyond the p95, q4-4w no sets of runs on four
chips yet; PERF.md, section 2.
Layer: ingest (io/server.py, io/format.py) — the read waits for the
interpreter lock that NDJSON parsing and the drain hold."""


def read(ctx):
    return ctx["measures"].percentile(
        ctx["measures"].read_latencies_ms(ctx["run"]), 95)
