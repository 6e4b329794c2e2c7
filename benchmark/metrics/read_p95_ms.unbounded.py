"""The end-to-end ``read_p95_ms`` arithmetic, reported per layer in the
cells where it cannot be held to a bound (q3: its runs spread by 17–21 %;
PERF.md, section 2). Layer: ingest (io/server.py, io/format.py) — the read
waits for the interpreter lock that NDJSON parsing and the drain hold."""


def read(ctx):
    return ctx["measures"].percentile(
        ctx["measures"].read_latencies_ms(ctx["run"]), 95)
