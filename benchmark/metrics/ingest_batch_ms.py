"""Median client-clock time to push one tick's batch (three POSTs), ms.
Layer: ingest (io/server.py, io/format.py). Source: the load generator's
clock (program_span: spans the harness records around the ingest route)."""


def read(ctx):
    xs = ctx["measures"].push_seconds(ctx["run"])
    p = ctx["measures"].percentile(xs, 50)
    return None if p is None else p * 1e3
