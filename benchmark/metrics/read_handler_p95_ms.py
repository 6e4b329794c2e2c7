"""The server's side of a read: the ``read`` span of ``GET /view/<v>``
(query and response written), p95 over the window's answered reads, ms.
The inside of ``read_p95_ms``, which adds HTTP, the connection's thread
and the wait for the interpreter lock.
Layer: ingest (io/server.py)."""

import span_measures as sm


def read(ctx):
    win = sm.window_of(ctx)
    if win is None:
        return None
    reads = sm.window_read_spans(win.spans, ctx["run"], ctx["measures"])
    if not reads:
        return None
    return ctx["measures"].percentile([s.seconds * 1e3 for s in reads], 95)
