"""The server's side of a read: the ``read`` span of ``GET /view/<v>``
(query and response written), p95 over the window's answered reads, ms.
The inside of the read tail (``read_p90_ms``), which adds HTTP, the
connection's thread and the wait for the interpreter lock.
Layer: ingest (io/server.py)."""

import span_measures as sm


def read(ctx):
    return sm.read_handler_ms(ctx, 95)
