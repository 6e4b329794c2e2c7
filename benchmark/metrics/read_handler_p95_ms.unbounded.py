"""The ``read_handler_p95_ms`` arithmetic in the cells whose read tail has
no bound (``read_p95_ms.unbounded``: q3, q4-4w), where it can move no
end-to-end read metric: the ``read`` span of ``GET /view/<v>``, p95 over
the window's answered reads, ms.
Layer: ingest (io/server.py)."""

import span_measures as sm


def read(ctx):
    return sm.read_handler_ms(ctx, 95)
