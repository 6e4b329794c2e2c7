"""Server-side time to parse one window batch and hand its rows to the
input handles: ``ingest.parse`` + ``ingest.push_rows`` summed over the
batch's three POSTs, median over the window's batches, ms. The inside of
``ingest_batch_ms`` (which also holds HTTP and the body's read).
Layer: ingest (io/server.py, io/format.py)."""

import span_measures as sm


def read(ctx):
    win = sm.window_of(ctx)
    if win is None:
        return None
    return 1e3 * ctx["measures"].percentile([
        sum(p.total("ingest.parse", "ingest.push_rows") for p in win.pushes[k])
        for k in sorted(win.pushes)], 50)
