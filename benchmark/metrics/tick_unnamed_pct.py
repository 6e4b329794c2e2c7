"""Share of a ``/step`` request that no phase span names: the self time
of ``step_request`` and of ``tick`` over the request's length, median over
the window's ticks, %. Before the result line it prints one fact line,
``{"phase": "tick_phases", "ticks": [...]}``: each window tick's request
seconds and its phases' (``span_measures.phase_table``).
Layer: tick (io/server.py, io/controller.py, compiled/driver.py)."""

import json

import span_measures as sm


def read(ctx):
    win = sm.window_of(ctx)
    if win is None:
        return None
    table = sm.phase_table(win)
    print(json.dumps({"phase": "tick_phases", "ticks": table}), flush=True)
    return 100.0 * ctx["measures"].percentile(
        [r["unnamed_s"] / r["step_request_s"] for r in table], 50)
