"""Of the idle seconds in the traced window's longest gaps
(``trace["gaps"]``), the share during which a phase span was open in the
program (any span but the containers ``step_request``, ``tick``,
``ingest``, ``read``), %.

The trace's clock is tied to the spans' by ``offset = run["open"] -
window_ns[0] / 1e9``: the harness opens the trace and sends ``run`` to the
load generator in consecutive statements, and the generator stamps ``open``
when it has the command and its first answer from the server. What that
ignores is bounded in every run: the traced stretch on the trace's clock,
less the same stretch on the generator's (open to the last traced
``/step``'s answer), is the sum of the two hand-overs, printed as
``clock_error_bound_ms``.

Before the result line it prints one fact line, ``{"phase": "idle_by_span",
"gaps": [[name, seconds], ...], ...}``: each gap's seconds by innermost
open span, longest gap first (``no_request``: no request was in the
program; ``unnamed``: only a container was open).
Layer: device."""

import json

import span_measures as sm


def read(ctx):
    trace, run = ctx["trace"], ctx["run"]
    win = sm.window_of(ctx)
    if win is None or not trace or not trace["gaps"] \
            or run["open"] is None:
        return None
    offset = run["open"] - trace["window_ns"][0] / 1e9
    gaps = [(g0 / 1e9 + offset, g1 / 1e9 + offset)
            for g0, g1 in trace["gaps"]]
    named = sm.name_gaps(win.spans, gaps)
    total: dict = {}
    for by_name in named:
        for name, s in by_name.items():
            total[name] = total.get(name, 0.0) + s
    idle = sum(total.values())
    last = ctx["traffic"]["setup_ticks"] + ctx["traffic"]["trace_ticks"] - 1
    done = run["step_done"].get(str(last))
    bound = None if done is None else (
        trace["window_s"] - (done - run["open"])) * 1e3
    print(json.dumps({
        "phase": "idle_by_span",
        "gaps": [[name, s] for by_name in named for name, s in sorted(
            by_name.items(), key=lambda kv: -kv[1])],
        "gap_seconds": [b - a for a, b in gaps],
        "by_span": dict(sorted(total.items(), key=lambda kv: -kv[1])),
        "clock_offset_s": offset, "clock_error_bound_ms": bound}),
        flush=True)
    named_s = sum(s for name, s in total.items()
                  if name not in ("unnamed", "no_request"))
    return 100.0 * named_s / idle if idle else None
