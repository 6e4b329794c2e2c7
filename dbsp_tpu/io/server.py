"""Per-pipeline HTTP server: control, stats, metrics, data endpoints.

Reference: ``adapters/src/server/mod.rs:250-378`` — the actix service every
compiled pipeline embeds: /start /pause /shutdown /status /stats /metrics
/dump_profile plus push/pull data endpoints /input_endpoint/{name} and
/output_endpoint/{name} — and the Prometheus export
(``server/prometheus.rs``). stdlib ThreadingHTTPServer; no web framework.
"""

from __future__ import annotations

import json
import threading
import time as _time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, Optional
from urllib.parse import parse_qs, urlparse

from dbsp_tpu.io.controller import Controller
from dbsp_tpu.io.format import INPUT_FORMATS, OUTPUT_FORMATS
from dbsp_tpu.obs import export as obs_export
from dbsp_tpu.obs.tracing import default_recorder
from dbsp_tpu.testing.tsan import maybe_instrument as _tsan_hook


class CircuitServer:
    def __init__(self, controller: Controller, host: str = "127.0.0.1",
                 port: int = 0, profiler=None, obs=None, findings=None):
        self.controller = controller
        self.profiler = profiler
        # obs: an obs.PipelineObs bundle — /metrics serves its registry
        # (plus the legacy names) and /trace its Chrome-trace span window
        self.obs = obs
        # the request spans (``ingest``, ``step_request``, ``read``) land in
        # the pipeline's ring, or the process's when no obs is attached
        self.spans = obs.spans if obs is not None else default_recorder()
        # Static-analysis gate (dbsp_tpu/analysis): ERROR findings refuse
        # to serve; WARNs are logged/counted and exposed at /analysis.
        # Callers that already verified (the manager) pass their findings
        # so the analyzer runs — and counts metrics — exactly once.
        if findings is None:
            circuit = getattr(controller.handle, "circuit", None)
            if circuit is not None:
                from dbsp_tpu.analysis import verify_circuit

                hh = getattr(controller.handle, "host_handle",
                             controller.handle)
                runtime = getattr(hh, "runtime", None)
                findings = verify_circuit(
                    circuit,
                    workers=getattr(runtime, "workers", 1),
                    registry=obs.registry if obs is not None else None)
        self.analysis_findings = findings or []
        # last served /profile and /lineage reports (for /debug)
        self._last_profile: Optional[dict] = None
        self._last_lineage: Optional[dict] = None
        server = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):  # quiet
                pass

            def _reply(self, code: int, body: bytes,
                       ctype="application/json", headers=None):
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                # the manager's console (another port) fetches these routes
                self.send_header("Access-Control-Allow-Origin", "*")
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def do_OPTIONS(self):  # CORS preflight for the console
                self.send_response(204)
                self.send_header("Access-Control-Allow-Origin", "*")
                self.send_header("Access-Control-Allow-Methods",
                                 "GET, POST, OPTIONS")
                self.send_header("Access-Control-Allow-Headers",
                                 "Content-Type")
                self.end_headers()

            def _json(self, obj, code=200, headers=None):
                self._reply(code, json.dumps(obj).encode(),
                            headers=headers)

            def _get_view(self, url, name, sp):
                """``GET /view/<name>`` inside its ``read`` span ``sp``:
                ``read.query`` until the response object is built,
                ``read.respond`` until it is written."""
                c = server.controller
                spans = server.spans
                with spans.span("read.query", "read"):
                    t0 = _time.perf_counter()
                    plane = c.read_plane
                    if not plane.enabled:
                        return self._json(
                            {"error": "read plane disabled "
                                      "(DBSP_TPU_READPLANE=0)"}, 503)
                    qs = parse_qs(url.query)
                    try:
                        key = tuple(int(x) for x in
                                    qs["key"][0].split(",")) \
                            if "key" in qs else None
                        lo = int(qs["lo"][0]) if "lo" in qs else None
                        hi = int(qs["hi"][0]) if "hi" in qs else None
                        limit = int(qs["limit"][0]) if "limit" in qs \
                            else None
                        obj = plane.query(name, key=key, lo=lo, hi=hi,
                                          limit=limit)
                    except KeyError:
                        return self._json(
                            {"error": f"unknown view {name!r}; have "
                                      f"{sorted(plane.views())}"}, 404)
                    except ValueError as e:
                        return self._json({"error": str(e)}, 400)
                    plane.note_read(
                        "view_point" if key is not None else
                        "view_range" if (lo is not None or hi is not None)
                        else "view_scan", t0)
                    # e2e attribution: age_s + per-stage breakdown of the
                    # served epoch's delta path, and the trace ids echoed
                    # as a response header for cross-process correlation
                    c.e2e.annotate_read(obj, t0)
                    ids = (obj.get("trace") or {}).get("ids") or ()
                sp.note(rows=len(obj.get("rows") or ()),
                        epoch=obj.get("epoch"))
                with spans.span("read.respond", "read"):
                    self._json(obj, headers={"X-Dbsp-Trace":
                                             ",".join(ids)} if ids
                               else None)

            def do_GET(self):
                url = urlparse(self.path)
                route = url.path.rstrip("/")
                c = server.controller
                if route == "/status":
                    self._json(server.status_dict())
                elif route == "/flight":
                    if server.obs is None:
                        self._json({"error": "flight recorder not "
                                             "enabled"}, 400)
                    else:
                        server.obs.watch()
                        qs = parse_qs(url.query)
                        limit = int(qs["n"][0]) if "n" in qs else None
                        self._json(server.obs.flight.to_dict(limit=limit))
                elif route == "/timeline":
                    # the unified per-tick timeline (obs/timeline.py):
                    # tick latency + flight events + freshness + incidents
                    # in one time-indexed ring. Quiesce-free: one watch()
                    # pass folds fresh flight events in, then the read is
                    # a ring snapshot under the timeline's own lock — the
                    # step lock is never taken on this path.
                    if server.obs is None:
                        self._json({"error": "timeline not enabled"}, 400)
                    else:
                        server.obs.watch()
                        qs = parse_qs(url.query)
                        since = int(qs["since"][0]) if "since" in qs else 0
                        view = qs["view"][0] if "view" in qs else None
                        limit = int(qs["n"][0]) if "n" in qs else None
                        self._json(server.obs.timeline.to_dict(
                            since=since, view=view, limit=limit))
                elif route == "/spikes":
                    # EXPLAIN SPIKE: outlier ticks vs the robust rolling
                    # baseline, each with ranked co-timed evidence. Same
                    # quiesce-free read discipline as /timeline.
                    if server.obs is None:
                        self._json({"error": "timeline not enabled"}, 400)
                    else:
                        server.obs.watch()
                        qs = parse_qs(url.query)
                        limit = int(qs["n"][0]) if "n" in qs else None
                        self._json(server.obs.timeline.explain_spikes(
                            limit=limit))
                elif route == "/incidents":
                    if server.obs is None:
                        self._json({"error": "SLO watchdog not enabled"},
                                   400)
                    else:
                        server.obs.watch()
                        qs = parse_qs(url.query)
                        full = qs.get("window", ["1"])[0] != "0"
                        self._json({
                            "status": server.obs.slo.status_dict(),
                            "incidents": server.obs.slo.incidents(
                                with_window=full)})
                elif route == "/stats":
                    self._json(c.stats())
                elif route == "/metrics":
                    self._reply(200, server.prometheus().encode(),
                                obs_export.CONTENT_TYPE)
                elif route == "/analysis":
                    self._json([f.to_dict()
                                for f in server.analysis_findings])
                elif route == "/trace":
                    self._reply(200, server.spans.to_json().encode())
                elif route == "/dump_profile":
                    if server.profiler is None:
                        self._json({"error": "profiler not enabled"}, 400)
                    else:
                        self._reply(200, server.profiler.dump_json().encode())
                elif route == "/profile":
                    # operator-level EXPLAIN ANALYZE — the shared report
                    # schema both engines emit (obs/opprofile.py). ?ticks=N
                    # arms the compiled MEASURED mode (segmented per-node
                    # timing, bit-identity asserted, engine rewound);
                    # ?format=dot renders graphviz like the reference's
                    # dump_profile.
                    if server.profiler is None:
                        return self._json({"error": "profiler not enabled"},
                                          400)
                    from dbsp_tpu.obs.opprofile import (ProfileDivergence,
                                                        report_dot)

                    qs = parse_qs(url.query)
                    ticks = int(qs["ticks"][0]) if "ticks" in qs else None
                    try:
                        report = server.profile_report(ticks=ticks)
                    except ProfileDivergence as e:
                        # segmented != fused is a real engine bug — a 500,
                        # never silently degraded
                        return self._json(
                            {"error": f"ProfileDivergence: {e}"}, 500)
                    except Exception as e:  # noqa: BLE001 — API error
                        return self._json(
                            {"error": f"{type(e).__name__}: {e}"}, 400)
                    if qs.get("format", ["json"])[0] == "dot":
                        self._reply(200, report_dot(report).encode(),
                                    "text/vnd.graphviz")
                    else:
                        self._json(report)
                elif route == "/lineage":
                    # row-level lineage (EXPLAIN WHY, obs/lineage.py):
                    # backward provenance slice of one output row —
                    # ?view=<output>&key=<csv> [&n=<rows/hop>]
                    # [&format=dot]; read-only, quiesced under the
                    # controller step lock.
                    from dbsp_tpu.obs import lineage as _lineage

                    code, payload, dot = _lineage.http_query(
                        server.lineage_report, parse_qs(url.query))
                    if dot:
                        self._reply(code, payload.encode(),
                                    "text/vnd.graphviz")
                    else:
                        self._json(payload, code)
                elif route == "/debug":
                    # the one-shot diagnostics bundle — "attach this to
                    # the bug report": status + SLO + incidents + flight
                    # summary + last profile/lineage + analysis findings,
                    # composed purely from the existing surfaces
                    self._json(server.debug_bundle())
                elif route.startswith("/view/"):
                    # point/range/scan read against the PUBLISHED snapshot
                    # (dbsp_tpu/serving.py): ?key=k1[,k2..] | ?lo=&hi= |
                    # no params = full scan; &limit=N caps rows. Lock-free
                    # like /timeline: resolves the current epoch's
                    # immutable snapshot with one atomic load — the step
                    # lock and quiesce() are NEVER taken on this path
                    # (C003). Staleness <= one validation interval. 503
                    # when the plane is off (DBSP_TPU_READPLANE=0).
                    name = route.rsplit("/", 1)[1]
                    with server.spans.span("read", "read",
                                           args={"view": name}) as sp:
                        self._get_view(url, name, sp)
                elif route == "/changefeed":
                    # changefeed read with a resume-from-epoch cursor:
                    # ?view=<name>&after=<epoch>[&timeout=<s>][&limit=N].
                    # Long-poll waits on the plane's wakeup condition —
                    # never the step lock (C003); a cursor behind the
                    # ring's retention gets a synthesized full-state
                    # snapshot record first.
                    t0 = _time.perf_counter()
                    plane = c.read_plane
                    if not plane.enabled:
                        return self._json(
                            {"error": "read plane disabled "
                                      "(DBSP_TPU_READPLANE=0)"}, 503)
                    qs = parse_qs(url.query)
                    if "view" not in qs:
                        return self._json({"error": "?view= required"}, 400)
                    name = qs["view"][0]
                    try:
                        obj = plane.changefeed(
                            name,
                            after_epoch=int(qs.get("after", ["0"])[0]),
                            timeout_s=float(qs.get("timeout", ["0"])[0]),
                            limit=int(qs["limit"][0]) if "limit" in qs
                            else None)
                    except KeyError:
                        return self._json(
                            {"error": f"unknown view {name!r}; have "
                                      f"{sorted(plane.views())}"}, 404)
                    except ValueError as e:
                        return self._json({"error": str(e)}, 400)
                    plane.note_read("changefeed", t0)
                    self._json(obj)
                elif route.startswith("/output_endpoint/"):
                    # Non-destructive sample of the latest emitted batch.
                    # Read plane ON (default): served from the last
                    # PUBLISHED snapshot — one atomic reference load, no
                    # step lock, no quiesce; the served batch is the very
                    # object the controller emitted at the last validation
                    # publish (bit-identical to a quiesced peek) and is at
                    # most ONE VALIDATION INTERVAL stale (host engine: one
                    # step). Read plane OFF (DBSP_TPU_READPLANE=0, the A/B
                    # control): the historical quiesced read — step lock
                    # held, open interval flushed, then peek.
                    # The X-Dbsp-Step tick id lets pollers dedup repeats
                    # (the same batch is re-served until the next publish).
                    t0 = _time.perf_counter()
                    name = route.rsplit("/", 1)[1]
                    try:
                        col = c.catalog.output(name)
                    except KeyError as e:
                        return self._json({"error": str(e)}, 404)
                    fmt = parse_qs(url.query).get("format", ["json"])[0]
                    plane = c.read_plane
                    epoch = None
                    if plane.enabled:
                        snap = plane.snapshot(name)
                        step, batch = str(snap.last_step), snap.last_batch
                        epoch = str(snap.epoch)
                    else:
                        with c.quiesce():
                            step = str(col.handle.step_id)
                            batch = col.handle.peek()
                    if batch is None:
                        self.send_response(200)
                        self.send_header("X-Dbsp-Step", step)
                        if epoch is not None:
                            self.send_header("X-Dbsp-Epoch", epoch)
                        self.send_header("Access-Control-Allow-Origin", "*")
                        self.send_header("Content-Length", "0")
                        self.end_headers()
                    else:
                        body = OUTPUT_FORMATS[fmt]().encode(batch)
                        self.send_response(200)
                        self.send_header("X-Dbsp-Step", step)
                        if epoch is not None:
                            self.send_header("X-Dbsp-Epoch", epoch)
                        self.send_header("Access-Control-Allow-Origin", "*")
                        self.send_header("Content-Type", "text/plain")
                        self.send_header("Content-Length", str(len(body)))
                        self.end_headers()
                        self.wfile.write(body)
                    plane.note_read("output", t0)
                else:
                    self._json({"error": f"no route {route}"}, 404)

            def _post_input(self, url, name, sp):
                """``POST /input_endpoint/<name>`` inside its ``ingest``
                span ``sp``."""
                c = server.controller
                spans = server.spans
                with spans.span("ingest.read_body", "ingest"):
                    n = int(self.headers.get("Content-Length", 0))
                    body = self.rfile.read(n)
                fmt = parse_qs(url.query).get("format", ["json"])[0]
                try:
                    col = c.catalog.input(name)
                except KeyError as e:
                    return self._json({"error": str(e)}, 404)
                with spans.span("ingest.parse", "ingest") as parse_sp:
                    parser = INPUT_FORMATS[fmt](col.dtypes)
                    try:
                        parser.feed(body)
                        parser.eoi()
                        # the whole POST as one block of columns, held to
                        # their domain here: a bad line or value anywhere
                        # answers 400 with nothing buffered
                        rows = parser.take_columns()
                    except (ValueError, KeyError) as e:
                        return self._json(
                            {"error": f"parse error: {e}"}, 400)
                    parse_sp.note(columnar=parser.columnar,
                                  fallback=parser.fallback)
                with spans.span("ingest.push_rows", "ingest"):
                    col.push_rows(rows)
                    # HTTP pushes must wake the circuit loop like transport
                    # rows do — found by the console JS-path test: pushed
                    # rows sat unstepped until an explicit /step.
                    # An X-Dbsp-Trace request header is adopted as the
                    # batch's e2e trace id (cross-process propagation);
                    # otherwise one is minted — either way it is echoed.
                    trace_id = c.note_pushed(
                        len(rows),
                        trace_id=self.headers.get("X-Dbsp-Trace") or None,
                        columnar=parser.columnar)
                sp.note(records=len(rows), bytes=n, trace=trace_id)
                resp = {"records": len(rows)}
                if trace_id is not None:
                    resp["trace"] = trace_id
                self._json(resp, headers={"X-Dbsp-Trace": trace_id}
                           if trace_id else None)

            def do_POST(self):
                url = urlparse(self.path)
                route = url.path.rstrip("/")
                c = server.controller
                if route == "/start":
                    c.start()
                    self._json({"state": c.state})
                elif route == "/pause":
                    c.pause()
                    self._json({"state": c.state})
                elif route == "/shutdown":
                    threading.Thread(target=c.stop, daemon=True).start()
                    self._json({"state": "shutdown"})
                elif route == "/step":
                    with server.spans.span("step_request", "step"):
                        c.step()
                        self._json({"steps": c.steps})
                elif route == "/checkpoint":
                    # write one durable checkpoint generation now
                    # (quiesced under the step lock); 400 when no
                    # directory is configured
                    try:
                        info = c.checkpoint()
                    except Exception as e:  # noqa: BLE001 — API error
                        return self._json(
                            {"error": f"{type(e).__name__}: {e}"}, 400)
                    self._json(info)
                elif route.startswith("/input_endpoint/"):
                    name = route.rsplit("/", 1)[1]
                    with server.spans.span("ingest", "ingest",
                                           args={"table": name}) as sp:
                        self._post_input(url, name, sp)
                else:
                    self._json({"error": f"no route {route}"}, 404)

        self.httpd = ThreadingHTTPServer((host, port), Handler)
        self.port = self.httpd.server_address[1]
        self._thread: Optional[threading.Thread] = None
        _tsan_hook(self)

    def status_dict(self) -> dict:
        """The /status body: serving state + mode + SLO health in one
        poll (the compiled->host fallback cliff must be visible here,
        not only in a counter); /debug embeds the same dict."""
        c = self.controller
        out = {"state": c.state,
               "mode": getattr(c.handle, "mode", "host"),
               # durability: the tick recovery would resume from
               # (None = no checkpoint yet/configured)
               "last_checkpoint_tick": getattr(
                   c, "last_checkpoint_tick", None),
               "checkpoints": getattr(c, "checkpoints", 0),
               # freshness: seconds the open deferred-validation interval
               # has been accumulating unpublished ticks (None = closed /
               # host engine, which publishes every step)
               "open_interval_age_s": getattr(
                   c.handle, "open_interval_age_s", None),
               # rows buffered per input endpoint awaiting the next drain
               # (endpoint locks only — never the step lock)
               "input_queue_depths": c.input_queue_depths()}
        ck_err = getattr(c, "checkpoint_error", None)
        if ck_err:
            out["checkpoint_error"] = ck_err
        # tiered trace residency (dbsp_tpu/residency.py): omitted when no
        # budget is configured and nothing ever demoted
        from dbsp_tpu import residency as _res

        rs = _res.summary(c.handle)
        if rs is not None:
            out["residency"] = rs
        if self.obs is not None:
            self.obs.watch()
            out["slo"] = self.obs.slo.status_dict()
            # the watchdog's latched copy, NOT a ring scan: the one-shot
            # deploy-time event ages out of the ring on a long-running
            # pipeline
            fb = self.obs.slo.fallback_reason
            if fb is not None:
                out["fallback_reason"] = fb
        return out

    def lineage_report(self, view: str, key, max_rows=None) -> dict:
        """The ``/lineage`` backward provenance slice, quiesced: holds
        the controller's step lock (no serving tick in flight — the
        compiled provider decodes a snapshot of the live states) and
        flushes any open deferred-validation interval first. Counts the
        gated lineage metrics and records one flight event per query;
        never mutates serving state."""
        from dbsp_tpu.obs import lineage

        kwargs = {} if max_rows is None else {"max_rows": max_rows}
        with self.controller.quiesce():
            report = lineage.slice_pipeline(
                self.controller.handle, self.controller.catalog, view, key,
                **kwargs)
        if self.obs is not None:
            lineage.observe_query(self.obs.registry, self.obs.flight,
                                  report)
        self._last_lineage = report
        return report

    def debug_bundle(self) -> dict:
        """One JSON for the bug report: status, stats, SLO health, the
        captured incidents (summaries), a flight-ring summary, the last
        profile/lineage reports served (None until one ran — composing
        a measured profile here would quiesce the pipeline unasked), and
        the static-analysis findings."""
        c = self.controller
        out = {"status": self.status_dict(),
               "stats": c.stats(),
               "analysis": [f.to_dict() for f in self.analysis_findings],
               "profile": getattr(self, "_last_profile", None),
               "lineage": getattr(self, "_last_lineage", None)}
        if self.obs is not None:
            # status_dict() already ran the watchdog and embedded the SLO
            # dict — alias it rather than polling + serializing it twice
            out["slo"] = out["status"].get("slo")
            out["incidents"] = self.obs.slo.incidents(with_window=False)
            out["flight"] = self.obs.flight.to_dict(limit=64)
            # span-ring drop accounting: a truncated /trace window must
            # announce itself in the bug-report bundle
            dropped = self.spans.dropped_steps
            out["trace"] = {"dropped_steps": dropped,
                            "truncated": dropped > 0}
        return out

    def profile_report(self, ticks=None) -> dict:
        """The unified ``/profile`` report, quiesced: holds the
        controller's step lock (no serving tick in flight — the measured
        mode snapshots, runs hypothetical ticks, and rewinds) and flushes
        any open deferred-validation interval first. Spans land operator
        slices in the existing ``/trace`` window; the registry receives
        the gated per-node metric families only when a MEASURED profile
        actually runs (opprofile.export_node_metrics)."""
        with self.controller.quiesce():
            report = self.profiler.profile_report(
                ticks=ticks,
                spans=self.spans,
                registry=self.obs.registry if self.obs is not None else None)
        self._last_profile = report  # /debug embeds the last served report
        return report

    def prometheus(self) -> str:
        """The /metrics payload: the obs registry's canonical exposition
        (when a PipelineObs is attached) followed by the legacy
        ``dbsp_steps``-era names — scrapers written against either surface
        keep working. All formatting lives in obs/export.py."""
        legacy = obs_export.legacy_controller_lines(self.controller.stats())
        body = "\n".join(legacy) + "\n"
        if self.obs is not None:
            body = obs_export.prometheus_text(self.obs.registry) + body
        return body

    def start(self) -> None:
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        daemon=True, name="circuit-http")
        self._thread.start()

    def stop(self) -> None:
        self.httpd.shutdown()
