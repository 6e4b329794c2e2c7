"""The load generator against a stub server: what it does at the close.

A tick whose publish lags behind its ``/step`` response is late, not lost:
the watcher waits for it past the close and its age counts the wait. One
that never shows is ``ticks_never_visible``. The loop itself reads nothing
but the responses to its own requests (no ``/status``).

    python -m pytest benchmark/tests/test_loadgen.py -q
"""

import json
import os
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import loadgen  # noqa: E402
import measures  # noqa: E402

TRAFFIC = {"setup_ticks": 1, "max_window_ticks": 3, "reader_interval_ms": 20,
           "reader_limit": 10, "read_timeout_s": 5.0,
           "changefeed_timeout_s": 0.2, "trace_ticks": 1}


class Stub:
    """Acknowledges pushes, counts steps, and publishes step s only
    ``lag_s`` after ``/step`` s has answered (never, if ``lag_s`` is None
    and s is the last)."""

    def __init__(self, lag_s, last_step):
        self.lag_s, self.last_step = lag_s, last_step
        self.stepped = self.published = 0
        self.paths: list = []
        stub = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _reply(self, obj):
                body = json.dumps(obj).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                url = urlparse(self.path)
                stub.paths.append(url.path)
                data = self.rfile.read(int(self.headers["Content-Length"]))
                if url.path == "/step":
                    stub.stepped += 1
                    s = stub.stepped
                    if s < stub.last_step or stub.lag_s is not None:
                        threading.Timer(
                            stub.lag_s or 0.0, stub.publish, (s,)).start()
                    self._reply({"step": s})
                else:
                    self._reply({"records": data.count(b"\n") + 1})

            def do_GET(self):
                url = urlparse(self.path)
                stub.paths.append(url.path)
                if url.path == "/changefeed":
                    after = int(parse_qs(url.query)["after"][0])
                    time.sleep(0.02)
                    p = stub.published
                    self._reply({"epoch": p, "records": [
                        {"step": s, "epoch": s}
                        for s in range(after + 1, p + 1)]})
                else:
                    self._reply({"step": stub.published, "rows": []})

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(target=self.httpd.serve_forever, daemon=True).start()
        self.base = f"http://127.0.0.1:{self.httpd.server_port}"

    def publish(self, s):
        self.published = max(self.published, s)

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def drive(stub):
    with open(os.path.join(BENCH, "configs", "nexmark-q3.json")) as f:
        config = json.load(f)
    config["events_per_tick"] = 100
    bodies = loadgen.make_bodies(config, 7, 4)
    client = loadgen.Client(stub.base)
    records, ok = loadgen.push_batch(client, config, bodies[0])
    assert ok and records == 100
    assert client.call("step", "/step", b"") is not None
    n0 = len(client.ops)
    run = loadgen.Mix(client, config, TRAFFIC, bodies, 60.0,
                      lambda ev: None).run()
    run["ops"] = client.ops[n0:]
    return json.loads(json.dumps(run))   # as the harness receives it


def test_late_publish_is_late_not_lost():
    stub = Stub(lag_s=0.4, last_step=4)
    try:
        run = drive(stub)
    finally:
        stub.close()
    assert measures.window_ticks(run) == [1, 2, 3]   # closed by count
    assert all(str(k + 1) in run["visible"] for k in (1, 2, 3))
    ages = measures.delta_ages(run)
    assert len(ages) == 3 and min(ages) >= 0.4        # the wait is counted
    # the last tick showed only after the close
    assert run["visible"]["4"] > run["close"]
    assert measures.window_ops(run)[1] == 0
    assert "/status" not in stub.paths


def test_tick_that_never_shows_is_counted(monkeypatch):
    monkeypatch.setattr(loadgen, "VISIBLE_WAIT_S", 0.5)
    stub = Stub(lag_s=None, last_step=4)
    try:
        run = drive(stub)
    finally:
        stub.close()
    assert measures.window_ticks(run) == [1, 2, 3]
    assert "4" not in run["visible"] and "3" in run["visible"]
    assert measures.delta_ages(run) is None
    assert measures.window_ops(run)[1] == 1


def test_one_batch_in_flight():
    """Batch k is pushed only after /step k-1 has answered."""
    stub = Stub(lag_s=0.0, last_step=4)
    try:
        run = drive(stub)
    finally:
        stub.close()
    for k in (2, 3):
        assert run["push"][str(k)][0] >= run["step_done"][str(k - 1)]
        assert run["step_sent"][str(k)] >= run["push"][str(k)][1]
    posts = [p for p in stub.paths
             if p == "/step" or p.startswith("/input_endpoint/")]
    assert posts == (["/input_endpoint/persons", "/input_endpoint/auctions",
                      "/input_endpoint/bids", "/step"] * 4)
