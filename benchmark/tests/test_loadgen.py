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


# -- the sample behind each tail ----------------------------------------------


def _recorded_run(ticks: int, tick_s: float, push_s: float,
                  read_every_s: float, read_ms: float) -> dict:
    """A run record written by hand: ``ticks`` window ticks (set-up tick 0
    before them), each a push of ``push_s`` then a step of ``tick_s``; a
    read due every ``read_every_s`` that takes ``read_ms``, and waits out
    the push where it meets one."""
    run = {"open": 100.0, "push": {}, "step_sent": {}, "step_done": {},
           "visible": {}, "reads": [], "ops": []}
    t = run["open"]
    for k in range(1, ticks + 1):
        run["push"][str(k)] = (t, t + push_s)
        run["step_sent"][str(k)] = t + push_s
        t += push_s + tick_s
        run["step_done"][str(k)] = t
        run["visible"][str(k + 1)] = t + 0.01
    run["close"] = t
    i = 0
    while run["open"] + i * read_every_s <= run["close"]:
        due = run["open"] + i * read_every_s
        got = due + read_ms / 1e3
        for a, b in run["push"].values():
            if a <= due < b:
                got = b + read_ms / 1e3
        run["reads"].append((due, due, got, True))
        i += 1
    return run


def test_sample_counts_of_a_recorded_run():
    """What the ``summary`` fact line states beside each tail: 40 ticks of
    0.63 s with a 0.055 s push, a read every 100 ms."""
    run = _recorded_run(40, 0.63, 0.055, 0.1, 2.5)
    assert len(measures.window_ticks(run)) == 40
    assert measures.beyond(40, 95) == 2            # the p95 tick is the 38th
    reads = measures.read_latencies_ms(run)
    assert len(reads) == 275                       # 27.4 s at 10 a second
    assert measures.beyond(len(reads), 95) == 13   # the 262nd of 275
    assert measures.beyond(120, 95) == 6           # the old window's p95
    assert measures.beyond(0, 95) == 0 and measures.beyond(1, 95) == 0
    met = measures.reads_meeting_push(run)
    assert 20 <= met <= 40                         # 40 pushes of 0.055 s
    # a read that met a push waited it out: the tail holds them, the median
    # and the p90 do not
    t = measures.tails(reads)
    assert t["p50"] == pytest.approx(2.5) and t["p90"] == pytest.approx(2.5)
    assert t["max"] > 50.0 and t["p95"] > 2.5
    assert sum(1 for r in reads if r > 2.6) <= met
    assert measures.tails([]) is None


def test_committed_mix_supports_a_p95():
    """The committed ``saturated`` mix holds at least 32 window ticks at a
    reader interval of at most 100 ms: at the ledger's 0.63 s tick (PR 31,
    q4) 200 reads or more, ten or more beyond a p95. An edit of the mix
    that starves the tail again fails here."""
    with open(os.path.join(BENCH, "traffic", "saturated.json")) as f:
        mix = json.load(f)
    assert mix["max_window_ticks"] >= 32
    assert mix["reader_interval_ms"] <= 100
    reads = int(mix["max_window_ticks"] * 0.63
                / (mix["reader_interval_ms"] / 1e3))
    assert reads >= 200 and measures.beyond(reads, 95) >= 10
