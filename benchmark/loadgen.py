#!/usr/bin/env python3
"""The load generator: pusher, stepper, reader and visibility watcher.

A process of its own that imports neither JAX nor ``dbsp_tpu`` and talks
HTTP only. It makes every tick's events from ``--seed`` with the benchmark's
generator, serialises them to NDJSON once, and then obeys one-line JSON
commands on stdin (the harness, ``run.py``, writes them) and answers with
one-line JSON events on stdout:

    {"cmd": "connect", "base": "http://127.0.0.1:PORT"}
    {"cmd": "tick", "k": 0}            a set-up tick: push batch k, POST /step
    {"cmd": "run", "seconds": 44}      the window's traffic mix (see below)
    {"cmd": "view"}                    GET /view/<view>, every row
    {"cmd": "exit"}

``run`` drives ticks ``setup_ticks``.. in a closed loop with one batch in
flight: batch k is pushed (one POST per relation) once ``/step`` k-1 has
returned, and ``/step`` k goes as soon as batch k is wholly acknowledged.
The loop acts only on what a client of the server sees: the responses to
its own requests. The window opens when ``run`` begins, the set-up ticks
done, and closes at the first ``/step`` return at or after ``seconds``, or
after ``max_window_ticks``. Beside the loop one reader GETs
``/view`` on a fixed schedule and one watcher long-polls ``/changefeed``;
after the close the watcher waits, up to ``VISIBLE_WAIT_S``, until the last
tick stepped has been shown to it. All times are ``time.monotonic()``
seconds, which the harness's process shares.

One general generator: a traffic file's keys are its parameters
(``traffic/*.json``; ``PERF.md`` lists them).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import generator  # noqa: E402 — the benchmark's own copy

SETUP_HTTP_TIMEOUT_S = 1150.0  # a set-up /step can hold a whole cold compile
VISIBLE_WAIT_S = 60.0  # how long after the close a tick may take to show


def ndjson(cols) -> bytes:
    """Columns -> the ingest route's NDJSON insert envelopes."""
    return "\n".join(
        json.dumps({"insert": row})
        for row in zip(*(c.tolist() for c in cols))).encode()


def make_bodies(config: dict, seed: int, ticks: int) -> list:
    """Per tick ``{relation: NDJSON bytes}`` for events
    ``[k * events_per_tick, (k + 1) * events_per_tick)``."""
    gen = generator.from_config(config, seed)
    n = config["events_per_tick"]
    bodies = []
    for k in range(ticks):
        cols = gen.generate(k * n, (k + 1) * n)
        bodies.append({rel: ndjson([cols[rel][c] for c in names])
                       for rel, names in generator.COLUMNS.items()})
    return bodies


def push_batch(client, config: dict, body: dict) -> tuple:
    """POST one tick's relations, one after the other; returns
    ``(records acknowledged, every POST succeeded)``."""
    records, ok = 0, True
    for rel in generator.COLUMNS:
        r = client.call("push", f"/input_endpoint/{rel}?format="
                        f"{config['ingest_format']}", body[rel])
        ok = ok and r is not None
        records += (r or {}).get("records", 0)
    return records, ok


class Client:
    """HTTP calls with a record of each: ``ops`` rows are
    ``(kind, start, end, ok)``; a call that raises or times out is a failed
    operation and returns None."""

    def __init__(self, base: str):
        self.base = base
        self.ops: list = []
        self._lock = threading.Lock()

    def call(self, kind: str, path: str, data: bytes | None = None,
             timeout: float = SETUP_HTTP_TIMEOUT_S):
        req = urllib.request.Request(
            self.base + path, data=data,
            method="GET" if data is None else "POST")
        t0 = time.monotonic()
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                out = json.loads(r.read())
            ok = True
        except (urllib.error.URLError, OSError, ValueError) as e:
            out, ok = None, False
            print(f"loadgen: {kind} {path.split('?')[0]} failed: {e!r}",
                  file=sys.stderr, flush=True)
        t1 = time.monotonic()
        with self._lock:
            self.ops.append((kind, t0, t1, ok))
        return out


class Mix:
    """One ``run``: the traffic mix over the window's ticks."""

    def __init__(self, client: Client, config: dict, traffic: dict,
                 bodies: list, seconds: float, emit):
        self.c, self.config, self.t = client, config, traffic
        self.bodies, self.seconds, self.emit = bodies, seconds, emit
        self.view = config["view"]
        self.lock = threading.Lock()  # guards ``visible``
        self.acked: dict = {}        # k -> records acknowledged, when whole
        self.push: dict = {}         # k -> (begin, end)
        self.step_sent: dict = {}    # k -> time
        self.step_done: dict = {}    # k -> time
        self.visible: dict = {}      # step number -> first receipt time
        self.open_t: float | None = None
        self.close_t: float | None = None
        self.done = False            # the loop has ended
        self.reads: list = []        # (due, sent, received, ok)
        self.last_tick: int | None = None
        self.epoch0 = 0

    # -- the closed loop ------------------------------------------------------
    def _loop(self) -> None:
        """Push batch k, POST /step, and again: each request goes when the
        response to the one before it has come. A lost push or step ends
        the run with the window unclosed."""
        setup = self.t["setup_ticks"]
        for k in range(setup, setup + self.t["max_window_ticks"]):
            t0 = time.monotonic()
            records, ok = push_batch(self.c, self.config, self.bodies[k])
            self.push[k] = (t0, time.monotonic())
            if not ok:
                break
            self.acked[k] = records
            self.step_sent[k] = time.monotonic()
            r = self.c.call("step", "/step", b"")
            now = time.monotonic()
            self.step_done[k] = now
            self.last_tick = k
            self.emit({"ev": "step", "k": k, "sent": self.step_sent[k],
                       "done": now})
            if r is None:
                break
            if (now - self.open_t >= self.seconds
                    or k + 1 == setup + self.t["max_window_ticks"]):
                self.close_t = now
                break
        self.done = True

    # -- watcher and reader ---------------------------------------------------
    def _note_visible(self, step: int, t: float) -> None:
        with self.lock:
            for s in range(step, 0, -1):
                if s in self.visible:
                    break
                self.visible[s] = t

    def _all_shown(self) -> bool:
        """Every tick stepped has been shown to the watcher or the reader."""
        with self.lock:
            return self.last_tick is None or (
                self.last_tick + 1) in self.visible

    def _watcher(self) -> None:
        after = self.epoch0
        timeout = self.t["changefeed_timeout_s"]
        give_up = None
        while True:
            if self.done:
                # past the close: an answer may come late, up to a minute
                give_up = give_up or time.monotonic() + VISIBLE_WAIT_S
                if self._all_shown() or time.monotonic() > give_up:
                    break
            r = self.c.call(
                "changefeed", f"/changefeed?view={self.view}&after={after}"
                f"&timeout={timeout}", timeout=timeout + 10.0)
            t = time.monotonic()
            if r is None:
                time.sleep(0.1)
            else:
                for rec in r["records"]:
                    self._note_visible(rec["step"], t)
                    after = max(after, rec["epoch"])
            if self.done and not self._all_shown():
                # a tick whose delta is empty writes no record: ask the view
                v = self.c.call("read", f"/view/{self.view}?limit=1",
                                timeout=self.t["read_timeout_s"])
                if v is not None:
                    self._note_visible(v["step"], time.monotonic())

    def _reader(self) -> None:
        period = self.t["reader_interval_ms"] / 1000.0
        path = f"/view/{self.view}?limit={self.t['reader_limit']}"
        t0 = time.monotonic()
        i = 0
        while not self.done:
            due = t0 + i * period
            now = time.monotonic()
            if now < due:
                time.sleep(due - now)
            sent = time.monotonic()
            r = self.c.call("read", path, timeout=self.t["read_timeout_s"])
            got = time.monotonic()
            if r is not None:
                self._note_visible(r["step"], got)
            self.reads.append((due, sent, got, r is not None))
            # a read that overran its slot skips the slots it covered
            i = max(i + 1, int((time.monotonic() - t0) / period))

    def run(self) -> dict:
        st = self.c.call("read", f"/changefeed?view={self.view}&after=0")
        self.epoch0 = st["epoch"] if st else 0
        self.open_t = time.monotonic()
        threads = [threading.Thread(target=f, name=f.__name__, daemon=True)
                   for f in (self._loop, self._watcher, self._reader)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        return {
            "ev": "run", "open": self.open_t,
            "close": self.close_t, "last_tick": self.last_tick,
            "push": {str(k): v for k, v in self.push.items()},
            "acked": {str(k): v for k, v in self.acked.items()},
            "step_sent": {str(k): v for k, v in self.step_sent.items()},
            "step_done": {str(k): v for k, v in self.step_done.items()},
            "visible": {str(k): v for k, v in self.visible.items()},
            "reads": self.reads,
        }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--events-per-tick", type=int, default=None,
                    help="rehearsal only: a tiny tick")
    args = ap.parse_args(argv)
    with open(args.config) as f:
        config = json.load(f)
    with open(args.traffic) as f:
        traffic = json.load(f)
    if args.events_per_tick:
        config["events_per_tick"] = args.events_per_tick

    out_lock = threading.Lock()

    def emit(obj: dict) -> None:
        with out_lock:
            sys.stdout.write(json.dumps(obj) + "\n")
            sys.stdout.flush()

    t0 = time.monotonic()
    ticks = traffic["setup_ticks"] + traffic["max_window_ticks"]
    bodies = make_bodies(config, args.seed, ticks)
    emit({"ev": "generated", "ticks": ticks,
          "seconds": time.monotonic() - t0,
          "bytes": sum(len(b) for t in bodies for b in t.values())})

    client = None
    acked_total = 0
    for line in sys.stdin:
        cmd = json.loads(line)
        if cmd["cmd"] == "connect":
            client = Client(cmd["base"])
            emit({"ev": "connected"})
        elif cmd["cmd"] == "tick":
            k = cmd["k"]
            t1 = time.monotonic()
            records, ok = push_batch(client, config, bodies[k])
            t2 = time.monotonic()
            ok = ok and client.call("step", "/step", b"") is not None
            acked_total += records
            emit({"ev": "tick", "k": k, "ok": ok, "records": records,
                  "push_s": t2 - t1, "step_s": time.monotonic() - t2})
        elif cmd["cmd"] == "run":
            n0 = len(client.ops)
            res = Mix(client, config, traffic, bodies, cmd["seconds"],
                      emit).run()
            acked_total += sum(res["acked"].values())
            res["ops"] = client.ops[n0:]
            res["acked_total"] = acked_total
            emit(res)
        elif cmd["cmd"] == "view":
            r = client.call("read", f"/view/{config['view']}",
                            timeout=60.0)
            emit({"ev": "view", "ok": r is not None,
                  "step": (r or {}).get("step"),
                  "rows": (r or {}).get("rows")})
        elif cmd["cmd"] == "exit":
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
