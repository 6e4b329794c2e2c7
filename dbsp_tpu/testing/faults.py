"""Deterministic fault-injection harness for durability testing.

Four fault families, each seeded/deterministic so a failing run replays
exactly (the harness is the proof side of README §Durability):

* **Kill-at-tick** — a pipeline runs as a SUBPROCESS
  (``python -m dbsp_tpu.testing.faults --serve <config.json>``) writing a
  per-tick status file and a per-tick output-delta JSONL; the parent
  watches the status file and SIGKILLs the child the moment it passes the
  planned tick. SIGKILL (not SIGTERM) means no atexit/flush runs — the
  crash the checkpoint store's atomic-generation discipline must survive.
  The child re-launched with ``"resume": true`` restores the newest valid
  checkpoint generation and continues, and its subsequent delta stream
  must be bit-identical to an uninterrupted run's (tests/test_faults.py
  proves this for Nexmark q4 in host AND compiled modes).

* **Transport chaos** — :func:`transport_chaos` monkeypatches the
  minikafka client connection to fail its first N connects/reads with
  ``ConnectionError`` (deterministic counters, not probabilities),
  exercising the bounded-backoff retry path
  (``dbsp_tpu_io_transport_retries_total``) and, past the retry budget,
  the endpoint-terminates-instead-of-hanging contract.

* **Slow consumer** — :class:`StallingOutputTransport` stalls ``write``
  for a configured duration every Nth delivery (a backpressured sink);
  the controller must keep serving control/status traffic and deliver
  everything once the stall clears.

* **Checkpoint corruption** — :func:`corrupt_checkpoint` flips/truncates
  bytes in the CURRENT generation's manifest or a seeded-chosen blob;
  restore must fall back to the previous generation and surface exactly
  one SLO-visible ``restore`` incident.

* **Seeded interleavings** — :class:`InterleaveSchedule` injects sleeps
  at the yield points the concurrency sanitizer's instrumented locks
  expose (``dbsp_tpu.testing.tsan.set_schedule``), widening the thread
  schedules a hammer test explores. Deterministic per seed: the decision
  SEQUENCE (which acquire/release yields) replays exactly; what the OS
  scheduler does with each yield is the explored dimension.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import random
import signal
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

from dbsp_tpu.io.transport import OutputTransport


@dataclasses.dataclass
class FaultPlan:
    """A deterministic fault schedule for one harness run."""

    seed: int = 1
    kill_at_tick: Optional[int] = None   # SIGKILL once status passes this
    fail_connects: int = 0               # transport: first N connects fail
    fail_reads: int = 0                  # transport: first N reads fail


# ---------------------------------------------------------------------------
# Kill-at-tick: pipeline child process + parent-side controls
# ---------------------------------------------------------------------------


def child_config(mode: str, checkpoint_dir: str, status_path: str,
                 out_path: str, query: str = "q4", ticks: int = 24,
                 batch: int = 250, seed: int = 1, checkpoint_every: int = 5,
                 resume: bool = False, validate_every: int = 1) -> dict:
    """The JSON config a pipeline child runs from (see :func:`_serve`)."""
    return {"mode": mode, "query": query, "ticks": int(ticks),
            "batch": int(batch), "seed": int(seed),
            "checkpoint_dir": checkpoint_dir,
            "checkpoint_every": int(checkpoint_every),
            "status_path": status_path, "out_path": out_path,
            "resume": bool(resume), "validate_every": int(validate_every)}


def spawn_child(cfg: dict, cfg_path: str) -> "subprocess.Popen":
    """Launch one pipeline child (inherits the environment — test runs
    pass JAX_PLATFORMS=cpu and the shared compile cache through it)."""
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)
    import dbsp_tpu

    repo_root = os.path.dirname(os.path.dirname(
        os.path.abspath(dbsp_tpu.__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    # stderr goes to a FILE beside the config, never a pipe: nobody drains
    # a pipe while the child runs, and a child that logs more than the
    # pipe holds (XLA warns once per stale compile-cache entry, ~3 KB
    # each) blocks in write() forever — read it back with child_stderr()
    with open(cfg_path + ".stderr", "w") as errf:
        proc = subprocess.Popen(
            [sys.executable, "-m", "dbsp_tpu.testing.faults", "--serve",
             cfg_path],
            env=env, stdout=subprocess.DEVNULL, stderr=errf)
    proc.stderr_path = errf.name
    return proc


def child_stderr(proc: "subprocess.Popen") -> str:
    """What a :func:`spawn_child` child wrote to stderr so far."""
    with open(proc.stderr_path, errors="replace") as f:
        return f.read()


def read_status(status_path: str) -> Optional[dict]:
    try:
        with open(status_path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None  # not written yet / mid-replace


def wait_for_tick(status_path: str, tick: int, proc=None,
                  timeout_s: float = 300.0) -> dict:
    """Block until the child's status file reports ``tick`` (or beyond).
    Raises on timeout or child death."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        st = read_status(status_path)
        if st is not None and st.get("tick", -1) >= tick:
            return st
        if proc is not None and proc.poll() is not None:
            err = child_stderr(proc)
            raise RuntimeError(
                f"pipeline child exited rc={proc.returncode} before tick "
                f"{tick}: {err[-2000:]}")
        time.sleep(0.02)
    raise TimeoutError(f"child never reached tick {tick}")


def kill9(proc: "subprocess.Popen") -> None:
    """SIGKILL — the crash no handler sees (atomic checkpoint proof)."""
    proc.send_signal(signal.SIGKILL)
    proc.wait(timeout=30)


def run_child(cfg: dict, cfg_path: str, timeout_s: float = 600.0) -> dict:
    """Run one child to completion; returns its final status."""
    proc = spawn_child(cfg, cfg_path)
    try:
        rc = proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        raise
    if rc != 0:
        err = child_stderr(proc)
        raise RuntimeError(f"pipeline child failed rc={rc}: {err[-2000:]}")
    st = read_status(cfg["status_path"])
    if st is None or not st.get("done"):
        raise RuntimeError(f"child exited without finishing: {st}")
    return st


def read_deltas(out_path: str) -> Dict[int, list]:
    """tick -> sorted delta rows from a child's output JSONL. A torn final
    line (the SIGKILL case) is dropped — its tick replays after restore."""
    out: Dict[int, list] = {}
    try:
        with open(out_path) as f:
            for line in f:
                try:
                    obj = json.loads(line)
                except ValueError:
                    continue  # torn write at the kill point
                if "tick" in obj:
                    out[obj["tick"]] = obj["delta"]
    except OSError:
        pass
    return out


# ---------------------------------------------------------------------------
# Transport chaos
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def transport_chaos(fail_connects: int = 0, fail_reads: int = 0):
    """Deterministically fail the first N minikafka connects and/or
    request round-trips with ``ConnectionError`` (counted process-wide
    across connections while active). Exercises the retry/backoff path;
    with N past the retry budget, the terminal-failure path."""
    from dbsp_tpu.io import minikafka

    counters = {"connects": 0, "reads": 0}
    orig_connect = minikafka._Conn._connect
    orig_roundtrip = minikafka._Conn._roundtrip

    def chaotic_connect(self):
        counters["connects"] += 1
        if counters["connects"] <= fail_connects:
            raise ConnectionError(
                f"injected connect failure #{counters['connects']}")
        return orig_connect(self)

    def chaotic_roundtrip(self, payload):
        counters["reads"] += 1
        if counters["reads"] <= fail_reads:
            raise ConnectionError(
                f"injected read failure #{counters['reads']}")
        return orig_roundtrip(self, payload)

    minikafka._Conn._connect = chaotic_connect
    minikafka._Conn._roundtrip = chaotic_roundtrip
    try:
        yield counters
    finally:
        minikafka._Conn._connect = orig_connect
        minikafka._Conn._roundtrip = orig_roundtrip


class StallingOutputTransport(OutputTransport):
    """Output sink that stalls every ``every``-th write for ``stall_s`` —
    the slow-consumer fault. Collects everything it was given so tests
    can assert nothing was lost once the stalls cleared."""

    name = "stalling_output"

    def __init__(self, stall_s: float = 0.2, every: int = 2):
        self.stall_s = float(stall_s)
        self.every = max(1, int(every))
        self.writes = 0
        self.stalls = 0
        self.chunks: List[bytes] = []

    def write(self, data: bytes) -> None:
        self.writes += 1
        if self.writes % self.every == 0:
            self.stalls += 1
            time.sleep(self.stall_s)
        self.chunks.append(data)


# ---------------------------------------------------------------------------
# Seeded interleaving schedules (yield-point injection at traced locks)
# ---------------------------------------------------------------------------


class InterleaveSchedule:
    """Seeded yield-point injector for the runtime concurrency sanitizer.

    Installed via ``tsan.set_schedule`` (or ``tsan.session(schedule=...)``)
    it is called at every instrumented lock acquire/release with the
    event kind and the lock's ``Class.attr`` name. With probability
    ``rate`` (decided by a seeded RNG, so the decision sequence is
    deterministic) it sleeps ``sleep_s`` — long enough that any runnable
    peer thread gets scheduled into the window the yield opens. This is
    the deliberate-interleaving half of ThreadSanitizer's recipe: races
    that need a narrow preemption window (check-then-act on a shared
    field, a reader between a clear and a refill) reproduce under the
    widened schedule instead of once a quarter in production.

    ``only`` restricts injection to lock names containing any of the
    given substrings (e.g. ``("Controller.",)``); ``max_yields`` bounds
    total injected sleeps so a hammer test's duration stays bounded.
    """

    def __init__(self, seed: int = 1, rate: float = 0.25,
                 sleep_s: float = 0.002, max_yields: int = 2000,
                 only: Optional[tuple] = None):
        self.rng = random.Random(seed)
        self.rate = float(rate)
        self.sleep_s = float(sleep_s)
        self.max_yields = int(max_yields)
        self.only = tuple(only) if only else None
        self.yields = 0
        self.decisions = 0
        self._lock = threading.Lock()

    def yield_point(self, hook: str, lock_name: str) -> None:
        if self.only is not None and \
                not any(s in lock_name for s in self.only):
            return
        with self._lock:
            self.decisions += 1
            if self.yields >= self.max_yields:
                return
            fire = self.rng.random() < self.rate
            if fire:
                self.yields += 1
        if fire:
            time.sleep(self.sleep_s)


# ---------------------------------------------------------------------------
# Checkpoint corruption
# ---------------------------------------------------------------------------


def corrupt_checkpoint(path: str, kind: str = "blob", seed: int = 0) -> str:
    """Deterministically corrupt the CURRENT generation: ``"manifest"``
    scribbles over manifest.json, ``"blob"`` flips a byte mid-file in a
    seeded-chosen array blob, ``"truncate"`` cuts one in half. Returns the
    corrupted file's path."""
    with open(os.path.join(path, "CURRENT")) as f:
        gen = f.read().strip()
    gen_dir = os.path.join(path, gen)
    if kind == "manifest":
        target = os.path.join(gen_dir, "manifest.json")
        with open(target, "r+b") as f:
            f.seek(max(0, os.path.getsize(target) // 2))
            f.write(b"\x00CORRUPT\x00")
        return target
    blobs = sorted(n for n in os.listdir(gen_dir) if n.endswith(".npy"))
    if not blobs:
        raise ValueError(f"no blobs to corrupt in {gen_dir}")
    # prefer blobs EXCLUSIVE to this generation (nlink == 1): the fault
    # being modeled is a torn/corrupted fresh write — clean deep levels
    # are hard-linked across generations (one inode), so scribbling on
    # one would corrupt every generation sharing it, which is media
    # bitrot, not a crash mode the generation store claims to survive
    exclusive = [n for n in blobs
                 if os.stat(os.path.join(gen_dir, n)).st_nlink == 1]
    target = os.path.join(
        gen_dir, random.Random(seed).choice(exclusive or blobs))
    size = os.path.getsize(target)
    if kind == "truncate":
        with open(target, "r+b") as f:
            f.truncate(max(1, size // 2))
    elif kind == "blob":
        with open(target, "r+b") as f:
            f.seek(max(0, size - 3))  # flip payload bytes, not the header
            b = f.read(1)
            f.seek(max(0, size - 3))
            f.write(bytes([b[0] ^ 0xFF]))
    else:
        raise ValueError(f"unknown corruption kind {kind!r}")
    return target


# ---------------------------------------------------------------------------
# Child main: a checkpointing Nexmark pipeline driven tick-by-tick
# ---------------------------------------------------------------------------


def _write_status(path: str, obj: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def _serve(cfg: dict) -> int:
    """Child entry: run ``ticks`` deterministic Nexmark ticks through a
    controller-owned pipeline (host or compiled driver) with periodic
    checkpointing, recording each tick's output delta durably (fsync per
    line, so a SIGKILL tears at most the final line). With ``resume``,
    restores the newest valid checkpoint generation first and continues
    from its tick — the inputs are a function of (seed, tick), so the
    replay past the checkpoint is exact."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.io import Catalog
    from dbsp_tpu.io.controller import Controller, ControllerConfig
    from dbsp_tpu.nexmark import (GeneratorConfig, NexmarkGenerator,
                                  build_inputs, queries)

    query = getattr(queries, cfg["query"])

    def build(c):
        streams, handles = build_inputs(c)
        return handles, query(*streams).output()

    handle, (handles, out) = Runtime.init_circuit(1, build)
    driver = handle
    if cfg["mode"] == "compiled":
        from dbsp_tpu.compiled.driver import CompiledCircuitDriver

        driver = CompiledCircuitDriver(
            handle, validate_every=cfg.get("validate_every", 1))
    ctl = Controller(driver, Catalog(), ControllerConfig(
        checkpoint_dir=cfg["checkpoint_dir"],
        checkpoint_every_ticks=cfg.get("checkpoint_every", 0)))
    start_tick = 0
    restored = None
    if cfg.get("resume"):
        from dbsp_tpu import checkpoint as ckpt

        if ckpt.exists(cfg["checkpoint_dir"]):
            restored = ctl.restore_from()
            start_tick = ctl.steps
    gen = NexmarkGenerator(GeneratorConfig(seed=cfg.get("seed", 1)))
    cursor = out.register_consumer()
    batch = cfg["batch"]
    with open(cfg["out_path"], "w") as outf:
        outf.write(json.dumps({
            "header": True, "start_tick": start_tick,
            "restored_tick": restored["tick"] if restored else None,
            "fallback_from": (restored or {}).get("fallback_from"),
        }) + "\n")
        outf.flush()
        os.fsync(outf.fileno())
        for t in range(start_tick, cfg["ticks"]):
            gen.feed(handles, t * batch, (t + 1) * batch)
            ctl.step()
            b = out.read_consumer(cursor)
            delta = {} if b is None else b.to_dict()
            rows = sorted([list(k) + [int(w)] for k, w in delta.items()])
            outf.write(json.dumps({"tick": t, "delta": rows}) + "\n")
            outf.flush()
            os.fsync(outf.fileno())
            _write_status(cfg["status_path"], {"tick": t})
    ctl.stop()  # graceful: flush + final checkpoint generation
    _write_status(cfg["status_path"],
                  {"tick": cfg["ticks"] - 1, "done": True,
                   "start_tick": start_tick,
                   "checkpoints": ctl.checkpoints,
                   "last_checkpoint_tick": ctl.last_checkpoint_tick})
    # every durable artifact is fsynced above; skip interpreter teardown,
    # where XLA:CPU executable destructors have been observed to segfault
    # (the parent would misread a clean run as a crash)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0)


def main(argv: Optional[List[str]] = None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    if len(argv) == 2 and argv[0] == "--serve":
        with open(argv[1]) as f:
            return _serve(json.load(f))
    print("usage: python -m dbsp_tpu.testing.faults --serve <config.json>",
          file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main())
