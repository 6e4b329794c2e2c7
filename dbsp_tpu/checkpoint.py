"""Checkpoint / restore: durable, crash-safe snapshots of pipeline state.

Designed fresh — the reference has NO checkpointing; its closest capability
is the RocksDB ``PersistentTrace`` (``trace/persistent/mod.rs:40-45``,
SURVEY.md §5: "state spilling, not restartability"). The durability model
here is Flink's asynchronous barrier snapshotting (Carbone et al., "State
Management in Apache Flink", VLDB'17) collapsed to our single-clock
setting: the tick number IS the barrier, so a checkpoint is one consistent
cut — engine state at a validated tick plus the retained (not yet
validated) input feeds past it — and recovery replays those retained
inputs deterministically for exactly-once resumption (the same
high-water-mark semantics the compiled engine's overflow replay already
relies on).

Format (version 2) — versioned, checksummed, atomically written:

    <dir>/CURRENT               name of the newest valid generation
    <dir>/gen-00000007/
        manifest.json           {"payload": {...}, "sha256": <hex>}
        <blob>.npy              one numpy array per state-tree leaf

Every blob's SHA-256 (and the manifest payload's own) is recorded and
verified on load; a generation is written under a temp name and
``os.replace``d into place, then CURRENT is atomically swapped — a
PROCESS crash (SIGKILL included) at ANY point leaves the previous
generation intact and loadable. A corrupted/truncated CURRENT generation
falls back to the newest older generation that still verifies (callers
surface this as a ``restore`` flight event / SLO incident).
``DBSP_TPU_CHECKPOINT_FSYNC=1`` additionally fsyncs every write for
power-loss durability (see :data:`FSYNC` for why it defaults off).

Incremental across generations: deep trace levels of a compiled handle are
version-counted by maintenance drains (the same counters PR 3's
incremental ``snapshot()`` uses). A level untouched since the previous
generation is HARD-LINKED into the new one instead of re-serialized, so
steady-state checkpoint cost is O(level 0 + small states), not O(trace).

Three targets share the format (``engine`` field): a host
:class:`~dbsp_tpu.circuit.runtime.CircuitHandle` (operator ``state_dict``
walk), a bare :class:`~dbsp_tpu.compiled.compiler.CompiledHandle`, and a
serving :class:`~dbsp_tpu.compiled.driver.CompiledCircuitDriver` (engine
states + caps + slotted-l0 geometry + maintain cursors + tick counter +
retained-feed replay window). The circuit must be rebuilt by the same
constructor before ``restore`` — structure is checked and a mismatch
rejected.
"""

from __future__ import annotations

import hashlib
import io
import json
import os
import shutil
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import jax.numpy as jnp

from dbsp_tpu.zset.batch import Batch

FORMAT_VERSION = 2

#: generations retained on disk (older ones pruned after a successful
#: write); >= 2 so a corrupted CURRENT always has a fallback
KEEP_GENERATIONS = max(2, int(os.environ.get("DBSP_TPU_CHECKPOINT_KEEP",
                                             "3")))

#: default periodic-checkpoint cadence (controller ticks) when a
#: checkpoint directory is configured but no explicit interval is set
DEFAULT_EVERY_TICKS = 64

#: fsync policy (DBSP_TPU_CHECKPOINT_FSYNC=1 to enable). Default OFF:
#: the crash model checkpoints exist for is PROCESS death (SIGKILL —
#: the fault harness's induced crash), which the page cache survives, so
#: the atomic write/rename ordering alone makes restores exact; fsync
#: buys durability against POWER/kernel loss at ~170 ms per save on a
#: typical fs (measured: ~85% of warm-save cost), and even without it a
#: torn post-power-loss generation is caught by the checksums and falls
#: back one generation — the same default posture as RocksDB WAL writes
#: and Kafka's page-cache flush policy.
FSYNC = os.environ.get("DBSP_TPU_CHECKPOINT_FSYNC", "0") == "1"


def _maybe_fsync(f) -> None:
    if FSYNC:
        f.flush()
        os.fsync(f.fileno())


class CheckpointError(AssertionError):
    """Unloadable/mismatched checkpoint. Subclasses AssertionError for
    backwards compatibility with pre-v2 callers that caught the structure
    check's assert."""


# ---------------------------------------------------------------------------
# State-schema registry (tools/check_state.py lints against this)
# ---------------------------------------------------------------------------

#: Every instance attribute of the stateful serving classes must be claimed
#: here, keyed by class, as one of:
#:   "persisted"  — captured in the checkpoint manifest/blobs
#:   "derived"    — reconstructible from persisted state (caches, stats,
#:                  observability samples); safe to lose on crash
#:   "config"     — rebuilt from the program/config at deploy, not state
#:   "runtime"    — process-local machinery (locks, threads, sockets)
#: ``tools/check_state.py`` walks the class bodies and fails when an
#: attribute is missing here (state growth can never silently break
#: restore) or when a claimed attribute vanished (stale schema).
STATE_SCHEMA: Dict[str, Dict[str, str]] = {
    "CompiledHandle": {
        "states": "persisted",
        "maintain_pending": "persisted",
        "_level_versions": "persisted",
        "circuit": "config",
        "runtime": "config",
        "mesh": "config",
        "workers": "config",
        "order": "config",
        "cnodes": "config",      # caps + _slot_cap persisted per cnode
        "by_index": "config",
        "deferred_consolidations": "config",
        "_op_to_index": "config",
        "_gen_fn": "config",
        "_step_jit": "derived",
        "_scan_jits": "derived",
        # device-resident tick cursor (program output, re-uploaded on any
        # discontinuity) + the transfer-guard level testing/retrace.py arms
        "_tick_dev": "derived",
        "_tick_host": "derived",
        "_steady_guard": "runtime",
        "_checks": "derived",
        # what the time nodes observe behind the requirements (re-made by
        # every trace), the nodes of a windowed view (from the graph), and
        # the GC'd levels the last snapshot copied (a span's arg)
        "_observed": "derived",
        # how a tick's record names each check (re-made by every trace)
        "_check_names": "derived",
        "_windowed": "derived",
        "snapshot_gc_levels": "derived",
        "_req": "derived",
        "_max_jit": "derived",
        "last_req": "derived",
        "last_outputs": "derived",
        "step_times_ns": "derived",
        "overflow_replays": "derived",
        # exchange-bucket overflow subset of the replays (skew hazard
        # observability; mirrored process-wide in parallel/exchange.py)
        "exchange_overflows": "derived",
        "host_overhead_ns": "derived",
        "tick_causes": "derived",
        "_pending_causes": "derived",
        "maintain_stats": "derived",
        "_snap_levels": "derived",
        "_ckpt_salt": "derived",  # hard-link scope marker, per process
        # tiered trace residency (dbsp_tpu/residency.py): the tier map and
        # disk blob metadata are persisted (payload "residency" /
        # "cold_blobs") so restore can leave disk-demoted levels on disk;
        # the LRU clock, transition observability, and the store handle
        # rebuild from a fresh run
        "residency_cfg": "config",
        "_tiers": "persisted",
        "_cold_meta": "persisted",
        "_cold_store": "runtime",
        "_lru": "derived",
        "_interval": "derived",
        "residency_stats": "derived",
        "residency_log": "derived",
        "cold_events": "derived",
    },
    "CompiledCircuitDriver": {
        "mode": "config",
        "_tick": "persisted",
        "_retained": "persisted",
        "host_handle": "config",
        "circuit": "config",
        "ch": "config",           # its own persisted parts listed above
        "validate_every": "config",
        "_inputs": "config",
        "input_labels": "config",  # span arg per input, from the catalog
        "_outputs": "config",
        "_snap": "derived",       # rebuilt from restored state on resume
        "_out_buffer": "derived",  # rebuilt by replaying _retained
        "_interval_open_ts": "derived",  # wall-clock restamped on resume
        "spans": "runtime",
    },
    "Controller": {
        "steps": "persisted",
        "total_pushed": "persisted",
        # which parser path took this process's pushes: observability of
        # the running parser, restarts from zero like any process counter
        "parsed_columnar": "runtime",
        "parsed_fallback": "runtime",
        "handle": "config",
        "catalog": "config",
        "config": "config",
        "checkpoint_dir": "config",
        "checkpoint_every": "config",
        "_residency_cfg": "config",  # resolved residency budgets,
                                     # re-applied after a host restore
        "inputs": "config",       # endpoint counters persisted via
        "outputs": "config",      # _controller_state() (see _InputEndpoint)
        "state": "runtime",
        "_stop": "runtime",
        "_pushed": "derived",     # buffered-not-yet-stepped rows replay
        "_pushed_lock": "runtime",
        "_running": "runtime",
        "_thread": "runtime",
        "_step_lock": "runtime",
        "_lifecycle_lock": "runtime",
        "_monitors": "runtime",
        "flight": "runtime",
        "timeline": "runtime",   # obs wiring; its ring is rebuilt live
        "checkpoints": "derived",
        "checkpoint_error": "derived",
        "last_checkpoint_tick": "persisted",
        "_last_ckpt_step": "derived",
        "read_plane": "persisted",  # per-view merged state rides the
                                    # "read_plane" payload; epoch in the
                                    # manifest ("read_epoch")
        "e2e": "runtime",   # delta-trace contexts die with the process:
                            # a restored pipeline mints fresh trace ids
        "spans": "runtime",  # this process's span ring — trace surface
    },
    "_InputEndpoint": {
        "total_records": "persisted",   # consumed high-water mark: the
        "total_bytes": "persisted",     # replay position recovery resumes
        "name": "config",               # input feeds from
        "collection": "config",
        "transport": "config",
        "parser": "config",
        "notify_arrival": "config",  # freshness stamp hook (controller)
        "lock": "runtime",
        "rows": "derived",    # in-flight rows not yet stepped: upstream
        "eoi": "derived",     # replays them past the checkpoint tick
        "paused": "derived",
        "error": "derived",
        "skip_rows": "derived",  # set from the persisted total_records at
    },                           # restore (replay-from-start transports)
    "_OutputEndpoint": {
        "name": "config",
        "collection": "config",
        "transport": "config",
        "encoder": "config",
        "total_records": "derived",  # at-least-once on the output side:
        "total_bytes": "derived",    # sinks dedup by tick (X-Dbsp-Step)
        "cursor": "derived",
        "error": "derived",
        "pending": "persisted",  # failed-write retry batch rides the
    },                           # manifest (output_pending) so a crash
                                 # cannot drop an undelivered delta
    "ReadPlane": {
        "enabled": "config",
        "capacity": "config",
        "compact_after": "config",
        "_lock": "runtime",
        "_wakeup": "runtime",
        "_views": "persisted",   # each view's merged snapshot state is a
                                 # consolidated Batch in the "read_plane"
                                 # payload (state_batches()/restore())
        "epoch": "persisted",    # manifest "read_epoch" via
                                 # Controller._controller_state()
        "publishes": "derived",
        "last_publish_ts": "derived",
        "flight": "runtime",
        "_read_qps": "runtime",
        "_read_seconds": "runtime",
        "_publish_total": "runtime",
    },
    "_ViewState": {
        "name": "config",
        "handle": "config",
        "mode": "config",
        "nkeys": "derived",      # recomputed from the restored batch
        "cid": "runtime",        # consumer re-registered on restore
        "snap": "persisted",     # the merged rows ARE the read_plane blob
        "prev_rows": "derived",  # rebuilt from the restored snapshot
        "feed": "derived",       # reset; old cursors resume through a
        "dropped_epoch": "derived",  # synthesized kind="snapshot" record
        "seen_step": "derived",
    },
    "ReplicaServer": {
        # stateless by contract: the whole state is the changefeed fold,
        # reconstructible from epoch 0 (or any snapshot record) — nothing
        # to checkpoint, which is what makes replicas free to scale
        "primary": "config",
        "views_served": "config",
        "name": "config",
        "poll_timeout_s": "config",
        "_lock": "runtime",
        "_state": "derived",
        "_cursor": "derived",
        "_nkeys": "derived",
        "_applied_ts": "derived",
        "_sorted": "derived",
        "applied": "derived",
        "stalled": "runtime",
        "_stop": "runtime",
        "_httpd": "runtime",
        "port": "runtime",
        "_serve_thread": "runtime",
        "_feed_thread": "runtime",
        "e2e": "runtime",     # shared tracer wiring (writer-owned)
        "spans": "runtime",   # this process's span ring — trace surface
        "_trace": "derived",  # per-view applied trace annotations: the
                              # changefeed fold re-derives them
    },
}


# ---------------------------------------------------------------------------
# State-tree encoding (arrays out-of-line as named blobs)
# ---------------------------------------------------------------------------


class _Encoder:
    def __init__(self):
        self.arrays: Dict[str, np.ndarray] = {}
        self.counter = 0
        self._hint = "a"

    def _store(self, arr) -> str:
        key = f"{self._hint}{self.counter}"
        self.counter += 1
        self.arrays[key] = np.asarray(arr)
        return key

    def encode(self, v: Any, hint: Optional[str] = None) -> Any:
        """Encode a state pytree; ``hint`` prefixes this subtree's blob
        names (deterministic names are what lets an unchanged trace level
        hard-link its previous generation's blobs)."""
        if hint is not None:
            prev_hint, prev_counter = self._hint, self.counter
            self._hint, self.counter = hint + "_", 0
            try:
                return self.encode(v)
            finally:
                self._hint, self.counter = prev_hint, prev_counter
        if isinstance(v, Batch):
            return {"__batch__": {
                "keys": [self._store(c) for c in v.keys],
                "vals": [self._store(c) for c in v.vals],
                "weights": self._store(v.weights),
                # sorted-run aux metadata: part of the batch's identity
                # (consolidation regime dispatch + compiled pytree aux)
                "runs": list(v.runs) if v.runs is not None else None,
            }}
        from dbsp_tpu.trace.spine import Spine

        if isinstance(v, Spine):
            return {"__spine__": {
                "key_dtypes": [str(d) for d in v.key_dtypes],
                "val_dtypes": [str(d) for d in v.val_dtypes],
                "batches": [self.encode(b) for b in v.batches],
                "dirty": v.dirty,
            }}
        if isinstance(v, (jnp.ndarray, np.ndarray)):
            return {"__array__": self._store(v)}
        if isinstance(v, np.generic):  # numpy scalar (int64(3), bool_, ...)
            return {"__scalar__": v.item(), "dtype": str(v.dtype)}
        if isinstance(v, dict):
            return {"__dict__": {k: self.encode(x) for k, x in v.items()}}
        if isinstance(v, (list, tuple)):
            return {"__seq__": [self.encode(x) for x in v],
                    "tuple": isinstance(v, tuple)}
        if v is None or isinstance(v, (bool, int, float, str)):
            return v
        raise TypeError(f"unsupported checkpoint value type {type(v)}")


class _NpDecoder:
    """Variant decoder materializing HOST numpy copies — used for
    residency-demoted (host-tier) trace levels at restore, which must not
    round-trip through device memory just to come back off it. Built on
    the same loader; only ``_arr`` differs."""

    def __init__(self, load_array):
        self.load = load_array

    def _arr(self, name: str) -> np.ndarray:
        return np.array(self.load(name))  # copy: the loader cache is shared

    decode = None  # assigned below (shares _Decoder.decode)


class _Decoder:
    """Decodes against a blob loader (verifying checksums lazily).

    Every array materializes through :meth:`_arr` — ``jnp.array`` (a
    COPY), never ``jnp.asarray``: on the CPU backend ``asarray`` can
    zero-copy-wrap the numpy buffer, and the compiled step program
    DONATES its state inputs — XLA would then alias/free memory the
    decoder still owns (observed: garbage int64 state one tick after
    restore, heap corruption, flaky SIGSEGV)."""

    def __init__(self, load_array):
        self.load = load_array

    def _arr(self, name: str) -> jnp.ndarray:
        return jnp.array(self.load(name))

    def decode(self, v: Any) -> Any:
        if isinstance(v, dict):
            if "__batch__" in v:
                b = v["__batch__"]
                runs = tuple(b["runs"]) if b.get("runs") is not None else None
                return Batch(
                    tuple(self._arr(k) for k in b["keys"]),
                    tuple(self._arr(k) for k in b["vals"]),
                    self._arr(b["weights"]), runs)
            if "__spine__" in v:
                from dbsp_tpu.trace.spine import Spine

                s = v["__spine__"]
                spine = Spine([jnp.dtype(d) for d in s["key_dtypes"]],
                              [jnp.dtype(d) for d in s["val_dtypes"]])
                spine.batches = [self.decode(b) for b in s["batches"]]
                spine.dirty = s["dirty"]
                return spine
            if "__array__" in v:
                return self._arr(v["__array__"])
            if "__scalar__" in v:
                return np.dtype(v["dtype"]).type(v["__scalar__"])
            if "__dict__" in v:
                return {k: self.decode(x) for k, x in v["__dict__"].items()}
            if "__seq__" in v:
                seq = [self.decode(x) for x in v["__seq__"]]
                return tuple(seq) if v["tuple"] else seq
        return v


_NpDecoder.decode = _Decoder.decode  # same walk, numpy leaves


# ---------------------------------------------------------------------------
# Generation store: atomic writes, checksums, fallback scan
# ---------------------------------------------------------------------------


def _gen_name(n: int) -> str:
    return f"gen-{n:08d}"


def _gen_number(name: str) -> Optional[int]:
    if name.startswith("gen-"):
        try:
            return int(name[4:])
        except ValueError:
            return None
    return None


def _list_generations(path: str) -> List[Tuple[int, str]]:
    """(number, name) of every generation directory, newest first."""
    out = []
    try:
        entries = os.listdir(path)
    except OSError:
        return []
    for name in entries:
        n = _gen_number(name)
        if n is not None and os.path.isdir(os.path.join(path, name)):
            out.append((n, name))
    out.sort(reverse=True)
    return out


def exists(path: str) -> bool:
    """True when ``path`` holds at least one checkpoint generation."""
    return bool(path) and os.path.isdir(path) and \
        bool(_list_generations(path))


def _sha256_file(p: str) -> str:
    h = hashlib.sha256()
    with open(p, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _payload_digest(payload: dict) -> str:
    return hashlib.sha256(
        json.dumps(payload, sort_keys=True).encode()).hexdigest()


def _read_manifest(gen_dir: str) -> dict:
    """Load + verify one generation's manifest; raises CheckpointError."""
    mpath = os.path.join(gen_dir, "manifest.json")
    try:
        with open(mpath) as f:
            wrapper = json.load(f)
    except (OSError, ValueError) as e:
        raise CheckpointError(f"unreadable manifest {mpath}: {e}") from e
    payload = wrapper.get("payload")
    if not isinstance(payload, dict) or \
            wrapper.get("sha256") != _payload_digest(payload):
        raise CheckpointError(f"manifest checksum mismatch in {gen_dir}")
    if payload.get("format") != FORMAT_VERSION:
        raise CheckpointError(
            f"checkpoint format {payload.get('format')} != {FORMAT_VERSION}")
    return payload


def _verify_blobs(gen_dir: str, payload: dict,
                  bytes_cache: Optional[Dict[str, bytes]] = None) -> None:
    """Verify every blob's size+digest up front (restore must not get
    halfway through mutating engine state before hitting corruption).
    ``bytes_cache`` keeps the verified bytes for the loader so the
    restore path reads each blob from disk exactly once."""
    for name, meta in payload.get("arrays", {}).items():
        p = os.path.join(gen_dir, name + ".npy")
        try:
            with open(p, "rb") as f:
                data = f.read()
        except OSError as e:
            raise CheckpointError(
                f"blob {name} unreadable in {gen_dir}: {e}") from e
        if len(data) != meta["bytes"]:
            raise CheckpointError(f"blob {name} truncated in {gen_dir}")
        if hashlib.sha256(data).hexdigest() != meta["sha256"]:
            raise CheckpointError(
                f"blob {name} checksum mismatch in {gen_dir}")
        if bytes_cache is not None:
            bytes_cache[name] = data


def _make_loader(gen_dir: str, payload: dict,
                 bytes_cache: Optional[Dict[str, bytes]] = None):
    cache: Dict[str, np.ndarray] = {}
    bytes_cache = bytes_cache if bytes_cache is not None else {}

    def load(name: str) -> np.ndarray:
        if name not in cache:
            data = bytes_cache.pop(name, None)  # verified read, if any
            if data is None:
                p = os.path.join(gen_dir, name + ".npy")
                with open(p, "rb") as f:
                    data = f.read()
            cache[name] = np.load(io.BytesIO(data), allow_pickle=False)
        return cache[name]

    return load


def load_manifest(path: str, verify_blobs: bool = True,
                  bytes_cache: Optional[Dict[str, bytes]] = None
                  ) -> Tuple[str, dict, Optional[str]]:
    """(generation name, verified payload, fallback_from) for the newest
    loadable generation. Tries CURRENT first, then older generations —
    ``fallback_from`` names the corrupt generation that was skipped (the
    caller's cue to emit a ``restore`` incident). Raises
    :class:`CheckpointError` when nothing verifies.

    ``verify_blobs=False`` checks only the manifest (its own checksum):
    the SAVE path uses it to find the previous generation for hard-link
    reuse — re-hashing the whole previous state per periodic checkpoint
    would make saves O(state) again, and a bit-rotted linked blob is
    still caught at RESTORE time (the recorded digest rides along)."""
    if not os.path.isdir(path):
        raise CheckpointError(f"no checkpoint directory {path!r}")
    current = None
    try:
        with open(os.path.join(path, "CURRENT")) as f:
            current = f.read().strip() or None
    except OSError:
        pass
    gens = [name for _, name in _list_generations(path)]
    if current in gens:  # CURRENT first, then the rest newest-first
        gens.remove(current)
        gens.insert(0, current)
    if not gens:
        raise CheckpointError(f"no checkpoint generations under {path!r}")
    fallback_from: Optional[str] = None
    last_err: Optional[Exception] = None
    for name in gens:
        gen_dir = os.path.join(path, name)
        try:
            payload = _read_manifest(gen_dir)
            if verify_blobs:
                _verify_blobs(gen_dir, payload, bytes_cache)
            return name, payload, fallback_from
        except CheckpointError as e:
            if bytes_cache is not None:
                bytes_cache.clear()  # partial reads of a bad generation
            if fallback_from is None:
                fallback_from = name
            last_err = e
    raise CheckpointError(
        f"no valid checkpoint generation under {path!r}: {last_err}")


def _fsync_dir(path: str) -> None:
    if not FSYNC:
        return
    try:
        fd = os.open(path, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
    except OSError:
        pass  # fsync on directories is best-effort on some filesystems


def _write_generation(path: str, payload: dict, enc: _Encoder,
                      linked: Dict[str, str],
                      linked_meta: Optional[Dict[str, dict]] = None,
                      copied: Optional[Dict[str, str]] = None
                      ) -> Tuple[str, dict]:
    """Write one generation atomically: blobs + manifest land in a temp
    dir, which is renamed into place before CURRENT is swapped. ``linked``
    maps blob name -> absolute source path to hard-link instead of
    serializing (clean deep levels); ``linked_meta`` carries their
    already-recorded digests so a linked blob is never re-hashed (saves
    stay O(dirty state), not O(state)). ``copied`` maps blob name ->
    source path to COPY (new inode): the first generation's capture of a
    cold-store blob must not share the store file's inode, or in-place
    bit-rot would take the recovery copy down with the store (subsequent
    generations hard-link the generation copy). Returns
    (gen name, stats)."""
    os.makedirs(path, exist_ok=True)
    # sweep orphaned temp dirs from writers that died mid-save (SIGKILL
    # mid-serialization leaves up to a full state copy under .tmp-*; a
    # crash-looping pipeline would otherwise fill the disk one orphan per
    # crash — the store has one writer by design, so any .tmp-* is dead)
    for entry in os.listdir(path):
        if entry.startswith(".tmp-"):
            shutil.rmtree(os.path.join(path, entry), ignore_errors=True)
    gens = _list_generations(path)
    gen_no = (gens[0][0] + 1) if gens else 1
    name = _gen_name(gen_no)
    payload = dict(payload, format=FORMAT_VERSION, generation=gen_no,
                   created_ts=time.time())
    tmp = os.path.join(path, f".tmp-{name}-{os.getpid()}")
    os.makedirs(tmp)
    arrays: Dict[str, dict] = {}
    nbytes = 0
    linked_meta = linked_meta or {}
    for blob, src in linked.items():
        dst = os.path.join(tmp, blob + ".npy")
        try:
            os.link(src, dst)
        except OSError:  # cross-device / FS without hard links
            shutil.copy2(src, dst)
        meta = linked_meta.get(blob)
        if meta is None:  # unexpected: fall back to hashing the file
            meta = {"sha256": _sha256_file(dst),
                    "bytes": os.path.getsize(dst)}
        arrays[blob] = meta
        nbytes += meta["bytes"]
    for blob, src in (copied or {}).items():
        dst = os.path.join(tmp, blob + ".npy")
        shutil.copy2(src, dst)
        meta = linked_meta.get(blob)
        if meta is None:
            meta = {"sha256": _sha256_file(dst),
                    "bytes": os.path.getsize(dst)}
        arrays[blob] = meta
        nbytes += meta["bytes"]
    for key, arr in enc.arrays.items():
        # serialize to memory, hash the bytes, write ONCE — hashing the
        # file after np.save would re-read every fresh blob from disk,
        # doubling save-path I/O on the periodic hot path
        buf = io.BytesIO()
        np.save(buf, arr, allow_pickle=False)
        data = buf.getvalue()
        with open(os.path.join(tmp, key + ".npy"), "wb") as f:
            f.write(data)
            _maybe_fsync(f)
        arrays[key] = {"sha256": hashlib.sha256(data).hexdigest(),
                       "bytes": len(data)}
        nbytes += len(data)
    payload["arrays"] = arrays
    wrapper = {"payload": payload, "sha256": _payload_digest(payload)}
    mpath = os.path.join(tmp, "manifest.json")
    with open(mpath, "w") as f:
        json.dump(wrapper, f)
        _maybe_fsync(f)
    final = os.path.join(path, name)
    shutil.rmtree(final, ignore_errors=True)  # stale dir from a dead writer
    os.replace(tmp, final)
    _fsync_dir(path)
    # CURRENT swap: readers always see either the old or the new pointer
    cur_tmp = os.path.join(path, ".CURRENT.tmp")
    with open(cur_tmp, "w") as f:
        f.write(name)
        _maybe_fsync(f)
    os.replace(cur_tmp, os.path.join(path, "CURRENT"))
    _fsync_dir(path)
    # retention: prune old generations (hard-linked blobs stay alive via
    # the new generation's directory entries)
    for n, gname in _list_generations(path)[KEEP_GENERATIONS:]:
        shutil.rmtree(os.path.join(path, gname), ignore_errors=True)
    return name, {"generation": gen_no,
                  "arrays": len(arrays),
                  "linked_arrays": len(linked),
                  "copied_arrays": len(copied or {}),
                  "bytes": nbytes}


# ---------------------------------------------------------------------------
# Host circuit walking (engine = "host")
# ---------------------------------------------------------------------------


def _walk(circuit, prefix=()):
    for node in circuit.nodes:
        if node.kind == "strict_input":
            continue  # same operator instance as its strict_output partner
        yield (*prefix, node.index), node
        if node.child is not None:
            yield from _walk(node.child, (*prefix, node.index))


def _host_structure(circuit) -> list:
    return [[list(gid), node.operator.name, node.kind]
            for gid, node in _walk(circuit)]


def _save_host(handle, enc: _Encoder) -> dict:
    from dbsp_tpu import residency as _res

    # disk-tier spine levels are streaming-VERIFIED in place before they
    # are serialized: encoding raw memmap bytes would stamp a bit-rotted
    # blob with a fresh valid checksum — corruption laundered into a
    # checkpoint that verifies clean forever. verify_meta (not a fault):
    # no whole-tier materialization in RAM, no spine mutation, no
    # release/sweep churn — the tiers survive the save untouched.
    for sp in _res.circuit_spines(handle.circuit):
        batches = getattr(sp, "batches", None)
        if not batches:
            continue
        for i, b in enumerate(list(batches)):
            if not isinstance(b.weights, np.memmap):
                continue
            meta = getattr(sp, "_disk_meta", {}).get(id(b)) or \
                _res.meta_from_batch(b)
            if sp._store().verify_meta(meta):
                # a blob was healed: the open memmap still maps the OLD
                # corrupted inode — re-open so the encoder reads the
                # recovered bytes, and re-key the meta to the new object
                fresh = _res.disk_batch(meta, sp._store())
                sp.batches[i] = fresh
                if sp._disk_meta.pop(id(b), None) is not None:
                    sp._disk_meta[id(fresh)] = meta
    states = {}
    for gid, node in _walk(handle.circuit):
        sd = node.operator.state_dict()
        if sd:
            states[json.dumps(list(gid))] = enc.encode(sd)
    return {"engine": "host",
            "structure": _host_structure(handle.circuit),
            "states": states,
            "tick": len(handle.step_times_ns)}


def _restore_host(handle, payload: dict, dec: _Decoder) -> None:
    structure = _host_structure(handle.circuit)
    if structure != payload["structure"]:
        raise CheckpointError(
            "circuit structure differs from the checkpointed circuit — "
            "rebuild with the same constructor before restoring")
    states = payload["states"]
    # two-phase: decode everything BEFORE the first load_state_dict, so a
    # decode failure cannot leave a half-restored circuit
    decoded = {key: dec.decode(st) for key, st in states.items()}
    for gid, node in _walk(handle.circuit):
        key = json.dumps(list(gid))
        if key in decoded:
            node.operator.load_state_dict(decoded[key])


# ---------------------------------------------------------------------------
# Compiled engine (engine = "compiled")
# ---------------------------------------------------------------------------


def _compiled_structure(ch) -> list:
    return [[cn.node.index, cn.op.name, type(cn).__name__]
            for cn in ch.cnodes]


def _level_fingerprint(ch, key: str, i: int, cap: int) -> str:
    vers = ch._level_versions.get(key)
    v = vers[i] if vers is not None and i < len(vers) else 0
    salt = getattr(ch, "_ckpt_salt", None)
    if salt is None:
        # scopes hard-link reuse to THIS handle instance: two handles
        # checkpointing into one directory must never alias each other's
        # blobs on coincidentally equal version counters
        salt = ch._ckpt_salt = uuid.uuid4().hex[:12]
    return f"{salt}/{key}/{i}/v{v}/c{cap}/w{ch.workers}"


def _save_compiled(ch, enc: _Encoder, states: Dict[str, Any],
                   prev: Optional[Tuple[str, dict]],
                   path: str) -> Tuple[dict, Dict[str, str],
                                       Dict[str, dict]]:
    """Encode a CompiledHandle's engine state. ``states`` is the state
    dict to persist (live states, or the interval-start snapshot when a
    replay window is open). Returns (payload fragment, linked blobs,
    linked blob digests carried over from the previous manifest)."""
    from dbsp_tpu.compiled import cnodes as _cn

    prev_payload = prev[1] if prev is not None else None
    prev_dir = os.path.join(path, prev[0]) if prev is not None else None
    prev_levels = (prev_payload or {}).get("level_blobs", {})
    prev_arrays = (prev_payload or {}).get("arrays", {})
    enc_states: Dict[str, Any] = {}
    level_blobs: Dict[str, dict] = {}
    linked: Dict[str, str] = {}
    linked_meta: Dict[str, dict] = {}
    copied: Dict[str, str] = {}
    residency: Dict[str, list] = {}
    cold_blobs: Dict[str, Dict[str, dict]] = {}
    for key, st in states.items():
        cn = ch.by_index.get(int(key))
        leveled = isinstance(cn, _cn._Leveled) and isinstance(st, tuple) \
            and len(st) == 2 and isinstance(st[0], tuple)
        if not leveled:
            enc_states[key] = enc.encode(st, hint=f"s{key}")
            continue
        levels, base = st
        tiers = getattr(ch, "_tiers", {}).get(key)
        if tiers:
            residency[key] = list(tiers)
        enc_levels = []
        for i, lvl in enumerate(levels):
            hint = f"s{key}_l{i}"
            fp = _level_fingerprint(ch, key, i, lvl.cap)
            ent = getattr(ch, "_cold_meta", {}).get(key, {}).get(i)
            disk_ent = ent if (i > 0 and ent is not None
                               and ent.get("batch") is lvl) else None
            reuse = prev_levels.get(fp) if i > 0 else None
            if reuse is not None and prev_dir is not None and all(
                    os.path.exists(os.path.join(prev_dir, b + ".npy"))
                    for b in reuse["blobs"]):
                # clean deep level: reuse the previous generation's encoded
                # node verbatim and hard-link its blobs (same names — the
                # hint is deterministic per (state, level)). Disk-demoted
                # levels take this path on every save AFTER the first: the
                # generation chain links its OWN first copy, whose inode is
                # deliberately independent of the cold store's (see below)
                enc_levels.append(reuse["node"])
                for b in reuse["blobs"]:
                    linked[b] = os.path.join(prev_dir, b + ".npy")
                    if b in prev_arrays:
                        linked_meta[b] = prev_arrays[b]
                level_blobs[fp] = reuse
                if disk_ent is not None:
                    cold_blobs.setdefault(key, {})[str(i)] = \
                        disk_ent["blob"]
                    ch._store().note_recovery_dir(path)
                continue
            # disk-demoted level, first generation capture: its columns
            # ALREADY live as content-addressed blobs in the cold store —
            # verified COPY into the generation (no serialization from
            # memory; the recorded digests ride along). A hard link here
            # would share the store file's INODE, and in-place bit-rot
            # would corrupt the recovery copy together with the store —
            # defeating the fallback the cold tier's corruption contract
            # depends on. Subsequent saves hard-link the generation copy
            # (fp reuse above), so warm saves stay O(hot state).
            if disk_ent is not None:
                store = ch._store()
                blob = disk_ent["blob"]
                cols = [*blob["keys"], *blob["vals"], blob["weights"]]
                nk = len(blob["keys"])
                names = [f"{hint}_c{j}" for j in range(len(cols))]
                if all(os.path.exists(store.blob_path(m["sha256"]))
                       for m in cols):
                    if store.verify_meta(blob):  # never launder rot —
                        # and a HEAL replaced the file: re-point every
                        # live holder off the corrupted inode
                        lvl = _reheal_level(ch, states, key, i, lvl, blob)
                    node = {"__batch__": {
                        "keys": names[:nk],
                        "vals": names[nk:-1],
                        "weights": names[-1],
                        "runs": blob.get("runs")}}
                    for name, m in zip(names, cols):
                        copied[name] = store.blob_path(m["sha256"])
                        linked_meta[name] = {"sha256": m["sha256"],
                                             "bytes": m["bytes"]}
                    enc_levels.append(node)
                    level_blobs[fp] = {"node": node, "blobs": names}
                    cold_blobs.setdefault(key, {})[str(i)] = blob
                    store.note_recovery_dir(path)
                    continue
            if isinstance(lvl.weights, np.memmap):
                # disk level with stale/missing meta (identity guard
                # failed): streaming-VERIFY (and heal) before serializing
                # — encoding raw memmap bytes would launder a corrupted
                # blob into a clean-checksummed checkpoint
                from dbsp_tpu import residency as _res

                stale_meta = _res.meta_from_batch(lvl)
                ch._store().verify_meta(stale_meta)
                # re-open regardless (a heal replaced the file under the
                # open memmap; a fresh view is free either way) AND swap
                # the fresh batch into the live holders so the engine
                # stops reading the old inode too
                lvl = _reheal_level(ch, states, key, i, lvl, stale_meta)
            before = set(enc.arrays)
            node = enc.encode(lvl, hint=hint)
            blobs = sorted(set(enc.arrays) - before)
            enc_levels.append(node)
            if i > 0:
                level_blobs[fp] = {"node": node, "blobs": blobs}
        enc_states[key] = {"__levels__": enc_levels,
                           "base": enc.encode(base, hint=f"s{key}_base")}
    caps = {str(cn.node.index): dict(cn.caps)
            for cn in ch.cnodes if cn.caps}
    slots = {str(cn.node.index): cn._slot_cap
             for cn in ch.cnodes
             if getattr(cn, "_slot_cap", None) is not None}
    return {
        "engine": "compiled",
        "structure": _compiled_structure(ch),
        "workers": ch.workers,
        "states": enc_states,
        "caps": caps,
        "slots": slots,
        "level_versions": {k: list(v)
                           for k, v in ch._level_versions.items()},
        "maintain_pending": bool(ch.maintain_pending),
        "level_blobs": level_blobs,
        "residency": residency,
        "cold_blobs": cold_blobs,
    }, linked, linked_meta, copied


def _adopt_cold_blobs(store, blob: dict, enc_node: dict,
                      gen_dir: str) -> None:
    """Ensure every column blob of one disk-tier level exists in the cold
    store, hard-linking (or copying) the generation's verified files in
    by content hash — restore never re-serializes cold state."""
    names = []
    if isinstance(enc_node, dict) and "__batch__" in enc_node:
        b = enc_node["__batch__"]
        names = [*b["keys"], *b["vals"], b["weights"]]
    metas = [*blob["keys"], *blob["vals"], blob["weights"]]
    for j, m in enumerate(metas):
        dst = store.blob_path(m["sha256"])
        if os.path.exists(dst):
            continue
        src = os.path.join(gen_dir, (names[j] if j < len(names)
                                     else "") + ".npy")
        if not os.path.exists(src):
            continue  # fault_batch will surface/recover the miss later
        try:
            os.link(src, dst)
        except OSError:
            shutil.copy2(src, dst)


def _reheal_level(ch, states: Dict[str, Any], key: str, i: int,
                  old: Batch, blob: dict) -> Batch:
    """After ``verify_meta`` healed a blob on disk, any OPEN memmap still
    maps the corrupted inode (the heal is an ``os.replace``): re-open a
    fresh view and swap it into every live holder whose level IS the
    healed object — the engine states (so subsequent step programs stop
    reading rotted bytes), the states dict being saved, and the blob
    bookkeeping's identity anchor."""
    from dbsp_tpu import residency as _res

    fresh = _res.disk_batch(blob, ch._store())
    for holder in (ch.states, states):
        st = holder.get(key)
        if isinstance(st, tuple) and len(st) == 2 and \
                isinstance(st[0], tuple) and i < len(st[0]) and \
                st[0][i] is old:
            lv = list(st[0])
            lv[i] = fresh
            holder[key] = (tuple(lv), st[1])
    ent = getattr(ch, "_cold_meta", {}).get(key, {}).get(i)
    if ent is not None and ent.get("batch") is old:
        ent["batch"] = fresh
    return fresh


def _restore_compiled(ch, payload: dict, dec: _Decoder,
                      gen_dir: Optional[str] = None,
                      path: Optional[str] = None) -> Dict[str, Any]:
    """Apply a compiled payload onto a freshly compiled handle: caps, slot
    geometry, maintain cursors, and the decoded states (re-placed over
    the worker mesh when sharded). TWO-PHASE: everything is decoded and
    device-placed BEFORE the first mutation, so a decode/placement
    failure leaves the handle exactly as built (a half-mutated engine
    served as 'fresh' would double-apply replayed inputs). Returns the
    decoded state dict.

    Residency: when the restoring handle runs with active budgets
    (``residency_cfg.active``), the payload's persisted tier map is
    honored — disk-demoted levels are re-adopted into the cold store by
    content hash and come back as memmap views (the restore that leaves
    cold state on disk), host-tier levels decode straight to numpy. A
    handle with no budgets decodes everything device-resident (legacy
    behavior, bit-identical either way)."""
    from dbsp_tpu import residency as _res

    if _compiled_structure(ch) != payload["structure"]:
        raise CheckpointError(
            "compiled circuit structure differs from the checkpointed "
            "circuit — rebuild with the same constructor before restoring")
    if payload.get("workers", 1) != ch.workers:
        raise CheckpointError(
            f"checkpoint was taken at workers={payload.get('workers')} != "
            f"this runtime's {ch.workers}")
    honor_tiers = getattr(ch, "residency_cfg", None) is not None and \
        ch.residency_cfg.active and ch.workers == 1
    residency = payload.get("residency") or {}
    cold_blobs = payload.get("cold_blobs") or {}
    npdec = _NpDecoder(dec.load)
    # phase 1: decode + place (no mutation of ch/cnodes yet)
    states: Dict[str, Any] = {}
    tiers_out: Dict[str, list] = {}
    cold_meta_out: Dict[str, Dict[int, dict]] = {}
    for key, enc_st in payload["states"].items():
        if isinstance(enc_st, dict) and "__levels__" in enc_st:
            tiers = residency.get(key) if honor_tiers else None
            levels = []
            for i, lv in enumerate(enc_st["__levels__"]):
                tier = tiers[i] if tiers and i < len(tiers) \
                    else _res.TIER_DEVICE
                blob = cold_blobs.get(key, {}).get(str(i))
                if tier == _res.TIER_DISK and blob is not None and \
                        gen_dir is not None:
                    store = ch._store()
                    _adopt_cold_blobs(store, blob, lv, gen_dir)
                    lvl = _res.disk_batch(blob, store)
                    store.retain(blob)  # sweep-protect the restored level
                    cold_meta_out.setdefault(key, {})[i] = {
                        "blob": blob, "batch": lvl}
                    if path is not None:
                        store.note_recovery_dir(path)
                elif tier == _res.TIER_HOST:
                    lvl = npdec.decode(lv)
                else:
                    tier = _res.TIER_DEVICE
                    lvl = dec.decode(lv)
                levels.append(lvl)
                if tiers:
                    tiers[i] = tier  # downgraded disk->device when no dir
            if tiers and any(t != _res.TIER_DEVICE for t in tiers):
                tiers_out[key] = list(tiers)
            states[key] = (tuple(levels), dec.decode(enc_st["base"]))
        else:
            states[key] = dec.decode(enc_st)
    if ch.workers > 1:
        import jax

        from dbsp_tpu.parallel.mesh import worker_sharding

        states = jax.device_put(states, worker_sharding(ch.mesh))
    # phase 2: apply
    for cn in ch.cnodes:
        key = str(cn.node.index)
        saved = payload["caps"].get(key)
        if saved:
            cn.caps.update({k: int(v) for k, v in saved.items()})
        if key in payload.get("slots", {}):
            cn._slot_cap = int(payload["slots"][key])
        if key in tiers_out:
            cn.residency_tiers = tuple(tiers_out[key])
        cn._live_cache = None
    ch.states = states
    ch._tiers = tiers_out
    ch._cold_meta = cold_meta_out
    ch._level_versions = {k: list(v)
                          for k, v in payload["level_versions"].items()}
    ch.maintain_pending = bool(payload.get("maintain_pending", False))
    ch._snap_levels.clear()
    ch._step_jit = None
    ch._scan_jits = {}
    ch._req = None
    # tick discontinuity: the next dispatch re-uploads the cursor
    # explicitly (compiler._tick_operand)
    ch._tick_dev = None
    ch._tick_host = None
    ch._ckpt_salt = uuid.uuid4().hex[:12]  # new buffers, new link scope
    return states


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def _driver_of(target):
    """(driver, compiled_handle, host_handle) for any supported target."""
    from dbsp_tpu.compiled.compiler import CompiledHandle
    from dbsp_tpu.compiled.driver import CompiledCircuitDriver

    if isinstance(target, CompiledCircuitDriver):
        return target, target.ch, None
    if isinstance(target, CompiledHandle):
        return None, target, None
    return None, None, target


def save(target, path: str, controller: Optional[dict] = None,
         tick: Optional[int] = None,
         output_pending: Optional[Dict[str, Batch]] = None,
         read_plane: Optional[Dict[str, Batch]] = None) -> dict:
    """Write one checkpoint generation of ``target`` under ``path``.

    ``target`` is a host ``CircuitHandle``, a ``CompiledHandle``, or a
    serving ``CompiledCircuitDriver`` (which also persists its tick counter
    and the retained-feed replay window of an open validation interval).
    ``controller`` is an opaque JSON-safe dict persisted alongside (the
    Controller stores step/endpoint counters there); ``output_pending``
    maps output-endpoint names to delta batches whose sink write failed —
    persisting them keeps the output stream at-least-once across a crash
    (the input high-water marks cover the step that produced them, so a
    restore would otherwise never re-emit them); ``read_plane`` maps
    served view names to their compacted published state so a restored
    controller republishes snapshots (and answers changefeed resume
    cursors) without waiting for new traffic. Returns
    ``{"tick", "generation", "path", ...}``."""
    driver, ch, host = _driver_of(target)
    enc = _Encoder()
    linked: Dict[str, str] = {}
    linked_meta: Dict[str, dict] = {}
    copied: Dict[str, str] = {}
    if host is not None:
        payload = _save_host(host, enc)
    else:
        prev = None
        try:
            # manifest-only verification: the save path must stay
            # O(dirty state) — see load_manifest
            name, prev_payload, _ = load_manifest(path,
                                                  verify_blobs=False)
            if prev_payload.get("engine") == "compiled":
                prev = (name, prev_payload)
        except CheckpointError:
            prev = None
        if driver is not None and driver._retained:
            # open validation interval: persist the VALIDATED interval-
            # start snapshot plus the retained feeds — recovery replays
            # them deterministically past the checkpoint tick
            states = driver._snap
            base_tick = driver._retained[0][0]
            retained = [
                [t, {str(ch._op_to_index[id(op)]):
                     enc.encode(b, hint=f"r{t}i{ch._op_to_index[id(op)]}")
                     for op, b in feeds.items()}]
                for t, feeds in driver._retained]
        else:
            states = ch.states
            base_tick = driver._tick if driver is not None else 0
            retained = []
        payload, linked, linked_meta, copied = _save_compiled(
            ch, enc, states, prev, path)
        payload["retained"] = retained
        payload["tick"] = base_tick
    if tick is not None:
        payload["tick"] = int(tick)
    if controller is not None:
        payload["controller"] = controller
    if output_pending:
        payload["output_pending"] = {
            n: enc.encode(b, hint=f"op_{i}")
            for i, (n, b) in enumerate(sorted(output_pending.items()))}
    if read_plane:
        payload["read_plane"] = {
            n: enc.encode(b, hint=f"rp_{i}")
            for i, (n, b) in enumerate(sorted(read_plane.items()))}
    name, stats = _write_generation(path, payload, enc, linked,
                                    linked_meta, copied)
    return dict(stats, tick=payload["tick"], path=path, name=name)


def restore(target, path: str) -> dict:
    """Restore the newest valid generation under ``path`` into ``target``
    (a freshly rebuilt circuit / freshly compiled driver of the same
    structure). Returns ``{"tick", "generation", "fallback_from",
    "controller"}`` — ``fallback_from`` names a corrupted newer generation
    that was skipped (surface it as a ``restore`` incident)."""
    bytes_cache: Dict[str, bytes] = {}
    name, payload, fallback_from = load_manifest(path,
                                                 bytes_cache=bytes_cache)
    gen_dir = os.path.join(path, name)
    dec = _Decoder(_make_loader(gen_dir, payload, bytes_cache))
    driver, ch, host = _driver_of(target)
    engine = payload.get("engine")
    if host is not None:
        if engine != "host":
            raise CheckpointError(
                f"checkpoint engine {engine!r} cannot restore into a host "
                "circuit handle — rebuild the matching driver first")
        _restore_host(host, payload, dec)
        tick = payload.get("tick", 0)
    else:
        if engine != "compiled":
            raise CheckpointError(
                f"checkpoint engine {engine!r} cannot restore into a "
                "compiled handle")
        _restore_compiled(ch, payload, dec, gen_dir=gen_dir, path=path)
        tick = int(payload.get("tick", 0))
        if driver is not None:
            retained = [
                (int(t), {int(i): dec.decode(b) for i, b in feeds.items()})
                for t, feeds in (payload.get("retained") or [])]
            driver.restore_checkpoint(tick, retained)
    return {"tick": tick,
            "generation": payload.get("generation"),
            "name": name,
            "fallback_from": fallback_from,
            "controller": payload.get("controller"),
            "output_pending": {
                n: dec.decode(b)
                for n, b in (payload.get("output_pending") or {}).items()},
            "read_plane": {
                n: dec.decode(b)
                for n, b in (payload.get("read_plane") or {}).items()}}
