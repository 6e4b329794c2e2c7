"""Spine: the LSM-style trace of a stream — accumulated state as a small set
of consolidated batches in geometric size classes.

TPU-native rethink of the reference's fueled spine
(``crates/dbsp/src/trace/spine_fueled.rs:107``): the reference amortizes merge
work by carrying "fuel" through partially-completed merges; here a merge is a
single fused device kernel (concat + sort + segment-sum + compact), so instead
of fuel we bound *when* merges fire — two batches in the same power-of-two
capacity bucket merge immediately, giving the same O(log n) level structure
and O(1) amortized merges per insert, with no partially-merged state to track.

Host-side bookkeeping (which batches exist, their buckets) is Python; all data
movement is jitted device work. Capacities are power-of-two buckets so the set
of compiled kernel shapes stays logarithmic.
"""

from __future__ import annotations

import os
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dbsp_tpu.zset import kernels
from dbsp_tpu.zset.batch import Batch, Row, bucket_cap, concat_batches

# Device-residency budget (rows) for EACH spine: levels beyond it live in
# HOST memory as numpy-backed batches and transfer on probe, and — one
# tier further (HOST_BUDGET_ROWS) — as content-addressed blobs in the
# disk ColdStore, faulted back to host on probe with digest verification.
# None = no cap. The larger-than-device-memory story (reference: the
# RocksDB-backed PersistentTrace, trace/persistent/trace.rs:34 — a
# drop-in Spine whose cold levels spill to disk): the hierarchy is
# HBM <- host RAM <- disk, and the transfer unit is a whole cold level.
# BOTH knobs (and the store directory) are owned by dbsp_tpu.residency —
# the one config point the compiled engine shares — and aliased here for
# backward compatibility (tests monkeypatch these module attributes).
from dbsp_tpu import residency as _res  # noqa: E402

DEVICE_BUDGET_ROWS: Optional[int] = _res.DEVICE_ROWS
HOST_BUDGET_ROWS: Optional[int] = _res.HOST_ROWS

# Maintenance budget (rows one maintenance call may move/merge) — the ONE
# owner of the DBSP_TPU_MAINTAIN_BUDGET_ROWS knob; the compiled engine
# (compiled/compiler.py) imports it so both engines stay in lockstep. An
# equal-bucket compaction whose pair cost exceeds the budget defers to a
# later insert/maintain call instead of landing its whole merge in one
# tick. The trace is the union of its batches at every point, so deferral
# changes only WHEN compaction happens, never any consumer result
# (tests/test_maintenance.py proves bit-identity). 0/negative = unbounded
# (None); unset defaults to 131072 rows.
_env_maintain = os.environ.get("DBSP_TPU_MAINTAIN_BUDGET_ROWS")
if _env_maintain:
    MAINTAIN_BUDGET_ROWS: Optional[int] = (
        int(_env_maintain) if int(_env_maintain) > 0 else None)
else:
    MAINTAIN_BUDGET_ROWS = 1 << 17


def _to_cold(batch: Batch) -> Batch:
    """Move a batch's columns to host memory (numpy). jnp kernels accept
    numpy operands and device_put them per call, so cold levels stay fully
    probe-able — each probe pays the transfer, nothing persists on device
    (the fetched operand buffers die with the call)."""
    return _res.to_host(batch)


def _is_cold(batch: Batch) -> bool:
    return isinstance(batch.weights, np.ndarray)


def _is_disk(batch: Batch) -> bool:
    return isinstance(batch.weights, np.memmap)


class Spine:
    """An append-only Z-set trace with amortized device merges.

    Reference behaviors covered (``trace/mod.rs:86``): ``insert`` (:meth:`insert`),
    the dirty flag (:attr:`dirty`), lower-bound GC ``truncate_keys_below``
    (:meth:`truncate_keys_below`), and cursor-style key probes
    (:meth:`probe_ranges`).
    """

    def __init__(self, key_dtypes: Sequence, val_dtypes: Sequence = (),
                 device_budget_rows: Optional[int] = None,
                 maintain_budget_rows: Optional[int] = None,
                 host_budget_rows: Optional[int] = None,
                 cold_store=None):
        self.key_dtypes = tuple(jnp.dtype(d) for d in key_dtypes)
        self.val_dtypes = tuple(jnp.dtype(d) for d in val_dtypes)
        self.batches: List[Batch] = []
        self.dirty = False  # any insert since last clear (fixedpoint checks)
        self._consolidated: Optional[Batch] = None
        self.device_budget_rows = (device_budget_rows
                                   if device_budget_rows is not None
                                   else DEVICE_BUDGET_ROWS)
        self.host_budget_rows = (host_budget_rows
                                 if host_budget_rows is not None
                                 else HOST_BUDGET_ROWS)
        # disk tier (residency.ColdStore); lazily defaulted when the host
        # budget first forces a demotion and no store was configured
        self.cold_store = cold_store
        # per-batch disk blob metadata, keyed by batch object identity
        # (the batch object stays referenced in self.batches while listed,
        # so ids are stable for the entry's lifetime)
        self._disk_meta: Dict[int, dict] = {}
        # residency observability: transition counts keyed
        # (tier_from, tier_to, cause) and a bounded transition log —
        # exported as dbsp_tpu_trace_residency_transitions_total and
        # polled into `residency` flight events
        self.residency_stats: Dict[Tuple[str, str, str], int] = {}
        self.residency_log: List[dict] = []
        self.maintain_budget_rows = (maintain_budget_rows
                                     if maintain_budget_rows is not None
                                     else MAINTAIN_BUDGET_ROWS)
        # amortization bookkeeping: last_slice_rows is the row capacity the
        # most recent insert/maintain call actually merged (what the
        # cascade test bounds); pending_compaction flags deferred merges
        self.maintain_stats = {"merged_rows": 0, "max_slice_rows": 0,
                               "merges": 0, "forced_merges": 0}
        self.last_slice_rows = 0
        self.pending_compaction = False

    def device_resident_rows(self) -> int:
        """Capacity currently held in DEVICE memory (cold levels excluded)
        — what the budget bounds; tests and the ``dbsp_tpu_trace_device_
        resident_rows`` gauge read this. Sharded batches count their
        per-worker capacity (each worker holds ``cap`` rows of HBM), the
        same capacity :meth:`_enforce_budget` charges against the budget."""
        return sum(b.cap for b in self.batches if not _is_cold(b))

    def host_offloaded_rows(self) -> int:
        """Row capacity living in HOST memory (cold levels, disk-tier
        memmaps excluded) — exported as
        ``dbsp_tpu_trace_host_offloaded_rows``."""
        return sum(b.cap for b in self.batches
                   if _is_cold(b) and not _is_disk(b))

    def disk_resident_rows(self) -> int:
        """Row capacity living as disk blobs (memmap-backed levels)."""
        return sum(b.cap for b in self.batches if _is_disk(b))

    def tier_rows(self) -> Dict[str, int]:
        """Resident row capacity per tier (metric label values)."""
        return {_res.TIER_DEVICE: self.device_resident_rows(),
                _res.TIER_HOST: self.host_offloaded_rows(),
                _res.TIER_DISK: self.disk_resident_rows()}

    def _note_transition(self, tier_from: str, tier_to: str, rows: int,
                         cause: str) -> None:
        key = (tier_from, tier_to, cause)
        self.residency_stats[key] = self.residency_stats.get(key, 0) + 1
        if len(self.residency_log) < 512:  # bounded; stats stay exact
            self.residency_log.append(
                {"tier_from": tier_from, "tier_to": tier_to,
                 "rows": int(rows), "cause": cause})

    def _store(self):
        if self.cold_store is None:
            self.cold_store = _res.default_store()
        return self.cold_store

    def _fault(self, b: Batch, cause: str = "probe") -> Batch:
        """Fault one disk-tier batch back to host (verified read — the
        corruption-detection point; recovery + incident semantics in
        :meth:`dbsp_tpu.residency.ColdStore.read_verified`), replacing it
        in the level list. Demand-driven promotion: a probe touching a
        disk level pays exactly this."""
        meta = self._disk_meta.get(id(b))
        if meta is None:
            # untracked memmap (bookkeeping went stale): the store is
            # content-addressed, so the filenames still carry the
            # expected digests — reconstruct and VERIFY; never read raw
            hot = _res.fault_batch(_res.meta_from_batch(b), self._store())
        else:
            # meta is dropped (and its blobs released toward the sweep)
            # only AFTER the verified read succeeds: a failed fault
            # (ColdError before a recovery dir exists) must leave the
            # level tracked for the retry
            hot = _res.fault_batch(meta, self._store())
            del self._disk_meta[id(b)]
            self._store().release(meta)
            self._store().sweep()  # host engine: no replay window to wait for
        i = next(i for i, x in enumerate(self.batches) if x is b)
        self.batches[i] = hot
        self._note_transition(_res.TIER_DISK, _res.TIER_HOST, b.cap, cause)
        return hot

    def _fault_all(self, cause: str = "probe") -> None:
        for b in list(self.batches):
            if _is_disk(b):
                self._fault(b, cause)

    def _enforce_budget(self) -> None:
        """Offload the largest device levels to host until the device
        residency fits the budget. Largest-first: deep levels are the
        coldest (probed identically but re-merged the least), so one
        offload buys the most headroom per transfer.

        Budget semantics on multichip spines: SHARDED batches count toward
        the resident total (they occupy HBM and the residency gauge counts
        them) but are never offload candidates — a cold (numpy) operand
        cannot participate in the SPMD collectives that probe sharded
        levels. The budget is therefore enforced where it can be (unsharded
        levels), and a spine whose sharded levels alone exceed the budget
        stays over it — visibly, since metric and enforcement now agree."""
        if self.device_budget_rows is not None:
            hot = sorted((b for b in self.batches
                          if not _is_cold(b) and not b.sharded),
                         key=lambda b: b.cap, reverse=True)
            resident = sum(b.cap for b in self.batches if not _is_cold(b))
            # hard cap, largest level first (deep levels are re-merged the
            # least, so one offload buys the most headroom per transfer); a
            # budget below the delta size degrades to offload-every-insert —
            # bounded residency at bounded (transfer-per-probe) slowdown,
            # which is the PersistentTrace contract
            for b in hot:
                if resident <= self.device_budget_rows:
                    break
                # identity lookup: dataclass == would compare columns
                i = next(i for i, x in enumerate(self.batches) if x is b)
                self.batches[i] = _to_cold(b)
                self._note_transition(_res.TIER_DEVICE, _res.TIER_HOST,
                                      b.cap, "budget")
                resident -= b.cap
        if self.host_budget_rows is None:
            return
        # second tier: host levels past the host budget demote to the disk
        # blob store, largest-first for the same headroom-per-transfer
        # argument; probes FAULT them back (verified) on demand
        warm = sorted((b for b in self.batches
                       if _is_cold(b) and not _is_disk(b)),
                      key=lambda b: b.cap, reverse=True)
        resident = sum(b.cap for b in warm)
        for b in warm:
            if resident <= self.host_budget_rows:
                break
            cold, meta = _res.demote_batch_to_disk(b, self._store())
            i = next(i for i, x in enumerate(self.batches) if x is b)
            self.batches[i] = cold
            self._disk_meta[id(cold)] = meta
            self._note_transition(_res.TIER_HOST, _res.TIER_DISK,
                                  b.cap, "budget")
            resident -= b.cap

    # -- maintenance --------------------------------------------------------
    def insert(self, batch: Batch) -> None:
        """Insert a consolidated delta batch; merge equal-sized levels
        (amortized — see :meth:`maintain`)."""
        batch = _shrink(batch)
        if batch is None:
            return
        self.dirty = True
        self._consolidated = None
        self.batches.append(batch)
        self.batches.sort(key=lambda b: b.cap, reverse=True)
        self.maintain()
        self._enforce_budget()

    def maintain(self, budget_rows: Optional[int] = None) -> bool:
        """One bounded compaction slice: merge levels sharing a capacity
        bucket (LSM compaction) until the per-call budget is spent.

        Levels are consolidated (sorted), so each merge is one rank-based
        sorted-merge kernel, not a re-sort of the combined rows. The budget
        (default: the spine's ``maintain_budget_rows``) bounds the summed
        row capacity merged per call — the host-path analog of the
        reference's merge fuel (spine_fueled.rs:107) and of the compiled
        engine's drain budget: a cascade (merge chains re-bucketing into
        the next class) spreads over subsequent insert/maintain calls
        instead of one tick absorbing it. Deferred pairs are correct
        merely-uncompacted state (probes fan over all batches); a bucket
        holding MORE than two batches force-merges regardless of budget so
        a budget below one pair's cost degrades to late compaction, never
        to unbounded batch growth. Returns True while work remains
        (``pending_compaction``)."""
        budget = (budget_rows if budget_rows is not None
                  else self.maintain_budget_rows)
        left = budget if budget and budget > 0 else None
        sliced = 0
        merged = True
        deferred = False
        while merged:
            merged = False
            buckets: Dict[int, int] = {}
            for b in self.batches:
                buckets[b.cap] = buckets.get(b.cap, 0) + 1
            for i in range(len(self.batches) - 1):
                if self.batches[i].cap != self.batches[i + 1].cap:
                    continue
                cost = self.batches[i].cap + self.batches[i + 1].cap
                over = left is not None and cost > left - sliced
                forced = buckets.get(self.batches[i].cap, 0) > 2
                if over and not forced:
                    deferred = True
                    continue
                # a merge READS both sides: disk-tier operands fault to
                # host first (verified — the write path must never fold
                # unverified bytes into the trace)
                for b in (self.batches[i], self.batches[i + 1]):
                    if _is_disk(b):
                        self._fault(b, cause="maintain")
                a = self.batches.pop(i + 1)
                b = self.batches.pop(i)
                m = _shrink(a.merge_with(b))
                if m is not None:
                    self.batches.insert(i, m)
                    self.batches.sort(key=lambda b: b.cap, reverse=True)
                sliced += cost
                self.maintain_stats["merged_rows"] += cost
                self.maintain_stats["merges"] += 1
                if over:
                    self.maintain_stats["forced_merges"] += 1
                merged = True
                break
        self.last_slice_rows = sliced
        self.maintain_stats["max_slice_rows"] = max(
            self.maintain_stats["max_slice_rows"], sliced)
        self.pending_compaction = deferred
        return deferred

    def is_empty(self) -> bool:
        return not self.batches

    def clear_dirty(self) -> None:
        self.dirty = False

    @property
    def total_cap(self) -> int:
        return sum(b.cap for b in self.batches)

    def consolidated(self) -> Batch:
        """All levels merged into one canonical batch (cached until insert).

        O(total state) when (re)built — use :meth:`probe_ranges` /
        per-level access in per-step hot paths; this is for aggregation
        snapshots, output handles, and tests.
        """
        if self._consolidated is None:
            self._fault_all(cause="probe")  # reads every level anyway
            if not self.batches:
                self._consolidated = Batch.empty(self.key_dtypes, self.val_dtypes)
            elif len(self.batches) == 1:
                self._consolidated = self.batches[0]
            else:
                # fold small->large so the accumulator stays as small as it can
                acc = None
                for b in sorted(self.batches, key=lambda b: b.cap):
                    acc = b if acc is None else acc.merge_with(b)
                c = _shrink(acc)
                self._consolidated = c if c is not None else Batch.empty(
                    self.key_dtypes, self.val_dtypes)
        return self._consolidated

    # -- GC (reference: TraceBound truncation, operator/trace.rs:29-120) ----
    def truncate_keys_below(self, bound_key: Tuple) -> None:
        """Drop all rows whose key tuple is lexicographically < ``bound_key``.

        Consumers (windows, GC) declare monotone lower bounds; state below
        them can never affect future outputs and is reclaimed here.
        """
        self._fault_all(cause="gc")  # truncation rewrites every level
        new: List[Batch] = []
        for b in self.batches:
            kept = _shrink(_truncate_batch(b, bound_key))
            if kept is not None:
                new.append(kept)
        self._disk_meta.clear()  # every batch object was replaced
        self.batches = sorted(new, key=lambda b: b.cap, reverse=True)
        self._consolidated = None
        self._enforce_budget()

    # -- probes (cursor equivalents) ----------------------------------------
    def probe_ranges(self, query_keys: Tuple[jnp.ndarray, ...]
                     ) -> List[Tuple[Batch, jnp.ndarray, jnp.ndarray]]:
        """Per-level [lo, hi) ranges of rows matching each query key.

        Delta-proportional (O(m log n) binary-search probes per level); the
        replacement for the reference's per-batch cursors + CursorList k-way
        merge (``trace/cursor/cursor_list.rs``) — consumers fan out over the
        O(log n) levels and combine with segment reductions.
        """
        nk = len(self.key_dtypes)
        out = []
        for b in list(self.batches):
            if _is_disk(b):
                # demand-driven promotion: a probe touching a disk level
                # faults it to host (verified read; stays host until the
                # budget demotes it again)
                b = self._fault(b, cause="probe")
            tk = b.keys[:nk]
            lo = kernels.lex_probe(tk, query_keys, side="left")
            hi = kernels.lex_probe(tk, query_keys, side="right")
            out.append((b, lo, hi))
        return out

    # -- host views ----------------------------------------------------------
    def to_dict(self) -> Dict[Row, int]:
        self._fault_all(cause="probe")
        out: Dict[Row, int] = {}
        for b in self.batches:
            for r, w in b.to_dict().items():
                out[r] = out.get(r, 0) + w
                if out[r] == 0:
                    del out[r]
        return out


@jax.jit
def _truncate_weights(keys, weights, bound):
    ge = jnp.zeros(weights.shape, jnp.bool_)
    all_eq = jnp.ones(weights.shape, jnp.bool_)
    for k, bv in zip(keys, bound):
        kv = jnp.asarray(bv, k.dtype)
        ge = ge | (all_eq & (k > kv))
        all_eq = all_eq & (k == kv)
    ge = ge | all_eq
    return jnp.where(ge, weights, 0)


def _truncate_batch(b: Batch, bound_key: Tuple) -> Batch:
    nk = len(bound_key)
    w = _truncate_weights(b.keys[:nk], b.weights, tuple(bound_key))
    return Batch(b.keys, b.vals, w).consolidate()


def _shrink(batch: Batch) -> Optional[Batch]:
    """Shrink a consolidated batch to its tight capacity bucket; None if empty.

    The one host<->device sync per insert (a scalar live-row count); keeps
    level capacities proportional to live data so probe/merge cost tracks
    actual state size.
    """
    live = int(batch.live_count())
    if live == 0:
        return None
    return batch.with_cap(bucket_cap(live))
