"""Pure-functional operator evals for compiled circuit execution.

Each compiled node (``C*`` class) mirrors one host operator class from
``dbsp_tpu/operators/`` but expresses its per-tick eval as a PURE function
``eval(ctx, state, inputs) -> (state', output)`` over static-capacity device
batches, so the scheduler's whole eval sequence can be traced into one XLA
program (see compiler.py). The algorithms are the same — the kernels are
literally shared with the host path (``_join_level_impl``,
``_reduce_groups_impl``, ...); what changes is the *driver*: grow-on-demand
host loops and per-eval ``device_get`` checks become static capacities plus
device-side "required capacity" scalars that the runner validates out of the
hot loop (reference analog: the dataflow-jit backend compiles circuits whose
shapes Rust generics would otherwise fix at compile time,
``crates/dataflow-jit/src/dataflow/mod.rs``).

State capacities live in ``self.caps`` (plain ints). Every eval registers its
requirements via ``ctx.require(self, cap_key, device_scalar)``; the runner
compares the running max of those scalars against the configured caps at
validation points and grows + retraces on overflow.

INPUT trace states (CTrace — the integrators consumers probe) are LEVELED
inside the program — the spine, compiled (reference: the fueled spine's
amortization contract, ``crates/dbsp/src/trace/spine_fueled.rs:1-81``).
Each trace is a static tuple of K level batches in geometric capacity
classes; a tick's delta lands in a SLOT of level 0 with one
dynamic-update-slice (O(|Δ|) copied bytes, no merge — see
``_Leveled._levels_append``), and deeper compaction happens between
validated intervals in host-driven maintenance — so per-tick HBM traffic
is O(Δ) and the merge work is amortized to one sorted-run fold per
interval instead of per tick.

Two design rules keep leveling from costing more than it saves (measured
on Nexmark q4, CPU backend — violating either regressed steady-state ~5x):

  * Consumers combine their K per-level probe results into ONE shared
    static buffer at running offsets (:func:`join_levels`,
    :func:`gather_levels`) and consolidate ONCE — sort volume stays
    O(out_cap), not O(K·out_cap), and the probes themselves are
    delta-proportional binary searches, so fan-out over levels is cheap.
  * OUTPUT traces (an aggregate's previous-outputs batch, a topk's, a
    linear aggregate's accumulators) are NOT leveled: consolidated they
    hold exactly one live row per key, so the old-value gather is an
    exact q_cap expansion. Leveled, a key's current value smears into
    un-netted insert/retract pairs across levels and the gather
    requirement grows with the RUN (observed: 98k rows gathered per tick
    for a 12.5k-event delta) — strictly worse than the single O(keys)
    merge they pay per tick.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from dbsp_tpu.zset import kernels
from dbsp_tpu.zset.batch import Batch, bucket_cap, concat_batches

# ---------------------------------------------------------------------------
# Static leveled trace (the in-program spine)
# ---------------------------------------------------------------------------

# Level count K (including the tail) and the default capacity ratio between
# adjacent levels. Level capacities self-scale to the observed delta size
# through the requirement/grow machinery; these only seed the ladder.
# Read at CTrace construction time (not import) so harnesses that know the
# planned run length can pick K before compiling — see levels_for_run().
# Clamped to >= 1: K=0 would make every levels fan-out (join_levels /
# gather_levels) trace over an empty sequence and fail obscurely.
TRACE_LEVELS = max(1, int(os.environ.get("DBSP_TPU_TRACE_LEVELS", "4")))
LEVEL0_CAP = int(os.environ.get("DBSP_TPU_TRACE_L0", "1024"))
# growth 4 measured 42% faster steady-state than 8 on Nexmark q4/CPU at the
# default protocol (11.5k vs 8.1k ev/s; p99 1.6s vs 2.0s; growth 3 within
# noise of 4): tighter capacity classes make each spill's merge cheaper
# without meaningfully increasing spill frequency
LEVEL_GROWTH = int(os.environ.get("DBSP_TPU_TRACE_GROWTH", "4"))


def lazy_post_enabled() -> bool:
    """LAZY post views: after a SLOTTED append, consumers probe the
    (consolidated) delta itself as one more ladder level instead of
    re-reading the freshly written level-0 slot — CTrace.eval stops being
    a materialization consumers wait on (the dynamic_update_slice's only
    remaining reader is the donated state carry, which XLA aliases in
    place). The Z-set a consumer sees is IDENTICAL — the written slot
    holds exactly the delta's rows — only the raw slot order of the fused
    consumers' (pre-consolidation) buffers changes, which every consumer
    canonicalizes away (CJoin consolidates, the reducers net, distinct
    reads ``pre``). ``DBSP_TPU_TRACE_LAZY_POST=0`` is the code-free A/B
    control (pairs with the ``DBSP_TPU_NATIVE`` per-kernel force-off)."""
    return os.environ.get("DBSP_TPU_TRACE_LAZY_POST", "1") != "0"


def levels_for_run(ticks: int) -> int:
    """Level count that amortizes tail merges for a planned run length.

    State ≈ ticks·Δ and L0 holds ~2 deltas, so with growth ratio g the tail
    absorbs a spill every ~2·g^(K-2) ticks; K ≈ log_g(ticks/8) deep levels
    keeps that to a handful per run. Short runs (few large batches) get
    K=1-2 — a K too high for the run length loses steady-state to spill
    overhead, and K too low loses to O(state) re-merges (BENCH round-4
    sweep, pre-slotting: K=1 2831 ev/s, K=2 4342, K=4 5231 at 96 ticks).

    Since the SLOTTED level 0 landed (one ladder of per-delta slots folded
    once per interval), l0 itself absorbs what the first deep level used
    to, so the formula carries one level less than the pre-slot tuning:
    re-measured on Nexmark q4/CPU at 100 ticks, K=3 beats K=4 on both p50
    (9.4 vs 9.8 ms) and elapsed (1.53 vs 1.62 s)."""
    import math

    if ticks <= 1:
        return 1
    extra = max(0.0, math.log(ticks / 8, LEVEL_GROWTH))
    return max(1, min(4, 1 + math.ceil(extra)))


class _Leveled:
    """Mixin managing a leveled static trace state: ``(levels, base_live)``
    where ``levels`` is a tuple of K consolidated batches (level 0 smallest,
    last = tail) and ``base_live`` is a device scalar carrying the frozen
    live-row count of levels 1..K-1. Capacity keys are "l0".."l{K-2}" plus
    the subclass's ``TAIL_KEY`` (which keeps its legacy name so
    MONOTONE_CAPS / presize semantics carry over unchanged).

    Spill scheduling is HOST-DRIVEN: the per-tick program only writes the
    delta into level 0 (a slot append — see :meth:`_levels_append`) and
    touches nothing else — levels 1..K-1 flow through the step unmodified,
    so XLA aliases them instead of copying. Draining level k into level k+1
    happens BETWEEN validated intervals in ``CompiledHandle.maintain()``
    (an earlier in-program ``lax.cond`` cascade copied every level's full
    capacity on every non-spill tick: measured ~10ms/tick per trace at q4
    state sizes — the reference runs its spine merges on background fuel
    for the same reason, spine_fueled.rs:1-81). Because only level 0
    changes inside an interval, ``base_live`` stays exact between
    maintenance points and the whole-trace size requirement (what presize's
    monotone projection keys off) costs one O(cap_l0) reduction per tick.
    """

    TAIL_KEY = "trace"

    def _init_level_caps(self) -> None:
        n = max(1, TRACE_LEVELS)
        self.level_keys: Tuple[str, ...] = tuple(
            f"l{k}" for k in range(n - 1)) + (self.TAIL_KEY,)
        cap = LEVEL0_CAP
        for key in self.level_keys[:-1]:
            self.caps.setdefault(key, bucket_cap(cap))
            cap *= LEVEL_GROWTH

    def _levels_init(self, schema, lead, migrated: Optional[Batch]):
        lv = [Batch.empty(*schema, cap=self.caps[k], lead=lead)
              for k in self.level_keys]
        # level 0's run tag is ALWAYS None: the slotted append produces an
        # untagged batch, and the tag is pytree AUX data — it must be
        # byte-identical at init, after appends, and across drains, or
        # scan carries mismatch and every tick retraces the step program
        lv[0] = lv[0].tagged(None)
        base = 0
        if migrated is not None:
            # warm start: the host spine's consolidated state becomes the tail
            lv[-1] = migrated.with_cap(self.caps[self.TAIL_KEY])
            base = int(migrated.max_worker_live())
        return (tuple(lv), jnp.full(lead, base, jnp.int64))

    def _levels_append(self, ctx, state, delta: Batch):
        """Append a delta to level 0 (the only in-program state write).

        SLOTTED append (the steady-state path): level 0 is a ladder of
        ``cap(l0) / cap(delta)`` static SLOTS of one delta capacity each.
        Appending writes the (padded, consolidated) delta into the next
        free slot with one ``dynamic_update_slice`` — O(|delta|) copied
        bytes, NO merge, NO O(cap) sentinel re-fill. The slot contents are
        sorted runs at STATIC offsets, so consumers probe them as extra
        ladder levels (:meth:`_view_levels`) and maintenance folds them
        with sorted merges once per interval instead of the step program
        merging every tick (measured ~1-1.6 ms per trace per tick at q4
        caps — the single largest per-tick cost after the fused cursors
        landed). Occupancy is DERIVED (count of non-empty slots — empty
        deltas re-use their slot), so the state layout is unchanged.
        Falls back to the legacy merge when the slot geometry doesn't hold
        (delta capacity not dividing l0) or the trace is window-GC'd
        (in-program truncation compacts across slot boundaries).

        Registers two requirements: level 0's consumed capacity (slots in
        use after this append x slot size — drained each maintenance
        interval, so its running max is the per-interval inflow) and the
        whole-trace size (base_live + level-0 rows) under ``TAIL_KEY`` —
        the monotone capacity presize projects linearly. When the slots
        are full, further rows land in the LAST slot (clobbered) and the
        capacity requirement exceeds cap — the runner's validation grows
        and replays, the standard overflow contract.
        """
        from jax import lax

        levels, base = state
        new = list(levels)
        l0 = new[0]
        dcap = delta.cap
        can_slot = (not getattr(self, "_gc_refresh", False)
                    and not getattr(self, "_no_slots", False)  # per-level
                    # consumers (range join / window / rolling) fan one
                    # launch per viewed level — see compiler.__init__
                    and len(self.level_keys) > 1  # K=1: l0 IS the tail —
                    # no maintenance drain would ever fold the slots
                    and l0.cap % dcap == 0
                    and delta.weights.ndim == l0.weights.ndim)
        # the slot size is PINNED per instance: geometry must describe the
        # CONTENT of l0, which survives across retraces — re-deriving it
        # from each trace's delta capacity would reinterpret slots written
        # at one size as sorted runs at another (unsorted garbage to every
        # fused probe). A delta whose capacity doesn't match the pin takes
        # the canonicalize-then-merge fallback below; its output (one
        # consolidated run) remains a valid slot ladder at ANY size, so
        # matching deltas resume slotting afterwards.
        if can_slot and getattr(self, "_slot_cap", None) is None:
            self._slot_cap = dcap
        slotted = can_slot and self._slot_cap == dcap
        # static per-trace decision consumed by the lazy post view (the
        # same inputs retrace to the same value, so the step program's
        # structure is stable across retraces)
        self._append_slotted = slotted
        if slotted:
            nslots = l0.cap // dcap
            w_slots = l0.weights.reshape(
                *l0.weights.shape[:-1], nslots, dcap)
            occ = jnp.sum(jnp.any(w_slots != 0, axis=-1), axis=-1)
            has = jnp.any(delta.weights != 0, axis=-1)
            start = (jnp.minimum(occ, nslots - 1) * dcap).astype(jnp.int32)
            # write ONLY when the delta has rows and a free slot exists: an
            # unconditional write would clobber the last occupied slot on
            # an empty delta at full occupancy (no overflow would fire —
            # the requirement stays == cap), silently losing rows. A full
            # ladder with a NON-empty delta also skips the write: its rows
            # are lost either way, but the capacity requirement then
            # exceeds cap and the runner replays from the snapshot.
            write = has & (occ < nslots)

            def put(dst, src):
                return jnp.where(
                    write,
                    lax.dynamic_update_slice_in_dim(
                        dst, src.astype(dst.dtype), start, axis=-1),
                    dst)

            l0_live = jnp.sum(l0.weights != 0) + jnp.sum(delta.weights != 0)
            new[0] = Batch(
                tuple(put(k, dk) for k, dk in zip(l0.keys, delta.keys)),
                tuple(put(v, dv) for v, dv in zip(l0.vals, delta.vals)),
                put(l0.weights, delta.weights))
            ctx.require(self, self.level_keys[0],
                        (occ + jnp.where(has, 1, 0)) * dcap)
            if self.TAIL_KEY != self.level_keys[0]:
                ctx.require(self, self.TAIL_KEY,
                            self._deep_live(base, new) + l0_live)
            return (tuple(new), base)
        if getattr(self, "_slot_cap", None) is not None:
            # l0 may hold slot runs: canonicalize before the merge (whose
            # contract requires sorted inputs). Only mismatched-capacity
            # deltas pay this in-program sort; stable feeds never do.
            nk0 = len(l0.keys)
            cols0, w0 = kernels.consolidate_cols(l0.cols, l0.weights)
            l0 = Batch(cols0[:nk0], cols0[nk0:], w0)
        m0 = l0.merge_with(delta)
        live0 = m0.live_count()
        ctx.require(self, self.level_keys[0], live0)
        if self.TAIL_KEY != self.level_keys[0]:
            ctx.require(self, self.TAIL_KEY,
                        self._deep_live(base, new) + live0)
        new[0] = m0.with_cap(self.caps[self.level_keys[0]]).tagged(None)
        return (tuple(new), base)

    def _deep_live(self, base, levels):
        """Live rows of levels 1..K-1 for the whole-trace requirement:
        ``base_live``, which maintenance sets to the SUM of what its drains
        moved — or, in a windowed view (``CTrace._counts_lives``), a count.
        There half of what a drain moves cancels in the merge, so the sum
        stands far above the rows held, and a capacity grown to fit it
        holds rows that are gone."""
        if getattr(self, "_counts_lives", False):
            return sum(lvl.live_count() for lvl in levels[1:])
        return base

    def _view_levels(self, levels) -> Tuple[Batch, ...]:
        """The level tuple consumers probe: slotted level 0 expands into
        its per-slot runs (static slices, each a consolidated batch), the
        deeper levels pass through. The fused trace cursors fan over the
        whole expansion in one probe, so extra slots cost probe lanes, not
        kernel launches."""
        slot = getattr(self, "_slot_cap", None)
        l0 = levels[0]
        if not slot or l0.cap == slot or l0.cap % slot != 0:
            return tuple(levels)
        slices = tuple(
            Batch(tuple(k[..., i * slot:(i + 1) * slot] for k in l0.keys),
                  tuple(v[..., i * slot:(i + 1) * slot] for v in l0.vals),
                  l0.weights[..., i * slot:(i + 1) * slot], runs=(slot,))
            for i in range(l0.cap // slot))
        return (*slices, *levels[1:])

    def _levels_repad(self, state):
        levels, base = state
        # re-tag while re-padding: levels are consolidated by contract —
        # EXCEPT a slotted level 0, whose runs live at slot offsets (its
        # state rides untagged; maintain re-tags before folding). A
        # uniform tag per level keeps the state pytree aux byte-stable
        # across drains/restores (an aux change would retrace the step).
        out = []
        for i, (b, k) in enumerate(zip(levels, self.level_keys)):
            if i == 0 and getattr(self, "_slot_cap", None) is not None:
                # a SLOTTED l0 canonicalizes on restore: the grow that
                # preceded it may have changed the producer's delta
                # capacity, and the append path re-checks the pinned slot
                # size against a consolidated l0 safely (any contiguous
                # window of a consolidated region is itself a valid
                # sorted run at every slot size). Never-slotted traces
                # keep their l0 consolidated by construction — no sort.
                b = b.consolidate().with_cap(self.caps[k]).tagged(None)
            elif i == 0:
                b = b.with_cap(self.caps[k]).tagged(None)
            else:
                b = b.with_cap(self.caps[k]).tagged((self.caps[k],))
            out.append(b)
        return (tuple(out), base)


def static_append(trace: Batch, delta: Batch) -> Tuple[Batch, jnp.ndarray]:
    """Merge ``delta`` into a fixed-capacity SINGLE-batch trace.

    Returns (new trace at the SAME capacity, required live rows). Live rows
    pack to the front after a merge, so slicing back to the trace capacity
    drops only dead tail — unless required > cap, which the runner detects.
    This is the state layout for operator OUTPUT traces (one live row per
    key; see module doc for why those must not be leveled)."""
    merged = trace.merge_with(delta)
    required = merged.live_count()
    return merged.with_cap(trace.cap), required


def join_levels(delta: Batch, levels: Sequence[Batch], nk: int, fn,
                out_cap: int, sorted_emit=None) -> Tuple[Batch, jnp.ndarray]:
    """Join a delta against ALL trace levels into ONE out_cap buffer via the
    fused trace cursor (zset/cursor.py): one probe pair over the whole
    ladder and one cross-level expansion, where the per-level loop emitted
    K probe kernels, K expansions, and K offset-scatters. With a
    permutation pair fn (``sorted_emit`` — see ``JoinCore.sorted_emit``)
    the native path applies the fn IN the call and the buffer comes back
    as one consolidated run, so the post-join consolidate rank-folds
    instead of sorting. The returned requirement is the UNCLAMPED total
    across levels — when it exceeds ``out_cap`` the tail matches drop off
    the end and the runner's validation grows the cap and replays."""
    from dbsp_tpu.zset import cursor

    assert levels, "join_levels: trace has no levels (TRACE_LEVELS >= 1)"
    out, total = cursor.join_ladder(delta, levels, nk, fn, out_cap,
                                    sorted_emit)
    return out, total.astype(jnp.int64)


def gather_levels(qkeys, qlive, levels: Sequence[Batch], out_cap: int):
    """Gather the query keys' rows from ALL trace levels into ONE shared
    (qrow, vals, w) part of capacity ``out_cap`` via the fused trace cursor
    (one ladder probe pair + one cross-level expansion). Dead slots carry
    qrow == q_cap + sentinel vals. Returns (part, unclamped total). NOTE:
    with K > 1 the combined part may hold cross-level insert/retract rows
    for the same (qrow, vals) — reducers must net them
    (``_reduce_groups_impl(..., net=True)``)."""
    from dbsp_tpu.zset import cursor

    assert levels, "gather_levels: trace has no levels (TRACE_LEVELS >= 1)"
    part, total = cursor.gather_ladder(qkeys, qlive, levels, out_cap)
    return part, total.astype(jnp.int64)


def ensure_side_cap(cn: "CNode", key: str, floor: int) -> int:
    """Size a fused join consumer's shared output buffer lazily on FIRST
    eval (compile time knows no delta shapes) — the ONE sizing helper both
    join directions and the range join share. The floor lands on
    ``bucket_cap``'s power-of-two grow ladder: the old raw ``max(64,
    delta.cap)`` guess lived OFF the ladder the requirement-driven regrow
    (CompiledHandle.grow) climbs, so the first-tick guess and the
    ladder-total requirement could drift apart across the two directions
    (left at a raw 6900, right regrown to a bucketed 8192 — two different
    capacity vocabularies for one node's A/B and presize accounting)."""
    if not cn.caps.get(key):
        cn.caps[key] = bucket_cap(max(64, floor))
    return cn.caps[key]


def trim_queries(ctx, cn: "CNode", qkeys, qlive):
    """Slice the (front-packed) unique-key buffer down to the "queries"
    capacity, requirement-checked. The compiled analog of the host path's
    ``_unique_keys`` re-bucketing (aggregate.py:211): every downstream
    gather/reduce/diff in the aggregate family is sized by this buffer, so
    leaving it at delta capacity drags delta-sized kernels through evals
    that touch few groups (a 21-group GROUP BY under a 32k-cap delta)."""
    if not cn.caps.get("queries"):
        cn.caps["queries"] = 64
    q_cap = cn.caps["queries"]
    ctx.require(cn, "queries", jnp.sum(qlive))
    return tuple(c[..., :q_cap] for c in qkeys), qlive[..., :q_cap]


@dataclasses.dataclass
class CView:
    """Compiled analog of ``operators.trace_op.TraceView``: the trace of a
    stream before (z^-1) and after this tick's append. ``pre``/``post`` are
    the LEVEL TUPLES of the leveled trace state — consumers fan out over
    them like host operators fan out over ``spine.batches``."""

    delta: Batch
    pre: Tuple[Batch, ...]
    post: Tuple[Batch, ...]


class CNode:
    """Base: a compiled counterpart of one circuit node.

    ``caps`` holds named static capacities; ``init_state`` builds the state
    pytree (or None for stateless nodes); ``eval`` must be pure/traceable.

    ``MONOTONE_CAPS`` names the capacities that integrate the stream (trace
    sizes, per-key gathers against growing groups): their requirements grow
    roughly linearly with tick count, so a warmed-up run can pre-size them
    for a planned run length (compiler.presize) instead of climbing the
    grow/retrace ladder during measurement.
    """

    MONOTONE_CAPS: frozenset = frozenset()

    # the capacities that size a state carried from tick to tick, which
    # ``repad_state`` refits after a grow: a single-batch trace's buffer and
    # (``sizes_state``) every level of a leveled trace
    STATE_CAPS: Tuple[str, ...] = ("trace", "out_trace", "acc_trace", "state")

    def __init__(self, node, op):
        self.node = node
        self.op = op
        self.caps: Dict[str, int] = {}

    def profile_meta(self) -> Dict[str, object]:
        """Graph metadata the operator profiler (obs/opprofile.py) joins
        onto this node's attribution row: enough to name the node in a
        report without walking the circuit again."""
        meta: Dict[str, object] = {
            "caps": dict(self.caps),
            "inputs": [int(i) for i in self.node.inputs],
            "sharded": bool(getattr(self, "lead", ())),
        }
        if isinstance(self, _Leveled) and hasattr(self, "level_keys"):
            meta["trace_levels"] = len(self.level_keys)
            slot = getattr(self, "_slot_cap", None)
            if slot:
                meta["slot_cap"] = int(slot)
            # tiered residency tag (dbsp_tpu/residency.py): per-level tier
            # of this trace's state, maintained by the handle's enforcement
            # OUTSIDE the jitted state pytree (tiers are host bookkeeping,
            # never traced data). Absent = fully device-resident.
            tiers = getattr(self, "residency_tiers", None)
            if tiers and any(t != "device" for t in tiers):
                meta["residency_tiers"] = list(tiers)
        return meta

    def init_state(self):
        return None

    def sizes_state(self, key: str) -> bool:
        """True where capacity ``key`` sizes state carried across ticks
        (what :meth:`repad_state` refits); False where it sizes a buffer
        the step program fills anew each tick (an input, a join's fan-out,
        an aggregate's or top-K's queries and gather, a window's slides,
        an exchange's buckets)."""
        return key in self.STATE_CAPS or \
            key in getattr(self, "level_keys", ())

    def repad_state(self, st):
        """Re-fit a snapshotted state to the CURRENT capacities (after a
        grow); default handles the single-Batch trace states."""
        cap_key = next((k for k in self.caps if self.sizes_state(k)), None)
        if cap_key and isinstance(st, Batch) and st.cap != self.caps[cap_key]:
            return st.with_cap(self.caps[cap_key])
        return st

    def note_requirement(self, key: str, required: int) -> None:
        """Hook fired with each VALIDATED requirement level — lets a node
        reclassify a capacity once observed behavior contradicts its static
        assumption (see CAggregate's gather)."""

    def note_observations(self, values: Dict[str, int]) -> None:
        """Hook fired, after a validation that found no overflow, with the
        scalars this node handed ``ctx.observe``: facts of the interval
        that ride the requirement vector and are checked against no
        capacity (the time nodes fill ``timeseries/counters.py``)."""

    def settle(self) -> bool:
        """Hook fired after every validation that found no overflow: a node
        whose capacities were PROVISIONAL until then (static bounds that no
        interval can overflow) sets them from what the interval read.
        True when a capacity changed, so the step program is traced anew."""
        return False

    def eval(self, ctx, state, inputs):  # -> (state', output)
        raise NotImplementedError


# ---------------------------------------------------------------------------
# Stateless nodes
# ---------------------------------------------------------------------------


class CInput(CNode):
    """Source: the tick's feed batch (from the traced generator or the feeds
    argument). The compiler injects the value via ctx.feeds.

    Sharded mode: the host input handle hash-distributes pushed rows
    (io_handles.py sets ``key_sharded`` on sources, mirroring the
    reference's key-hash input routing, input.rs:309-311), so the compiled
    source must uphold the same placement. A traced ``gen_fn`` produces the
    FULL tick batch on every worker (counter-based generation is pure ALU —
    replicating it is far cheaper than exchanging rows over the
    interconnect); each worker then keeps its key-hash share and compacts
    to a per-worker capacity (compaction preserves sort order, so the
    slice stays consolidated)."""

    def eval(self, ctx, state, inputs):
        batch = ctx.feeds.get(self.node.index)
        if batch is None:
            sch = (self.op.key_dtypes, self.op.val_dtypes)
            batch = Batch.empty(*sch)
        lead = getattr(self, "lead", ())
        if not lead:
            return None, batch
        # Sharded: ALWAYS register the requirement (a conditional check
        # would shift the _checks/_req index when a feed appears between
        # retraces and desynchronize validation).
        from jax import lax

        from dbsp_tpu.parallel.exchange import worker_of
        from dbsp_tpu.parallel.mesh import WORKER_AXIS

        workers = lead[0]
        w = lax.axis_index(WORKER_AXIS)
        out = batch.compacted((batch.weights != 0) &
                              (worker_of(batch.keys[0], workers) == w))
        if not self.caps.get("input"):
            # balanced-hash estimate; skew is caught by the requirement
            self.caps["input"] = bucket_cap(max(batch.cap // workers, 8) * 2)
        ctx.require(self, "input", out.live_count())
        return None, out.with_cap(self.caps["input"])

    def note_requirement(self, key: str, required: int) -> None:
        # only a sharded source registers the requirement: the worst
        # worker's share of the tick against its static capacity
        from dbsp_tpu.parallel.exchange import note_exchange_site

        note_exchange_site("input", self.node.index, required,
                           self.caps["input"])


class CPure(CNode):
    """Map/filter/flat_map — the host op's kernel is already a pure
    Batch -> Batch function; reuse it directly. With
    ``defer_consolidate`` (compiler placement pass) a map/flat_map skips
    its trailing consolidation — every consumer canonicalizes anyway."""

    def eval(self, ctx, state, inputs):
        if getattr(self, "defer_consolidate", False):
            return None, self.op._inner_raw(inputs[0])
        return None, self.op._inner(inputs[0])


class CPlus(CNode):
    def eval(self, ctx, state, inputs):
        a, b = inputs
        return None, a.merge_with(b)


class CMinus(CNode):
    def eval(self, ctx, state, inputs):
        return None, inputs[0].merge_with(inputs[1].neg())


class CStreamDistinct(CNode):
    def eval(self, ctx, state, inputs):
        return None, type(self.op)._kernel(inputs[0])


class CNeg(CNode):
    def eval(self, ctx, state, inputs):
        return None, inputs[0].neg()


class CSumN(CNode):
    def eval(self, ctx, state, inputs):
        cat = concat_batches(list(inputs))
        if getattr(self, "defer_consolidate", False):
            return None, cat
        return None, cat.consolidate()


class COutput(CNode):
    """Sink: expose the batch as a per-tick run output."""

    def eval(self, ctx, state, inputs):
        ctx.outputs[self.node.index] = inputs[0]
        return None, None


# ---------------------------------------------------------------------------
# Stateful nodes
# ---------------------------------------------------------------------------


def _migrate_spine(spine) -> Optional[Batch]:
    """One consolidated batch of a host-path spine (None if empty) — the
    state-migration bridge for warm starts; consolidates ONCE."""
    if not spine.batches:
        return None
    return spine.consolidated()


class CTrace(CNode, _Leveled):
    """integrate_trace as a leveled static trace (see module doc)."""

    MONOTONE_CAPS = frozenset({"trace"})
    TAIL_KEY = "trace"
    DEFAULT_CAP = 1024

    def __init__(self, node, op):
        super().__init__(node, op)
        self._migrated = _migrate_spine(op.spine)
        live = 0 if self._migrated is None \
            else int(self._migrated.max_worker_live())
        self.caps["trace"] = bucket_cap(max(live * 2, self.DEFAULT_CAP))
        self._init_level_caps()

    def init_state(self):
        sch = (self.op.key_dtypes, self.op.val_dtypes)
        return self._levels_init(sch, getattr(self, "lead", ()),
                                 self._migrated)

    def repad_state(self, st):
        return self._levels_repad(st)

    # the trace of a windowed view (compiler.__init__ sets it: the trace
    # under a GC bound and every trace downstream of its window): rows
    # leave as fast as they arrive, so the step program counts each level's
    # live rows and maintenance plans from those counts
    _counts_lives = False
    # each level's live rows at the last validation, until maintenance
    # consumes them, and their sum
    observed_lives: Optional[List[int]] = None
    live_rows = 0

    def note_observations(self, values: Dict[str, int]) -> None:
        from dbsp_tpu.timeseries import counters

        self.observed_lives = [values[f"live.{k}"] for k in self.level_keys]
        self.live_rows = sum(self.observed_lives)
        if "gc_truncated" in values:
            counters.note_gc(self.node.index, self.live_rows,
                             values["gc_truncated"],
                             sum(self.caps[k] for k in self.level_keys))

    def eval(self, ctx, state, inputs):
        delta = inputs[0]
        post = self._levels_append(ctx, state, delta)
        pre = self._view_levels(state[0])
        # LAZY post view (see lazy_post_enabled): after a slotted append
        # the post-tick trace IS pre + delta — hand consumers the delta as
        # one more ladder level instead of making them read the slot just
        # written. Gated on a tagged-consolidated delta (the slot ladder's
        # run invariant) — anything else keeps the materialized view.
        if getattr(self, "_append_slotted", False) and \
                delta.sorted_runs == 1 and lazy_post_enabled():
            post_view: Tuple[Batch, ...] = (*pre, delta)
        else:
            post_view = self._view_levels(post[0])
        return post, CView(delta=delta, pre=pre, post=post_view)


class CJoin(CNode):
    """Bilinear incremental join over CViews (operators/join.py semantics:
    ΔA ⋈ trace(B)_post  +  ΔB ⋈ trace(A)_pre), one consolidation."""

    def __init__(self, node, op):
        super().__init__(node, op)
        self.caps["left"] = 0    # sized on first trace from delta caps
        self.caps["right"] = 0

    def eval(self, ctx, state, inputs):
        left, right = inputs
        lcore = self.op._left_core
        rcore = self.op._right_core
        nk = lcore.nk
        cap_l = ensure_side_cap(self, "left", left.delta.cap)
        cap_r = ensure_side_cap(self, "right", right.delta.cap)
        # ΔL joins every level of trace(R) post-append; ΔR every level of
        # trace(L) pre-append — each side's K level results land in ONE
        # shared buffer (requirement = total across levels). With a
        # permutation pair fn on the native path each side comes back as
        # one consolidated run (sorted_emit), so the final consolidate is
        # a 2-run rank fold — one linear merge, NO sort; otherwise it
        # sorts 2 buffers regardless of K.
        lout, ltot = join_levels(left.delta, right.post, nk, lcore.fn,
                                 cap_l,
                                 sorted_emit=lcore.sorted_emit(
                                     left.delta, right.post))
        ctx.require(self, "left", ltot)
        rout, rtot = join_levels(right.delta, left.pre, nk, rcore.fn,
                                 cap_r,
                                 sorted_emit=rcore.sorted_emit(
                                     right.delta, left.pre))
        ctx.require(self, "right", rtot)
        out = concat_batches([lout, rout])
        if not getattr(self, "defer_consolidate", False):
            out = out.consolidate()
        return None, out


class CAggregate(CNode):
    """General incremental aggregate (Min/Max/Fold): gather touched groups
    from the input trace view, reduce, diff against own output trace.

    Semigroup aggregates (``agg.insert_combinable`` — Min/Max) take a fast
    path: groups whose delta holds only insertions combine the delta's own
    reduction with the previous output (new max = max(old max, delta max)),
    so NO history comes back from the input trace — per-tick cost is
    O(delta), not O(touched history). The combine is only sound while every
    net weight in the integrated trace is non-negative (a positive delta
    row could otherwise partially cancel an over-retracted trace row and
    surface a value that is NOT present); the state carries an
    ``ever_negative`` flag — once ANY retraction has entered the stream,
    touched groups re-gather (requirement-checked; stays zero on
    append-only streams like Nexmark bids). The reference's eval
    (aggregate/mod.rs:600) always walks the touched groups' trace cursors —
    this is a strict improvement enabled by keeping the previous outputs in
    a probe-able batch."""

    # gather grows too: touched groups' FULL histories come back from the
    # input trace, and hot groups accumulate rows over the run
    MONOTONE_CAPS = frozenset({"out_trace", "gather"})

    def __init__(self, node, op):
        super().__init__(node, op)
        self.caps["gather"] = 0
        self.caps["out_trace"] = 0
        if getattr(op.agg, "insert_combinable", False):
            # the gather only serves retracted groups -> not monotone...
            self.MONOTONE_CAPS = frozenset({"out_trace"})

    def note_requirement(self, key, required):
        # ...until a retraction actually engages the slow path: from then on
        # every touched group re-gathers its FULL history, so the gather
        # requirement does grow with the run — reclassify it as monotone so
        # presize projects it linearly instead of climbing a grow/retrace
        # ladder (each retrace is a whole-program compile on an accelerator)
        if key == "gather" and required > 0 \
                and "gather" not in self.MONOTONE_CAPS:
            self.MONOTONE_CAPS = self.MONOTONE_CAPS | {"gather"}

    def init_state(self):
        # ever_neg carries the same per-worker lead axis as the batch state:
        # every state leaf must be rank>=1 under PartitionSpec('workers') and
        # the shard_map squeeze (a[0]) assumes a leading worker axis
        lead = getattr(self, "lead", ())
        migrated = _migrate_spine(self.op.out_spine)
        if not self.caps["out_trace"]:
            live = 0 if migrated is None else int(migrated.max_worker_live())
            self.caps["out_trace"] = bucket_cap(max(live * 2, 1024))
        if migrated is not None:
            # a host-warmed spine has unknown retraction history — the fast
            # path must assume the worst
            return (migrated.with_cap(self.caps["out_trace"]),
                    jnp.full(lead, True))
        return (Batch.empty(*self.op.out_schema, cap=self.caps["out_trace"],
                            lead=lead),
                jnp.full(lead, False))

    def repad_state(self, st):
        batch, ever_neg = st
        if batch.cap != self.caps["out_trace"]:
            batch = batch.with_cap(self.caps["out_trace"])
        return (batch, ever_neg)

    def eval(self, ctx, state, inputs):
        from dbsp_tpu.operators.aggregate import _diff_outputs_impl
        from dbsp_tpu.zset import cursor

        view: CView = inputs[0]
        out_trace, ever_neg = state
        agg = self.op.agg
        nk = len(self.op.key_dtypes)
        delta = view.delta
        if not self.caps.get("queries"):
            self.caps["queries"] = 64  # trim_queries' seed, same contract
        # effective query capacity = the trim_queries slice semantics: the
        # unique-key buffer can never hold more rows than the delta has
        q_cap = min(self.caps["queries"], delta.cap)
        fast = getattr(agg, "insert_combinable", False)
        if not self.caps["gather"]:
            self.caps["gather"] = 64 if fast else max(64, 2 * q_cap)

        ever_neg = ever_neg | jnp.any(delta.weights < 0)
        # the ladder gate rides as a RUNTIME value: on the fast path the
        # slow re-gather engages only once ANY retraction has entered the
        # stream (a positive delta may then partially cancel a net-negative
        # trace row — combine would be unsound); no retrace when it flips
        flag = ever_neg if fast else jnp.asarray(True)
        # ONE fused call: unique touched keys (run-boundary scan of the
        # consolidated delta — the same scan feeds the fast path's segment
        # ids, never recomputed), previous outputs from the out trace
        # (exact q_cap expansion: it holds one live row per present key),
        # the touched groups' ladder histories netted + reduced, and the
        # fast path's delta-side reduction (cursor.agg_ladder — native
        # megakernel / stitched XLA control)
        (qkeys, qlive, nq, old_vals, old_present, lad_vals, lad_present,
         d_vals, d_present, gtot) = cursor.agg_ladder(
            delta, nk, out_trace, view.post, agg, q_cap,
            self.caps["gather"], fast, flag)
        ctx.require(self, "queries", nq)
        ctx.require(self, "gather", gtot)
        if fast:
            fast_vals = agg.combine(old_vals, old_present, d_vals,
                                    d_present)
            fast_present = old_present | d_present
            slow = qlive & jnp.broadcast_to(ever_neg, qlive.shape)
            new_vals = tuple(jnp.where(slow, sv.astype(fv.dtype), fv)
                             for sv, fv in zip(lad_vals, fast_vals))
            new_present = jnp.where(slow, lad_present, fast_present)
        else:
            new_vals, new_present = lad_vals, lad_present

        cols, w = _diff_outputs_impl(qkeys, qlive, new_vals, new_present,
                                     old_vals, old_present)
        out = Batch(cols[:nk], cols[nk:], w, runs=(int(w.shape[-1]),))
        state2, required = static_append(out_trace, out)
        ctx.require(self, "out_trace", required)
        return (state2, ever_neg), out


class CLinearAggregate(CNode):
    """Linear fast path: per-key accumulator state in a static trace batch
    (one live row per key — NOT leveled, see module doc)."""

    MONOTONE_CAPS = frozenset({"acc_trace"})

    def __init__(self, node, op):
        super().__init__(node, op)
        self.caps["acc_trace"] = 0

    def init_state(self):
        migrated = _migrate_spine(self.op.acc_spine)
        if not self.caps["acc_trace"]:
            live = 0 if migrated is None else int(migrated.max_worker_live())
            self.caps["acc_trace"] = bucket_cap(max(live * 2, 1024))
        if migrated is not None:
            return migrated.with_cap(self.caps["acc_trace"])
        return Batch.empty(*self.op._state_schema,
                           cap=self.caps["acc_trace"],
                           lead=getattr(self, "lead", ()))

    def eval(self, ctx, state, inputs):
        from dbsp_tpu.operators.aggregate import (_gather_level_impl,
                                                  _unique_keys_impl)
        from dbsp_tpu.operators.aggregate_linear import (_combine_diff_impl,
                                                         _net_state_impl,
                                                         _weigh_deltas_impl)

        agg = self.op.agg
        nk = len(self.op.key_dtypes)
        delta = inputs[0]
        qkeys, qlive = _unique_keys_impl(delta, nk)
        qkeys, qlive = trim_queries(ctx, self, qkeys, qlive)
        q_cap = qlive.shape[-1]
        acc_delta, cnt_delta = _weigh_deltas_impl(delta, agg, nk)
        # per-unique-key segment sums, packed like qkeys: trim to match
        # (ids past q_cap are caught by the "queries" requirement)
        acc_delta = tuple(a[..., :q_cap] for a in acc_delta)
        cnt_delta = cnt_delta[..., :q_cap]

        # the consolidated accumulator trace holds one live row per key, so
        # a q_cap expansion is exact — no requirement check needed; the
        # unique keys of a consolidated delta, front-packed, are sorted
        qrow, vals, w, _ = _gather_level_impl(
            qkeys, qlive, state, q_cap,
            sorted_queries=delta.sorted_runs == 1)
        old = _net_state_impl(((qrow, vals, w),), q_cap)
        out, sdiff = _combine_diff_impl(qkeys, qlive, tuple(acc_delta),
                                        cnt_delta, *old, agg, nk)
        state2, required = static_append(state, sdiff)
        ctx.require(self, "acc_trace", required)
        return state2, out


class CTopK(CNode):
    """Incremental per-key top-K (operators/topk.py): recompute touched
    groups' top-K from the input trace view, diff against the previous
    output kept in a static out trace (k live rows per key — NOT leveled,
    see module doc; the old gather is exact at k*q_cap).

    ``gather`` holds what a tick RE-READS: the whole histories of the
    groups the delta touched. It is bounded by the groups a tick touches
    times the rows a group holds, so it is a PER-DELTA capacity, not a
    monotone one, and presize does not project it as if it integrated the
    stream. (NEXmark q6's top-1 per auction re-reads 36,782 rows in tick 0
    and plateaus at ~120,000 from tick 10 on, as hot auctions rotate: the
    linear projection gave it 4,194,304 rows, 3 % full, and every tick
    sorted all of them; PERF.md 6, PR 38.) Where the histories ramp before
    they plateau, a deployment states the plateau as a multiple of the
    first reading (``deployment.per_delta_headroom``, as for an
    aggregate's ``queries``). ``out`` is the output delta's own capacity:
    the rows whose top-K changed — at most k retracted and k inserted per
    touched key — requirement-checked like ``CWindow``'s, so every node
    downstream is sized by what changed and not by the gather.

    Until an interval has validated, the three are PROVISIONAL: each is the
    static bound of what it can hold — ``queries`` the delta's width,
    ``gather`` the input trace's summed level capacities, ``out`` the new
    part's and the old part's widths, bucketed — so none can overflow, and the first
    trace of a tick whose upstream has grown reads them all exactly. On a
    capacity seeded small instead, each replay of the first tick read a
    truncated delta and uncovered the next overflow downstream (q6's tick
    0 traced seven step programs, one per seed uncovered; PERF.md 7, fault
    13). :meth:`settle` then sets each to twice its first reading, as
    ``grow`` would have.

    What a tick did rides the requirement vector (``_Ctx.observe``):
    groups touched, rows re-read, rows inserted and retracted; validation
    hands them to ``timeseries/counters.py``."""

    MONOTONE_CAPS = frozenset({"out_trace"})

    def __init__(self, node, op):
        super().__init__(node, op)
        self.caps["gather"] = 0
        self.caps["out_trace"] = 0
        self.caps["out"] = 0
        # what the last validated tick observed (note_observations)
        self.observed: Dict[str, int] = {}
        # the last requirement read of each capacity (note_requirement),
        # and whether the provisional capacities have been set from them
        self._read: Dict[str, int] = {}
        self._settled = False

    def init_state(self):
        migrated = _migrate_spine(self.op.out_spine)
        if not self.caps["out_trace"]:
            live = 0 if migrated is None else int(migrated.max_worker_live())
            self.caps["out_trace"] = bucket_cap(max(live * 2, 1024))
        if migrated is not None:
            return migrated.with_cap(self.caps["out_trace"])
        return Batch.empty(*self.op.schema, cap=self.caps["out_trace"],
                           lead=getattr(self, "lead", ()))

    def eval(self, ctx, state, inputs):
        from dbsp_tpu.operators.aggregate import (_gather_level_impl,
                                                  _unique_keys_impl)
        from dbsp_tpu.operators.topk import _topk_rows

        view: CView = inputs[0]
        nk = len(self.op.schema[0])
        delta = view.delta
        if not self._settled:  # provisional: the static bounds (docstring)
            self.caps["queries"] = delta.cap
            self.caps["gather"] = sum(lvl.cap for lvl in view.post)
        qkeys, qlive = _unique_keys_impl(delta, nk)
        qkeys, qlive = trim_queries(ctx, self, qkeys, qlive)
        q_cap = qlive.shape[-1]

        g, gtot = gather_levels(qkeys, qlive, view.post, self.caps["gather"])
        ctx.require(self, "gather", gtot)
        new_part = _topk_rows(g[0], qkeys, g[1], g[2], self.op.k,
                              self.op.largest, 1, q_cap)
        # each part holds at most k rows a touched key, and no more rows
        # than it was selected from: the gather's, or the consolidated out
        # trace's (<= k live rows a key). Both widths are exact
        kq = self.op.k * q_cap
        new_cap, old_cap = min(kq, self.caps["gather"]), min(kq, state.cap)
        o = _gather_level_impl(qkeys, qlive, state, old_cap,
                               sorted_queries=delta.sorted_runs == 1)[:3]
        old_part = _topk_rows(o[0], qkeys, o[1], o[2], self.op.k,
                              self.op.largest, -1, q_cap)
        # each part is one sorted run (by query slot, so by key, then
        # value): cut to its width, the two are merged, not sorted as the
        # gather's width. Consolidated, the live rows stand first: the
        # capacity handed on is the output's own
        out = concat_batches([new_part.with_cap(new_cap).tagged((new_cap,)),
                              old_part.tagged((old_cap,))]).consolidate()
        if not self._settled:  # on the power-of-two ladder, as its seed was
            self.caps["out"] = bucket_cap(new_cap + old_cap)
        ctx.require(self, "out", out.live_count())
        out = out.with_cap(self.caps["out"])
        state2, required = static_append(state, out)
        ctx.require(self, "out_trace", required)
        ctx.observe(self, "groups", jnp.sum(qlive))
        ctx.observe(self, "gathered", gtot)
        ctx.observe(self, "inserted", jnp.sum(out.weights > 0))
        ctx.observe(self, "retracted", jnp.sum(out.weights < 0))
        return state2, out

    def note_requirement(self, key: str, required: int) -> None:
        self._read[key] = required

    def settle(self) -> bool:
        if self._settled:
            return False
        self._settled = True
        for key in ("queries", "gather", "out"):
            self.caps[key] = bucket_cap(max(64, 2 * self._read.get(key, 0)))
        return True

    def note_observations(self, values: Dict[str, int]) -> None:
        from dbsp_tpu.timeseries import counters

        self.observed = dict(values)
        counters.note_topk(self.node.index, values["groups"],
                           values["gathered"], self.caps["gather"],
                           values["inserted"], values["retracted"],
                           self.caps["queries"], self.op.k,
                           len(self.op.schema[1]))


class CDistinct(CNode):
    """Incremental distinct over a CView (stateless given the view); the
    old-weight lookup probes every pre-tick level in one fused cursor."""

    def eval(self, ctx, state, inputs):
        from dbsp_tpu.operators.distinct import _distinct_delta_impl
        from dbsp_tpu.zset import cursor

        view: CView = inputs[0]
        old_w = cursor.old_weights_ladder(view.delta, view.pre)
        return None, _distinct_delta_impl(view.delta, old_w)


def range_gather_levels(qp, qlo, qhi, qlive, levels: Sequence[Batch],
                        out_cap: int):
    """Per-row [lo, hi] time-range gather over K trace levels in ONE fused
    cursor launch — the range twin of :func:`gather_levels` through the
    SAME shared entry point (cursor.gather_ladder with distinct lo/hi
    probe columns + the time key column gathered back; shared with
    timeseries/rolling.py's host RangeGather). Returns
    ((qrow, t, vals, w), unclamped total); dead slots carry qrow == q_cap
    (the trash segment) + sentinel cols."""
    from dbsp_tpu.zset import cursor

    assert levels
    (qrow, cols, w), total = cursor.gather_ladder(
        (qp, qlo), qlive, tuple(levels), out_cap, qhi_keys=(qp, qhi),
        gather_keys=1)
    return (qrow, cols[0], cols[1:], w), total.astype(jnp.int64)


class CRangeJoin(CNode):
    """Incremental relative-range join over CViews (operators/join_range.py
    semantics: ΔL ⋈r trace(R)_post + ΔR ⋈r trace(L)_pre), with each side's
    K per-level expansions landing in one shared static buffer."""

    def __init__(self, node, op):
        super().__init__(node, op)
        self.caps["left"] = 0
        self.caps["right"] = 0

    def _fan(self, ctx, cap_key, delta, levels, core):
        from dbsp_tpu.operators.join_range import _range_join_level_impl

        out_cap = self.caps[cap_key]
        j = jnp.arange(out_cap, dtype=jnp.int32)
        bufs = wbuf = None
        offset = jnp.asarray(0, jnp.int32)
        req = jnp.asarray(0, jnp.int64)
        for lvl in levels:
            out, total = _range_join_level_impl(
                delta, lvl, core.lo_off, core.hi_off, core.fn, out_cap)
            req = req + total.astype(jnp.int64)
            t32 = jnp.minimum(total, out_cap).astype(jnp.int32)
            idx = jnp.where(j < t32, j + offset, out_cap)
            if bufs is None:
                bufs = tuple(kernels.sentinel_fill((out_cap,), c.dtype)
                             for c in out.cols)
                wbuf = jnp.zeros((out_cap,), out.weights.dtype)
            bufs = tuple(b.at[idx].set(c, mode="drop")
                         for b, c in zip(bufs, out.cols))
            wbuf = wbuf.at[idx].set(jnp.where(j < t32, out.weights, 0),
                                    mode="drop")
            offset = jnp.minimum(offset + t32, out_cap)
        ctx.require(self, cap_key, req)
        nko = len(self.op.out_schema[0])
        return Batch(bufs[:nko], bufs[nko:], wbuf)

    def eval(self, ctx, state, inputs):
        left, right = inputs
        ensure_side_cap(self, "left", left.delta.cap)
        ensure_side_cap(self, "right", right.delta.cap)
        lout = self._fan(ctx, "left", left.delta, right.post,
                         self.op._left)
        rout = self._fan(ctx, "right", right.delta, left.pre,
                         self.op._right)
        out = concat_batches([lout, rout])
        if not getattr(self, "defer_consolidate", False):
            out = out.consolidate()
        return None, out


class CRolling(CNode):
    """Partitioned rolling aggregate (timeseries/rolling.py) over a CView:
    find dirty (p, t') slots, recompute each window [t'-range, t'] from the
    input trace levels, diff against the previous outputs kept in a static
    out trace. The window-recompute path only (the radix-tree fast path
    keeps host-driven level state; rolling queries wanting it run the host
    scheduler) — within one tick everything is the same shared-buffer fan
    machinery as the equality aggregates."""

    MONOTONE_CAPS = frozenset({"out_trace", "affected", "window"})

    def __init__(self, node, op):
        super().__init__(node, op)
        self.caps["affected"] = 0
        self.caps["dirty"] = 0
        self.caps["window"] = 0
        self.caps["out_trace"] = 0

    def init_state(self):
        migrated = _migrate_spine(self.op.out_spine)
        if not self.caps["out_trace"]:
            live = 0 if migrated is None else int(migrated.max_worker_live())
            self.caps["out_trace"] = bucket_cap(max(live * 2, 1024))
        if migrated is not None:
            return migrated.with_cap(self.caps["out_trace"])
        return Batch.empty(*self.op.out_schema,
                           cap=self.caps["out_trace"],
                           lead=getattr(self, "lead", ()))

    def eval(self, ctx, state, inputs):
        from dbsp_tpu.operators.aggregate import (_TupleMax,
                                                  _diff_outputs_impl,
                                                  _gather_level_impl,
                                                  _reduce_groups_impl)
        from dbsp_tpu.timeseries.rolling import (_dirty_rows_impl,
                                                 _rolling_reduce_impl)

        view: CView = inputs[0]
        delta = view.delta
        rng = self.op.range_ms
        dp, dt = delta.keys[0], delta.keys[1]
        dlive = delta.weights != 0
        if not self.caps["affected"]:
            self.caps["affected"] = max(64, 2 * delta.cap)
            self.caps["dirty"] = max(64, 2 * delta.cap)
            self.caps["window"] = max(64, 4 * delta.cap)

        # 1. dirty slots: trace rows in [ts, ts+range] per delta row (keys
        # only) + the delta's own rows
        key_only = [Batch(b.keys, (), b.weights) for b in view.post]
        (qrow, t, _v, w), aff_req = range_gather_levels(
            dp, dt, dt + rng, dlive, key_only, self.caps["affected"])
        ctx.require(self, "affected", aff_req)
        ap, at, alive = _dirty_rows_impl(dp, dt, dlive, qrow, t, w)
        ctx.require(self, "dirty", jnp.sum(alive))
        a_cap = self.caps["dirty"]

        def fit(arr, fill):
            n = arr.shape[-1]
            if n >= a_cap:
                return arr[..., :a_cap]
            pad = jnp.full((*arr.shape[:-1], a_cap - n), fill, arr.dtype)
            return jnp.concatenate([arr, pad], axis=-1)

        ap = fit(ap, kernels.sentinel_for(ap.dtype))
        at = fit(at, kernels.sentinel_for(at.dtype))
        alive = fit(alive, False)

        # 2. recompute each dirty window from the input trace
        (wrow, wt, wvals, ww), win_req = range_gather_levels(
            ap, at - rng, at, alive, view.post, self.caps["window"])
        ctx.require(self, "window", win_req)
        new_vals, new_present = _rolling_reduce_impl(
            wrow, wt, wvals, ww, at, self.op.agg, a_cap)

        # 3. diff vs previous outputs (one live row per (p, t'): exact)
        oqrow, ovals, ow, _ = _gather_level_impl((ap, at), alive, state,
                                                 a_cap)
        old_vals, old_present = _reduce_groups_impl(
            ((oqrow, ovals, ow),), _TupleMax(len(self.op.agg.out_dtypes)),
            a_cap)
        cols, w = _diff_outputs_impl((ap, at), alive, new_vals, new_present,
                                     old_vals, old_present)
        out = Batch(cols[:2], cols[2:], w, runs=(int(w.shape[-1]),))
        state2, required = static_append(state, out)
        ctx.require(self, "out_trace", required)
        return state2, out


class CUpsertIn(CNode):
    """Upsert source (operators/upsert.py): the host feeds a COMMAND batch
    (unique sorted keys; +1 rows carry new values, -1 rows are deletes);
    the node diffs it against the maintained map state to emit exact
    Z-set deltas — retract the touched keys' live rows, insert the new
    values (upsert.rs:37's state diff, with the state as a static batch)."""

    MONOTONE_CAPS = frozenset({"state"})

    def __init__(self, node, op):
        super().__init__(node, op)
        migrated = _migrate_spine(op.spine)
        live = 0 if migrated is None else int(migrated.max_worker_live())
        self.caps["state"] = bucket_cap(max(live * 2, 1024))
        self._migrated = migrated

    def init_state(self):
        if self._migrated is not None:
            return self._migrated.with_cap(self.caps["state"])
        return Batch.empty(self.op.key_dtypes, self.op.val_dtypes,
                           cap=self.caps["state"],
                           lead=getattr(self, "lead", ()))

    def eval(self, ctx, state, inputs):
        from dbsp_tpu.operators.aggregate import _gather_level_impl
        from dbsp_tpu.operators.upsert import _retractions

        cmds = ctx.feeds.get(self.node.index)
        if cmds is None:
            cmds = Batch.empty(self.op.key_dtypes, self.op.val_dtypes)
        nk = len(self.op.key_dtypes)
        qkeys = cmds.keys[:nk]
        qlive = cmds.weights != 0
        q_cap = qlive.shape[-1]
        qrow, vals, w, _ = _gather_level_impl(qkeys, qlive, state, q_cap)
        retract = _retractions(qrow, qkeys, vals, w)
        inserts = cmds.masked(cmds.weights > 0)
        out = concat_batches([retract, inserts]).consolidate()
        state2, required = static_append(state, out)
        ctx.require(self, "state", required)
        return state2, out


class CZ1Input(CNode):
    """Input half of a strict z^-1 feedback (operators/z1.py; the node pair
    builder.py:85-116 schedules as source + sink). Owns the delayed value
    as a static-capacity batch: the arriving value (e.g. integrate's
    ``acc = s + z1(acc)``) has a per-tick merge capacity, so it re-buckets
    to the state cap with a requirement check — the host path's
    ``shrink_to_fit`` sync, turned into the standard grow/replay contract."""

    MONOTONE_CAPS = frozenset({"trace"})

    def __init__(self, node, op):
        super().__init__(node, op)
        migrated = op.state if isinstance(op.state, Batch) else None
        live = 0 if migrated is None else int(migrated.max_worker_live())
        self.caps["trace"] = bucket_cap(max(live * 2, 1024))
        self._migrated = migrated

    def init_state(self):
        lead = getattr(self, "lead", ())
        if self._migrated is not None and \
                int(self._migrated.max_worker_live()) > 0:
            return self._migrated.with_cap(self.caps["trace"])
        zero = self.op.zero_factory()
        assert isinstance(zero, Batch), (
            "compiled z^-1 supports Batch-valued streams only")
        return Batch.empty(zero.key_dtypes(), zero.val_dtypes(),
                           cap=self.caps["trace"], lead=lead,
                           weight_dtype=zero.weights.dtype)

    def eval(self, ctx, state, inputs):
        v = inputs[0]
        merged = v if v.cap == self.caps["trace"] else \
            v.with_cap(self.caps["trace"])
        ctx.require(self, "trace", v.live_count())
        return merged, None


class CZ1Output(CNode):
    """Output half: emits the value its partner stored LAST tick (state
    flows through the states dict under the partner's index — ``ctx.states``
    is the tick's INPUT state, so this is exactly out(t) = in(t-1))."""

    def eval(self, ctx, state, inputs):
        st = ctx.states.get(str(self.node.partner))
        assert st is not None, "z1 feedback loop was never closed"
        return None, st


# ---------------------------------------------------------------------------
# Time-series nodes (watermark / apply / window)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CMaybe:
    """A device scalar stream value that may not exist yet (the host path's
    ``None`` before the first event — e.g. a watermark). ``value`` is
    arbitrary traced arithmetic; ``valid`` masks every consumer, so the
    garbage value computed before the first event never becomes observable."""

    valid: jnp.ndarray
    value: object


_WM_FLOOR = int(jnp.iinfo(jnp.int64).min) // 4  # headroom for bound arithmetic


def truncate_below(batch: Batch, bound) -> Batch:
    """Drop rows whose leading key is below ``bound`` (compiled analog of
    ``Spine.truncate_keys_below`` — the TraceBound GC, operator/trace.rs:29);
    capacity unchanged, live rows stay packed + sorted. The comparison runs
    in int64: the pre-first-bounds sentinel (_WM_FLOOR) would wrap if cast
    down to an int32 key column and truncate live negative-key rows."""
    k0 = batch.keys[0]
    return batch.compacted(
        (batch.weights != 0) &
        (k0.astype(jnp.int64) >= jnp.asarray(bound, jnp.int64)))


class CWatermark(CNode):
    """``watermark_monotonic`` (watermark.rs:33): running max of a live
    timestamp column minus lateness, as device scalars — state is
    (wm, valid) instead of the host path's ``None``-able Python int.

    Sharded: the watermark is a GLOBAL property of the stream — each
    worker's local max combines across the mesh with one ``lax.pmax``
    (the reference computes it on the unsharded stream; a collective is
    the SPMD equivalent), so every worker carries the same (wm, valid)
    and downstream window bounds agree everywhere."""

    def init_state(self):
        lead = getattr(self, "lead", ())
        return (jnp.full(lead, _WM_FLOOR, jnp.int64),
                jnp.full(lead, False))

    def eval(self, ctx, state, inputs):
        batch = inputs[0]
        ts = self.op.ts_fn(batch.keys, batch.vals).astype(jnp.int64)
        live = batch.weights != 0
        m = jnp.max(jnp.where(live, ts, _WM_FLOOR))
        any_live = jnp.any(live)
        if getattr(self, "lead", ()):
            from jax import lax

            from dbsp_tpu.parallel.mesh import WORKER_AXIS

            m = lax.pmax(m, WORKER_AXIS)
            any_live = lax.pmax(any_live.astype(jnp.int32),
                                WORKER_AXIS) > 0
        wm0, valid0 = state
        wm1 = jnp.where(any_live,
                        jnp.maximum(wm0, m - self.op.lateness), wm0)
        valid1 = valid0 | any_live
        ctx.observe(self, "watermark_ms", jnp.where(valid1, wm1, 0))
        return (wm1, valid1), CMaybe(valid1, wm1)

    watermark_ms = 0

    def note_observations(self, values: Dict[str, int]) -> None:
        from dbsp_tpu.timeseries import counters

        self.watermark_ms = values["watermark_ms"]
        counters.note_watermark(self.node.index, self.watermark_ms)


class CApply(CNode):
    """Host ``apply`` over scalar streams: trace the Python fn on the device
    value. A ``CMaybe`` input keeps its validity (the fn's host-side
    ``None`` branch is unreachable under tracing — tracers are never None)."""

    def eval(self, ctx, state, inputs):
        v = inputs[0]
        if isinstance(v, CMaybe):
            return None, CMaybe(v.valid, self.op.fn(v.value))
        return None, self.op.fn(v)


class CWindow(CNode):
    """Moving-bounds window (window.rs:75-130) over a compiled trace view.

    Same three-part delta as the host op (new rows in [a1,b1); minus rows
    that slid out of [a0,min(a1,b0)); plus rows that slid in from
    [max(b0,a1),b1)) — but range extraction is two masked slices of the
    SINGLE consolidated trace batch instead of per-spine-level cursors, and
    the pre-first-bounds tick is expressed by masking (weights to 0) rather
    than an early return. With ``gc=True`` the lower bound feeds back into
    the trace node's state via ``ctx.gc_bounds`` — the compiler truncates
    the trace inside the same XLA program (TraceBound GC)."""

    def __init__(self, node, op):
        super().__init__(node, op)
        self.caps["slide_out"] = 0
        self.caps["slide_in"] = 0
        self.caps["out"] = 0
        # rows slid out of / into the window, summed over the trace's
        # levels: of the last validated tick, and since the circuit began
        self.slid_last = {"out": 0, "in": 0}
        self.slid_total = {"out": 0, "in": 0}

    def init_state(self):
        # (a0, b0, had_bounds) — per worker under a mesh (the bounds stream
        # is globally consistent, see CWatermark, so the slices agree; each
        # worker windows its own key-hash slice and the union is exact)
        lead = getattr(self, "lead", ())
        return (jnp.full(lead, 0, jnp.int64), jnp.full(lead, 0, jnp.int64),
                jnp.full(lead, False))

    def eval(self, ctx, state, inputs):
        from dbsp_tpu.timeseries.window import _filter_window, _slice_range

        view, bounds = inputs
        if not isinstance(bounds, CMaybe):
            bounds = CMaybe(jnp.asarray(True), bounds)
        a1, b1 = (jnp.asarray(x, jnp.int64) for x in bounds.value)
        valid1 = bounds.valid
        a0, b0, had = state
        # first bounds ever -> previous window is the empty range [a1, a1)
        a0e = jnp.where(had, a0, a1)
        b0e = jnp.where(had, b0, a1)

        if not self.caps["slide_out"]:
            # seeds, like an aggregate's ``queries``: what the validations
            # read sizes them. (A first guess of the delta's capacity each
            # made q5's window hand on a delta of 9 x 327,680 rows — its
            # share and 8 slices — for 76,000 live ones, and every kernel
            # downstream was sized by it.) The output starts at the
            # delta's capacity: a tick's first rows are all inflow.
            self.caps["slide_out"] = self.caps["slide_in"] = 64
            self.caps["out"] = max(64, view.delta.cap)
        # slide ranges are extracted per trace level (shared slide caps —
        # the requirement's running max sizes them to the worst level)
        parts = [_filter_window(view.delta, a1, b1)]
        # what comes in must go out: a full window slides out in a tick
        # what a tick brings in, so the slide-out capacity is asked to hold
        # a tick's inflow from the first tick on, when nothing slides yet
        # and no reading of its own could size it
        inflow = jnp.where(valid1, parts[0].live_count(), 0)
        slid_out = slid_in = jnp.zeros((), jnp.int64)
        for lvl in view.pre:
            out_b, n_out = _slice_range(lvl, a0e, jnp.minimum(a1, b0e),
                                        self.caps["slide_out"])
            ctx.require(self, "slide_out", jnp.maximum(n_out, inflow))
            parts.append(out_b.neg())
            in_b, n_in = _slice_range(lvl, jnp.maximum(b0e, a1), b1,
                                      self.caps["slide_in"])
            ctx.require(self, "slide_in", n_in)
            parts.append(in_b)
            slid_out, slid_in = slid_out + n_out, slid_in + n_in
        # summed over the levels, where the requirements keep the worst one
        ctx.observe(self, "out", jnp.where(valid1, slid_out, 0))
        ctx.observe(self, "in", jnp.where(valid1, slid_in, 0))
        # masked: everything is dead until bounds exist. Consolidated, the
        # live rows stand first: the capacity handed on is the output's own
        out = concat_batches(parts).consolidate().masked(valid1)
        ctx.require(self, "out", out.live_count())
        out = out.with_cap(self.caps["out"])

        if self.op.gc:
            ctx.gc_bounds[self.node.inputs[0]] = \
                jnp.where(valid1, a1, jnp.asarray(_WM_FLOOR, jnp.int64))
        state2 = (jnp.where(valid1, a1, a0), jnp.where(valid1, b1, b0),
                  had | valid1)
        return state2, out

    def note_observations(self, values: Dict[str, int]) -> None:
        from dbsp_tpu.timeseries import counters

        self.slid_last = dict(values)
        for direction, rows in values.items():
            self.slid_total[direction] += rows
        counters.note_slide(self.node.index, values["out"], values["in"])


# ---------------------------------------------------------------------------
# Communication nodes (sharded compiled step only; the whole step runs under
# one shard_map, so these are plain collective calls)
# ---------------------------------------------------------------------------


class CExchange(CNode):
    """Key-hash repartition (shard.rs:89): bucket + all_to_all + compact to
    a static per-worker capacity. The all_to_all's raw output capacity is
    W x cap_local (worst-case skew); the compiled path re-buckets to
    ``caps['exchange']`` with a requirement check instead of the host path's
    per-eval scalar sync. Rows past the static bucket would fall off the
    ``with_cap`` slice — the requirement check turns that into an overflow
    REPLAY (grow + re-run the interval), counted on
    ``dbsp_tpu_exchange_overflow_total`` under kind=exchange, never
    silent data loss."""

    # worst-worker live rows at the last validation — the observable the
    # skew gauges export (occupancy ratio = last_required / cap)
    last_required: int = 0

    def note_requirement(self, key: str, required: int) -> None:
        if key == "exchange":
            from dbsp_tpu.parallel.exchange import note_exchange_site

            self.last_required = required
            note_exchange_site("exchange", self.node.index, required,
                               self.caps["exchange"])

    def eval(self, ctx, state, inputs):
        from dbsp_tpu.parallel.exchange import exchange_local

        batch = inputs[0]
        out = exchange_local(batch, self.op.nworkers)
        if not self.caps.get("exchange"):
            self.caps["exchange"] = batch.cap  # balanced-hash estimate
        ctx.require(self, "exchange", out.live_count())
        return None, out.with_cap(self.caps["exchange"])


class CUnshard(CNode):
    """All-to-one gather (gather.rs:41): the union lands on worker 0; every
    other worker holds an empty (dead-sentinel) slice. Keeping exactly ONE
    live copy preserves Z-set weights through whatever follows — a
    re-exchange re-distributes rows (not W copies of them) and an output
    union counts each row once. Output capacity is exact (sum of per-worker
    caps), so no requirement check is needed."""

    def eval(self, ctx, state, inputs):
        from jax import lax

        from dbsp_tpu.parallel.exchange import gather_local
        from dbsp_tpu.parallel.mesh import WORKER_AXIS

        union = gather_local(inputs[0])
        return None, union.masked(lax.axis_index(WORKER_AXIS) == 0)


# ---------------------------------------------------------------------------
# pytree registration for the inter-node value types
# ---------------------------------------------------------------------------
# Inside the FUSED step program CView/CMaybe only ever live within one
# trace, so they never needed to be pytrees. The segmented profiler
# (obs/opprofile.py) compiles each node's eval as its OWN jit program, so
# these values cross jit boundaries there — registering them makes that
# legal without changing anything on the fused path (no tree_map in
# compiler.py ever receives one: states, feeds, and outputs carry only
# Batches and arrays).

jax.tree_util.register_pytree_node(
    CView,
    lambda v: ((v.delta, v.pre, v.post), None),
    lambda _, c: CView(*c))
jax.tree_util.register_pytree_node(
    CMaybe,
    lambda v: ((v.valid, v.value), None),
    lambda _, c: CMaybe(*c))
