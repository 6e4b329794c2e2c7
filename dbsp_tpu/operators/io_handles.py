"""Input and output handles: the host <-> circuit data boundary.

Reference: ``operator/input.rs`` (``add_input_zset`` :75,
``add_input_indexed_zset`` :107, upsert-style ``add_input_set/map``
:230,313) and ``operator/output.rs:29``.

Differences by design: the reference spreads input across worker threads
round-robin and merges worker outputs with ``gather``; here a single handle
owns the (device-resident) batch, and worker distribution is the shard
operator's hash exchange inside the SPMD step (parallel/exchange.py), so
handles are worker-count agnostic.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from dbsp_tpu.circuit.builder import Circuit, Stream
from dbsp_tpu.circuit.operator import SinkOperator, SourceOperator
from dbsp_tpu.operators.registry import stream_method
from dbsp_tpu.zset.batch import Batch, ColumnBlock, Row, concat_batches


class ZSetInput(SourceOperator):
    """Source draining a host-side buffer of rows, column blocks and
    batches once per tick."""

    name = "input"

    # Optional lineage tap (obs/lineage.py enable_taps): a host spine this
    # source folds every drained delta into — the raw input-table integral
    # backward provenance slicing resolves to. Both engines drain inputs
    # through this eval (the compiled serving driver calls it per tick),
    # so one tap serves both. Opt-in: None = zero cost.
    lineage_tap = None

    # (path, rows) of the latest drain, for the ``tick.build_inputs`` span:
    # ``host_block`` = the whole batch came through Batch.from_block's one
    # program, ``mixed`` = row tuples or pushed batches were folded into
    # it, ``device`` = no block at all (each array operation of
    # from_columns its own program); rows as pushed, a pushed batch
    # counting its capacity
    last_drain = ("device", 0)

    def __init__(self, key_dtypes: Sequence, val_dtypes: Sequence = ()):
        self.key_dtypes = tuple(key_dtypes)
        self.val_dtypes = tuple(val_dtypes)
        self._rows: List[Tuple[Row, int]] = []
        self._blocks: List[ColumnBlock] = []  # one per pushed block
        self._batches: List[Tuple[Batch, bool]] = []  # (batch, consolidated)

    def eval(self) -> Batch:
        from dbsp_tpu.circuit.runtime import Runtime

        rt = Runtime.current()
        workers = rt.workers if rt is not None else 1
        # SWAP the buffers out FIRST (one atomic-under-the-GIL statement):
        # consolidation below can jit-compile for hundreds of ms, and rows
        # pushed from other threads during that window must land in the
        # NEXT tick's buffer — a clear-after-read here destroyed them
        # (found by the slow-consumer fault test: a stalling sink widened
        # the eval window and rows pushed mid-step vanished)
        rows, blocks, batches, self._rows, self._blocks, self._batches = \
            self._rows, self._blocks, self._batches, [], [], []
        # canonicalize each part once, then fold with rank-merges — pushed
        # batches that are already consolidated (the common generator path)
        # are never re-sorted
        parts = [b if done else b.consolidate() for b, done in batches]
        if rows:
            parts.append(Batch.from_tuples(
                rows, self.key_dtypes, self.val_dtypes))
        if blocks:
            # the tick's POSTs as one batch: host columns padded on the
            # host and consolidated by one program per (schema, capacity),
            # the same batch from_tuples would build
            block = ColumnBlock.concat(blocks)
            parts.append(Batch.from_block(block, len(self.key_dtypes)))
        self.last_drain = (
            "host_block" if blocks and len(parts) == 1 else
            "mixed" if blocks else "device",
            len(rows) + sum(map(len, blocks)) + sum(b.cap for b, _ in batches))
        if not parts:
            return Batch.empty(self.key_dtypes, self.val_dtypes,
                               lead=(workers,) if workers > 1 else ())
        acc = parts[0]
        for p in parts[1:]:
            acc = acc.merge_with(p)
        if self.lineage_tap is not None:
            # tapped BEFORE sharding: the tap is a 1-D host integral even
            # on a worker mesh (lineage readers union state host-side)
            self.lineage_tap.insert(acc)
        if workers > 1:
            # distribute by key hash over the mesh (the reference spreads
            # input across workers at the handle, input.rs:66-67/309-311)
            from dbsp_tpu.obs.tracing import child_span
            from dbsp_tpu.parallel.exchange import shard_batch

            with child_span("tick.shard_inputs",
                            args={"rows": acc.cap, "workers": workers}):
                acc = shard_batch(acc, rt.mesh).shrink_to_fit()
        return acc

    def state_dict(self):
        # host checkpoints carry the lineage tap so restored pipelines
        # keep answering provenance queries (the pending buffers stay
        # transient — consumed counts are the controller's to persist)
        if self.lineage_tap is not None:
            return {"lineage_tap": self.lineage_tap}
        return {}

    def load_state_dict(self, state):
        tap = state.get("lineage_tap")
        if tap is not None:
            self.lineage_tap = tap


class InputHandle:
    """Host-side feeder for a :class:`ZSetInput` (reference:
    ``CollectionHandle``, input.rs:591)."""

    def __init__(self, op: ZSetInput):
        self._op = op

    def push(self, row: Row, weight: int = 1) -> None:
        self._op._rows.append((row, weight))

    def extend(self, rows) -> None:
        """Buffer weighted row tuples, or a :class:`ColumnBlock` of the
        source's columns whole: one append, so a tick drains all of a
        block or none of it."""
        if isinstance(rows, ColumnBlock):
            op = self._op
            want = tuple(np.dtype(d) for d in
                         (*op.key_dtypes, *op.val_dtypes))
            assert tuple(c.dtype for c in rows.cols) == want, (
                f"block columns {[c.dtype for c in rows.cols]} != "
                f"schema {list(want)}")
            if len(rows):
                op._blocks.append(rows)
        else:
            self._op._rows.extend(rows)

    def push_batch(self, batch: Batch, consolidated: bool = False) -> None:
        """Zero-copy path: feed an already-built (device) batch. Pass
        ``consolidated=True`` when the batch already satisfies the
        consolidated invariant (sorted, unique, dead sentinel tail) to skip
        its canonicalization sort."""
        self._op._batches.append((batch, consolidated))


class OutputOperator(SinkOperator):
    name = "output"

    # lagging consumers coalesce their backlog past this many queued deltas
    MAX_QUEUED = 256

    # build-time view-mode stamp (set by ``output()``): True when the
    # stream feeding this sink ends in ``integrate()``, i.e. every emitted
    # batch is the FULL INTEGRAL of the view (the read plane serves
    # "last"), not a per-tick delta to fold
    integral = False

    def __init__(self):
        self.current: Optional[Batch] = None
        self.step_id = 0  # monotone tick counter (lets HTTP readers dedup)
        self._consumers: Dict[int, List[Batch]] = {}
        self._next_cid = 0

    def eval(self, v: Batch) -> None:
        if isinstance(v, Batch) and v.sharded:
            # collapse to one host-side batch so every consumer (tests,
            # transports, HTTP readers) sees worker-count-independent output
            from dbsp_tpu.obs.tracing import child_span
            from dbsp_tpu.parallel.exchange import unshard_batch

            with child_span("tick.unshard_outputs",
                            args={"rows": v.cap,
                                  "workers": v.weights.shape[0]}):
                v = unshard_batch(v)
        self.current = v
        self.step_id += 1
        for q in self._consumers.values():
            q.append(v)
            if len(q) > self.MAX_QUEUED:
                # Z-set deltas compose additively, so a backlog coalesces to
                # their sum without losing information
                q[:] = [concat_batches(q).consolidate().shrink_to_fit()]


class OutputHandle:
    """Reads the value a stream produced in the latest step (reference:
    ``OutputHandle::take_from_all/consolidate``, output.rs:173-219).

    Multiple consumers (e.g. an output transport endpoint AND the HTTP
    server's ``/read``) must not share the destructive :meth:`take`: each
    should :meth:`register_consumer` and poll :meth:`read_consumer`, which
    delivers every delta exactly once per consumer (a slow consumer gets
    the Z-set sum of everything it missed, never a gap).
    """

    def __init__(self, op: OutputOperator):
        self._op = op

    def take(self) -> Optional[Batch]:
        v, self._op.current = self._op.current, None
        return v

    def peek(self) -> Optional[Batch]:
        return self._op.current

    @property
    def step_id(self) -> int:
        """Tick counter of the latest produced batch."""
        return self._op.step_id

    def register_consumer(self) -> int:
        cid = self._op._next_cid
        self._op._next_cid += 1
        self._op._consumers[cid] = []
        return cid

    def read_consumer(self, cid: int) -> Optional[Batch]:
        """Drain this consumer's pending deltas (coalesced into one batch)."""
        q = self._op._consumers[cid]
        if not q:
            return None
        out = q[0] if len(q) == 1 else \
            concat_batches(q).consolidate().shrink_to_fit()
        q.clear()
        return out

    def to_dict(self) -> Dict[Row, int]:
        v = self._op.current
        return {} if v is None else v.to_dict()

    @property
    def integral(self) -> bool:
        """True when emissions are full integrals (``integrate()`` tail),
        False for per-tick deltas — the read plane's mode switch."""
        return self._op.integral


def add_input_zset(circuit: Circuit, key_dtypes: Sequence,
                   val_dtypes: Sequence = ()) -> Tuple[Stream, InputHandle]:
    """reference: ``add_input_zset`` (input.rs:75). The returned stream's
    schema metadata propagates through schema-preserving operators."""
    from dbsp_tpu.circuit.runtime import Runtime

    op = ZSetInput(key_dtypes, val_dtypes)
    s = circuit.add_source(op)
    s.schema = (op.key_dtypes, op.val_dtypes)
    s.key_sharded = Runtime.worker_count() > 1  # sources hash-distribute
    s.shard_intent = True  # ... and would on any larger mesh too
    return s, InputHandle(op)


@stream_method
def output(self: Stream) -> OutputHandle:
    op = OutputOperator()
    # the `integrate()` builder ends in a _PlusNamed("integrate") node, so
    # the final node's operator name is a reliable build-time marker that
    # this sink sees full integrals every tick
    op.integral = getattr(self.node.operator, "name", "") == "integrate"
    self.circuit.add_sink(op, self)
    return OutputHandle(op)
