"""Incremental per-key top-K: maintain the K extreme rows of each group.

The reference expresses top-K via SQL window functions (ROW_NUMBER <= K,
compiled by its SQL frontend into per-key sorted traversals); engine-side it
is the same delta pattern as aggregation (``aggregate/mod.rs:600``): for keys
touched by the delta, recompute the group's top-K from the input trace and
diff against the previous output.

TPU shape: gather touched groups (grow-on-demand expansion), consolidate,
then a segmented rank computed from cumulative-sum algebra — rank-from-end
``r`` of a present row within its group is O(1) from prefix sums, no sort
beyond the consolidation's. Rows with rank < K (ordered lexicographically by
the value columns; ``largest`` picks the tail) form the new top-K set;
deltas are new(+1) + old(-1) consolidated.

Ordering contract: rows rank by their VALUE columns lexicographically —
index the stream so the priority column(s) come first (e.g. for "last 10 by
close time", vals = (close_ts, ...)). Set semantics: a row with multiplicity
w > 1 occupies one slot.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from dbsp_tpu.circuit.builder import Stream
from dbsp_tpu.circuit.operator import UnaryOperator
from dbsp_tpu.operators.aggregate import (GroupGather, _unique_keys,
                                          concat_parts)
from dbsp_tpu.operators.registry import stream_method
from dbsp_tpu.operators.trace_op import TraceView
from dbsp_tpu.trace.spine import Spine
from dbsp_tpu.zset import kernels
from dbsp_tpu.zset.batch import Batch, concat_batches


@kernels._scoped
def topk_rows(qrow, qkeys, val_cols, w, k: int, largest: bool,
              weight_sign: int, q_cap: int) -> Batch:
    """Select the top-K present rows per q segment; emit with ±1 weights.

    Segment ids are query-slot indices in [0, q_cap) — sized by q_cap (like
    aggregate's _reduce_groups), NOT by the gathered-row count, which can be
    smaller when the gather capacity cache was trained on denser deltas.
    Runs under the scope ``k.topk_rows``: a device trace names it inside
    the compiled ``CTopK``'s node scope."""
    cols, w = kernels.consolidate_cols((qrow, *val_cols), w)
    qrow, val_cols = cols[0], cols[1:]
    present = w > 0
    seg = qrow  # consolidation sorted by (qrow, vals); dead rows at the end
    cum = jnp.cumsum(present)
    base_src = cum - jnp.where(present, 1, 0)
    num_seg = q_cap + 1
    seg_ids = jnp.where((qrow >= 0) & (qrow < q_cap), qrow,
                        q_cap).astype(jnp.int32)
    base = jax.ops.segment_min(base_src, seg_ids, num_segments=num_seg)
    total = jax.ops.segment_sum(jnp.where(present, 1, 0), seg_ids,
                                num_segments=num_seg)
    within = cum - base[seg_ids]          # 1-based rank among present rows
    if largest:
        rank = total[seg_ids] - within    # 0 == last (largest) present row
    else:
        rank = within - 1                 # 0 == first (smallest)
    keep = present & (rank < k) & (qrow >= 0)
    keys = tuple(
        jnp.where(keep, kc[jnp.clip(qrow, 0, kc.shape[0] - 1)],
                  kernels.sentinel_for(kc.dtype))
        for kc in qkeys)
    out_w = jnp.where(keep, weight_sign, 0).astype(w.dtype)
    out_cols, out_w = kernels.compact((*keys, *val_cols), out_w, keep)
    nk = len(qkeys)
    return Batch(out_cols[:nk], out_cols[nk:], out_w)


_topk_rows_jit = jax.jit(topk_rows,
                         static_argnames=("k", "largest", "weight_sign",
                                          "q_cap"))


def _topk_rows_factory(k: int, largest: bool, weight_sign: int, q_cap: int):
    return lambda qrow, qkeys, val_cols, w: topk_rows(
        qrow, qkeys, val_cols, w, k, largest, weight_sign, q_cap)


def _topk_rows(qrow, qkeys, val_cols, w, k, largest, weight_sign, q_cap):
    """Dispatch: per-worker under the mesh when the parts are sharded."""
    if w.ndim > 1:
        from dbsp_tpu.parallel.lift import lifted

        return lifted(_topk_rows_factory, k, largest, weight_sign, q_cap)(
            qrow, qkeys, val_cols, w)
    return _topk_rows_jit(qrow, qkeys, val_cols, w, k, largest, weight_sign,
                          q_cap)


class TopKOp(UnaryOperator):
    def __init__(self, k: int, schema, largest: bool = True, name=None):
        self.k = k
        self.largest = largest
        self.schema = schema
        self.name = name or f"topk<{k}>"
        self.out_spine = Spine(*schema)
        self._group_gather = GroupGather()
        self._old_gather = GroupGather()

    def clock_start(self, scope: int) -> None:
        if scope > 0:
            self.out_spine = Spine(*self.schema)

    def eval(self, view: TraceView) -> Batch:
        delta = view.delta
        nk = len(self.schema[0])
        if int(delta.live_count()) == 0:
            return Batch.empty(*self.schema,
                               lead=tuple(delta.weights.shape[:-1]))
        qkeys, qlive = _unique_keys(delta, nk)
        q_cap = qlive.shape[-1]  # trimmed to distinct-key bucket
        parts = []
        gathered = self._group_gather(qkeys, qlive, view.spine.batches, q_cap)
        if gathered is not None:
            g = concat_parts(gathered)
            parts.append(_topk_rows(g[0], qkeys, g[1], g[2],
                                    self.k, self.largest, 1, q_cap))
        old = self._old_gather(qkeys, qlive, self.out_spine.batches, q_cap)
        if old is not None:
            # previous top-K rows of the touched keys, retracted; K is
            # larger than any group's slot count so keep=present suffices
            o = concat_parts(old)
            parts.append(_topk_rows(o[0], qkeys, o[1], o[2],
                                    self.k, self.largest, -1, q_cap))
        if not parts:
            return Batch.empty(*self.schema)
        out = parts[0] if len(parts) == 1 else \
            concat_batches(parts).consolidate().shrink_to_fit()
        self.out_spine.insert(out)
        return out

    def state_dict(self):
        return {"out_spine": self.out_spine}

    def load_state_dict(self, state):
        self.out_spine = state["out_spine"]


@stream_method
def topk(self: Stream, k: int, largest: bool = True, name=None) -> Stream:
    """Top-K rows per key, ordered by the value columns (see module doc)."""
    from dbsp_tpu.operators.registry import require_schema

    schema = require_schema(self, "topk")
    # sharded streams stay sharded: rows are key-hash distributed, so every
    # group lives wholly on one worker and per-worker top-K unions exactly
    # (the reference's window-function path self-shards the same way)
    t = self.trace()
    out = self.circuit.add_unary_operator(
        TopKOp(k, (tuple(schema[0]), tuple(schema[1])), largest, name), t)
    out.schema = schema
    out.key_sharded = getattr(t, "key_sharded", False)
    return out
