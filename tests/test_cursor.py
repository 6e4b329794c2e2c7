"""Bit-identity of the fused trace cursors and the consolidation regimes.

The perf tentpole (fused ladder probes + sortedness propagation) is only
legal because every new regime produces the IDENTICAL batches as the code
it replaced:

* ``cursor.join_ladder`` / ``cursor.gather_ladder`` /
  ``cursor.old_weights_ladder`` vs the per-level kernel loops, on
  adversarial ladders (duplicate rows across levels, sentinel tails,
  zero-net weights, dead query rows);
* ``Batch.consolidate()``'s rank-merge fold (sorted-run metadata) vs the
  full sort path;
* run-metadata propagation invariants under every tagging operator;
* ``kernels.searchsorted1`` with queries WIDER than the table dtype
  (the silent-narrowing regression);
* the same checks per worker slice on the 8-way virtual mesh
  (the dryrun_multichip path) via the sharded host join.
"""

from collections import Counter

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dbsp_tpu.zset import cursor, kernels
from dbsp_tpu.zset.batch import Batch, concat_batches

pytestmark = pytest.mark.fast


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _consolidated(rng, n_live, cap, nk=2, nv=1, key_range=40,
                  allow_neg=True):
    """A consolidated Batch with ``n_live`` random rows at capacity ``cap``
    (duplicates collapse, so live count may come out lower)."""
    lo = -3 if allow_neg else 1
    rows = []
    for _ in range(n_live):
        key = tuple(int(rng.integers(0, key_range)) for _ in range(nk + nv))
        w = int(rng.integers(lo, 4)) or 1
        rows.append((key, w))
    cols = [np.array([r[0][i] for r in rows], dtype=np.int64)
            for i in range(nk + nv)]
    ws = np.array([r[1] for r in rows], dtype=np.int64)
    return Batch.from_columns(cols[:nk], cols[nk:], ws, cap=cap)


def _batch_arrays(b: Batch):
    return tuple(np.asarray(c) for c in (*b.cols, b.weights))


def assert_batches_bitequal(a: Batch, b: Batch, msg=""):
    for x, y in zip(_batch_arrays(a), _batch_arrays(b)):
        np.testing.assert_array_equal(x, y, err_msg=msg)


def check_runs(b: Batch, context: str = "") -> None:
    """Verify the sorted-run metadata invariant: each tagged segment is a
    consolidated slice (sorted lex, unique live rows, live-packed, dead
    rows sentinel-keyed at weight 0)."""
    if b.runs is None:
        return
    assert sum(b.runs) == b.cap, f"{context}: runs {b.runs} != cap {b.cap}"
    cols = [np.asarray(c).reshape(-1, np.asarray(c).shape[-1])
            for c in b.cols]
    ws = np.asarray(b.weights).reshape(-1, np.asarray(b.weights).shape[-1])
    for wslice in range(ws.shape[0]):  # per worker slice, if sharded
        off = 0
        for r in b.runs:
            w = ws[wslice, off:off + r]
            seg = [c[wslice, off:off + r] for c in cols]
            live = w != 0
            nlive = int(live.sum())
            assert live[:nlive].all(), \
                f"{context}: run at {off} not live-packed"
            rows = list(zip(*[c[:nlive].tolist() for c in seg])) \
                if seg else [()] * nlive
            assert rows == sorted(rows), f"{context}: run at {off} unsorted"
            assert len(set(rows)) == len(rows), \
                f"{context}: duplicate live rows in run at {off}"
            for c in seg:
                dead = c[nlive:]
                if dead.size:
                    sent = np.asarray(kernels.sentinel_for(c.dtype))
                    assert (dead == sent).all(), \
                        f"{context}: dead rows not sentinel in run at {off}"
            off += r


def _ladder(rng, caps=(256, 64, 32, 16), **kw):
    """Adversarial spine ladder: overlapping key ranges so rows repeat
    across levels (some with cancelling weights)."""
    return tuple(_consolidated(rng, max(2, c // 3), c, **kw) for c in caps)


# ---------------------------------------------------------------------------
# searchsorted1 regression (satellite): wide query vs narrow table
# ---------------------------------------------------------------------------


def test_searchsorted1_wide_query_not_truncated():
    table = jnp.asarray(np.array([10, 20, 30, 40], np.int32))
    # 2^33 + 5 truncates to 5 under an int32 cast -> would insert at 0
    q = jnp.asarray(np.array([(1 << 33) + 5, -(1 << 33), 25], np.int64))
    got = np.asarray(kernels.searchsorted1(table, q))
    np.testing.assert_array_equal(got, [4, 0, 2])
    # and the common-dtype widening keeps the narrow fast path exact
    qs = jnp.asarray(np.array([5, 25, 45], np.int32))
    np.testing.assert_array_equal(
        np.asarray(kernels.searchsorted1(table, qs)), [0, 2, 4])


# ---------------------------------------------------------------------------
# fused ladder probes vs per-level loops
# ---------------------------------------------------------------------------


def test_lex_probe_ladder_matches_per_level():
    rng = np.random.default_rng(0)
    levels = _ladder(rng)
    delta = _consolidated(rng, 20, 32)
    for side in ("left", "right"):
        fused = np.asarray(cursor.lex_probe_ladder(
            [lvl.keys for lvl in levels], delta.keys, side))
        for k, lvl in enumerate(levels):
            ref = np.asarray(kernels.lex_probe(lvl.keys, delta.keys, side))
            np.testing.assert_array_equal(fused[k], ref, err_msg=side)


def test_join_ladder_matches_per_level_loop():
    from dbsp_tpu.operators.join import _join_level_impl

    rng = np.random.default_rng(1)
    fn = lambda k, lv, rv: (k, (*lv, *rv))  # noqa: E731
    for trial in range(5):
        levels = _ladder(rng, allow_neg=trial % 2 == 0)
        delta = _consolidated(rng, 10 + trial * 7, 64)
        out_cap = 2048
        fused, total = cursor.join_ladder(delta, levels, 2, fn, out_cap)
        ref_parts, ref_total = [], 0
        for lvl in levels:
            part, t = _join_level_impl(delta, lvl, 2, fn, out_cap)
            ref_parts.append(part)
            ref_total += int(t)
        assert int(total) == ref_total
        assert ref_total <= out_cap, "test shapes must not overflow"
        assert_batches_bitequal(
            fused.consolidate(),
            concat_batches(ref_parts).consolidate().with_cap(out_cap),
            "fused join != per-level join")


def test_gather_ladder_matches_per_level_loop():
    from dbsp_tpu.operators.aggregate import _gather_level_impl

    rng = np.random.default_rng(2)
    levels = _ladder(rng)
    delta = _consolidated(rng, 24, 32)
    qkeys = delta.keys
    qlive = np.asarray(delta.weights) != 0
    qlive[-3:] = False  # some dead query rows
    qlive = jnp.asarray(qlive)
    out_cap = 2048
    (qrow, vals, w), total = cursor.gather_ladder(qkeys, qlive, levels,
                                                  out_cap)
    ref_rows, ref_total = [], 0
    for lvl in levels:
        rq, rv, rw, t = _gather_level_impl(qkeys, qlive, lvl, out_cap)
        ref_total += int(t)
        for i in range(out_cap):
            if int(rw[i]) != 0 or int(rq[i]) < qlive.shape[0]:
                if int(rq[i]) < qlive.shape[0]:
                    ref_rows.append((int(rq[i]),
                                     tuple(int(c[i]) for c in rv),
                                     int(rw[i])))
    got_rows = [(int(qrow[i]), tuple(int(c[i]) for c in vals), int(w[i]))
                for i in range(out_cap) if int(qrow[i]) < qlive.shape[0]]
    assert int(total) == ref_total
    assert sorted(got_rows) == sorted(ref_rows)


def test_old_weights_ladder_matches_per_level_sum():
    from dbsp_tpu.operators.distinct import _old_weights_level_impl

    rng = np.random.default_rng(3)
    levels = _ladder(rng, nk=1, nv=1)
    delta = _consolidated(rng, 16, 32, nk=1, nv=1)
    fused = np.asarray(cursor.old_weights_ladder(delta, levels))
    ref = sum(np.asarray(_old_weights_level_impl(delta, lvl))
              for lvl in levels)
    np.testing.assert_array_equal(fused, ref)


# ---------------------------------------------------------------------------
# the probe's second formulation: sorted queries ranked by one merge
# ---------------------------------------------------------------------------


def _total_order_key(row):
    """Sort key of a row under the order lax.sort uses: NaN greatest."""
    return tuple((1, 0.0) if isinstance(v, float) and np.isnan(v)
                 else (0, v) for v in row)


def _sorted_cols(rng, n, n_dead, nk, dtype, special):
    """``nk`` columns of ``n`` rows sorted under the total order: few
    distinct values so rows repeat within and across operands, ``special``
    values (NaN for floats) among them, ``n_dead`` sentinel rows."""
    dt = np.dtype(dtype)
    sent = kernels.sentinel_scalar(dt)
    vals = [dt.type(v).item() for v in (-3, 0, 1, 2, 5, 9)] + list(special)
    rows = [tuple(vals[int(rng.integers(0, len(vals)))] for _ in range(nk))
            for _ in range(n - n_dead)] + [(sent,) * nk] * n_dead
    rows.sort(key=_total_order_key)
    return tuple(jnp.asarray(np.array([r[i] for r in rows], dt))
                 for i in range(nk))


_rank_sorted = jax.jit(kernels.rank_sorted, static_argnames=("side",))

RANK_SHAPES = {  # (table rows, dead among them, queries, dead among them)
    "m_lt_cap": (96, 20, 24, 5),
    "m_gt_cap": (24, 5, 96, 20),
    "empty_level": (0, 0, 24, 5),
    "dead_level": (32, 32, 24, 5),
}


@pytest.mark.parametrize("shape", RANK_SHAPES)
@pytest.mark.parametrize("dtype", ["int64", "int32", "float32"])
@pytest.mark.parametrize("nk", [1, 2])
@pytest.mark.parametrize("side", ["left", "right"])
def test_rank_sorted_equals_lex_probe(side, nk, dtype, shape,
                                      accelerator_dispatch):
    n, n_dead, m, m_dead = RANK_SHAPES[shape]
    rng = np.random.default_rng(n * 7 + m + nk)
    special = (float("nan"), float("inf")) if dtype == "float32" else ()
    table = _sorted_cols(rng, n, n_dead, nk, dtype, special)
    query = _sorted_cols(rng, m, m_dead, nk, dtype, special)
    want = np.asarray(kernels.lex_probe(table, query, side))
    got = np.asarray(_rank_sorted(table, query, side))
    assert got.dtype == np.int32 and got.shape == (m,)
    np.testing.assert_array_equal(got, want)


def test_rank_sorted_widens_a_narrow_table_as_the_search_does(
        accelerator_dispatch):
    table = (jnp.asarray(np.array([10, 20, 30, 40], np.int32)),)
    query = (jnp.asarray(np.array([-(1 << 33), 25, (1 << 33) + 5],
                                  np.int64)),)
    for side in ("left", "right"):
        np.testing.assert_array_equal(
            np.asarray(_rank_sorted(table, query, side)),
            np.asarray(kernels.lex_probe(table, query, side)))


def _primitives(jaxpr, into=None):
    """How often each primitive occurs in a jaxpr, its loop bodies and
    inner programs included."""
    into = Counter() if into is None else into
    for eqn in jaxpr.eqns:
        into[eqn.primitive.name] += 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives(inner, into)
    return into


def test_rank_sorted_program_streams_and_nothing_else():
    rng = np.random.default_rng(5)
    table = _sorted_cols(rng, 200, 40, 2, "int64", ())
    query = _sorted_cols(rng, 50, 10, 2, "int64", ())
    used = _primitives(jax.make_jaxpr(
        lambda t, q: kernels.rank_sorted(t, q, "right"))(table, query).jaxpr)
    assert "while" in used and "dynamic_slice" in used
    banned = {p for p in used
              if any(w in p for w in ("gather", "scatter", "sort", "cumsum"))}
    assert not banned, banned
    # the search it replaces is made of gathers: the walk does find them
    assert "gather" in _primitives(jax.make_jaxpr(
        lambda t, q: kernels._probe_search(t, q, "right"))(table,
                                                           query).jaxpr)


def test_rank_rule_on_the_cells_shapes():
    """The shapes the cells' step programs probe (my chip run, PR 37, call
    1) and what the chip measured cheaper at each (tools/probe_rates.py)."""
    merge, search = kernels.rank_by_merge, lambda *a: not merge(*a)
    # q4's bids delta, 65,536 lanes, in the auctions' trace: a slot of one
    # auctions delta, the deep levels, the tail (0.28-0.62 ms against
    # 12.2-17.9 ms by the search)
    assert all(merge(65_536, cap, 1)
               for cap in (4_096, 32_768, 131_072, 262_144))
    # q4's auctions delta, 4,096 lanes, in the bids' trace: a slot merges
    # (0.23 against 1.06 ms), the levels of millions keep the search (1.39
    # against 5.88 ms at 2,097,152 rows)
    assert merge(4_096, 65_536, 1)
    assert all(search(4_096, cap, 1)
               for cap in (524_288, 2_097_152, 4_194_304))
    # q4's aggregate: 8,192 unique keys of two columns in the joined trace
    assert merge(8_192, 262_144, 2) and merge(8_192, 1_048_576, 2)
    assert search(8_192, 2_097_152, 2) and search(8_192, 4_194_304, 2)
    # q3's joins: 1,024 and 4,096 lanes in levels of 1,024 to 32,768 rows
    assert all(merge(m, cap, 1) for m in (1_024, 4_096)
               for cap in (1_024, 4_096, 16_384, 32_768))
    # measured either side of the line at 2,097,152 rows
    assert search(16_384, 2_097_152, 1) and merge(65_536, 2_097_152, 1)
    # a handful of lanes against a level: the search
    assert search(64, 65_536, 2)


def _took(before):
    return {k for k, v in kernels.KERNEL_DISPATCH_COUNTS.items()
            if v != before.get(k, 0)}


def test_probe_ladder_sends_each_level_its_cheaper_way(accelerator_dispatch):
    rng = np.random.default_rng(6)
    caps = (1 << 15, 64, 128)  # a level of the search, two of the merge
    m = 48
    assert [kernels.rank_by_merge(m, c, 2) for c in caps] == \
        [False, True, True]
    tables = [_sorted_cols(rng, c, c // 3, 2, "int64", ()) for c in caps]
    query = _sorted_cols(rng, m, 9, 2, "int64", ())
    for side in ("left", "right"):
        before = dict(kernels.KERNEL_DISPATCH_COUNTS)
        fused = np.asarray(cursor.lex_probe_ladder(
            tables, query, side, sorted_queries=True))
        counts = {k: v - before.get(k, 0)
                  for k, v in kernels.KERNEL_DISPATCH_COUNTS.items()
                  if k[0] == "probe_ladder" and v != before.get(k, 0)}
        assert counts == {("probe_ladder", "xla"): 1,
                          ("probe_ladder", "xla_merge"): 2}
        for k, t in enumerate(tables):
            np.testing.assert_array_equal(
                fused[k], np.asarray(kernels.lex_probe(t, query, side)))
    # no claim, no merge
    before = dict(kernels.KERNEL_DISPATCH_COUNTS)
    cursor.lex_probe_ladder(tables, query, "left")
    assert _took(before) == {("probe_ladder", "xla")}


@pytest.mark.parametrize("consumer", ["join_ladder", "gather_ladder",
                                      "agg_ladder", "old_weights_ladder"])
def test_consumers_claim_of_sorted_queries_holds(consumer,
                                                 accelerator_dispatch):
    """Each consumer that states its queries are sorted gives, on random
    consolidated deltas, what it gives without the statement (an untagged
    delta keeps the search)."""
    from dbsp_tpu.operators.aggregate import Max, _unique_keys_impl

    rng = np.random.default_rng(7)
    fn = lambda k, lv, rv: (k, (*lv, *rv))  # noqa: E731
    for trial in range(3):
        levels = _ladder(rng, allow_neg=trial != 1)
        delta = _consolidated(rng, 12 + 9 * trial, 64)
        check_runs(delta, consumer)
        outs = []
        for claim in (True, False):
            d = delta if claim else delta.tagged(None)
            before = dict(kernels.KERNEL_DISPATCH_COUNTS)
            if consumer == "join_ladder":
                out, total = cursor.join_ladder(d, levels, 2, fn, 2048)
                flat = (*_batch_arrays(out), np.asarray(total))
            elif consumer == "gather_ladder":
                qkeys, qlive = _unique_keys_impl(delta, 2)
                (qrow, vals, w), total = cursor.gather_ladder(
                    qkeys, qlive, levels, 2048, sorted_queries=claim)
                flat = (qrow, *vals, w, total)
            elif consumer == "agg_ladder":
                out_trace = _consolidated(rng, 8, 32, nk=2, nv=1)
                flat = [x for x in cursor._agg_ladder_stitched(
                    d, 2, out_trace, levels, Max(0), 64, 2048, False,
                    jnp.asarray(True)) if x is not None]
                flat = [y for x in flat
                        for y in (x if isinstance(x, tuple) else (x,))]
            else:
                flat = (cursor.old_weights_ladder(d, levels),)
            merged = ("probe_ladder", "xla_merge") in _took(before)
            assert merged == claim, (consumer, claim)
            outs.append([np.asarray(x) for x in flat])
        if consumer == "agg_ladder":
            continue  # a fresh random out trace each way: the claim's
            # engagement is what this case checks
        for a, b in zip(*outs):
            np.testing.assert_array_equal(a, b, err_msg=consumer)


def test_ladder_steps_carry_their_scopes(accelerator_dispatch):
    """A device trace splits a join into probe, expand and gather
    (tools/trace_scopes.py) because each step lowers under its own name:
    the merge inside the probe too, under the caller's scopes."""
    rng = np.random.default_rng(9)
    levels, delta = _ladder(rng), _consolidated(rng, 20, 32)
    fn = lambda k, lv, rv: (k, (*lv, *rv))  # noqa: E731
    text = jax.jit(lambda d, lv: cursor.join_ladder(d, lv, 2, fn, 256)).lower(
        delta, levels).as_text(debug_info=True)
    for scope in ("k.lex_probe_ladder/k.rank_sorted/while/body/",
                  "k.expand_ladder/", "k._select_gather/"):
        assert scope in text, scope


def _table(rng, n_live, cap, nk, key_range):
    """A consolidated level of ``nk`` int64 keys and one value: few keys,
    so a key holds several rows; a sentinel tail past the live rows."""
    cols = [rng.integers(0, key_range, n_live) for _ in range(nk + 1)]
    return Batch.from_columns([jnp.asarray(c) for c in cols[:nk]],
                              [jnp.asarray(cols[nk])],
                              jnp.asarray(rng.integers(1, 3, n_live)),
                              cap=cap)


# (nk, table live rows, table capacity, query capacity, the rule's choice)
GATHER_LEVEL_CASES = {
    "nk1_merge": (1, 90, 128, 64, True),
    "nk2_merge": (2, 90, 128, 64, True),
    "nk1_search": (1, 3_000, 1 << 15, 64, False),
    "nk2_search": (2, 3_000, 1 << 15, 64, False),
    "empty_table_merge": (2, 0, 128, 64, True),
}


@pytest.mark.parametrize("case", GATHER_LEVEL_CASES)
def test_gather_level_claim_of_sorted_queries_holds(case,
                                                    accelerator_dispatch):
    """``_gather_level_impl`` told its queries are sorted gives what the
    search gives, lane for lane: the front-packed unique keys of a
    consolidated delta, some absent from the table, dead sentinel lanes
    behind them, against a level whose keys repeat and whose tail is dead.
    Each side takes the formulation ``rank_by_merge`` prices cheaper."""
    from dbsp_tpu.operators.aggregate import (_gather_level_impl,
                                              _unique_keys_impl)

    nk, n_live, cap, m, merges = GATHER_LEVEL_CASES[case]
    assert kernels.rank_by_merge(m, cap, nk) == merges
    rng = np.random.default_rng(cap + n_live + nk)
    key_range = 12 if cap < 1024 else 400
    level = _table(rng, n_live, cap, nk, key_range)
    # queries over twice the table's key range: about half are absent
    delta = _table(rng, 40, m, nk, 2 * key_range)
    qkeys, qlive = _unique_keys_impl(delta, nk)
    assert 0 < int(jnp.sum(qlive)) < m  # live lanes, then dead ones
    outs = {}
    for claim in (True, False):
        before = dict(kernels.KERNEL_DISPATCH_COUNTS)
        outs[claim] = [np.asarray(x) for x in jax.tree.leaves(
            _gather_level_impl(qkeys, qlive, level, 4 * cap,
                               sorted_queries=claim))]
        counts = {k: v - before.get(k, 0)
                  for k, v in kernels.KERNEL_DISPATCH_COUNTS.items()
                  if k[0] == "probe" and v != before.get(k, 0)}
        took = "xla_merge" if claim and merges else "xla"
        assert counts == {("probe", took): 2}, (case, claim, counts)
    for a, b in zip(outs[True], outs[False]):
        np.testing.assert_array_equal(a, b, err_msg=case)
    assert int(outs[True][-1]) > 0 or n_live == 0  # rows were gathered


@pytest.mark.parametrize("op", ["linear_count", "max", "topk"])
def test_compiled_aggregate_equals_its_search_form(op, accelerator_dispatch,
                                                   monkeypatch):
    """A compiled ``CLinearAggregate`` (a count), ``CAggregate`` (a max)
    and ``CTopK`` (a top-2) over a stream with retractions give, tick for
    tick, what they give with every single-level probe searched: the form
    before their sorted queries were stated."""
    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.compiled.driver import CompiledCircuitDriver
    from dbsp_tpu.operators import LinearCount, Max, add_input_zset

    def build(c):
        s, h = add_input_zset(c, (jnp.int64, jnp.int64), (jnp.int64,))
        view = {"linear_count": lambda: s.aggregate(LinearCount()),
                "max": lambda: s.aggregate(Max(0)),
                "topk": lambda: s.topk(2)}[op]()
        return h, view.output()

    rng = np.random.default_rng(11)
    ticks, live = [], []
    for n in (90, 15, 15):
        rows = [((int(rng.integers(0, 9)), int(rng.integers(0, 3)),
                  int(rng.integers(0, 50))), 1) for _ in range(n)]
        gone = [live[i] for i in rng.choice(len(live), min(20, len(live)),
                                            replace=False)] if live else []
        live = [r for r in live if r not in gone] + [r for r, _ in rows]
        ticks.append(rows + [(r, -1) for r in gone])

    def run():
        handle, (h, out) = Runtime.init_circuit(1, build)
        driver = CompiledCircuitDriver(handle)
        before = dict(kernels.KERNEL_DISPATCH_COUNTS)
        seen = []
        for rows in ticks:
            h.extend(rows)
            driver.step()
            seen.append(out.to_dict())
        return seen, ("probe", "xla_merge") in _took(before)

    claimed, merged = run()
    assert merged
    lex_probe = kernels.lex_probe
    monkeypatch.setattr(kernels, "lex_probe",
                        lambda t, q, side="left", sorted_queries=False:
                        lex_probe(t, q, side))
    searched, merged = run()
    assert not merged
    assert claimed == searched
    # every tick changed the view, and the later ones retracted rows
    assert all(claimed) and all(min(d.values()) < 0 for d in claimed[1:])


def test_range_gather_never_ranks_by_merge(accelerator_dispatch):
    rng = np.random.default_rng(8)
    levels = _ladder(rng)
    delta = _consolidated(rng, 24, 32)
    qlive = delta.weights != 0
    qhi = (delta.keys[0], delta.keys[1] + 5)
    want = cursor.gather_ladder(delta.keys, qlive, levels, 2048,
                                qhi_keys=qhi, gather_keys=1)
    before = dict(kernels.KERNEL_DISPATCH_COUNTS)
    got = cursor.gather_ladder(delta.keys, qlive, levels, 2048, qhi_keys=qhi,
                               gather_keys=1, sorted_queries=True)
    assert ("probe_ladder", "xla_merge") not in _took(before)
    (qrow, vals, w), total = got
    (qrow0, vals0, w0), total0 = want
    for a, b in zip((qrow, *vals, w, total), (qrow0, *vals0, w0, total0)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# the gather's second formulation: one gather from the levels laid end to end
# ---------------------------------------------------------------------------


def _mixed(rng, n_live, cap, key_range=30):
    """A consolidated batch of two int64 keys and an int32 and a bool
    value column."""
    k = [rng.integers(0, key_range, n_live) for _ in range(2)]
    v32 = rng.integers(-5, 5, n_live).astype(np.int32)
    vb = rng.integers(0, 2, n_live).astype(bool)
    w = rng.integers(1, 3, n_live).astype(np.int64)
    return Batch.from_columns([jnp.asarray(c) for c in k],
                              [jnp.asarray(v32), jnp.asarray(vb)],
                              jnp.asarray(w), cap=cap)


def _all_dead(b: Batch) -> Batch:
    """``b`` with every weight 0 and its keys kept: probes still match its
    rows, and the gather reads weight 0 and their values."""
    return Batch(b.keys, b.vals, jnp.zeros_like(b.weights), b.runs)


def _select_case(k):
    """``_select_gather`` alone: K levels of mixed capacities (one of a
    single row at K = 5), int64, int32 and bool columns, slots spread over
    the levels with ``src`` below twice the largest capacity, so most slots
    of the smaller levels read past their level's end."""
    caps = {1: (48,), 2: (64, 16), 3: (256, 8, 32), 4: (256, 64, 32, 16),
            5: (512, 128, 1, 64, 16)}[k]
    rng = np.random.default_rng(40 + k)
    levels = [(jnp.asarray(rng.integers(-9, 9, c)),
               jnp.asarray(rng.integers(-9, 9, c).astype(np.int32)),
               jnp.asarray(rng.integers(0, 2, c).astype(bool)))
              for c in caps]
    level = jnp.asarray(rng.integers(0, k, 200).astype(np.int32))
    src = jnp.asarray(rng.integers(0, 2 * max(caps), 200).astype(np.int32))
    return lambda: cursor._select_gather(levels, level, src)


def _ladder_case(case):
    rng = np.random.default_rng(50)
    fn = lambda k, lv, rv: (k, (*lv, *rv))  # noqa: E731
    if case == "join_empty_and_dead_levels":
        levels = (_mixed(rng, 90, 256), Batch.empty(
            (jnp.int64, jnp.int64), (jnp.int32, jnp.bool_), cap=64),
            _all_dead(_mixed(rng, 20, 32)), _mixed(rng, 10, 16))
        delta = _mixed(rng, 25, 64)
        # most of the 2,048 slots are dead and resolve past their level
        return lambda: cursor.join_ladder(delta, levels, 2, fn, 2048)
    levels = _ladder(rng)
    delta = _consolidated(rng, 24, 32)
    if case == "gather_ladder":
        return lambda: cursor.gather_ladder(
            delta.keys, delta.weights != 0, levels, 1024,
            sorted_queries=True)
    if case == "range_gather_keys":
        qhi = (delta.keys[0], delta.keys[1] + 5)
        return lambda: cursor.gather_ladder(
            delta.keys, delta.weights != 0, levels, 1024, qhi_keys=qhi,
            gather_keys=1)
    assert case == "lifted_per_worker"
    from dbsp_tpu.parallel.exchange import spmd
    from dbsp_tpu.parallel.mesh import make_mesh

    def stack(batches):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *batches)

    w = 4
    wlevels = tuple(stack([_consolidated(rng, max(2, c // 3), c)
                           for _ in range(w)]) for c in (128, 32, 16))
    wdelta = stack([_consolidated(rng, 20, 32) for _ in range(w)])
    # a new SPMD callable each call: each dispatch traces its own program
    return lambda: jax.jit(spmd(make_mesh(w), lambda d, lv: cursor.join_ladder(
        d, lv, 2, fn, 512)))(wdelta, wlevels)


FLAT_CASES = (*(f"select_k{k}" for k in range(1, 6)),
              "join_empty_and_dead_levels", "gather_ladder",
              "range_gather_keys", "lifted_per_worker")


@pytest.mark.parametrize("case", FLAT_CASES)
def test_flat_gather_equals_per_level_bit_for_bit(case, accelerator_dispatch,
                                                  monkeypatch):
    """Both accelerator formulations of ``_select_gather`` read the same
    cell per slot, dead slots' clamped reads included: every output array,
    sentinels and garbage too, is equal bit for bit and of one dtype."""
    call = _select_case(int(case[-1])) if case.startswith("select_") \
        else _ladder_case(case)
    outs = {}
    for flat in (False, True):
        monkeypatch.setattr(kernels, "gather_flat",
                            lambda *a, flat=flat: flat)
        before = dict(kernels.KERNEL_DISPATCH_COUNTS)
        outs[flat] = [np.asarray(x) for x in jax.tree.leaves(call())]
        took = {b for k, b in _took(before) if k == "gather"}
        assert took == {"xla_flat" if flat else "xla"}, (case, took)
    assert len(outs[True]) == len(outs[False])
    for a, b in zip(outs[False], outs[True]):
        assert a.dtype == b.dtype, case
        np.testing.assert_array_equal(a, b, err_msg=case)


def test_gather_rule_on_the_cells_shapes():
    """What ``kernels.gather_flat`` takes at the shapes of the cells' step
    programs (PERF.md 5)."""
    flat = kernels.gather_flat
    # q6's top-1: 262,144 slots from the joined bids' four levels
    q6_levels = (4_194_304, 1_048_576, 262_144, 65_536)
    assert flat(262_144, q6_levels, 6)
    # q4's join: 131,072 slots from K = 6 levels of the auctions' trace
    assert flat(131_072, (262_144, 131_072, 32_768, 4_096, 4_096, 4_096), 5)
    # a few dozen lanes against levels of millions: one gather a level
    assert not flat(64, q6_levels, 6)
    # one level: the direct gather, whatever the width
    assert not flat(262_144, (4_194_304,), 6)


@pytest.mark.parametrize("flat", [True, False])
def test_flat_gather_program_gathers_once_a_column(flat, monkeypatch,
                                                   accelerator_dispatch):
    """The flat form's program gathers each column once, from one
    concatenation; the per-level form gathers each column once a level."""
    monkeypatch.setattr(kernels, "gather_flat", lambda *a: flat)
    rng = np.random.default_rng(60)
    caps, ncols = (256, 64, 32, 16), 3
    levels = [tuple(jnp.asarray(rng.integers(0, 9, c)) for _ in range(ncols))
              for c in caps]
    level = jnp.zeros(100, jnp.int32)
    jaxpr = jax.make_jaxpr(cursor._select_gather)(levels, level, level).jaxpr
    used = _primitives(jaxpr)
    assert used["gather"] == ncols * (1 if flat else len(caps))
    assert used["concatenate"] == (ncols if flat else 0)


# ---------------------------------------------------------------------------
# consolidation regimes
# ---------------------------------------------------------------------------


def test_rank_fold_bitidentical_to_sort():
    rng = np.random.default_rng(4)
    for nruns in (2, 3, 5, 8):
        parts = [_consolidated(rng, 12, 32, key_range=10) for _ in
                 range(nruns)]
        # adversarial: a part that exactly cancels another
        parts.append(parts[0].neg())
        cat = concat_batches(parts)
        assert cat.sorted_runs == nruns + 1
        folded = cat.consolidate()
        sorted_ref = cat.tagged(None).consolidate()
        assert folded.sorted_runs == 1
        assert_batches_bitequal(folded, sorted_ref,
                                f"rank fold != sort ({nruns} runs)")
        check_runs(folded, "rank fold output")


def test_consolidate_skip_is_noop():
    rng = np.random.default_rng(5)
    b = _consolidated(rng, 20, 32)
    assert b.sorted_runs == 1
    assert b.consolidate() is b  # free by construction


def test_consolidate_counts_paths():
    rng = np.random.default_rng(6)
    before = dict(kernels.CONSOLIDATE_COUNTS)
    b = _consolidated(rng, 20, 32)
    b.consolidate()  # skipped
    concat_batches([b, b.neg()]).consolidate()  # rank fold
    concat_batches([b, b]).tagged(None).consolidate()  # sort or native
    delta = {k: v - before.get(k, 0)
             for k, v in kernels.CONSOLIDATE_COUNTS.items()}
    assert delta["skipped"] >= 1
    assert delta["rank"] >= 1
    assert delta["native"] + delta["sort"] >= 1


def test_runs_metadata_invariants_under_operators():
    rng = np.random.default_rng(7)
    b = _consolidated(rng, 24, 64)
    check_runs(b, "consolidated")
    assert b.sorted_runs == 1

    # weight ops preserve; scale drops (documented conservative choice)
    check_runs(b.neg(), "neg")
    assert b.neg().sorted_runs == 1
    assert b.scale(2).sorted_runs == 0

    # compaction preserves one run
    keep = jnp.asarray(rng.integers(0, 2, b.cap).astype(bool))
    c = b.compacted(keep & (b.weights != 0))
    assert c.sorted_runs == 1
    check_runs(c, "compacted")

    # masked: scalar cond preserves, per-row cond drops
    assert b.masked(jnp.asarray(True)).sorted_runs == 1
    assert b.masked(jnp.asarray(False)).sorted_runs == 1
    check_runs(b.masked(jnp.asarray(False)), "masked-false")
    assert b.masked(b.weights > 0).sorted_runs == 0

    # with_cap: grow extends the tail run, shrink keeps a single run
    g = b.with_cap(128)
    assert g.sorted_runs == 1
    check_runs(g, "grown")
    s = b.consolidate().shrink_to_fit()
    assert s.sorted_runs == 1
    check_runs(s, "shrunk")

    # concat accumulates runs; unknown input poisons
    cat = concat_batches([b, c])
    assert cat.runs == (b.cap, c.cap)
    check_runs(cat, "concat")
    assert concat_batches([b, b.scale(2)]).sorted_runs == 0

    # merge emits one canonical run
    m = b.merge_with(c)
    assert m.sorted_runs == 1
    check_runs(m, "merged")


def test_operator_kernels_tag_outputs():
    """Filter / map / stream-distinct outputs carry (and honor) run tags."""
    from dbsp_tpu.operators.distinct import StreamDistinct
    from dbsp_tpu.operators.filter_map import FilterOp, MapOp

    rng = np.random.default_rng(8)
    b = _consolidated(rng, 24, 64, allow_neg=True)
    f = FilterOp(lambda k, v: k[0] % 2 == 0)._inner(b)
    assert f.sorted_runs == 1
    check_runs(f, "filter")
    m = MapOp(lambda k, v: ((k[0] // 3,), (v[0],)))._inner(b)
    assert m.sorted_runs == 1
    check_runs(m, "map")
    d = StreamDistinct._kernel(b)
    assert d.sorted_runs == 1
    check_runs(d, "stream_distinct")
    # raw (deferred) map: unordered, but canonicalizes to the same Z-set
    raw = MapOp(lambda k, v: ((k[0] // 3,), (v[0],)))._inner_raw(b)
    assert raw.sorted_runs == 0
    assert raw.consolidate().to_dict() == m.to_dict()


# ---------------------------------------------------------------------------
# compiled placement pass
# ---------------------------------------------------------------------------


def test_placement_pass_defers_join_before_canonicalizing_consumers():
    """join -> filter -> map -> output: the join's consolidation leaves the
    program (deferred); outputs stay identical to the host path."""
    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.compiled import cnodes, compile_circuit
    from dbsp_tpu.nexmark import GeneratorConfig, NexmarkGenerator, \
        build_inputs, device_gen, queries

    cfg = GeneratorConfig(seed=5)

    def build(c):
        streams, handles = build_inputs(c)
        return handles, queries.q4(*streams).output()

    handle, (handles, out) = Runtime.init_circuit(1, build)
    hp, ha, hb = handles

    def gen_fn(tick):
        p, a, b = device_gen.generate_tick(cfg, tick * 20, 20)
        return {hp: p, ha: a, hb: b}

    ch = compile_circuit(handle, gen_fn=gen_fn)
    assert ch.deferred_consolidations >= 1
    joins = [cn for cn in ch.cnodes if isinstance(cn, cnodes.CJoin)]
    assert joins and all(getattr(cn, "defer_consolidate", False)
                         for cn in joins)

    outs = {}

    def capture(next_tick):
        b = ch.output(out)
        outs[next_tick - 1] = b.to_dict() if b is not None else {}

    ch.run_ticks(0, 3, validate_every=1, on_validated=capture)

    gen = NexmarkGenerator(cfg)
    handle2, (handles2, out2) = Runtime.init_circuit(1, build)
    n = 0
    for t in range(3):
        gen.feed(handles2, n, n + 1000)
        handle2.step()
        b = out2.take()
        assert outs[t] == (b.to_dict() if b is not None else {}), \
            f"tick {t} diverged under deferred consolidation"
        n += 1000


def test_placement_pass_keeps_consolidation_before_stateful_consumers():
    """join -> distinct (via trace): the join output feeds a spine insert,
    so its consolidation must NOT defer (q8 shape)."""
    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.compiled import cnodes, compile_circuit
    from dbsp_tpu.nexmark import GeneratorConfig, build_inputs, device_gen, \
        queries

    cfg = GeneratorConfig(seed=6)

    def build(c):
        streams, handles = build_inputs(c)
        return handles, queries.q8(*streams).output()

    handle, _ = Runtime.init_circuit(1, build)
    h = compile_circuit(handle, gen_fn=None)
    joins = [cn for cn in h.cnodes if isinstance(cn, cnodes.CJoin)]
    assert joins and not any(getattr(cn, "defer_consolidate", False)
                             for cn in joins)


def test_slotted_l0_survives_varying_delta_capacity():
    """Regression: the slotted level-0 geometry is PINNED per trace. A tick
    whose delta capacity differs from the pin (feeds mode buckets each
    tick's rows independently) must not reinterpret existing slots at a
    new slot size — distinct would silently re-emit rows already present."""
    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.compiled import compile_circuit
    from dbsp_tpu.operators import add_input_zset

    def run(pad_tick2: int):
        def build(c):
            s, h = add_input_zset(c, (jnp.int64,), ())
            return h, s.distinct().output()

        handle, (t, out) = Runtime.init_circuit(1, build)
        ch = compile_circuit(handle)
        feeds = [
            [((k,), 1) for k in range(10, 16)],           # cap 8
            [((k,), 1) for k in range(0, 6)],             # cap 8
            # tick 2 re-feeds 10..15 among enough rows to force a BIGGER
            # delta capacity (retrace) — distinct must emit only the new
            [((k,), 1) for k in range(100, 100 + pad_tick2)] +
            [((k,), 1) for k in range(10, 16)],
        ]
        outs = []
        for tick, rows in enumerate(feeds):
            b = Batch.from_tuples(rows, [jnp.int64], [])
            ch.step(tick=tick, feeds={t: b})
            ch.validate()
            ch.maintain()
            o = ch.output(out)
            outs.append(o.to_dict() if o is not None else {})
        return outs

    grown = run(pad_tick2=20)    # tick-2 cap 32 != pinned slot 8
    stable = run(pad_tick2=2)    # tick-2 cap 8 == pinned slot
    for k in range(10, 16):
        assert (k,) not in grown[2], \
            f"distinct re-emitted {(k,)} after a delta-capacity change"
        assert (k,) not in stable[2]
    assert all((k,) in grown[2] for k in range(100, 120))


# ---------------------------------------------------------------------------
# 8-way mesh (the dryrun_multichip path): fused cursors per worker slice
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sharded_host_join_fused_ladder_8_equals_1():
    """The sharded host join (lifted fused ladder) over 8 virtual workers
    equals the single-worker evaluation — exchange + per-worker fused
    probes + output union, through the public Stream API."""
    from dbsp_tpu.circuit import Runtime
    from dbsp_tpu.nexmark import GeneratorConfig, NexmarkGenerator, \
        build_inputs, queries

    def run(workers):
        gen = NexmarkGenerator(GeneratorConfig(seed=9))

        def build(c):
            streams, handles = build_inputs(c)
            return handles, queries.q4(*streams).output()

        handle, (handles, out) = Runtime.init_circuit(workers, build)
        integral = {}
        n = 0
        for _ in range(2):
            gen.feed(handles, n, n + 1200)
            handle.step()
            b = out.take()
            if b is not None:
                for r, w in b.to_dict().items():
                    integral[r] = integral.get(r, 0) + w
                    if integral[r] == 0:
                        del integral[r]
            n += 1200
        return integral

    want = run(1)
    assert want and run(8) == want
