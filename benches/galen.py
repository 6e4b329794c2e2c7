#!/usr/bin/env python
"""Galen benchmark: mutually recursive Datalog over the incremental engine.

Reference: ``crates/dbsp/benches/galen.rs`` (the program is from
frankmcsherry/dynamic-datalog, problems/galen). Rules::

    p(x,z) :- p(x,y), p(y,z).
    p(x,z) :- p(y,w), u(w,r,z), q(x,r,y).
    p(x,z) :- c(y,w,z), p(x,w), p(x,y).
    q(x,r,z) :- p(x,y), q(y,r,z).
    q(x,q2,z) :- q(x,r,z), s(r,q2).
    q(x,e,o) :- q(x,y,z), r(y,u,e), q(z,u,o).

p and q are a MUTUAL least fixedpoint (recursive_streams) computed with
nested-timestamp operators, so a second epoch with a small edge delta does
delta-proportional work.

Data: the reference ships the dataset (galen_data.zip) — read at runtime,
never copied into this tree. Env knobs: GALEN_LIMIT (rows per relation,
default 800; 0 = full data), GALEN_ZIP (path override).

Prints one JSON line: {"metric": "galen_fixpoint", "value": <facts/s>, ...}.
"""

import json
import os
import sys
import tempfile
import time
import zipfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(os.path.dirname(os.path.dirname(
                          os.path.abspath(__file__))), ".jax_bench_cache"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

DEFAULT_ZIP = "/root/reference/crates/dbsp/benches/galen_data.zip"


def load_data(limit: int):
    path = os.environ.get("GALEN_ZIP", DEFAULT_ZIP)
    out = {}
    with zipfile.ZipFile(path) as z:
        for name in ("p", "q", "r", "c", "u", "s"):
            rows = []
            with z.open(f"{name}.txt") as fh:
                for i, line in enumerate(fh):
                    if limit and i >= limit:
                        break
                    rows.append(tuple(int(x) for x in line.split(b",")))
            out[name] = rows
    return out


def build_circuit(c):
    """The 6-rule galen program on the Stream API; returns handles + outs."""
    import jax.numpy as jnp

    from dbsp_tpu.operators import add_input_zset

    i64 = jnp.int64
    # base relations: p(x,z), q(x,r,z), r(y,u,e), c(y,w,z), u(w,r,z), s(r,q2)
    p0, hp = add_input_zset(c, (i64,), (i64,))
    q0, hq = add_input_zset(c, (i64,), (i64, i64))
    r0, hr = add_input_zset(c, (i64,), (i64, i64))
    c0, hc = add_input_zset(c, (i64,), (i64, i64))
    u0, hu = add_input_zset(c, (i64,), (i64, i64))
    s0, hs = add_input_zset(c, (i64,), (i64,))

    from dbsp_tpu.operators.recursive import recursive_streams

    def rules(child, Rs):
        P, Q = Rs
        e_u = child.import_stream(u0)
        e_s = child.import_stream(s0)
        e_r = child.import_stream(r0)
        e_c = child.import_stream(c0)

        def by(s, key_fn, key_dts, val_fn, val_dts, name):
            return s.index_by(key_fn, key_dts, val_fn=val_fn,
                              val_dtypes=val_dts, name=name)

        # p1: p(x,y) ⋈ p(y,z) on y
        p_by_dst = by(P, lambda k, v: (v[0],), (i64,),
                      lambda k, v: (k[0],), (i64,), "p-by-dst")
        p1 = p_by_dst.join_index(
            P, lambda k, a, b: ((a[0],), (b[0],)), (i64,), (i64,),
            name="p1")

        # p2: p(y,w) ⋈ u(w,r,z) on w -> t(y,r,z); ⋈ q(x,r,y) on (r,y)
        t2 = p_by_dst.join_index(  # p keyed by w(=dst) matches u's key w
            e_u, lambda k, pv, uv: ((uv[0], pv[0]), (uv[1],)),
            (i64, i64), (i64,), name="p2-pu")  # key (r, y), val (z)
        # q(x,r,y): the pattern's third position is y -> key (r, y), val (x)
        q_for_p2 = by(Q, lambda k, v: (v[0], v[1]), (i64, i64),
                      lambda k, v: (k[0],), (i64,), "q-by-r-z")
        p2 = t2.join_index(
            q_for_p2, lambda k, tv, qv: ((qv[0],), (tv[0],)),
            (i64,), (i64,), name="p2")

        # p3: c(y,w,z) ⋈ p(x,w) on w -> t(y,z,x); ⋈ p(x,y) on (x,y)
        c_by_w = by(e_c, lambda k, v: (v[0],), (i64,),
                    lambda k, v: (k[0], v[1]), (i64, i64), "c-by-w")
        t3 = c_by_w.join_index(
            p_by_dst, lambda k, cv, pv: ((pv[0], cv[0]), (cv[1],)),
            (i64, i64), (i64,), name="p3-cp")  # key (x, y), val (z)
        p_xy = by(P, lambda k, v: (k[0], v[0]), (i64, i64),
                  lambda k, v: (), (), "p-xy")
        p3 = t3.join_index(
            p_xy, lambda k, tv, pv: ((k[0],), (tv[0],)),
            (i64,), (i64,), name="p3")

        # q1: p(x,y) ⋈ q(y,r,z) on y
        q1 = p_by_dst.join_index(
            Q, lambda k, pv, qv: ((pv[0],), (qv[0], qv[1])),
            (i64,), (i64, i64), name="q1")

        # q2: q(x,r,z) ⋈ s(r,q2) on r
        q_by_r = by(Q, lambda k, v: (v[0],), (i64,),
                    lambda k, v: (k[0], v[1]), (i64, i64), "q-by-r")
        q2 = q_by_r.join_index(
            e_s, lambda k, qv, sv: ((qv[0],), (sv[0], qv[1])),
            (i64,), (i64, i64), name="q2")

        # q3: q(x,y,z) ⋈ r(y,u,e) on y -> t(x,z,u,e); ⋈ q(z,u,o) on (z,u)
        t4 = q_by_r.join_index(  # q keyed by its middle field y(=r slot)
            e_r, lambda k, qv, rv: ((qv[1], rv[0]), (qv[0], rv[1])),
            (i64, i64), (i64, i64), name="q3-qr")  # key (z, u), val (x, e)
        q_by_xr = by(Q, lambda k, v: (k[0], v[0]), (i64, i64),
                     lambda k, v: (v[1],), (i64,), "q-by-xr")
        q3 = t4.join_index(
            q_by_xr, lambda k, tv, qv: ((tv[0],), (tv[1], qv[0])),
            (i64,), (i64, i64), name="q3")

        p_step = p1.plus(p2).plus(p3)
        p_step.schema = ((i64,), (i64,))
        q_step = q1.plus(q2).plus(q3)
        q_step.schema = ((i64,), (i64, i64))
        return [p_step, q_step]

    p_out, q_out = recursive_streams(c, [p0, q0], rules)
    return ((hp, hq, hr, hc, hu, hs),
            (p_out.integrate().output(), q_out.integrate().output()))


def main():
    import jax

    # default to CPU (a capability bench); GALEN_PLATFORM=tpu opts into
    # the accelerator.
    if os.environ.get("GALEN_PLATFORM", "cpu") == "cpu":
        jax.config.update("jax_platforms", "cpu")

    from dbsp_tpu.circuit import Runtime

    limit = int(os.environ.get("GALEN_LIMIT", 800))
    data = load_data(limit)
    handle, (handles, outs) = Runtime.init_circuit(1, build_circuit)
    hp, hq, hr, hc, hu, hs = handles
    for h, name in ((hp, "p"), (hq, "q"), (hr, "r"), (hc, "c"), (hu, "u"),
                    (hs, "s")):
        h.extend([(row, 1) for row in data[name]])

    t0 = time.perf_counter()
    handle.step()
    elapsed = time.perf_counter() - t0
    p_facts = len(outs[0].to_dict())
    q_facts = len(outs[1].to_dict())
    total = p_facts + q_facts

    # incremental epoch: one new p edge
    t1 = time.perf_counter()
    hp.push((data["p"][0][0], data["p"][-1][1] + 1), 1)
    handle.step()
    inc_elapsed = time.perf_counter() - t1

    print(json.dumps({
        "metric": "galen_fixpoint",
        "value": round(total / elapsed, 1),
        "unit": "facts/s",
        "detail": {
            "limit_per_relation": limit,
            "p_facts": p_facts,
            "q_facts": q_facts,
            "elapsed_s": round(elapsed, 3),
            "incremental_update_s": round(inc_elapsed, 3),
        },
    }))


if __name__ == "__main__":
    main()
