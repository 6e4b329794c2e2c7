#!/usr/bin/env python3
"""How ``trace_small.xplane.pb`` was recorded (on the chip, PR 27):

    python benchmark/testdata/record_small_trace.py <out-dir>

A 4,096-row int32 sort-and-sum program named ``small_probe`` runs three
times with 20 ms of host sleep between runs, inside the harness's own
``Tracer`` (so the trace holds its begin and end marks). Writes the
``.xplane.pb``, the reduction's result and the trace's shape to
``<out-dir>``. ``selfcheck.py`` holds the reduction to those numbers.
"""

import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as harness  # noqa: E402
import trace_reduce  # noqa: E402


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    def small_probe(x):
        return jnp.sort(x).cumsum()[-1] + (x[:64, None] * x[None, :64]).sum()

    fn = jax.jit(small_probe)
    x = jax.random.randint(jax.random.PRNGKey(0), (4096,), 0, 1 << 20,
                           dtype=jnp.int32)
    jax.block_until_ready(fn(x))
    tracer = harness.Tracer("small")
    tracer.start()
    for _ in range(3):
        jax.block_until_ready(fn(x))
        time.sleep(0.02)
    # as Tracer.stop ends a trace, but the file is kept
    with jax.profiler.TraceAnnotation("bench.trace_end"):
        pass
    jax.profiler.stop_trace()
    tracer.active = False
    path = trace_reduce.find_xplane(tracer.dir)
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(path, os.path.join(out_dir, "trace_small.xplane.pb"))
    with open(os.path.join(out_dir, "trace_small.describe.json"), "w") as f:
        json.dump(trace_reduce.describe(path), f, indent=1)
    with open(os.path.join(out_dir, "trace_small.reduced.json"), "w") as f:
        json.dump(trace_reduce.reduce_trace(path), f, indent=1)
    shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    print(json.dumps({"recorded": os.path.getsize(
        os.path.join(out_dir, "trace_small.xplane.pb"))}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
