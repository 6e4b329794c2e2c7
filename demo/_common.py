"""Shared driver for the demo projects (reference: demo/project_demo00..03
+ demo/demo.py): start an in-process pipeline manager, register a program,
run its pipeline, push rows, and print a view."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402

if os.environ.get("DEMO_PLATFORM", "cpu") == "cpu":
    jax.config.update("jax_platforms", "cpu")  # demos run on the host

from dbsp_tpu.client import Connection, PipelineHandle  # noqa: E402
from dbsp_tpu.manager import PipelineManager  # noqa: E402


def run_demo(name, tables, sql, feeds, reads):
    mgr = PipelineManager()
    mgr.start()
    try:
        conn = Connection(port=mgr.port)
        spec = {t: {"columns": cols, "dtypes": ["int64"] * len(cols),
                    "key_columns": 1} for t, cols in tables.items()}
        conn.create_program(name, spec, sql)
        pipe = conn.start_pipeline(name, name)
        for coll, rows in feeds:
            pipe.push(coll, rows)
        pipe.step()
        for view in reads:
            print(f"\n== {view} ==")
            for row, w in sorted(pipe.read(view).items()):
                print(f"  {row}  (weight {w})")
    finally:
        mgr.stop()
