"""What decides ``correct`` has been shown to fail.

* The controls — the plain reference put in the program's place with one
  stated guarantee broken — come out as not correct (``test_control_*``).
  On the chip they were read at the cells' own size (``PERF.md``); here at
  a size a test run holds.
* The rest of a run, driven past the harness's look for a chip with the
  timed path broken underneath, prints ``correct: false``
  (``test_fault_*``), once for each fault these cells can have: a step
  that leaves its state unchanged, half of a batch left out, an answer
  altered where it is served. (One chip: there is no exchange to leave out.)

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import run as harness  # noqa: E402

CELLS = ("nexmark-q4.saturated", "nexmark-q3.saturated")


def _reference(spec):
    return harness.load_module(
        os.path.join(BENCH, "references", spec["config"]["reference"] + ".py"),
        "reference_" + spec["config"]["reference"])


@pytest.mark.parametrize("workload", CELLS)
def test_control_lost_batch_is_not_correct(workload):
    spec = harness.load_cell(workload)
    spec["config"]["events_per_tick"] = 10_000
    ref, ticks = _reference(spec), 4
    want = ref.recompute(harness.acked_events(spec, 7, ticks))
    sound = {"rows": [[*k, w] for k, w in want.items()], "step": ticks}
    assert harness.is_correct(harness.compare_view(
        sound, want, ticks, 40_000, 40_000))
    lost = ref.recompute(harness.acked_events(spec, 7, ticks,
                                              drop_last_batch=True))
    c = harness.compare_view(
        {"rows": [[*k, w] for k, w in lost.items()], "step": ticks},
        want, ticks, 40_000, 40_000)
    assert c["rows_mismatched"]["value"] > 0
    assert not harness.is_correct(c)


def test_control_int32_is_not_correct_for_q4():
    spec = harness.load_cell("nexmark-q4.saturated")
    ref, ticks = _reference(spec), 5   # 200,000 events: sums pass 2**31
    events = harness.acked_events(spec, 11, ticks)
    want = ref.recompute(events)
    low = ref.recompute(events, control="int32")
    c = harness.compare_view(
        {"rows": [[*k, w] for k, w in low.items()], "step": ticks},
        want, ticks, 0, 0)
    assert c["rows_mismatched"]["value"] > 0 and not harness.is_correct(c)


def test_compare_counts_steps_and_acks():
    want = {(10, 5): 1}
    view = {"rows": [[10, 5, 1]], "step": 3}
    assert harness.is_correct(harness.compare_view(view, want, 3, 9, 9))
    assert not harness.is_correct(harness.compare_view(view, want, 4, 9, 9))
    assert not harness.is_correct(harness.compare_view(view, want, 3, 8, 9))
    assert not harness.is_correct(harness.compare_view(view, {}, 3, 9, 9))


#: a tick small enough for a test, large enough that each view has rows
REHEARSAL_EVENTS = {"nexmark-q4.saturated": 500, "nexmark-q3.saturated": 5000}


def _run(workload, seed=5):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = harness.main(["--workload", workload, "--seed", str(seed),
                           "--seconds", "2", "--trace", "0",
                           "--rehearse-events",
                           str(REHEARSAL_EVENTS[workload])])
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def _fault_state_unchanged(monkeypatch):
    """The sixth step runs, delivers, and leaves the state as it was."""
    from dbsp_tpu.compiled.driver import CompiledCircuitDriver

    real, calls = CompiledCircuitDriver.step, {"n": 0}

    def step(self):
        calls["n"] += 1
        if calls["n"] != 6:
            return real(self)
        snap = self.ch.snapshot()
        real(self)
        self.ch.restore(snap)

    monkeypatch.setattr(CompiledCircuitDriver, "step", step)


def _fault_half_batch(monkeypatch):
    """Every POST is acknowledged in full; one in ten keeps half its rows."""
    from dbsp_tpu.io.catalog import InputCollection

    calls = {"n": 0}

    def push_rows(self, rows):
        calls["n"] += 1
        self.handle.extend(rows[: len(rows) // 2] if calls["n"] % 10 == 0
                           else rows)
        return len(rows)

    monkeypatch.setattr(InputCollection, "push_rows", push_rows)


def _fault_answer_altered(monkeypatch):
    """The read plane serves its first row with the last value off by one."""
    from dbsp_tpu.serving import ReadPlane

    real = ReadPlane.query

    def query(self, *a, **kw):
        obj = real(self, *a, **kw)
        if obj["rows"]:
            obj["rows"][0][-2] += 1
        return obj

    monkeypatch.setattr(ReadPlane, "query", query)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_rehearsal_is_correct(workload):
    line = _run(workload)
    assert line["correct"] is True and line["metrics"] == {}


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", (_fault_state_unchanged, _fault_half_batch,
                                   _fault_answer_altered),
                         ids=lambda f: f.__name__.lstrip("_"))
def test_fault_reads_not_correct(fault, workload, monkeypatch):
    fault(monkeypatch)
    line = _run(workload)
    assert line["correct"] is False
    assert line["compared"]["rows_mismatched"]["value"] > 0


# -- the four-chip cell: the exchange between chips left out ------------------

_FOUR_WORKERS = """
import sys
sys.path.insert(0, {bench!r})
sys.path.insert(0, {root!r})
import jax
import run as harness
if {fault}:
    # every bucketed row stays on the worker that held it
    jax.lax.all_to_all = lambda x, *a, **kw: x
sys.exit(harness.main([
    "--workload", "nexmark-q4-4w.saturated", "--seed", "5", "--seconds", "2",
    "--trace", "0", "--rehearse-events", "600"]))
"""


@pytest.mark.parametrize("fault", (False, True),
                         ids=("sound", "exchange_left_out"))
def test_four_workers_exchange_left_out_reads_not_correct(fault):
    """``nexmark-q4-4w.saturated`` on four virtual devices, in a process of
    its own (the device count is fixed when JAX starts): with
    ``all_to_all`` made the identity each worker averages the maxima it
    happens to hold, and the gathered view is not the reference's."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", _FOUR_WORKERS.format(
            bench=BENCH, root=os.path.dirname(BENCH), fault=fault)],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["device"]["count"] == 4
    assert line["correct"] is (not fault)
    assert (line["compared"]["rows_mismatched"]["value"] > 0) is fault
