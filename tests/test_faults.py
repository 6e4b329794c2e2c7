"""Fault-injection acceptance: crash-safe restore, transport hardening,
degraded health, corruption incidents, slow consumers.

The headline contract (ISSUE 6): SIGKILL a q4 pipeline at a seeded tick
mid-stream, restore-on-deploy from its checkpoint store, and the
subsequent output stream is BIT-IDENTICAL to an uninterrupted run — in
both host and compiled modes. The kill is a real subprocess SIGKILL
(dbsp_tpu.testing.faults), so the checkpoint store's atomic-generation
discipline is what's under test, not a cooperative shutdown.
"""

import json
import os
import time

import pytest
import jax.numpy as jnp

from dbsp_tpu.circuit import Runtime
from dbsp_tpu.io import Catalog
from dbsp_tpu.io.controller import Controller, ControllerConfig
from dbsp_tpu.operators import Count, add_input_zset
from dbsp_tpu.testing import faults

TICKS = 14
KILL_AT = 9
BATCH = 200


def _kill_and_restore(mode: str, tmp_path) -> None:
    base = str(tmp_path)

    def paths(tag):
        return (os.path.join(base, f"{tag}.status"),
                os.path.join(base, f"{tag}.out"),
                os.path.join(base, f"{tag}.cfg"),
                os.path.join(base, f"ckpt-{tag}"))

    # reference and victim children run CONCURRENTLY (independent
    # pipelines; halves the wall clock of the scenario)
    st_r, out_r, cfg_r, ck_r = paths("ref")
    st_k, out_k, cfg_k, ck_k = paths("kill")
    p_ref = faults.spawn_child(
        faults.child_config(mode, ck_r, st_r, out_r, ticks=TICKS,
                            batch=BATCH, checkpoint_every=4), cfg_r)
    p_kill = faults.spawn_child(
        faults.child_config(mode, ck_k, st_k, out_k, ticks=TICKS,
                            batch=BATCH, checkpoint_every=4), cfg_k)
    try:
        faults.wait_for_tick(st_k, KILL_AT, proc=p_kill, timeout_s=420)
        faults.kill9(p_kill)  # SIGKILL: no flush, no atexit
        rc = p_ref.wait(timeout=420)
        assert rc == 0, faults.child_stderr(p_ref)[-2000:]
    finally:
        for p in (p_ref, p_kill):
            if p.poll() is None:
                p.kill()
    ref = faults.read_deltas(out_r)
    assert sorted(ref) == list(range(TICKS))

    # the victim's store must hold at least one complete generation
    # (written BEFORE the kill; a torn in-flight write must not matter)
    gens = [n for n in os.listdir(ck_k) if n.startswith("gen-")]
    assert gens, "no checkpoint generation survived the kill"

    # restore-on-deploy: a fresh process resumes from the newest valid
    # generation and replays inputs past the checkpoint tick
    st2, out2, cfg2, _ = paths("resume")
    final = faults.run_child(
        faults.child_config(mode, ck_k, st2, out2, ticks=TICKS,
                            batch=BATCH, checkpoint_every=4, resume=True),
        cfg2, timeout_s=420)
    with open(out2) as f:
        header = json.loads(f.readline())
    restored = header["start_tick"]
    assert 0 < restored <= KILL_AT + 1, header  # resumed mid-stream
    res = faults.read_deltas(out2)
    # THE acceptance bit: every post-restore tick's delta is identical
    # to the uninterrupted run's
    for t in range(restored, TICKS):
        assert res.get(t) == ref.get(t), f"tick {t} diverged after restore"
    assert final["done"] and final["checkpoints"] >= 1


def test_kill9_and_restore_q4_host(tmp_path):
    _kill_and_restore("host", tmp_path)


def test_kill9_and_restore_q4_compiled(tmp_path):
    _kill_and_restore("compiled", tmp_path)


# ---------------------------------------------------------------------------
# transport hardening
# ---------------------------------------------------------------------------


def _count_pipeline():
    def build(c):
        s, h = add_input_zset(c, [jnp.int64], [jnp.int64])
        return h, s.aggregate(Count()).integrate().output()

    handle, (h, out) = Runtime.init_circuit(1, build)
    catalog = Catalog()
    catalog.register_input("events", h, (jnp.int64, jnp.int64))
    catalog.register_output("counts", out, (jnp.int64, jnp.int64))
    return handle, catalog, out


def test_transport_retries_recover_from_flaky_broker():
    """Injected read failures are retried with backoff (and counted);
    ingestion completes once the fault clears."""
    from dbsp_tpu.io import KafkaInputTransport
    from dbsp_tpu.io.minikafka import MiniKafkaBroker, MiniProducer

    broker = MiniKafkaBroker().start()
    ctl = None
    try:
        feed = MiniProducer(bootstrap_servers=broker.address)
        for k in range(4):
            feed.send("events", json.dumps({"insert": [k, k]}).encode())
        feed.flush()

        handle, catalog, _ = _count_pipeline()
        ctl = Controller(handle, catalog, ControllerConfig(
            min_batch_records=1, flush_interval_s=0.05,
            transport_timeout_s=2.0, transport_retries=8,
            transport_backoff_s=0.01))
        with faults.transport_chaos(fail_reads=3):
            ctl.add_input_endpoint(
                "kin", "events",
                KafkaInputTransport(broker.address, ["events"],
                                    poll_timeout=0.05), fmt="json")
            ctl.start()
            deadline = time.time() + 30
            while time.time() < deadline:
                s = ctl.stats()["inputs"]["kin"]
                if s["total_records"] >= 4:
                    break
                time.sleep(0.05)
        s = ctl.stats()["inputs"]["kin"]
        assert s["total_records"] >= 4
        assert s["transport_retries"] >= 1
        assert s["error"] is None

        # the retry counter is a first-class metric
        from dbsp_tpu.obs import PipelineObs, prometheus_text

        obs = PipelineObs(name="t")
        obs.attach_controller(ctl)
        text = prometheus_text(obs.registry)
        assert "dbsp_tpu_io_transport_retries_total" in text
    finally:
        if ctl is not None:
            ctl.stop()
        broker.stop()


def test_dead_broker_degrades_instead_of_hanging():
    """A broker that dies past the retry budget TERMINATES the endpoint
    (error + eoi) and latches a degraded SLO state; the controller thread
    keeps serving (stats/steps callable, no hang)."""
    from dbsp_tpu.io import KafkaInputTransport
    from dbsp_tpu.io.minikafka import MiniKafkaBroker, MiniProducer
    from dbsp_tpu.obs import PipelineObs

    broker = MiniKafkaBroker().start()
    handle, catalog, _ = _count_pipeline()
    ctl = Controller(handle, catalog, ControllerConfig(
        min_batch_records=1, flush_interval_s=0.05,
        transport_timeout_s=0.3, transport_retries=2,
        transport_backoff_s=0.01))
    obs = PipelineObs(name="deadbroker")
    try:
        feed = MiniProducer(bootstrap_servers=broker.address)
        feed.send("events", json.dumps({"insert": [1, 1]}).encode())
        feed.flush()
        ctl.add_input_endpoint(
            "kin", "events",
            KafkaInputTransport(broker.address, ["events"],
                                poll_timeout=0.05), fmt="json")
        obs.attach_controller(ctl)
        ctl.start()
        deadline = time.time() + 20
        while time.time() < deadline and \
                ctl.stats()["inputs"]["kin"]["total_records"] < 1:
            time.sleep(0.05)
        broker.stop()  # broker dies mid-stream
        deadline = time.time() + 30
        while time.time() < deadline:
            s = ctl.stats()["inputs"]["kin"]
            if s["error"] is not None and s["eoi"]:
                break
            time.sleep(0.05)
        s = ctl.stats()["inputs"]["kin"]
        assert s["error"] is not None, "dead broker never surfaced"
        assert s["eoi"], "endpoint left hanging instead of terminating"
        # SLO-visible: the watchdog latches a transport condition
        obs.watch()
        assert obs.slo.status() == "degraded"
        assert any(i["slo"] == "transport"
                   for i in obs.slo.incidents(with_window=False))
        # the circuit thread is alive and serving
        assert ctl.stats()["state"] == "running"
    finally:
        ctl.stop()


def test_slow_consumer_stall_does_not_lose_outputs():
    """A stalling output sink (slow consumer) delays delivery but loses
    nothing, and control-plane reads keep working during the stall."""
    handle, catalog, _ = _count_pipeline()
    ctl = Controller(handle, catalog, ControllerConfig(
        min_batch_records=1, flush_interval_s=0.02))
    sink = faults.StallingOutputTransport(stall_s=0.15, every=1)
    ctl.add_output_endpoint("slow", "counts", sink, fmt="json")
    ctl.start()

    def delivered_keys():
        rows = {}
        for chunk in list(sink.chunks):
            for line in chunk.decode().splitlines():
                if not line:
                    continue
                obj = json.loads(line)
                row = tuple(obj.get("insert") or obj.get("delete"))
                rows[row] = rows.get(row, 0) + \
                    (1 if "insert" in obj else -1)
        return {k for (k, _), w in rows.items() if w}

    try:
        for k in range(5):
            ctl.push("events", [((k, k), 1)])
            time.sleep(0.05)
            assert ctl.stats()["state"] == "running"  # mid-stall liveness
        deadline = time.time() + 30
        while time.time() < deadline and delivered_keys() != set(range(5)):
            time.sleep(0.05)
    finally:
        ctl.stop()
    assert sink.stalls >= 1
    # every pushed key's count survived the stalls — delayed, never lost
    assert delivered_keys() == set(range(5))


def test_undelivered_sink_delta_survives_crash(tmp_path):
    """A delta parked by a failed sink write is PERSISTED by the
    checkpoint and re-sent after restore — the output stream stays
    at-least-once across a crash (input high-water marks cover the step
    that produced it, so nothing else would ever re-emit it)."""
    from dbsp_tpu.io.transport import OutputTransport

    class FailingSink(OutputTransport):
        def __init__(self):
            self.fail = True
            self.chunks = []

        def write(self, data):
            if self.fail:
                raise ConnectionError("injected sink failure")
            self.chunks.append(data)

    ckdir = str(tmp_path / "ck")

    handle, catalog, out = _count_pipeline()
    ctl = Controller(handle, catalog, ControllerConfig(
        checkpoint_dir=ckdir))
    sink = FailingSink()
    ctl.add_output_endpoint("sink", "counts", sink, fmt="json")
    ctl.push("events", [((1, 10), 1), ((2, 20), 1)])
    ctl.step()  # write fails -> delta parked on out.pending
    assert ctl.outputs["sink"].pending is not None
    ctl.checkpoint()

    # fresh process equivalent: rebuild, restore; the sink works now
    handle2, catalog2, out2 = _count_pipeline()
    ctl2 = Controller(handle2, catalog2, ControllerConfig(
        checkpoint_dir=ckdir))
    sink2 = FailingSink()
    sink2.fail = False
    ctl2.add_output_endpoint("sink", "counts", sink2, fmt="json")
    info = ctl2.restore_from()
    assert info["output_pending"], "parked delta missing from checkpoint"
    assert ctl2.outputs["sink"].pending is not None
    ctl2._emit_outputs()  # first post-restore emission re-sends it
    rows = [json.loads(line) for chunk in sink2.chunks
            for line in chunk.decode().splitlines() if line]
    assert {tuple(r["insert"]) for r in rows} == {(1, 1), (2, 1)}


def test_transient_sink_blip_unlatches_degraded():
    """A transport failure latches degraded; the RECOVERY transition
    (pending-batch retry delivered) un-latches it and resolves the
    incident — a one-off blip must not mark the pipeline degraded for
    life."""
    from dbsp_tpu.obs import PipelineObs

    obs = PipelineObs(name="blip")
    obs.flight.record("transport", endpoint="kout", error="injected")
    obs.watch()
    assert obs.slo.status() == "degraded"
    assert any(i["slo"] == "transport" and i["resolved_ts"] is None
               for i in obs.slo.incidents(with_window=False))
    obs.flight.record("transport", endpoint="kout", recovered=True)
    obs.watch()
    assert obs.slo.status() == "ok"
    assert all(i["resolved_ts"] is not None
               for i in obs.slo.incidents(with_window=False)
               if i["slo"] == "transport")


def test_file_endpoint_replay_is_exactly_once_after_restore(tmp_path):
    """Restore-on-deploy with a file input: the transport re-reads the
    whole file, and the checkpointed consumed-row prefix is SKIPPED so
    restored state is not double-applied (exactly-once end to end)."""
    import time as _time

    src = tmp_path / "in.csv"
    rows = [(k, k * 10) for k in range(6)]
    src.write_text("".join(f"{k},{v}\n" for k, v in rows))
    ckdir = str(tmp_path / "ck")

    from dbsp_tpu.io.transport import FileInputTransport

    def run_once(restore):
        handle, catalog, out = _count_pipeline()
        ctl = Controller(handle, catalog, ControllerConfig(
            min_batch_records=1, flush_interval_s=0.02,
            checkpoint_dir=ckdir))
        ctl.add_input_endpoint("fin", "events",
                               FileInputTransport(str(src)), fmt="csv")
        if restore:
            info = ctl.restore_from()
            assert ctl.inputs["fin"].skip_rows == info["controller"][
                "inputs"]["fin"]["total_records"] > 0
        ctl.start()
        deadline = _time.time() + 30
        while not ctl.eoi_reached() and _time.time() < deadline:
            _time.sleep(0.02)
        view = out.to_dict()
        ctl.stop()
        return ctl, view

    # pass 1: consume the whole file, checkpointing (stop writes a final
    # generation at eoi)
    ctl1, view1 = run_once(restore=False)
    assert view1 == {(k, 1): 1 for k in range(6)}
    # pass 2: fresh process equivalent — same file endpoint, restore;
    # WITHOUT the skip the replayed file would double every count
    ctl2, view2 = run_once(restore=True)
    assert view2 == view1, "replayed file rows were double-applied"
    assert ctl2.stats()["inputs"]["fin"]["total_records"] == 6


# ---------------------------------------------------------------------------
# corruption -> previous generation + exactly one restore incident
# ---------------------------------------------------------------------------


def test_corrupted_checkpoint_restore_incident(tmp_path):
    """A corrupted CURRENT generation falls back to the previous one and
    surfaces EXACTLY ONE SLO-visible ``restore`` incident (re-evaluation
    must not duplicate it)."""
    from dbsp_tpu import checkpoint as ckpt
    from dbsp_tpu.compiled.driver import CompiledCircuitDriver
    from dbsp_tpu.obs import PipelineObs

    path = str(tmp_path / "ck")

    def build(c):
        s, h = add_input_zset(c, [jnp.int64], [jnp.int32])
        return h, s.aggregate(Count()).integrate().output()

    handle, (h, out) = Runtime.init_circuit(1, build)
    drv = CompiledCircuitDriver(handle)
    ctl = Controller(drv, Catalog(), ControllerConfig(checkpoint_dir=path))
    for t in range(3):
        h.extend([((i % 5, t + i), 1) for i in range(16)])
        ctl.step()
        ctl.checkpoint()
    faults.corrupt_checkpoint(path, kind="truncate", seed=2)

    handle2, (h2, out2) = Runtime.init_circuit(1, build)
    drv2 = CompiledCircuitDriver(handle2)
    ctl2 = Controller(drv2, Catalog(), ControllerConfig(checkpoint_dir=path))
    obs = PipelineObs(name="corrupt")
    obs.attach_controller(ctl2)
    info = ctl2.restore_from()
    assert info["fallback_from"] is not None
    assert info["tick"] == 2  # the previous generation's tick
    # the manager's deploy path records the restore event; emulate it
    obs.flight.record("restore", ok=True, tick=info["tick"],
                      generation=info.get("generation"),
                      fallback_from=info["fallback_from"])
    obs.watch()
    obs.watch()  # second evaluation must NOT duplicate the incident
    incidents = [i for i in obs.slo.incidents(with_window=False)
                 if i["slo"] == "restore"]
    assert len(incidents) == 1
    inc = incidents[0]
    assert inc["fallback_from"] == info["fallback_from"]
    assert inc["resolved_ts"] is not None  # one-shot, not a latched breach
    assert obs.slo.status() == "ok"  # successful restore: not degraded


def test_failed_restore_latches_degraded_and_strict_mode(tmp_path,
                                                        monkeypatch):
    """Restore failure (no valid generation at all): non-strict deploys
    start fresh with a latched fallback_reason + restore incident; strict
    mode refuses."""
    from dbsp_tpu.manager import Pipeline

    path = str(tmp_path / "fleet")
    # checkpoint stores holding only a garbage generation, one per
    # pipeline name (p1's graceful stop below writes a VALID generation
    # into its own store, so the strict case needs a separate name)
    for name in ("p1", "p2"):
        gen = os.path.join(path, name, "gen-00000001")
        os.makedirs(gen)
        with open(os.path.join(gen, "manifest.json"), "w") as f:
            f.write("{not json")
        with open(os.path.join(path, name, "CURRENT"), "w") as f:
            f.write("gen-00000001")

    program = {"name": "prog", "version": 1,
               "tables": {"t": {"columns": ["a", "b"],
                                "dtypes": ["int64", "int64"],
                                "key_columns": 1}},
               "sql": {"v": "SELECT a, SUM(b) AS s FROM t GROUP BY a"}}
    monkeypatch.setenv("DBSP_TPU_CHECKPOINT_DIR", path)

    p = Pipeline("p1", program)
    p.compile_and_start()
    try:
        assert p.restored_tick is None
        assert p.fallback_reason and "restore failed" in p.fallback_reason
        events = p.obs.flight.events(kinds=("restore",))
        assert events and events[-1]["ok"] is False
        p.obs.watch()
        assert p.obs.slo.status() == "degraded"
    finally:
        p.stop()

    monkeypatch.setenv("DBSP_TPU_RESTORE_STRICT", "1")
    p2 = Pipeline("p2", program)
    with pytest.raises(RuntimeError, match="strict"):
        p2.compile_and_start()
    p2.stop()
