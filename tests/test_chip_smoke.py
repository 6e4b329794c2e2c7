"""chip_smoke.py's body, small, on the CPU: the served q4 view equals the
from-scratch recomputation; without a TPU ``main`` fails and never says ok;
the compile-cache rule holds both ways."""

import json
import os
import subprocess
import sys

import jax
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import chip_smoke  # noqa: E402


def test_run_served_small_view_equals_recomputation():
    lines = []
    summary = chip_smoke.run_served(ticks=3, events_per_tick=400, seed=7,
                                    emit=lines.append)
    assert summary["ok"] and summary["view_equals_recompute"]
    assert summary["mode"] == "compiled"
    assert summary["view_rows"] > 0, "empty view — the check would be vacuous"
    assert summary["events"] == 1200 and summary["presize_used"]
    # the bodies the smoke (and the benchmark) pushes are regular NDJSON:
    # every row goes to columns in bulk, none through the line parser
    assert summary["parsed_records"] == {"columnar": 1200, "fallback": 0}
    # the counters count: a run that compiled a step program says so
    assert summary["compile_requests"] > 0
    assert summary["step_programs_traced"] >= 1
    assert summary["kernel_dispatch"], "no kernel tier on record"
    assert [ln["phase"] for ln in lines][:2] == ["start", "tick"]
    assert lines[-1] is summary
    json.dumps(lines)  # every fact line is one JSON object


def test_run_served_four_workers_shards_the_served_path():
    """The ``--chips 4`` body on virtual CPU devices: the serving thread's
    input drain key-hash-shards the tick's batch (it runs under the
    circuit's runtime), the view still equals the recomputation, and every
    state leaf spans the four workers."""
    summary = chip_smoke.run_served(ticks=2, events_per_tick=600, seed=3,
                                    workers=4, emit=lambda _: None)
    assert summary["ok"] and summary["view_equals_recompute"]
    sharding = summary["sharding"]
    assert sharding["devices_per_leaf"] == [4]
    assert sharding["replicated_leaves"] == 0
    # the exchange's counters and the per-chip bytes ride the summary line
    # (the CPU backend reports no memory statistics: four empty readings)
    assert len(sharding["bytes_in_use"]) == 4
    assert len(sharding["peak_bytes_in_use"]) == 4
    sites = sharding["exchange_sites"]
    assert {k.split(":")[0] for k in sites} == {"input", "exchange"}
    assert sum(k.startswith("input:") for k in sites) == 3
    for rows, cap in sites.values():
        assert 0 <= rows <= cap and cap & (cap - 1) == 0
    assert set(sharding["exchange_overflows"]) <= {"input", "exchange"}
    json.dumps(summary)


def test_q4_recompute_by_hand():
    auctions = {"id": [1, 2, 3], "category": [10, 10, 11],
                "date_time": [100, 100, 100], "expires": [200, 200, 200]}
    bids = {"auction": [1, 1, 1, 2, 3, 9], "price": [5, 9, 50, 4, 7, 99],
            "date_time": [100, 200, 201, 150, 99, 150]}
    # auction 1: 9 (50 came too late); auction 2: 4; auction 3: none in
    # window; auction 9: unknown. category 10 -> (9 + 4) // 2
    assert chip_smoke.q4_recompute(auctions, bids) == {(10, 6): 1}


def test_main_without_a_tpu_fails_and_never_says_ok():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(_ROOT, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout
    assert "TPU" in p.stderr


@pytest.fixture
def cache_config():
    """Restore JAX's cache directory config after a test that moves it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_rule(env_set, monkeypatch, tmp_path, cache_config):
    """JAX_COMPILATION_CACHE_DIR set -> no directory set in code; unset ->
    the fixed path inside the checkout."""
    from dbsp_tpu.compiled import driver

    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", "untouched")
        assert driver.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == "untouched"
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(_ROOT, ".jax_bench_cache")
        assert driver.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    assert "DBSP_TPU_COMPILE_CACHE_DIR" not in open(driver.__file__).read()
