# Tests run on the CPU backend with 8 virtual devices so multi-worker
# sharding (Mesh/shard_map/all_to_all) is exercised without TPU hardware.
# The platform is pinned both ways: through the environment (for this
# process if jax is not imported yet, and for any subprocess) and through
# jax.config (if something imported jax before conftest ran).
import os

os.environ["JAX_PLATFORMS"] = "cpu"  # for any subprocesses we spawn
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    # read at CPU client creation, which happens lazily after conftest
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Compile-once discipline: a persistent compilation cache makes re-runs and
# cross-test shape reuse cheap (first cold run still compiles).
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(os.path.dirname(__file__), ".jax_cache"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


# The full suite accumulates thousands of compiled executables (every
# capacity-bucket shape x every operator x 1- and 8-device variants); past a
# threshold XLA:CPU's compile-and-load segfaults (observed reproducibly at
# ~test 65 of the full run, never in per-module runs). Dropping compiled
# state between modules keeps the live-executable population bounded; the
# persistent on-disk cache makes the re-JITs cheap.
import gc

import pytest


@pytest.fixture(autouse=True, scope="module")
def _bound_live_executables():
    yield
    import jax as _jax

    _jax.clear_caches()
    # our own dispatch caches hold compiled callables too
    from dbsp_tpu.parallel.lift import _lifted_jit

    _lifted_jit.cache_clear()
    gc.collect()


@pytest.fixture
def accelerator_dispatch(monkeypatch):
    """Steer the backend-keyed dispatch (``zset.kernels.accelerator``) to
    its accelerator branches on the CPU: ``jax.default_backend`` answers
    ``"tpu"`` for the rest of the test. A jitted program traced under one
    dispatch must not be served under the other, so JAX's caches are
    dropped on both sides."""
    jax.clear_caches()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield
    jax.clear_caches()


# ---------------------------------------------------------------------------
# Test tiers: `pytest -m fast` is the <2-minute pre-commit subset — every
# operator's correctness oracle at small scale. Tests/modules marked `slow`
# (compiled-path differentials, nexmark full suite, SLT corpus, parallel
# 8-worker sweeps) are excluded from it; everything else is auto-marked
# `fast`, so the two tiers partition the suite.
def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running (excluded from the -m fast tier)")
    config.addinivalue_line(
        "markers", "fast: the <2-minute pre-commit correctness tier")
    config.addinivalue_line(
        "markers", "perf: throughput regression gate vs recorded bands "
        "(tests/perf_baseline.json; ~2-3 min on a quiet core)")


def pytest_collection_modifyitems(config, items):
    for item in items:
        if "slow" not in item.keywords:
            item.add_marker(pytest.mark.fast)
