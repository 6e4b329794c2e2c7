"""Device mesh construction for SPMD worker parallelism.

The analog of the reference's worker-thread pool (``circuit/runtime.rs:137``):
a worker here is a TPU core/chip in a 1-D ``jax.sharding.Mesh`` named
``"workers"``. Sharded state lives as arrays with a leading worker axis;
the exchange operator's all-to-all rides ICI (see parallel/exchange.py).
"""

from __future__ import annotations

import numpy as np
import jax
from jax import shard_map  # noqa: F401 — re-exported to the SPMD builders
from jax.sharding import Mesh, NamedSharding, PartitionSpec

WORKER_AXIS = "workers"


def make_mesh(workers: int) -> Mesh:
    devices = jax.devices()
    assert workers <= len(devices), (
        f"requested {workers} workers but only {len(devices)} devices are "
        "visible (use XLA_FLAGS=--xla_force_host_platform_device_count=N "
        "JAX_PLATFORMS=cpu for virtual-device testing)")
    return Mesh(np.asarray(devices[:workers]), (WORKER_AXIS,))


def worker_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for arrays with a leading [workers, ...] axis."""
    return NamedSharding(mesh, PartitionSpec(WORKER_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())
