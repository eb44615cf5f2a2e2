"""Mesh construction (functions only — importing never touches jax device
state; the dry-run sets XLA_FLAGS before any jax import).

Production target: TPU v5e-class pods of 256 chips, 16x16 per pod; the
multi-pod mesh adds a leading `pod` axis (2 pods = 512 chips).
"""
from __future__ import annotations

import jax
import numpy as np

__all__ = [
    "make_production_mesh",
    "make_local_mesh",
    "make_fleet_mesh",
    "HARDWARE",
]

#: roofline constants (TPU v5e-class), used by repro.analysis.roofline.
HARDWARE = {
    "peak_bf16_flops": 197e12,   # per chip
    "hbm_bandwidth": 819e9,      # bytes/s per chip
    "ici_link_bandwidth": 50e9,  # bytes/s per link
}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_local_mesh(data: int | None = None, model: int = 1):
    """Mesh over whatever devices exist (CPU smoke / single-host runs)."""
    n = len(jax.devices())
    if data is None:
        data = max(1, n // model)
    return jax.make_mesh(
        (data, model), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )


def make_fleet_mesh(shards: int | None = None):
    """1-D ``shard`` mesh for the sharded fleet service.

    One mesh slot per worker shard, over the host's devices: on CPU,
    set ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before
    the first jax import to expose N devices in one process (the
    N-shard CPU test rig; see `fleet.shard.ShardedFleetService`).  When
    fewer devices exist than `shards`, the mesh is built over what
    exists and `distributed.sharding.shard_placements` round-robins the
    shards onto it.
    """
    devs = jax.devices()
    n = len(devs) if shards is None else max(1, min(int(shards), len(devs)))
    return jax.sharding.Mesh(np.asarray(devs[:n]), ("shard",))
