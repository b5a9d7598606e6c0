"""Mesh construction and sharding helpers.

One logical axis ("batch") carries the signature dimension. On a single
chip the mesh is trivial; on a pod slice it spans all devices and the
batched verify shards rows across chips; per-row verdicts come back to
the host, which tallies them (no collective).
"""

from __future__ import annotations

from typing import Optional, Sequence

BATCH_AXIS = "batch"

# jax imports are lazy: the MeshRouter runs over *logical* host lanes
# (sim determinism rig, degraded-topology tests) without jax present;
# only building a real Mesh/NamedSharding needs the backend.


def make_mesh(devices: Optional[Sequence] = None, axis: str = BATCH_AXIS) -> "Mesh":
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devs = np.asarray(devices if devices is not None else jax.devices())
    return Mesh(devs, (axis,))


def batch_sharding(mesh: "Mesh", axis: str = BATCH_AXIS) -> "NamedSharding":
    """Shard the leading (batch) dimension across the mesh."""
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec(axis))


def replicated_sharding(mesh: "Mesh") -> "NamedSharding":
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec())


def pad_to_multiple(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple
