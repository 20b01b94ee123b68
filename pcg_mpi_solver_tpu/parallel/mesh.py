"""Device-mesh helpers.

The reference's distributed runtime is mpi4py over OpenMPI (1 rank = 1 mesh
partition, pcg_solver.py:91,968-970).  Here the runtime is a 1-D
``jax.sharding.Mesh`` over TPU devices: one device = one (or more, stacked)
mesh partition(s); collectives ride ICI inside the jitted program.  Multi-host
extends the same mesh over DCN via ``jax.distributed`` without code changes.
"""

from __future__ import annotations

import jax
import numpy as np

PARTS_AXIS = "parts"


def make_mesh(n_devices: int | None = None, devices=None) -> jax.sharding.Mesh:
    """1-D mesh over the parts axis."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            if len(devices) < n_devices:
                raise ValueError(
                    f"requested {n_devices} devices, only {len(devices)} available")
            devices = devices[:n_devices]
    return jax.sharding.Mesh(np.array(devices), (PARTS_AXIS,))


def part_spec() -> jax.sharding.PartitionSpec:
    """Leading-axis sharding: arrays are (P, ...) with P split over devices."""
    return jax.sharding.PartitionSpec(PARTS_AXIS)
