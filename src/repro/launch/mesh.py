"""The device mesh of sharded snapshot retrieval.

A function, not a module constant, so importing this module never touches
jax device state.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def retrieval_mesh(partitions: int, axis: str = "data") -> Mesh:
    """1-D mesh for sharded snapshot retrieval: one device per storage
    partition, so the ``word_cyclic`` layout row owned by partition ``p``
    lives on device ``p`` and the delta-apply chain runs collective-free
    (see :func:`repro.runtime.jax_exec.execute_singlepoint_sharded`)."""
    devices = jax.devices()
    if len(devices) < partitions:
        raise RuntimeError(f"need {partitions} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[:partitions]), (axis,))
