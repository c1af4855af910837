"""Device-mesh helpers.

The FL simulator's primary parallel axis is ``clients`` — the TPU-native
replacement for the reference's one-OS-process-per-client MPI layout
(SURVEY.md §2.9). A second optional ``model`` axis is reserved for
tensor-parallel large-model federation (splitnn/gkt-scale models).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh


def client_mesh(num_devices: Optional[int] = None, axis_name: str = "clients") -> Mesh:
    """1-D mesh over the first ``num_devices`` devices (default: all).
    All devices: ``create_device_mesh`` orders them along the physical
    ring. A proper subset of a TPU slice need not be a torus — 3 chips of
    a 2x2 make ``create_device_mesh`` fail a bare assertion — so a subset
    takes enumeration order; a 1-D axis is valid in any order."""
    devices = jax.devices()
    n = num_devices or len(devices)
    if n > len(devices):
        raise ValueError(f"client_mesh({n}): only {len(devices)} devices")
    if n == len(devices):
        return Mesh(mesh_utils.create_device_mesh((n,)), (axis_name,))
    return Mesh(np.asarray(devices[:n]), (axis_name,))


def mesh_2d(client_parallel: int, model_parallel: int,
            axis_names: Sequence[str] = ("clients", "model")) -> Mesh:
    devices = mesh_utils.create_device_mesh((client_parallel, model_parallel))
    return Mesh(devices, tuple(axis_names))
