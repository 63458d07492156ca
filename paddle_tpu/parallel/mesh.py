"""Device meshes and sharding helpers.

TPU-native replacement for the reference's device/communicator management:
`NCCLContextMap` / `NCCLCommunicator` flat + hierarchical rings
(reference: paddle/fluid/platform/nccl_helper.h:90,179) become a named
`jax.sharding.Mesh` over the chips; ring ids map to axis names
(parallel/env.py) and XLA GSPMD inserts the collectives that the reference
built manually as op-handles (details/all_reduce_op_handle.cc).

Axis conventions (the scaling-book layout):
  * ``dp``   — data parallel (batch dim). Rides ICI within a slice, DCN
               across slices (hierarchical allreduce analog,
               nccl_helper.h:179 — here just axis ordering in the mesh).
  * ``tp``   — tensor/model parallel (hidden dims of matmuls).
  * ``pp``   — pipeline stages.
  * ``sp``   — sequence/context parallel (ring attention).
  * ``ep``   — expert parallel (MoE / sharded embedding tables).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np

__all__ = ["make_mesh", "default_mesh", "data_parallel_mesh", "MeshGuard",
           "local_devices"]


_current_mesh = None


def local_devices(backend: Optional[str] = None):
    """Devices for mesh building. ``PADDLE_TPU_BACKEND`` overrides the jax
    default (the test suite sets it to ``cpu`` to get the 8-device virtual
    mesh while the process default backend is the real TPU)."""
    import os

    import jax

    backend = backend or os.environ.get("PADDLE_TPU_BACKEND") or None
    return jax.devices(backend) if backend else jax.devices()


def make_mesh(axes: Dict[str, int], devices=None, backend: Optional[str] = None):
    """Build a jax Mesh with named axes; sizes must multiply to #devices
    (or a divisor thereof — extra devices are left out).

    When the mesh spans every local device (and the caller did not pick
    ``devices`` itself), jax orders them along the physical links
    (``mesh_utils.create_device_mesh``): on a v5e 2x2 tray the flat id
    order 0,1,2,3 crosses a diagonal on two of a ring's four hops, the
    ring order is 0,1,3,2.  For CPU devices that call is the plain
    reshape.  A caller-chosen list, or a subset, keeps its order."""
    from jax.sharding import Mesh

    whole_backend = devices is None
    if devices is None:
        devices = local_devices(backend)
    sizes = list(axes.values())
    n = int(np.prod(sizes)) if sizes else 1
    if n > len(devices):
        raise ValueError(
            "mesh %r needs %d devices, have %d" % (axes, n, len(devices))
        )
    if whole_backend and n == len(devices):
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(sizes, devices=devices)
    else:
        dev_array = np.array(devices[:n]).reshape(sizes)
    return Mesh(dev_array, tuple(axes.keys()))


def data_parallel_mesh(num_devices: Optional[int] = None, backend: Optional[str] = None):
    if num_devices is None:
        num_devices = len(local_devices(backend))
    return make_mesh({"dp": num_devices}, backend=backend)


def default_mesh():
    """The mesh bound by MeshGuard, or a fresh all-devices dp mesh."""
    if _current_mesh is not None:
        return _current_mesh
    return data_parallel_mesh()


class MeshGuard:
    """Bind a mesh as the process-wide default (the reference's
    `ParallelExecutor` holding its NCCLContextMap for the run)."""

    def __init__(self, mesh):
        self.mesh = mesh
        self._prev = None

    def __enter__(self):
        global _current_mesh
        self._prev = _current_mesh
        _current_mesh = self.mesh
        return self.mesh

    def __exit__(self, *exc):
        global _current_mesh
        _current_mesh = self._prev
        return False
