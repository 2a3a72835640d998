"""Production meshes.

``make_production_mesh`` is a FUNCTION (not module-level state) so importing
this module never touches jax device state; the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init.

Single pod : 16 x 16 = 256 chips, axes ("data", "model")
Multi-pod  : 2 x 16 x 16 = 512 chips, axes ("pod", "data", "model");
             "pod" is pure data parallelism across the DCN/ICI-superpod link.
"""
from __future__ import annotations

import jax


def _auto(n):
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


#: axis names of the mesh a job on a MISO pod sub-slice (a contiguous row
#: range, ``SliceType.mesh_shape``) runs under
SLICE_AXES = ("data", "model")


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 1):
    """Small mesh for CPU integration tests (needs host-device override)."""
    if pod > 1:
        return jax.make_mesh((pod, data, model), ("pod", "data", "model"),
                             axis_types=_auto(3))
    return jax.make_mesh((data, model), ("data", "model"), axis_types=_auto(2))
