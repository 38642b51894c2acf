"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* any jax
initialization, and smoke tests must keep seeing 1 device.

single-pod: (16, 16)      axes ("data", "model")        — 256 chips
multi-pod:  (2, 16, 16)   axes ("pod", "data", "model") — 512 chips

v5e hardware constants for the roofline terms live here too.
"""

from __future__ import annotations

import jax

# TPU v5e per-chip constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12       # FLOP/s
HBM_BW = 819e9                 # B/s
ICI_BW = 50e9                  # B/s per link


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke/integration tests."""
    return _mesh((1, 1), ("data", "model"))


def mesh_chips(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
