"""Production meshes for the multi-pod dry-run (TPU v5e).

Defined as functions (never module-level constants) so importing this module
never touches jax device state.
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_local_mesh"]


def _mk(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_local_mesh():
    """``(n, 1)`` data x model mesh over the n devices this host has: one
    device for CPU smoke tests, a v5e host's 4 chips data-parallel."""
    return _mk((jax.device_count(), 1), ("data", "model"))
