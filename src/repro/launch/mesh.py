"""Production meshes.

Kept as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run sets
``--xla_force_host_platform_device_count=512`` before any jax import; tests
and benchmarks see the real single device unless they opt in themselves.
"""

from __future__ import annotations

import jax


def _mk(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def mesh_context(mesh):
    """``jax.set_mesh(mesh)``: the context that makes ``mesh`` current."""
    return jax.set_mesh(mesh)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_smoke_mesh(*, multi_pod: bool = False):
    """Tiny mesh for CPU tests (requires >=4 or >=8 host devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 2)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)
