"""Production mesh definitions.

Functions (not module-level constants) so importing this module never touches
jax device state — the dry-run sets XLA_FLAGS *before* any jax init.
"""

from __future__ import annotations

import jax


def _mk(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_test_mesh(*, multi_pod: bool = False):
    """Small mesh for CI-scale dry-run tests (8 host devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_single_device_mesh():
    return _mk((1, 1), ("data", "model"))
