"""Production meshes.  Functions, not module constants — importing this
module never touches jax device state (device count is locked on first use).

Single pod: 16x16 = 256 chips ("data", "model").
Multi-pod:  2x16x16 = 512 chips ("pod", "data", "model") — the "pod" axis
composes with "data" for batch/FSDP (DCI-crossing collectives stay on the
gradient reduce-scatter, never inside a layer).
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh for CPU tests of the pjit code paths."""
    return _make_mesh((1, 1), ("data", "model"))
