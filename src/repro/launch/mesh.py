"""Production mesh definitions.

FUNCTIONS, not module-level constants: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before importing jax)."""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """``jax.make_mesh`` with Auto axes.

    jax 0.9 makes Explicit axes by default, and ``with_sharding_constraint``
    (the distribution layer's sharding hints) accepts only Auto ones."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 v5e = 256 chips, axes (data, model).
    Multi-pod: 2 pods = 512 chips, axes (pod, data, model); the pod axis is
    the DCN boundary (data parallel / pipeline stage axis)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist right now, as a 1-D 'data' mesh (tests/examples)."""
    return make_mesh((len(jax.devices()),), ("data",))
