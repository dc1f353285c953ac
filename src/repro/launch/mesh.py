"""Production meshes.

Single pod: (data=16, model=16) — 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the 'pod' axis is the
DCN-connected dimension (kept outermost so cross-pod collectives are pure
data-parallel gradient reductions, optionally bf16/int8-compressed).

Defined as functions (never module-level) so importing this module does not
touch jax device state.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: shardings propagate from
    the arguments and the ``with_sharding_constraint`` hints."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over whatever devices exist (tests)."""
    return make_mesh((data, model), ("data", "model"))
