"""Production meshes.

Single pod: 256 chips as (data=16, model=16). Multi-pod: 2 pods = 512 chips
as (pod=2, data=16, model=16) — `pod` acts as an outer data-parallel axis
(gradient reduction crosses the inter-pod links once per step); the model/
TP axis never leaves a pod.

Defined as functions (never module-level constants) so importing this
module touches no jax device state.
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model: int = 1, data: int = 1):
    """Small mesh over host devices for tests/examples."""
    n = len(jax.devices())
    data = min(data, max(1, n // model))
    return _make_mesh((data, model), ("data", "model"))
