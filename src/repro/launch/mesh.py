"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Single-pod: 16x16 = 256 chips ("data",
"model"); multi-pod: 2 pods x 256 = 512 chips ("pod", "data", "model").
The pod axis carries pure data parallelism (params replicated across
pods; gradient all-reduce is the only cross-pod collective — it rides
the data-center interconnect, not ICI).
"""
from __future__ import annotations

import jax


def _mesh(shape, axes) -> jax.sharding.Mesh:
    # Auto axes: the models shard through annotations, not the Explicit
    # sharding-in-types that ``jax.make_mesh`` defaults to since jax 0.7
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Tiny mesh over the real host devices (tests / smoke runs)."""
    n = len(jax.devices())
    model = min(model, n)
    return _mesh((n // model, model), ("data", "model"))
