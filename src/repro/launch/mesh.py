"""Mesh construction: every mesh of the program is built here.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.

Axes are ``AxisType.Auto``: the models shard through ``NamedSharding``
annotations and let the compiler propagate them (jax's default for
``jax.make_mesh`` is ``Explicit``, under which the embedding gathers and
``shard_map`` pipelines would need per-op output shardings).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axes; ``devices`` defaults to this
    process's devices (pass described devices to compile for a chip that
    is not attached)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model: int = 1):
    """Whatever this host has — used by tests/examples (1 CPU device)."""
    n = len(jax.devices())
    return make_mesh((n // model, model), ("data", "model"))
