"""Production meshes. A FUNCTION, not a module-level constant — importing
this module never touches jax device state (per the brief)."""
from __future__ import annotations

import jax


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``. The model code shards
    through ``with_sharding_constraint`` on bare ``PartitionSpec``s
    (``distributed/sharding.constrain``), which only Auto axes accept;
    newer JAX defaults ``make_mesh`` to Explicit axes."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod ("data", "model"); multi_pod adds a leading
    pure-DP "pod" axis (2 pods = 512 chips, gradient all-reduce over DCN)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 4):
    """Small mesh for CPU multi-device tests (8 forced host devices)."""
    return make_mesh((data, model), ("data", "model"))
