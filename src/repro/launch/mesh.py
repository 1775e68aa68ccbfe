"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi pod:  (pod=2, data=16, model=16) = 512 chips; the `pod` axis is pure
data parallelism over the cross-pod interconnect (gradient all-reduce only,
where runtime.compress applies).

Functions, not module constants — importing this module never touches jax
device state (the dry-run sets XLA_FLAGS *before* any jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with ``Auto`` axes: the model code places tensors
    with ``with_sharding_constraint`` and the pipeline closes over
    operands inside ``shard_map``, both of which assume ``Auto``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_pipeline_mesh():
    """Optional PP mesh: 512 = pipe(4) x data(8) x model(16)."""
    return _auto_mesh((4, 8, 16), ("pipe", "data", "model"))


def make_local_mesh(axes: tuple[str, ...] = ("data",)):
    """All local devices on one axis (CPU tests / the core library)."""
    n = len(jax.devices())
    return _auto_mesh((n,) + (1,) * (len(axes) - 1), axes)
