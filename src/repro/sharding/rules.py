"""Logical-axis -> mesh-axis sharding rules.

Model code annotates tensors with *logical* axes ("batch", "seq", "heads",
"ff", "experts", "vocab", "embed", ...). A MeshCtx maps those onto whatever
physical mesh is active:

  single pod   (data=16, model=16):        batch->data,  model dims->model
  multi pod    (pod=2, data=16, model=16): batch->(pod,data), model->model

Outside any mesh (CPU smoke tests) every annotation is a no-op, so the same
model code runs on 1 device and on 512.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> tuple of mesh axes (filtered by mesh at use time)
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "fsdp": ("data",),  # weight dim sharded FSDP-style (train only)
    "model": ("model",),
    "seq_shard": ("data",),  # long-context decode: KV sequence dim
    "seq_shard_wide": ("data", "model"),  # batch=1 long-context: all chips
    "none": (),
}


@dataclasses.dataclass
class MeshCtx:
    mesh: Optional[Mesh] = None
    rules: dict[str, tuple[str, ...]] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_RULES))
    fsdp: bool = True  # False at serve time: weights replicated over data

    def axes(self, logical: Optional[str]) -> Optional[tuple[str, ...]]:
        if logical is None or self.mesh is None:
            return None
        if logical == "fsdp" and not self.fsdp:
            return None
        ax = tuple(a for a in self.rules[logical] if a in self.mesh.axis_names)
        return ax or None


_TLS = threading.local()


def set_mesh_ctx(ctx: Optional[MeshCtx]) -> None:
    _TLS.ctx = ctx


def get_mesh_ctx() -> Optional[MeshCtx]:
    return getattr(_TLS, "ctx", None)


def logical_to_spec(*logical: Optional[str], ctx: Optional[MeshCtx] = None) -> P:
    """PartitionSpec from per-dimension logical names (None = replicated)."""
    ctx = ctx or get_mesh_ctx()
    if ctx is None or ctx.mesh is None:
        return P()
    return P(*(ctx.axes(l) for l in logical))


def shard(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical axes; no-op without a mesh ctx."""
    ctx = get_mesh_ctx()
    if ctx is None or ctx.mesh is None:
        return x
    assert x.ndim == len(logical), (x.shape, logical)
    spec = logical_to_spec(*logical, ctx=ctx)
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


def named_sharding(*logical: Optional[str], ctx: Optional[MeshCtx] = None) -> Optional[NamedSharding]:
    ctx = ctx or get_mesh_ctx()
    if ctx is None or ctx.mesh is None:
        return None
    return NamedSharding(ctx.mesh, logical_to_spec(*logical, ctx=ctx))
