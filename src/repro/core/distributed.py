"""Distributed BLESS / FALKON over a (data,)-sharded dataset.

The paper's only distributed story is "SQUEAK with p machines"; here both
phases are jax-native SPMD (DESIGN.md §2):

  * FALKON CG matvec  v -> K_nM^T (K_nM v):  X and y are row-sharded over the
    ``data`` mesh axis; each device streams its shard through the jnp row
    streamer (``local_knm_quadratic``, one (block, M) Gram tile live at a
    time) and the (M,) partials are ``psum``-ed — the exact collective
    schedule of a DP gradient all-reduce, so it inherits XLA's overlap
    machinery.
  * BLESS candidate scoring lives behind the backend seam:
    ``repro.core.backend.ShardedBackend.masked_quadform`` (candidates
    row-sharded, the (Mbuf, Mbuf) Cholesky factor replicated — it is
    <= d_eff^2 by the paper's own space bound).

Everything here works on a 1-device mesh too, which is how the unsharded
tests exercise it; tests/test_distributed.py re-runs on 8 forced host
devices in a subprocess. The shard_maps run with ``check_vma=False``: the
streamers' scan carries start replicated and become per-device partials,
which the ``psum`` then reduces.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from .backend import STREAM_BLOCK, _jnp_knm_matvec
from .falkon import FalkonModel, local_knm_quadratic, local_knm_t
from .gram import Kernel

Array = jax.Array


def data_mesh(axis: str = "data") -> Mesh:
    """1-D mesh over all local devices (the core library's DP mesh).

    The axis is ``Auto``: the dist_* ops below close over replicated
    operands inside ``shard_map``, which ``Explicit`` axes refuse."""
    return jax.make_mesh((len(jax.devices()),), (axis,),
                         axis_types=(AxisType.Auto,))


def shard_rows(mesh: Mesh, x: Array, axis: str = "data") -> Array:
    """Place a (n, ...) array row-sharded; pads n up to the axis size."""
    p = (-x.shape[0]) % mesh.shape[axis]
    if p:
        x = jnp.pad(x, ((0, p),) + ((0, 0),) * (x.ndim - 1))
    return jax.device_put(x, NamedSharding(mesh, P(axis, *([None] * (x.ndim - 1)))))


def _block() -> int:
    """Rows per streamed Gram tile inside each shard (the jnp streamer's
    platform table): a whole (n / devices, M) tile would not fit HBM at
    SUSY's n with thousands of centers."""
    return STREAM_BLOCK.get(jax.default_backend(), 2048)


def _valid_rows(xl: Array, n_pad: int, n_valid: int, mesh: Mesh, axis: str) -> Array:
    """1.0 on this shard's real rows, 0.0 on ``shard_rows``' zero padding."""
    rows = jax.lax.axis_index(axis) * (n_pad // mesh.shape[axis]) + jnp.arange(xl.shape[0])
    return (rows < n_valid).astype(xl.dtype)


def dist_knm_quadratic(mesh: Mesh, kernel: Kernel, x_sharded: Array, z: Array,
                       n_valid: int, axis: str = "data", *,
                       mask: Array | None = None) -> Callable[[Array], Array]:
    """Returns v -> K_nM^T (K_nM v) with X row-sharded over ``axis``.

    ``v`` may be (M,) or an (M, k) panel (replicated either way): each
    device contracts its local Gram block against every column, and the
    psum-ed partial is (M,) or (M, k) accordingly.

    ``mask`` — optional per-column row-exclusion weights, row-sharded like
    X ((n,) or an (n, k) panel): column j computes K_nM^T diag(m_j) K_nM
    v_j, the exact-CV form, as one extra elementwise multiply on the local
    (rows, k) intermediate before the psum.
    """
    n_pad = x_sharded.shape[0]
    block = _block()

    @jax.jit
    def op(v: Array) -> Array:
        def local(xl: Array, vl: Array) -> Array:
            valid = _valid_rows(xl, n_pad, n_valid, mesh, axis)
            part = local_knm_quadratic(kernel, xl, z, block=block, mask=valid)(vl)
            return jax.lax.psum(part, axis)

        def local_masked(xl: Array, ml: Array, vl: Array) -> Array:
            valid = _valid_rows(xl, n_pad, n_valid, mesh, axis)
            mk = ml * (valid if ml.ndim == 1 else valid[:, None])
            part = local_knm_quadratic(kernel, xl, z, block=block, mask=mk)(vl)
            return jax.lax.psum(part, axis)

        if mask is None:
            return shard_map(local, mesh=mesh, in_specs=(P(axis, None), P()),
                             out_specs=P(), check_vma=False)(x_sharded, v)
        mspec = P(axis, *([None] * (mask.ndim - 1)))
        return shard_map(local_masked, mesh=mesh,
                         in_specs=(P(axis, None), mspec, P()),
                         out_specs=P(), check_vma=False)(x_sharded, mask, v)

    return op


def dist_knm_t(mesh: Mesh, kernel: Kernel, x_sharded: Array, y_sharded: Array, z: Array,
               n_valid: int, axis: str = "data") -> Array:
    """K_nM^T y with X, y row-sharded; ``y`` (n,) -> (M,), (n, k) -> (M, k)."""
    n_pad = x_sharded.shape[0]
    block = _block()

    def local(xl: Array, yl: Array) -> Array:
        valid = _valid_rows(xl, n_pad, n_valid, mesh, axis)
        mk = valid if yl.ndim == 1 else valid[:, None]
        return jax.lax.psum(local_knm_t(kernel, xl, z, yl, block=block, mask=mk), axis)

    return jax.jit(shard_map(local, mesh=mesh, in_specs=(P(axis, None), P(axis)),
                             out_specs=P(), check_vma=False))(x_sharded, y_sharded)


@functools.lru_cache(maxsize=None)
def _dist_knm_matvec_fn(mesh: Mesh, axis: str):
    """Jitted shard_map'd predict contraction, cached per (mesh, axis) so the
    serving hot path compiles once per wave shape, not once per call. Each
    shard streams its rows through the jnp predict contraction."""
    local = functools.partial(_jnp_knm_matvec, block=_block())
    return jax.jit(shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(axis, None), P(), P()), out_specs=P(axis),
        check_vma=False))


def dist_knm_matvec(mesh: Mesh, kernel: Kernel, x_sharded: Array, z: Array, v: Array,
                    n_valid: int, axis: str = "data") -> Array:
    """K_nM v with X row-sharded — the predict contraction. ``v`` may be
    (M,) or an (M, k) panel (one local Gram evaluation serves all columns).
    The output is row-parallel (each device owns its rows), so no collective
    is needed; padded rows produce values that are sliced off."""
    return _dist_knm_matvec_fn(mesh, axis)(kernel, x_sharded, z, v)[:n_valid]


def falkon_fit_distributed(mesh: Mesh, kernel: Kernel, x: Array, y: Array, centers: Array,
                           lam: float, *, a_diag: Array | None = None, iters: int = 20,
                           axis: str = "data") -> FalkonModel:
    """Data-parallel FALKON: X/y sharded over ``axis``, (M,*) state replicated.

    Thin wrapper: ``falkon_fit`` with a ``ShardedBackend`` pinned to ``mesh``
    — the backend stages X/y once (shard_rows) and serves both CG
    contractions through the same dist_* collectives defined above.
    """
    from .backend import ShardedBackend
    from .falkon import falkon_fit

    return falkon_fit(kernel, x, y, centers, lam, a_diag=a_diag, iters=iters,
                      backend=ShardedBackend(axis=axis, mesh=mesh))
