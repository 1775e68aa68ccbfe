"""Pallas TPU kernel: blocked Gram matrix k(X, Z).

The paper's universal hot-spot — every BLESS level and every FALKON CG
iteration starts from Gram blocks. TPU mapping (DESIGN.md §2):
``||x-z||^2 = ||x||^2 + ||z||^2 - 2 X Z^T`` puts all the FLOPs in one MXU
matmul per tile; the exp/epilogue runs on the VPU while the next tile's
matmul occupies the MXU.

Tiling: grid (n/bn, m/bm); X tile (bn, d) and Z tile (bm, d) live in VMEM,
``d`` is padded to a multiple of 128 (lane width) by ops.py. bn=bm=256 keeps
the working set (2*256*d + 256*256) * 4B well under VMEM for d <= 2048.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...families import get_family
from ..common import mxu_precision


def _gram_kernel(x_ref, z_ref, o_ref, *, kind: str, inv_scale: float, bf16: bool):
    fam = get_family(kind)  # kind is static: resolved once per trace
    x = x_ref[...].astype(jnp.float32)  # (bn, d)
    z = z_ref[...].astype(jnp.float32)  # (bm, d)
    # bf16: MXU operands dropped to bf16, fp32 accumulation; norms/epilogue
    # stay fp32 (only the distance cross-term loses precision — DESIGN.md §2).
    xc, zc = (x.astype(jnp.bfloat16), z.astype(jnp.bfloat16)) if bf16 else (x, z)
    prod = jax.lax.dot_general(xc, zc, (((1,), (1,)), ((), ())),
                               precision=mxu_precision(bf16),
                               preferred_element_type=jnp.float32)  # (bn, bm) on MXU
    if fam.dot_only:
        o_ref[...] = fam.epilogue(prod, inv_scale).astype(o_ref.dtype)
        return
    xn = jnp.sum(x * x, axis=-1)[:, None]
    zn = jnp.sum(z * z, axis=-1)[None, :]
    d2 = jnp.maximum(xn + zn - 2.0 * prod, 0.0)
    # the family's elementwise epilogue runs on the VPU while the next tile's
    # matmul occupies the MXU — same function as the jnp reference formula.
    o_ref[...] = fam.epilogue(d2, inv_scale).astype(o_ref.dtype)


@partial(jax.jit, static_argnames=("kind", "bn", "bm", "interpret", "inv_scale", "bf16"))
def gram_pallas(x: jax.Array, z: jax.Array, inv_scale: float, *, kind: str = "gaussian",
                bn: int = 256, bm: int = 256, interpret: bool = True,
                bf16: bool = False) -> jax.Array:
    """k(X, Z) for pre-padded inputs: n % bn == 0, m % bm == 0, d % 128 == 0."""
    n, d = x.shape
    m = z.shape[0]
    assert n % bn == 0 and m % bm == 0 and d % 128 == 0, (n, m, d)
    return pl.pallas_call(
        partial(_gram_kernel, kind=kind, inv_scale=float(inv_scale), bf16=bf16),
        grid=(n // bn, m // bm),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), x.dtype),
        interpret=interpret,
    )(x, z)
