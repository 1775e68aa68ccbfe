"""Pallas TPU kernel: fused Eq. 3 RLS score — gram tile -> quadform -> score.

The BLESS ladder's per-level hot loop evaluates

    l~(i) = (K_ii - k_i^T (K_JJ + lam n A)^{-1} k_i) / (lam n)

for a tile of candidates i against the full center set J. The pre-fusion
path moves the (R, M) Gram block through HBM three times (gram write,
G @ W read, elementwise read); this kernel keeps it in VMEM for its whole
lifetime: one MXU matmul forms the distance cross-term, the family epilogue
(VPU) produces the Gram tile, a second MXU matmul contracts it against the
resident (M, M) inverse W, and the score epilogue reduces each row to
one lane of a (1, bn) output block — one dispatch per ladder level.

Residency: z (M, d), W (M, M) and the center mask stay in VMEM across the
whole grid (M ~ d_eff, the same bound that lets FALKON replicate its
preconditioner), so the grid is 1-D over candidate tiles. ops.py guards the
M <= 1024 VMEM budget (4 MB for W at fp32) and the backend composes the
separate gram/quadform kernels above it. The mask, K_ii and the scores
travel as lane-dense (1, ·) rows (``common.lane_row_sums``).

The Cholesky-solve that produces W = (K_JJ + lam n A)^{-1} runs outside
(LAPACK/XLA beats a hand-rolled Pallas factorization at M ~ d_eff); what
the paper's cost model charges per level is the O(R M^2) contraction, which
is exactly what this kernel fuses. lam n arrives as a (1, 1) SMEM scalar so
sweeping the ladder's lam path reuses one compiled kernel.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...families import get_family
from ..common import lane_row_sums, mxu_precision, vmem_params


def _rls_score_kernel(lamn_ref, x_ref, z_ref, w_ref, zmask_ref, kdiag_ref, o_ref,
                      *, kind: str, inv_scale: float, bf16: bool):
    fam = get_family(kind)  # static: resolved once per trace
    x = x_ref[...].astype(jnp.float32)  # (bn, d)
    z = z_ref[...].astype(jnp.float32)  # (M, d) — resident across the grid
    xc, zc = (x.astype(jnp.bfloat16), z.astype(jnp.bfloat16)) if bf16 else (x, z)
    prod = jax.lax.dot_general(xc, zc, (((1,), (1,)), ((), ())),
                               precision=mxu_precision(bf16),
                               preferred_element_type=jnp.float32)  # (bn, M) MXU
    if fam.dot_only:
        pre = prod
    else:
        xn = jnp.sum(x * x, axis=-1)[:, None]
        zn = jnp.sum(z * z, axis=-1)[None, :]
        pre = jnp.maximum(xn + zn - 2.0 * prod, 0.0)
    # family epilogue on the VPU; invalid center columns zeroed so the padded
    # rows of W (identity there) cannot leak k(x, 0)^2 into the quadform
    g = fam.epilogue(pre, inv_scale) * zmask_ref[...]  # (1, M) row
    gw = g if not bf16 else g.astype(jnp.bfloat16)
    w = w_ref[...].astype(gw.dtype)  # (M, M) resident inverse
    acc = jax.lax.dot_general(gw, w, (((1,), (0,)), ((), ())),
                              precision=mxu_precision(bf16),
                              preferred_element_type=jnp.float32)  # (bn, M) MXU
    quad = lane_row_sums(acc * g)  # (1, bn)
    o_ref[...] = (kdiag_ref[...] - quad) / lamn_ref[0, 0]


@partial(jax.jit, static_argnames=("kind", "inv_scale", "bn", "interpret", "bf16"))
def rls_score_pallas(x: jax.Array, z: jax.Array, w: jax.Array, zmask: jax.Array,
                     kdiag: jax.Array, lamn: jax.Array, inv_scale: float, *,
                     kind: str = "gaussian", bn: int = 256,
                     interpret: bool = True, bf16: bool = False) -> jax.Array:
    """Fused Eq. 3 scores for pre-padded operands.

    x (R, d) candidates, z (M, d) centers, w (M, M) = (K_JJ + lam n A)^{-1},
    zmask (M,) center validity as fp32, kdiag (R,) = K_ii, lamn (1, 1) the
    scalar lam * n. Requires R % bn == 0, d % 128 == 0, M % 128 == 0.
    Returns (R,) fp32 scores (unclipped).
    """
    n, d = x.shape
    m = z.shape[0]
    assert n % bn == 0 and d % 128 == 0 and m % 128 == 0, (n, m, d)
    return pl.pallas_call(
        partial(_rls_score_kernel, kind=kind, inv_scale=float(inv_scale), bf16=bf16),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((bn, d), lambda i: (i, 0)),  # candidate tile
            pl.BlockSpec((m, d), lambda i: (0, 0)),  # z: resident
            pl.BlockSpec((m, m), lambda i: (0, 0)),  # W: resident
            pl.BlockSpec((1, m), lambda i: (0, 0)),  # center mask
            pl.BlockSpec((1, bn), lambda i: (0, i)),  # K_ii tile
        ],
        out_specs=pl.BlockSpec((1, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        compiler_params=vmem_params(
            [(bn, d), (m, d), (m, m), (1, m), (1, bn), (1, bn)], (bn, m)),
        interpret=interpret,
    )(lamn, x, z, w, zmask.reshape(1, m), kdiag.reshape(1, n))[0]
