"""Pallas TPU kernel: fused leverage-score quadratic form.

Computes  s_i = sum_jk G_ij W_jk G_ik  =  rowsum((G @ W) * G)  without ever
writing G @ W to HBM — the epilogue of Eq. 3 (l~ = (K_ii - s_i)/(lam n)).
A naive two-op version moves the (n, M) product through HBM twice; fusing
keeps it in VMEM, turning the op from memory- to compute-bound for M >= 512.

Grid (i, k, j), j innermost: the (bn, bk) slab of G@W accumulates in VMEM
scratch over j, then at j == last multiplies elementwise with G[i, k-tile]
and row-reduces into the lane-dense (1, bn) output block
(``common.lane_row_sums``; indexed by i only — Pallas revisits it across
k and j, which is legal under sequential TPU grids).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import lane_row_sums, mxu_precision


def _quadform_kernel(g_kj_ref, w_ref, g_ik_ref, o_ref, acc_ref, *, nj: int, nk: int,
                     bf16: bool):
    k = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when((k == 0) & (j == 0))
    def _init_out():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(j == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # bf16: MXU operands only; the fp32 VMEM accumulator and the elementwise
    # epilogue keep full precision (DESIGN.md §2 documents the tolerances).
    dt = jnp.bfloat16 if bf16 else jnp.float32
    g = g_kj_ref[...].astype(dt)  # (bn, bj) — G[:, j-tile]
    w = w_ref[...].astype(dt)  # (bj, bk)
    acc_ref[...] += jax.lax.dot_general(g, w, (((1,), (0,)), ((), ())),
                                        precision=mxu_precision(bf16),
                                        preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _epilogue():
        gk = g_ik_ref[...].astype(jnp.float32)  # (bn, bk) — G[:, k-tile]
        o_ref[...] += lane_row_sums(acc_ref[...] * gk)


@partial(jax.jit, static_argnames=("bn", "bm", "interpret", "bf16"))
def quadform_pallas(g: jax.Array, w: jax.Array, *, bn: int = 256, bm: int = 256,
                    interpret: bool = True, bf16: bool = False) -> jax.Array:
    """rowsum((G @ W) * G) for pre-padded G (n, m), W (m, m)."""
    n, m = g.shape
    assert n % bn == 0 and m % bm == 0, (n, m)
    nj = nk = m // bm
    return pl.pallas_call(
        partial(_quadform_kernel, nj=nj, nk=nk, bf16=bf16),
        grid=(n // bn, nk, nj),
        in_specs=[
            pl.BlockSpec((bn, bm), lambda i, k, j: (i, j)),  # G[:, j]
            pl.BlockSpec((bm, bm), lambda i, k, j: (j, k)),  # W[j, k]
            pl.BlockSpec((bn, bm), lambda i, k, j: (i, k)),  # G[:, k]
        ],
        out_specs=pl.BlockSpec((1, bn), lambda i, k, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, bm), jnp.float32)],
        interpret=interpret,
    )(g, w, g)[0]
