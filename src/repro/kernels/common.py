"""Shared helpers for the Pallas TPU kernels.

All kernels target TPU (MXU-aligned tiles, VMEM BlockSpecs). On a TPU they
compile through Mosaic; on the CPU backend (the test suite, run with
``JAX_PLATFORMS=cpu``) the same kernels run with ``interpret=True``.
``default_interpret()`` picks the mode from the platform JAX runs on, and
``tests/test_tpu_compile.py`` compiles the main-path kernels for a
described v5e chip so layout and VMEM faults surface without one.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

#: Mosaic runs a DEFAULT-precision fp32 matmul as one bf16 pass: on v5e a
#: Gram tile came out 5.6e-4 off in relative norm and Eq. 3 scores, which
#: cancel K_ii against the quadratic form, 1.3e-2 off. fp32 operands
#: therefore ask for full fp32; bf16 operands (the opt-in mixed-precision
#: mode) take one pass either way.
FP32 = jax.lax.Precision.HIGHEST


def mxu_precision(bf16: bool):
    """The ``precision`` for a dot whose operands are bf16 iff ``bf16``."""
    return None if bf16 else FP32


#: Scoped-VMEM ceiling for one kernel: a v5e core has 128 MiB of VMEM; the
#: rest is left to Mosaic's own scratch.
VMEM_CAP_BYTES = 100 << 20
#: Mosaic's default scoped-VMEM limit; small kernels keep it.
_VMEM_FLOOR_BYTES = 16 << 20


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def vmem_params(blocks: list[tuple[int, ...]],
                tile: tuple[int, int]) -> pltpu.CompilerParams:
    """Mosaic params with a scoped-VMEM limit sized for a kernel whose
    center-side operands stay resident across the grid.

    ``blocks`` lists the fp32 block shape of every operand and output (the
    pipeline double-buffers each); ``tile`` is the (bn, M) in-kernel
    intermediate, of which the Gram tile and its epilogue keep about two
    alive. Without this the (M, ·) blocks exhaust the 16 MiB default once
    M reaches a few thousand.
    """
    block_bytes = 4 * sum(math.prod(b) for b in blocks)
    need = 2 * block_bytes + 2 * 4 * math.prod(tile) + (4 << 20)
    return pltpu.CompilerParams(
        vmem_limit_bytes=int(min(max(need, _VMEM_FLOOR_BYTES), VMEM_CAP_BYTES)))


def lane_row_sums(p: jax.Array) -> jax.Array:
    """Row sums of an in-kernel (bn, k) tile as a lane-dense (1, bn) row.

    Kernels that reduce each row to one score emit them as (1, n) arrays:
    Mosaic refuses a 1-D (bn,) block whose tiling differs from XLA's
    T(1024) layout for 1-D fp32, and a (bn, 1) column is padded 128x in
    HBM. Summing over lanes and turning the result into a row is one MXU
    contraction against a ones row, kept at fp32 precision.
    """
    ones = jnp.ones((1, p.shape[1]), jnp.float32)
    return jax.lax.dot_general(ones, p, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def pad_dim(x: jax.Array, axis: int, to: int) -> jax.Array:
    pad = to - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)
