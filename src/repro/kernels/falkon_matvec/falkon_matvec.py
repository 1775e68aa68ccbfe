"""Pallas TPU kernels: the fused FALKON K_nM contractions.

Three operators share one tile schedule — each (bn, d) tile of X is streamed
HBM->VMEM exactly once, the Gram tile G = k(X_tile, Z) is built in VMEM, and
the contraction epilogue runs before the tile is discarded:

  * ``falkon_matvec_pallas``  R = K_nM^T (K_nM V)  — the CG quadratic matvec
  * ``knm_t_pallas``          R = K_nM^T Y         — the CG right-hand sides
  * ``knm_matvec_pallas``     R = K_nM V           — predict / KRR forward

Each takes a (·, kp) *panel* (the multi-RHS block-CG form; kp is the
lane-padded column count, 128-aligned): the Gram tile — one MXU matmul plus
the VPU distance/exp epilogue per (bn, M) block — is built once per tile and
contracted against every column in the MXU epilogue, so extra right-hand
sides add only (bn, M) x (M, kp) GEMM flops.

``falkon_matvec_pallas`` and ``knm_t_pallas`` also take a single vector
(ops.py picks this form when one column is live), and then contract on the
VPU instead: on the MXU a one-column contraction costs as much as a
128-column one (six bf16 passes at fp32 precision on v5e), so the two
GEMVs of a pass cost twice the Gram tile's own cross product. The vector
travels lane-major — v as a (1, M) row, y as (1, n) row blocks turned into
a (bn, 1) column in VMEM — and G v is an fp32 multiply plus a lane sum
into a (bn, 1) column, G^T t an fp32 multiply by that lane-broadcast column
plus a sublane sum into the (1, M) output row. The static branch stays
inside the same jitted function, so the device trace names the op as
before.

On GPU the reference FALKON implementation materializes K_nM block-by-block
in HBM and runs two GEMVs per block (arithmetic intensity ~4 FLOP/B on the
second pass). Fusing keeps HBM traffic at n*d reads + n*kp (or M*kp) writes
total; what remains is the cross product and the VPU epilogue (DESIGN.md §2
has the cost model).

The Gram tile's cross product X Z^T at fp32 precision costs, on v5e,
HIGHEST's six bf16 MXU passes over d padded to 128 lanes: six passes a
Gram entry for any d up to 128. Where fewer passes do (``ops.takes_packed``:
ceil((6d + 6) / 128) < 6 ceil(d / 128), 6d for a dot-product family, so
d = 18 takes 1, d = 28 2 and d = 90 5; d = 128 keeps HIGHEST), ops.py hands
the kernels ``cross="packed"`` operands instead: the three-way bf16 splits
of X and Z laid side by side so that one bf16 contraction with fp32
accumulation sums the same six products (all nine where they fit the same
lanes), plus the split row norms against ones and Z's exact -2 scale. The
contraction is then the squared distance itself, and the epilogue only
clamps it. Each pick is counted as ``kernels.cross.<cross>``
(``runtime.spans``).

Grid (n/bn,): Z (M, d) and the (M, kp) panel are VMEM-resident across the
whole sweep (M*(d+kp) <= ~4M floats for the paper's d_eff-sized center
sets); each call raises Mosaic's scoped-VMEM limit to what those blocks
and the (bn, M) Gram tile need (``common.vmem_params``). The reductions
(``falkon_matvec``/``knm_t``) revisit one output block every step and
accumulate; ``knm_matvec`` writes a private (bn, kp) block per step.

Mixed precision (``cross="bf16"``, ops.py's ``bf16=True``): the Gram
tile's dominant (bn, d) x (d, M) product loads its operands as bf16 and
accumulates on the MXU in fp32 (``preferred_element_type``); the row
norms, distance epilogue, exp, and the second-stage contractions all stay
fp32. See DESIGN.md §2 for the measured
parity tolerances (kernel values ~1e-2 relative on unit-scale data).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...families import get_family
from ..common import FP32, mxu_precision, vmem_params


def _gram_tile(x: jax.Array, z: jax.Array, *, kind: str, inv_scale: float,
               cross: str) -> jax.Array:
    """k(X_tile, Z) in VMEM for an x (bn, ·) tile against z (M, ·).

    ``kind`` names a registered kernel family; its elementwise epilogue runs
    here on the VPU (the same function body as the jnp reference).

    ``cross`` says how the operands hold the cross product (ops.py picks):
    ``"packed"``: x and z are the bf16 operands of ``ops._pack``, whose one
    contraction with fp32 accumulation sums (at least) the six bf16
    products of HIGHEST, and for a distance family also the split row norms
    and the exact -2 scale, so it gives the squared distance itself.
    ``"fp32"`` / ``"bf16"``: x and z are fp32 with d padded to 128 lanes;
    the cross product runs at HIGHEST (six bf16 passes), or as one pass
    over bf16 casts; the norms and epilogue are always fp32 so the only
    precision loss of ``"bf16"`` is the cross-term of the squared distance.
    """
    fam = get_family(kind)
    if cross == "packed":
        pre = _contract(x, z, None)  # (bn, M)
        return fam.epilogue(pre if fam.dot_only else jnp.maximum(pre, 0.0), inv_scale)
    bf16 = cross == "bf16"
    x, z = x.astype(jnp.float32), z.astype(jnp.float32)
    xc, zc = (x.astype(jnp.bfloat16), z.astype(jnp.bfloat16)) if bf16 else (x, z)
    prod = _contract(xc, zc, mxu_precision(bf16))  # (bn, M)
    if fam.dot_only:
        return fam.epilogue(prod, inv_scale)
    d2 = jnp.maximum(jnp.sum(x * x, -1)[:, None] + jnp.sum(z * z, -1)[None, :]
                     - 2.0 * prod, 0.0)
    return fam.epilogue(d2, inv_scale)


def _contract(x: jax.Array, z: jax.Array, precision) -> jax.Array:
    """X Z^T over the shared lane axis, fp32 MXU accumulation."""
    return jax.lax.dot_general(x, z, (((1,), (1,)), ((), ())), precision=precision,
                               preferred_element_type=jnp.float32)


def _panel_t_g(g: jax.Array, t: jax.Array) -> jax.Array:
    """G^T T: contract the shared (bn,) tile axis — (bn, M) x (bn, kp) ->
    (M, kp), fp32 MXU accumulation."""
    return jax.lax.dot_general(g, t, (((0,), (0,)), ((), ())), precision=FP32,
                               preferred_element_type=jnp.float32)


def _step_tile(x_ref, z_ref, *, kind: str, inv_scale: float, bn: int,
               n_valid: int, cross: str) -> jax.Array:
    """This grid step's (bn, M) Gram tile, padded X rows zeroed."""
    i = pl.program_id(0)
    g = _gram_tile(x_ref[...], z_ref[...], kind=kind, inv_scale=inv_scale, cross=cross)
    rows = i * bn + jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0)
    return jnp.where(rows < n_valid, g, 0.0)  # padded X rows contribute nothing


def _zero_on_first_step(o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)


def _matvec_kernel(x_ref, z_ref, v_ref, o_ref, **tile):
    _zero_on_first_step(o_ref)
    g = _step_tile(x_ref, z_ref, **tile)
    t = jnp.dot(g, v_ref[...].astype(jnp.float32), precision=FP32)  # (bn, kp): every column
    o_ref[...] += _panel_t_g(g, t)  # G^T T, still in VMEM


def _vec_matvec_kernel(x_ref, z_ref, v_ref, o_ref, **tile):
    """One live column on the VPU: v is a (1, M) row, the output a (1, M)
    row; both contractions are fp32 multiplies and sums."""
    _zero_on_first_step(o_ref)
    g = _step_tile(x_ref, z_ref, **tile)
    v = v_ref[...].astype(jnp.float32)  # (1, M)
    t = jnp.sum(g * v, axis=1, keepdims=True)  # (bn, 1) = G v: lane sum
    o_ref[...] += jnp.sum(g * t, axis=0, keepdims=True)  # (1, M) += G^T t: sublane sum


def _fused_sweep(kernel, x, z, rhs, rhs_spec: pl.BlockSpec, out_shape, *,
                 kind: str, inv_scale: float, bn: int, n_valid: int,
                 interpret: bool, cross: str) -> jax.Array:
    """The accumulating (n/bn,) sweep of the matvec and K_nM^T kernels: X
    tiles stream, Z and the ``out_shape`` output block stay resident."""
    n, d = x.shape
    m = z.shape[0]
    return pl.pallas_call(
        partial(kernel, kind=kind, inv_scale=float(inv_scale), bn=bn,
                n_valid=n_valid, cross=cross),
        grid=(n // bn,),
        in_specs=[pl.BlockSpec((bn, d), lambda i: (i, 0)),
                  pl.BlockSpec((m, d), lambda i: (0, 0)),
                  rhs_spec],
        out_specs=pl.BlockSpec(out_shape, lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        compiler_params=vmem_params(
            [(bn, d), (m, d), rhs_spec.block_shape, out_shape], (bn, m)),
        interpret=interpret,
    )(x, z, rhs)


@partial(jax.jit, static_argnames=("kind", "bn", "n_valid", "interpret",
                                   "inv_scale", "cross"))
def falkon_matvec_pallas(x: jax.Array, z: jax.Array, v: jax.Array, inv_scale: float,
                         *, kind: str = "gaussian", bn: int = 512, n_valid: int,
                         interpret: bool = True, cross: str = "fp32") -> jax.Array:
    """K_nM^T K_nM V for pre-padded x (n, d), z (M, d), V (M, kp) -> (M, kp);
    a (1, M) row v (one live column) -> the (1, M) row on the VPU path."""
    n, d = x.shape
    m = z.shape[0]
    assert n % bn == 0 and d % 128 == 0 and m % 128 == 0
    tile = dict(kind=kind, inv_scale=inv_scale, bn=bn, n_valid=n_valid,
                interpret=interpret, cross=cross)
    if v.shape == (1, m):
        return _fused_sweep(_vec_matvec_kernel, x, z, v,
                            pl.BlockSpec((1, m), lambda i: (0, 0)), (1, m), **tile)
    kp = v.shape[1]
    assert v.shape[0] == m and kp % 128 == 0
    return _fused_sweep(_matvec_kernel, x, z, v,
                        pl.BlockSpec((m, kp), lambda i: (0, 0)), (m, kp), **tile)


def _masked_matvec_kernel(x_ref, z_ref, v_ref, m_ref, o_ref, **tile):
    """The quadratic matvec with a per-column row-mask panel (exact-CV CG):
    column j accumulates G^T diag(m_j) G v_j. Identical tile schedule to
    ``_matvec_kernel`` plus one VPU multiply on the (bn, kp) intermediate —
    the mask tile rides the same HBM->VMEM stream as X."""
    _zero_on_first_step(o_ref)
    g = _step_tile(x_ref, z_ref, **tile)
    t = jnp.dot(g, v_ref[...].astype(jnp.float32), precision=FP32)  # (bn, kp)
    t = t * m_ref[...].astype(jnp.float32)  # per-column row exclusion
    o_ref[...] += _panel_t_g(g, t)


@partial(jax.jit, static_argnames=("kind", "bn", "n_valid", "interpret",
                                   "inv_scale", "cross"))
def falkon_matvec_masked_pallas(x: jax.Array, z: jax.Array, v: jax.Array,
                                mask: jax.Array, inv_scale: float, *,
                                kind: str = "gaussian", bn: int = 512,
                                n_valid: int, interpret: bool = True,
                                cross: str = "fp32") -> jax.Array:
    """K_nM^T diag(m_j) K_nM V per column, pre-padded; mask (n, kp)."""
    n, d = x.shape
    m, kp = z.shape[0], v.shape[1]
    assert n % bn == 0 and d % 128 == 0 and m % 128 == 0 and kp % 128 == 0
    assert mask.shape == (n, kp)
    return pl.pallas_call(
        partial(_masked_matvec_kernel, kind=kind, inv_scale=float(inv_scale),
                bn=bn, n_valid=n_valid, cross=cross),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((m, d), lambda i: (0, 0)),
            pl.BlockSpec((m, kp), lambda i: (0, 0)),
            pl.BlockSpec((bn, kp), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((m, kp), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, kp), jnp.float32),
        compiler_params=vmem_params(
            [(bn, d), (m, d), (m, kp), (bn, kp), (m, kp)], (bn, m)),
        interpret=interpret,
    )(x, z, v, mask)


def _knm_t_kernel(x_ref, z_ref, y_ref, o_ref, **tile):
    """R += k(X_tile, Z)^T Y_tile — the CG right-hand sides K_nM^T Y, fused."""
    _zero_on_first_step(o_ref)
    g = _step_tile(x_ref, z_ref, **tile)
    o_ref[...] += _panel_t_g(g, y_ref[...].astype(jnp.float32))  # (M, kp)


def _vec_knm_t_kernel(x_ref, z_ref, y_ref, o_ref, **tile):
    """One live column on the VPU: the (1, bn) row of y turned into a
    (bn, 1) column, then an fp32 multiply and sublane sum into (1, M)."""
    _zero_on_first_step(o_ref)
    g = _step_tile(x_ref, z_ref, **tile)
    y = jnp.transpose(y_ref[...].astype(jnp.float32))  # (bn, 1)
    o_ref[...] += jnp.sum(g * y, axis=0, keepdims=True)


@partial(jax.jit, static_argnames=("kind", "bn", "n_valid", "interpret",
                                   "inv_scale", "cross"))
def knm_t_pallas(x: jax.Array, z: jax.Array, y: jax.Array, inv_scale: float,
                 *, kind: str = "gaussian", bn: int = 512, n_valid: int,
                 interpret: bool = True, cross: str = "fp32") -> jax.Array:
    """K_nM^T Y for pre-padded x (n, d), z (M, d), Y (n, kp) -> (M, kp);
    a (1, n) row y (one live column) -> the (1, M) row on the VPU path."""
    n, d = x.shape
    m = z.shape[0]
    assert n % bn == 0 and d % 128 == 0 and m % 128 == 0
    tile = dict(kind=kind, inv_scale=inv_scale, bn=bn, n_valid=n_valid,
                interpret=interpret, cross=cross)
    if y.shape == (1, n):
        return _fused_sweep(_vec_knm_t_kernel, x, z, y,
                            pl.BlockSpec((1, bn), lambda i: (0, i)), (1, m), **tile)
    kp = y.shape[1]
    assert y.shape[0] == n and kp % 128 == 0
    return _fused_sweep(_knm_t_kernel, x, z, y,
                        pl.BlockSpec((bn, kp), lambda i: (i, 0)), (m, kp), **tile)


def _knm_matvec_kernel(x_ref, z_ref, a_ref, o_ref, *, kind: str,
                       inv_scale: float, cross: str):
    """O_tile = k(X_tile, Z) A — the predict / KRR forward contraction.

    No cross-step accumulation: each grid step owns its (bn, kp) output
    block, so no init/revisit protocol is needed. Padded X rows produce
    garbage that ops.py slices off; padded Z rows meet A's zero padding.
    """
    g = _gram_tile(x_ref[...], z_ref[...], kind=kind, inv_scale=inv_scale, cross=cross)
    o_ref[...] = jnp.dot(g, a_ref[...].astype(jnp.float32), precision=FP32)  # (bn, kp)


@partial(jax.jit, static_argnames=("kind", "bn", "interpret", "inv_scale", "cross"))
def knm_matvec_pallas(x: jax.Array, z: jax.Array, alpha: jax.Array, inv_scale: float,
                      *, kind: str = "gaussian", bn: int = 512,
                      interpret: bool = True, cross: str = "fp32") -> jax.Array:
    """K_nM A for pre-padded x (n, d), z (M, d), A (M, kp)."""
    n, d = x.shape
    m, kp = z.shape[0], alpha.shape[1]
    assert n % bn == 0 and d % 128 == 0 and m % 128 == 0 and kp % 128 == 0
    return pl.pallas_call(
        partial(_knm_matvec_kernel, kind=kind, inv_scale=float(inv_scale), cross=cross),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((m, d), lambda i: (0, 0)),
            pl.BlockSpec((m, kp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, kp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, kp), jnp.float32),
        compiler_params=vmem_params([(bn, d), (m, d), (m, kp), (bn, kp)], (bn, m)),
        interpret=interpret,
    )(x, z, alpha)
