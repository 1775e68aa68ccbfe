"""Public wrappers for the fused FALKON K_nM contractions.

``falkon_matvec`` (K_nM^T K_nM V), ``knm_t`` (K_nM^T Y) and ``knm_matvec``
(K_nM V — predict / KRR forward) are the operators
``repro.core.backend.PallasBackend`` serves to ``repro.core.falkon``; all
pad internally to tile boundaries. Every wrapper accepts a single vector
(the classic FALKON shapes) or an (·, k) multi-RHS panel. The path follows
the live column count: ``falkon_matvec`` and ``knm_t`` stream one column
(a vector or an (·, 1) panel) as a lane-major row through their VPU
kernels; k >= 2 columns, the masked matvec and ``knm_matvec`` pad the
panel up to the 128-lane tile width and stream it through the panel
kernels — one Gram tile evaluation for every column — and slice it back
(falkon_matvec.py). ``runtime.spans.taken("kernels")`` counts each pick.
``bf16=True`` selects the mixed-precision tile path (bf16 MXU operands,
fp32 accumulation — see falkon_matvec.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...families import get_family
from ...runtime import spans
from ..common import default_interpret, pad_dim, round_up
from .falkon_matvec import (falkon_matvec_masked_pallas, falkon_matvec_pallas,
                            knm_matvec_pallas, knm_t_pallas)
from .ref import (falkon_matvec_masked_ref, falkon_matvec_ref, knm_matvec_ref,
                  knm_t_ref)


def _inv_scale(kind: str, sigma: float) -> float:
    """The family's epilogue scalar — resolved from the registry, so every
    registered family (incl. matern32 / cauchy) flows through unchanged."""
    return float(get_family(kind).inv_scale(sigma))


def _as_panel(v: jax.Array) -> tuple[jax.Array, bool]:
    """(lane-padded (·, kp) panel, was_vector) for a (·,) or (·, k) input."""
    squeeze = v.ndim == 1
    vp = v[:, None] if squeeze else v
    return pad_dim(vp, 1, round_up(vp.shape[1], 128)), squeeze


def _unpanel(out: jax.Array, k_or_none: int | None) -> jax.Array:
    """Slice the lane padding back off; ``None`` restores a vector."""
    return out[:, 0] if k_or_none is None else out[:, :k_or_none]


def _rhs(v: jax.Array, to: int, op: str):
    """(kernel operand, unpack) for a right-hand side zero-padded along axis
    0 to ``to``. One live column ((·,) or (·, 1)) goes to the VPU kernels as
    a (1, to) row; a panel of k >= 2 columns is lane-padded. ``unpack(out,
    size)`` slices the kernel output back to ``size`` rows in ``v``'s shape.
    The pick is counted as ``<op>.vector`` or ``<op>.panel``."""
    if v.ndim == 1 or v.shape[1] == 1:
        spans.took(op + ".vector")
        return (pad_dim(v.reshape(1, v.shape[0]), 1, to),
                lambda out, size: out[0, :size].reshape((size,) + v.shape[1:]))
    spans.took(op + ".panel")
    return (_as_panel(pad_dim(v, 0, to))[0],
            lambda out, size: _unpanel(out[:size], v.shape[1]))


def falkon_matvec(x: jax.Array, z: jax.Array, v: jax.Array, sigma: float = 1.0, *,
                  kind: str = "gaussian", bn: int = 512,
                  interpret: bool | None = None, bf16: bool = False,
                  mask: jax.Array | None = None) -> jax.Array:
    """K_nM^T (K_nM v) -> (M,) or (M, k) fp32. Arbitrary shapes, padded
    internally; a panel ``v`` is the multi-RHS block-CG iterate.

    ``mask`` — optional per-column row-exclusion weights shaped like a
    length-n slice of ``v``'s panel-ness ((n,) with a vector, (n, k) with a
    panel): column j computes K_nM^T diag(m_j) K_nM v_j via the masked
    kernel variant (one extra VPU multiply per tile). ``mask=None``
    dispatches the original kernel unchanged."""
    n, d = x.shape
    m = z.shape[0]
    interpret = default_interpret() if interpret is None else interpret
    dp = round_up(d, 128)
    xp = pad_dim(pad_dim(x, 0, round_up(n, bn)), 1, dp)
    zp = pad_dim(pad_dim(z, 0, round_up(m, 128)), 1, dp)
    # padded Z rows are the all-zeros point; its kernel values are nonzero but
    # v is zero-padded so they never enter t, and we slice r back to (m,).
    if mask is None:
        vk, unpack = _rhs(v, round_up(m, 128), "kernels.falkon_matvec")
        out = falkon_matvec_pallas(xp, zp, vk, float(_inv_scale(kind, sigma)),
                                   kind=kind, bn=bn, n_valid=n,
                                   interpret=interpret, bf16=bf16)
        return unpack(out, m)
    vp, squeeze = _as_panel(pad_dim(v, 0, round_up(m, 128)))
    # zero-padded mask rows/columns: padded rows are killed by n_valid anyway
    # and padded v columns are zero, so the pad value never reaches the output.
    if mask.ndim == 1 and v.ndim == 2:
        mask = jnp.broadcast_to(mask[:, None], (n, v.shape[1]))
    mp, _ = _as_panel(pad_dim(mask.astype(x.dtype), 0, round_up(n, bn)))
    out = falkon_matvec_masked_pallas(xp, zp, vp, mp,
                                      float(_inv_scale(kind, sigma)), kind=kind,
                                      bn=bn, n_valid=n, interpret=interpret,
                                      bf16=bf16)
    return _unpanel(out[:m], None if squeeze else v.shape[1])


def make_knm_quadratic_op(x: jax.Array, z: jax.Array, sigma: float = 1.0, *,
                          kind: str = "gaussian", bn: int = 512,
                          interpret: bool | None = None, bf16: bool = False,
                          mask: jax.Array | None = None):
    """Close over (x, z) -> the CG quadratic operator ``falkon_matvec``;
    an optional ``mask`` panel selects the masked kernel (exact-CV CG)."""
    def op(v: jax.Array) -> jax.Array:
        return falkon_matvec(x, z, v, sigma, kind=kind, bn=bn, interpret=interpret,
                             bf16=bf16, mask=mask)

    return op


def knm_t(x: jax.Array, z: jax.Array, y: jax.Array, sigma: float = 1.0, *,
          kind: str = "gaussian", bn: int = 512,
          interpret: bool | None = None, bf16: bool = False,
          mask: jax.Array | None = None) -> jax.Array:
    """K_nM^T y -> (M,) or (M, k) fp32. Arbitrary shapes, padded internally;
    a panel ``y`` yields every CG right-hand side from one X sweep. A
    ``mask`` shaped like ``y`` folds into the targets (K_nM^T (mask * y))
    before the sweep — the mask enters linearly, so no kernel variant is
    needed."""
    if mask is not None:
        y = y * mask.astype(y.dtype)
    n, d = x.shape
    m = z.shape[0]
    interpret = default_interpret() if interpret is None else interpret
    dp = round_up(d, 128)
    xp = pad_dim(pad_dim(x, 0, round_up(n, bn)), 1, dp)
    zp = pad_dim(pad_dim(z, 0, round_up(m, 128)), 1, dp)
    yk, unpack = _rhs(y, round_up(n, bn), "kernels.knm_t")
    out = knm_t_pallas(xp, zp, yk, float(_inv_scale(kind, sigma)), kind=kind, bn=bn,
                       n_valid=n, interpret=interpret, bf16=bf16)
    return unpack(out, m)


def knm_matvec(x: jax.Array, z: jax.Array, alpha: jax.Array, sigma: float = 1.0, *,
               kind: str = "gaussian", bn: int = 512,
               interpret: bool | None = None, bf16: bool = False) -> jax.Array:
    """K_nM alpha -> (n,) or (n, k) fp32 — the predict contraction, fused in
    VMEM; an (M, k) ``alpha`` panel serves multi-output predict with one
    kernel evaluation."""
    n, d = x.shape
    m = z.shape[0]
    interpret = default_interpret() if interpret is None else interpret
    dp = round_up(d, 128)
    xp = pad_dim(pad_dim(x, 0, round_up(n, bn)), 1, dp)
    zp = pad_dim(pad_dim(z, 0, round_up(m, 128)), 1, dp)
    # zero alpha on padded Z rows
    ap, squeeze = _as_panel(pad_dim(alpha, 0, round_up(m, 128)))
    out = knm_matvec_pallas(xp, zp, ap, float(_inv_scale(kind, sigma)), kind=kind,
                            bn=bn, interpret=interpret, bf16=bf16)
    return _unpanel(out[:n], None if squeeze else alpha.shape[1])


falkon_matvec_reference = falkon_matvec_ref
falkon_matvec_masked_reference = falkon_matvec_masked_ref
knm_t_reference = knm_t_ref
knm_matvec_reference = knm_matvec_ref
