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
(falkon_matvec.py).

The cross product X Z^T follows d. Where ``takes_packed`` (one bf16
contraction over 6d + 6 lanes is fewer MXU passes than HIGHEST's six over d
padded to 128: d = 18, 28, 90, not 128), X and Z go to the kernels as
packed bf16 operands (``_pack``) whose one contraction is the squared
distance at HIGHEST's precision; otherwise as fp32, d lane-padded to 128.
The quadratic operator packs or pads X and Z once, when it is made, for
all its calls, and ``knm_operators`` once for the operator and its
right-hand side; ``knm_t`` and ``knm_matvec`` once per call. A streamed
pass calls these per chunk, so each chunk step packs its chunk and Z
anew; Z's share is small: (M, kp) bf16 is 2.9 MB at M = 5632, kp = 256,
against 33.5 MB for a 65536-row chunk.
``runtime.spans.taken("kernels")`` counts each pick: the cross product's
as ``kernels.cross.packed``, ``.fp32`` or ``.bf16``, beside the column
paths'. ``bf16=True`` selects the mixed-precision tile path (bf16 MXU
operands, fp32 accumulation — see falkon_matvec.py).
"""
from __future__ import annotations

from functools import partial

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


#: The bf16 products x_i z_j of the three-way splits, as (i, j), smallest
#: first. HIGHEST sums the last six; the first three, x2 z3, x3 z2, x3 z3,
#: are what it leaves out.
_PRODUCTS = ((2, 2), (1, 2), (2, 1), (2, 0), (0, 2), (1, 1), (1, 0), (0, 1), (0, 0))


def _split3(a: jax.Array) -> list[jax.Array]:
    """Three bf16 parts of fp32 ``a`` that sum to it exactly (8 + 8 + 8
    bits). ``reduce_precision`` rounds in fp32, so no compiler pass can
    fold the round trip away."""
    parts = []
    for _ in range(3):
        p = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
        parts.append(p.astype(jnp.bfloat16))
        a = a - p
    return parts


def _packed_width(d: int, kind: str) -> int:
    """Columns HIGHEST's six products take when packed, 6d, and for a
    distance family three split row norms and three ones a side."""
    return 6 * d + (0 if get_family(kind).dot_only else 6)


def takes_packed(d: int, kind: str, bf16: bool) -> bool:
    """Whether the cross product takes the packed path: one bf16 contraction
    over ``_packed_width`` lanes is fewer MXU passes than HIGHEST's six over
    d padded to 128 (d = 18, 28, 90 and 440; not d = 128, nor the 1536
    ImageNet features of the FALKON paper, 73 passes against 72). ``bf16``
    keeps its one pass."""
    return not bf16 and -(-_packed_width(d, kind) // 128) < 6 * -(-d // 128)


@partial(jax.jit, static_argnames=("rows", "side", "kind"))
def _pack(a: jax.Array, rows: int, side: int, kind: str) -> jax.Array:
    """One side of the packed cross product: fp32 (r, d) -> bf16 (rows, kp),
    zero rows appended up to ``rows`` and kp = ``_packed_width`` rounded up
    to 128 lanes. One compiled program, so no fp32 temporary of a's size
    is left in HBM.

    Column by column, side 0 (X) holds x_i and side 1 (Z) z_j of the
    products in ``_PRODUCTS``: the six of HIGHEST, or all nine where the
    three more fit in the lanes the six leave as padding. A distance family
    scales Z's by the exact -2 and adds (n_k, 1) and (1, m_k) pairs, the
    bf16 splits of the fp32 squared row norms against ones. The contraction
    of the two, each product exact in fp32 and the sum in fp32, is then
    x.z, or ||x||^2 + ||z||^2 - 2 x.z, at least as HIGHEST computes it.
    The leading terms (x1 z1, n1, m1) take the last lanes, so an
    accumulator that runs along the lanes adds the small terms first."""
    a = pad_dim(a.astype(jnp.float32), 0, rows)
    d = a.shape[1]
    width = _packed_width(d, kind)
    kp = round_up(width, 128)
    s = _split3(a)
    cols = [s[pair[side]] for pair in _PRODUCTS[0 if width + 3 * d <= kp else 3:]]
    if not get_family(kind).dot_only:
        cols = cols if side == 0 else [-2 * c for c in cols]
        norms = _split3(jnp.sum(a * a, axis=1, keepdims=True))
        one = jnp.ones_like(norms[0])
        pair = (lambda k: [norms[k], one]) if side == 0 else (lambda k: [one, norms[k]])
        small, lead = cols[:-1] + pair(2) + pair(1), cols[-1:] + pair(0)
    else:
        small, lead = cols[:-1], cols[-1:]
    small, lead = jnp.concatenate(small, axis=1), jnp.concatenate(lead, axis=1)
    gap = jnp.zeros((a.shape[0], kp - small.shape[1] - lead.shape[1]), jnp.bfloat16)
    return jnp.concatenate([small, gap, lead], axis=1)


def _operands(x: jax.Array, z: jax.Array, sigma: float, *, kind: str, bn: int,
              interpret: bool | None, bf16: bool):
    """(X operand, Z operand, the kernels' static arguments): rows padded
    to the tiles, then packed where ``takes_packed``, else lane-padded
    fp32. Padded rows are the all-zeros point. The pick is counted as
    ``kernels.cross.<cross>``: ``packed``, ``fp32`` or ``bf16``."""
    n, d = x.shape
    rows = round_up(n, bn), round_up(z.shape[0], 128)
    cross = "packed" if takes_packed(d, kind, bf16) else "bf16" if bf16 else "fp32"
    spans.took("kernels.cross." + cross)
    if cross == "packed":
        xk, zk = _pack(x, rows[0], 0, kind), _pack(z, rows[1], 1, kind)
    else:
        xk, zk = (pad_dim(pad_dim(a, 0, r), 1, round_up(d, 128)) for a, r in zip((x, z), rows))
    static = dict(inv_scale=_inv_scale(kind, sigma), kind=kind, bn=bn, cross=cross,
                  interpret=default_interpret() if interpret is None else interpret)
    return xk, zk, static


def _quadratic(xk: jax.Array, zk: jax.Array, static: dict, n: int, m: int,
               v: jax.Array, mask: jax.Array | None) -> jax.Array:
    """K_nM^T (K_nM v) over prepared operands (``_operands``)."""
    # padded Z rows are the all-zeros point; its kernel values are nonzero but
    # v is zero-padded so they never enter t, and we slice r back to (m,).
    if mask is None:
        vk, unpack = _rhs(v, zk.shape[0], "kernels.falkon_matvec")
        return unpack(falkon_matvec_pallas(xk, zk, vk, n_valid=n, **static), m)
    vp, squeeze = _as_panel(pad_dim(v, 0, zk.shape[0]))
    # zero-padded mask rows/columns: padded rows are killed by n_valid anyway
    # and padded v columns are zero, so the pad value never reaches the output.
    if mask.ndim == 1 and v.ndim == 2:
        mask = jnp.broadcast_to(mask[:, None], (n, v.shape[1]))
    mp, _ = _as_panel(pad_dim(mask.astype(jnp.float32), 0, xk.shape[0]))
    out = falkon_matvec_masked_pallas(xk, zk, vp, mp, n_valid=n, **static)
    return _unpanel(out[:m], None if squeeze else v.shape[1])


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
    xk, zk, static = _operands(x, z, sigma, kind=kind, bn=bn, interpret=interpret,
                               bf16=bf16)
    return _quadratic(xk, zk, static, x.shape[0], z.shape[0], v, mask)


def make_knm_quadratic_op(x: jax.Array, z: jax.Array, sigma: float = 1.0, *,
                          kind: str = "gaussian", bn: int = 512,
                          interpret: bool | None = None, bf16: bool = False,
                          mask: jax.Array | None = None):
    """Close over (x, z) -> the CG quadratic operator ``falkon_matvec``;
    an optional ``mask`` panel selects the masked kernel (exact-CV CG).
    X and Z are padded or packed here, once, for every call of the op."""
    return knm_operators(x, z, None, sigma, kind=kind, bn=bn, interpret=interpret,
                         bf16=bf16, mask=mask)[0]


def knm_operators(x: jax.Array, z: jax.Array, y: jax.Array | None,
                  sigma: float = 1.0, *, kind: str = "gaussian", bn: int = 512,
                  interpret: bool | None = None, bf16: bool = False,
                  mask: jax.Array | None = None):
    """(``make_knm_quadratic_op``, ``knm_t`` of ``y``) over one padding or
    packing of X and Z; ``y=None`` gives None for the second."""
    xk, zk, static = _operands(x, z, sigma, kind=kind, bn=bn, interpret=interpret,
                               bf16=bf16)
    n, m = x.shape[0], z.shape[0]

    def op(v: jax.Array) -> jax.Array:
        return _quadratic(xk, zk, static, n, m, v, mask)

    return op, None if y is None else _knm_t(xk, zk, static, n, m, y, mask)


def _knm_t(xk: jax.Array, zk: jax.Array, static: dict, n: int, m: int,
           y: jax.Array, mask: jax.Array | None) -> jax.Array:
    """K_nM^T (mask * y) over prepared operands (``_operands``)."""
    if mask is not None:
        y = y * mask.astype(y.dtype)
    yk, unpack = _rhs(y, xk.shape[0], "kernels.knm_t")
    return unpack(knm_t_pallas(xk, zk, yk, n_valid=n, **static), m)


def knm_t(x: jax.Array, z: jax.Array, y: jax.Array, sigma: float = 1.0, *,
          kind: str = "gaussian", bn: int = 512,
          interpret: bool | None = None, bf16: bool = False,
          mask: jax.Array | None = None) -> jax.Array:
    """K_nM^T y -> (M,) or (M, k) fp32. Arbitrary shapes, padded internally;
    a panel ``y`` yields every CG right-hand side from one X sweep. A
    ``mask`` shaped like ``y`` folds into the targets (K_nM^T (mask * y))
    before the sweep — the mask enters linearly, so no kernel variant is
    needed."""
    xk, zk, static = _operands(x, z, sigma, kind=kind, bn=bn, interpret=interpret,
                               bf16=bf16)
    return _knm_t(xk, zk, static, x.shape[0], z.shape[0], y, mask)


def knm_matvec(x: jax.Array, z: jax.Array, alpha: jax.Array, sigma: float = 1.0, *,
               kind: str = "gaussian", bn: int = 512,
               interpret: bool | None = None, bf16: bool = False) -> jax.Array:
    """K_nM alpha -> (n,) or (n, k) fp32 — the predict contraction, fused in
    VMEM; an (M, k) ``alpha`` panel serves multi-output predict with one
    kernel evaluation."""
    xk, zk, static = _operands(x, z, sigma, kind=kind, bn=bn, interpret=interpret,
                               bf16=bf16)
    # zero alpha on padded Z rows
    ap, squeeze = _as_panel(pad_dim(alpha, 0, zk.shape[0]))
    out = knm_matvec_pallas(xk, zk, ap, **static)
    return _unpanel(out[:x.shape[0]], None if squeeze else alpha.shape[1])


falkon_matvec_reference = falkon_matvec_ref
falkon_matvec_masked_reference = falkon_matvec_masked_ref
knm_t_reference = knm_t_ref
knm_matvec_reference = knm_matvec_ref
