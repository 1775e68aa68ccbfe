"""Plain float32 reference for the benchmark's correctness checks.

Written from the papers' equations in straightforward ``jax.numpy``; it
imports nothing of the system under test and takes none of its results
other than the inputs the timed call was given (the data and the center
set). Every matrix product is float32 at highest precision.

Contents:
  * ``gaussian_cross``   k(x, z) = exp(-||x - z||^2 / (2 sigma^2));
  * ``knm_quadratic`` / ``knm_t`` / ``knm_matvec``   K_nM^T K_nM v, K_nM^T y
    and K_nM a, streamed over row blocks so K_nM is never whole;
  * ``falkon``           FALKON with the generalized preconditioner of the
    BLESS paper (Def. 2, Example 1.3: eigendecomposition branch with the
    rank cut at 1e-5 of the largest eigenvalue) and plain CG.

The (M, M) eigendecomposition runs in host LAPACK (float32): XLA's TPU
eigh does not compile at M in the thousands.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

#: Rows per streamed block of K_nM: an (8192, M) fp32 tile is 200 MB at
#: M = 6052, small against 16 GB of device memory.
BLOCK = 8192
#: Eigenvalues below this share of the largest are dropped from the
#: preconditioner (Example 1.3 with q = the numerical rank of K_MM).
RANK_TOL = 1e-5


def dot(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def gaussian_cross(x, z, sigma):
    """(n, m) Gaussian kernel block; the squared distance is expanded into
    norms and one matrix product."""
    d2 = (jnp.sum(x * x, 1)[:, None] + jnp.sum(z * z, 1)[None, :]
          - 2.0 * dot(x, z.T))
    return jnp.exp(-jnp.maximum(d2, 0.0) / (2.0 * sigma * sigma))


def _blocks(x, block):
    n = x.shape[0]
    pad = (-n) % block
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    valid = (jnp.arange(n + pad) < n).astype(x.dtype)
    nb = (n + pad) // block
    return xp.reshape(nb, block, -1), valid.reshape(nb, block), pad


@partial(jax.jit, static_argnames=("block",))
def knm_quadratic(x, z, sigma, v, block=BLOCK):
    """K_nM^T (K_nM v) for v (M,)."""
    xb, vb, _ = _blocks(x, block)

    def body(acc, args):
        xi, wi = args
        g = gaussian_cross(xi, z, sigma) * wi[:, None]
        return acc + dot(g.T, dot(g, v)), None

    return jax.lax.scan(body, jnp.zeros_like(v), (xb, vb))[0]


@partial(jax.jit, static_argnames=("block",))
def knm_t(x, z, sigma, y, block=BLOCK):
    """K_nM^T y for y (n,)."""
    xb, vb, pad = _blocks(x, block)
    yb = jnp.pad(y, (0, pad)).reshape(vb.shape)

    def body(acc, args):
        xi, wi, yi = args
        return acc + dot((gaussian_cross(xi, z, sigma) * wi[:, None]).T, yi), None

    return jax.lax.scan(body, jnp.zeros((z.shape[0],), x.dtype), (xb, vb, yb))[0]


@partial(jax.jit, static_argnames=("block",))
def knm_matvec(x, z, sigma, a, block=BLOCK):
    """K_nM a for a (M,): predictions at the rows of x."""
    xb, _, _ = _blocks(x, block)
    out = jax.lax.map(lambda xi: dot(gaussian_cross(xi, z, sigma), a), xb)
    return out.reshape(-1)[: x.shape[0]]


@jax.jit
def _scaled_kmm(z, sigma, a_diag):
    s = 1.0 / jnp.sqrt(a_diag)
    kmm = gaussian_cross(z, z, sigma)
    return kmm, kmm * (s[:, None] * s[None, :]), s


@partial(jax.jit, static_argnames=("iters",))
def _cg(x, z, sigma, y, kmm, s, q, t, r, lam, iters):
    """Preconditioned CG on B^T H B beta = B^T K_nM^T y, alpha = B beta, with
    H = K_nM^T K_nM + lam n K_MM and B = n^{-1/2} A^{-1/2} Q T^{-1} R^{-1}."""
    n = x.shape[0]
    tr = t * r

    def b_apply(v):
        return s * dot(q, v / tr) / jnp.sqrt(n)

    def bt_apply(w):
        return dot(q.T, s * w / jnp.sqrt(n)) / tr

    def op(v):
        u = b_apply(v)
        return bt_apply(knm_quadratic(x, z, sigma, u)
                        + lam * n * dot(kmm, u))

    b = bt_apply(knm_t(x, z, sigma, y))

    def step(_, state):
        beta, res, p, rs = state
        ap = op(p)
        alpha = rs / jnp.maximum(jnp.dot(p, ap), 1e-30)
        beta = beta + alpha * p
        res = res - alpha * ap
        rs_new = jnp.dot(res, res)
        p = res + rs_new / jnp.maximum(rs, 1e-30) * p
        return beta, res, p, rs_new

    beta = jax.lax.fori_loop(0, iters, step,
                             (jnp.zeros_like(b), b, b, jnp.dot(b, b)))[0]
    return b_apply(beta)


def falkon(x, y, z, a_diag, sigma, lam, iters):
    """FALKON (BLESS paper Def. 2-3) on centers z with weights diag(A) =
    a_diag; returns alpha (M,)."""
    m = z.shape[0]
    kmm, kt, s = _scaled_kmm(z, jnp.float32(sigma), a_diag.astype(jnp.float32))
    eig, vec = np.linalg.eigh(np.asarray(kt))
    keep = eig > max(float(eig[-1]), 1e-30) * RANK_TOL
    t = np.sqrt(np.where(keep, eig, 1.0)).astype(np.float32)
    r = np.sqrt(np.where(keep, eig / m + lam, 1.0)).astype(np.float32)
    q = (vec * keep[None, :]).astype(np.float32)
    return _cg(x, z, jnp.float32(sigma), y, kmm, s, jnp.asarray(q), jnp.asarray(t),
               jnp.asarray(r), jnp.float32(lam), iters)
