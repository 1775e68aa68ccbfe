"""Streamed normal-equation accumulators for durable / online FALKON.

The classic ``falkon_fit`` host path re-streams X once per CG iteration —
optimal for a one-shot fit (nothing is stored), but hostile to durability:
the solver state mid-fit is "somewhere inside CG", which cannot be
checkpointed at a meaningful boundary, and absorbing new rows means
starting over. This module trades one (M, M) array for both properties by
accumulating the normal-equation operator itself:

    H = K_nM^T K_nM   (M, M)        b = K_nM^T y   (M,) or (M, k)

in ONE deterministic chunk-order pass over the data (same associativity
every run — DESIGN.md §10), then solving

    (H + lam n K_MM) alpha = b

with the paper's Def. 2 preconditioner and the shared multi-RHS CG from
``repro.core.falkon``. Consequences:

  * **Checkpointable**: (H, b, cursor) at a chunk barrier is the *entire*
    fit state — fp32 ``.npy`` round-trips are bit-exact, so a resumed fit
    replays the remaining chunks into the same bits (repro/online/durable).
  * **Incremental**: new rows fold in as ``H += G^T G``; ``b += G^T y`` —
    O(batch) work, no re-streaming (``OnlineFalkon.append``).
  * **Warm refits**: the solve costs O(M^2 iters), independent of n — the
    data pass is paid once, not once per CG iteration. This is the >= 5x
    warm-vs-cold gap the ``online`` bench row gates.

The price is the usual normal-equations caveat: H is formed explicitly, so
the accumulator path agrees with the operator path to streamed-fp32 parity
(the documented 1e-4 scale-relative cross-backend tolerance), not bitwise.

Per-chunk absorption is delegated to an ``inner`` backend's ``gram_block``
(jnp / Pallas / shard_map), jit-compiled per chunk shape when the inner is
jit-safe — exactly the ``StreamBackend`` composition discipline.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..core.falkon import cg, make_preconditioner
from ..core.gram import Kernel
from ..runtime import spans
from ..stream.store import _TRACKER, device_chunks

Array = jax.Array

def _absorb_chunk(kernel: Kernel, xb: Array, z: Array, yb: Array,
                  h: Array, b: Array, *, inner) -> tuple[Array, Array]:
    """Fold one (chunk, d) block into (H, b): H += G^T G, b += G^T y."""
    g = inner.gram_block(kernel, xb, z)
    return h + g.T @ g, b + g.T @ yb


_absorb_chunk_jit = partial(jax.jit, static_argnames=("inner",))(_absorb_chunk)


def absorb(kernel: Kernel, x, y, z: Array, h: Array, b: Array, *, inner,
           chunk: int | None = None) -> tuple[Array, Array]:
    """Fold rows (x, y) into the accumulators, chunk by chunk in row order.

    ``x``/``y`` may be host (numpy / ChunkStore-backed) or device arrays;
    chunks ride the double-buffered ``device_chunks`` iterator, so an
    appended batch larger than one chunk stays out-of-core. Accumulation
    order is the chunk order — deterministic, which is what makes the
    durable-fit resume bit-identical.
    """
    step = _absorb_chunk_jit if inner.jit_safe else _absorb_chunk
    for xb, yb in device_chunks(x, aux=y, chunk=chunk):
        _TRACKER.note_transient(4 * xb.shape[0] * z.shape[0])
        h, b = step(kernel, xb, z, yb, h, b, inner=inner)
    return h, b


@partial(jax.jit, static_argnames=("iters",))
@spans.retrace("online.acc_solve")
def _acc_solve(kernel: Kernel, h: Array, b: Array, centers: Array,
               a_diag: Array, lam: Array, n: Array, *,
               iters: int) -> tuple[Array, Array]:
    """Preconditioned CG on the accumulated normal equations, one compiled
    program: (H + lam n K_MM) alpha = b with B from Def. 2. Everything is
    (M, M)-sized — no data pass. Returns (alpha, residual trajectory)."""
    prec = make_preconditioner(kernel, centers, a_diag, lam, n)
    kmm = kernel.cross(centers, centers).astype(jnp.float32)

    def matvec(v: Array) -> Array:
        u = prec.apply(v)
        return prec.apply_t(h @ u + lam * n * (kmm @ u))

    beta, resid = cg(matvec, prec.apply_t(b), iters, trajectory=True)
    return prec.apply(beta), resid


def solve_accumulators(kernel: Kernel, h: Array, b: Array, centers: Array,
                       lam: float, n: int, *, a_diag: Array | None = None,
                       iters: int = 20) -> tuple[Array, Array]:
    """Solve (H + lam n K_MM) alpha = b; returns (alpha, cg residuals).

    ``lam`` and ``n`` are traced (sweeping them never recompiles); ``iters``
    and the array shapes key the jit cache — repeated warm refits reuse one
    executable (``online.acc_solve`` in ``runtime.spans`` counts the traces).
    """
    m = centers.shape[0]
    a_diag = (jnp.ones((m,), jnp.float32) if a_diag is None
              else jnp.asarray(a_diag, jnp.float32))
    return _acc_solve(kernel, h, b, centers, a_diag,
                      jnp.asarray(lam, jnp.float32),
                      jnp.asarray(n, jnp.float32), iters=iters)
