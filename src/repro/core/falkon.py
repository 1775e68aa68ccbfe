"""FALKON with generalized (BLESS-weighted) preconditioner — paper Sec. 3 / App. B.

Solves Nystrom-KRR

    alpha = (K_nM^T K_nM + lam n K_MM)^+ K_nM^T y        (Eq. 13, lam*n conv.)

by conjugate gradient on the preconditioned system (Def. 3)

    W beta = b,   W = B^T (K_nM^T K_nM + lam n K_MM) B,  b = B^T K_nM^T y,

with the generalized preconditioner of Def. 2 / Eq. (15):

    B = (1/sqrt(n)) A^{-1/2} T^{-1} R^{-1},
    T = chol_u(A^{-1/2} K_MM A^{-1/2}),   R = chol_u(T T^T / M + lam I)

so that B B^T = (n/M K_MM A^{-1} K_MM + lam n K_MM)^{-1}.

The CG matvec never materializes K_nM: the K_nM^T K_nM v / K_nM^T y
contractions come from the kernel-operator ``Backend`` seam
(``repro.core.backend``) — the local pure-jnp streamer, the Pallas fused
kernel (repro.kernels.falkon_matvec), or the shard_map data-parallel one in
core/distributed.py. All three share this file's CG loop, and
``FalkonModel.predict`` serves K_nM alpha through the same seam.

Multi-RHS block-CG: ``y`` may be (n,) or (n, k). All k right-hand sides ride
ONE CG — the iterate is an (M, k) panel, every K_nM stream (the dominant
cost, identical for every column) is evaluated once per iteration and
contracted against the whole panel, and the preconditioner is shared. Each
column keeps its own step sizes (alpha_j, mu_j from per-column reductions)
with per-column convergence masking: a column whose residual has collapsed
to fp32 noise freezes while the others keep iterating. Extra output columns
therefore cost only the extra (n, k) GEMM flops, not extra kernel
evaluations — see ``cg`` and DESIGN.md §2.4.

Fused whole-fit path (DESIGN.md §2.4): for jit-safe backends with no
per-iteration callback, ``falkon_fit`` compiles preconditioner + CG + alpha
recovery into ONE ``jax.jit`` call — repeated fits (benchmark sweeps,
serving-side refits) pay a single dispatch instead of ~iters host round
trips. The jit cache is shape-bucketed: X/y rows are padded up to a multiple
of the backend's stream block and masked inside the trace, and the RHS
count k >= 2 is padded up to a power-of-two column bucket (zero columns are
frozen by the convergence mask; single-output keeps true vector shapes), so
every (n, k) in a bucket shares one executable. Cache key (static): row
bucket, k bucket, (M, d), iters,
backend instance, kernel family. Traced (never retraces): lam, n, X, y,
centers, a_diag, kernel bandwidth. The padded y panel is donated (it is
always freshly allocated here); X is NOT donated — callers reuse it across
fits (lambda sweeps, warm-start refits, k-fold sweeps).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime import spans
from ..testing import faults
from . import health
from .gram import BackendLike, Kernel, resolve_backend
from .leverage import CenterSet  # noqa: F401 — re-exported for callers

Array = jax.Array

#: Precision of the M-space algebra (the preconditioner, K_MM u) and of the
#: jnp streamer's contractions against each Gram block. TPU runs a
#: DEFAULT-precision fp32 matmul as one bf16 pass; with a (M, k) panel that
#: would put ~1e-3 noise into every CG step.
_M_SPACE = jax.lax.Precision.HIGHEST


def _bcol(s: Array, v: Array) -> Array:
    """Broadcast a per-row scale (M,) against v of shape (M,) or (M, k)."""
    return s[:, None] if v.ndim == 2 else s


class Preconditioner(NamedTuple):
    """Factors of Def. 2, Example 1.3 (eigendecomposition branch).

    BLESS samples centers *with replacement*, so K_MM is routinely rank
    deficient (duplicate rows); the eigh-based partial isometry Q with rank
    truncation is the paper's own answer (Def. 2 requires only Q^T Q = I,
    q <= M) and is fp32-robust where the Cholesky branch explodes.

    ``apply``/``apply_t`` accept a single vector or an (·, k) panel — B is
    column-separable, so one application serves every CG right-hand side.
    """

    q_iso: Array  # (M, q) partial isometry
    t_diag: Array  # (q,)  T = diag(sqrt(eig))
    r_diag: Array  # (q,)  R = diag(sqrt(eig/M + lam))
    inv_sqrt_a: Array  # (M,) diag(A)^{-1/2}
    n: int

    def apply(self, v: Array) -> Array:
        """B v = (1/sqrt n) A^{-1/2} Q T^{-1} R^{-1} v,  v (q,) or (q, k)."""
        u = jnp.matmul(self.q_iso, v / _bcol(self.t_diag * self.r_diag, v),
                       precision=_M_SPACE)
        return _bcol(self.inv_sqrt_a, u) * u / jnp.sqrt(self.n)

    def apply_t(self, v: Array) -> Array:
        """B^T v,  v (M,) or (M, k) -> (q,) or (q, k)."""
        u = jnp.matmul(self.q_iso.T, _bcol(self.inv_sqrt_a, v) * v / jnp.sqrt(self.n),
                       precision=_M_SPACE)
        return u / _bcol(self.t_diag * self.r_diag, u)


def _host_eigh(a: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    with spans.span("precond.eigh", m=a.shape[0]):
        w, v = np.linalg.eigh(np.asarray(a))
        return w.astype(a.dtype), v.astype(a.dtype)


def _eigh(a: Array) -> tuple[Array, Array]:
    """Ascending eigenpairs of the symmetric (M, M) ``a``.

    XLA's TPU eigh is compiled for its size, and its compile time and host
    memory grow steeply with M: compiled for v5e here it took 72 s and
    4 GB at M = 1024, 233 s and 11 GB at M = 2048, and at M = 6052 it ran
    a 40 GiB chip host out of memory. On a TPU the one factorization per
    fit therefore runs in host LAPACK through a callback (an (M, M)
    transfer each way); elsewhere XLA's own eigh.
    """
    if jax.default_backend() != "tpu":
        return jnp.linalg.eigh(a)
    shapes = (jax.ShapeDtypeStruct(a.shape[:1], a.dtype),
              jax.ShapeDtypeStruct(a.shape, a.dtype))
    return jax.pure_callback(_host_eigh, shapes, a)


def make_preconditioner(kernel: Kernel, z: Array, a_diag: Array, lam: float, n: int,
                        *, rank_tol: float = 1e-5) -> Preconditioner:
    """Def. 2 factors for centers z (M, d) with weights diag(A) = a_diag.

    eigh of A^{-1/2} K_MM A^{-1/2}; eigenvalues below rank_tol * max are
    dropped (q = numerical rank), exactly Example 1.3 with q = rank(K_MM).
    """
    m = z.shape[0]
    kmm = kernel.cross(z, z).astype(jnp.float32)
    inv_sqrt_a = (1.0 / jnp.sqrt(a_diag)).astype(jnp.float32)
    kt = kmm * (inv_sqrt_a[:, None] * inv_sqrt_a[None, :])
    eig, vec = _eigh(kt)
    floor = jnp.maximum(eig[-1], 1e-30) * rank_tol
    keep = eig > floor
    # jit-friendly fixed shapes: keep all M columns but neutralize dropped
    # directions (T entry -> 1, Q column -> 0): B then annihilates them.
    t_diag = jnp.sqrt(jnp.where(keep, eig, 1.0))
    r_diag = jnp.sqrt(jnp.where(keep, eig / m + lam, 1.0))
    q_iso = vec * keep[None, :].astype(vec.dtype)
    return Preconditioner(q_iso, t_diag, r_diag, inv_sqrt_a, n)


# ---------------------------------------------------------------------------
# K_nM operators
# ---------------------------------------------------------------------------

KnmOp = Callable[[Array], tuple[Array, Array]]
# v (M,) or (M, k) -> (K_nM^T K_nM v, K_nM^T y)  -- the second returned once


def local_knm_quadratic(kernel: Kernel, x: Array, z: Array, *, block: int = 8192,
                        mask: Array | None = None) -> Callable[[Array], Array]:
    """v -> K_nM^T (K_nM v), streaming x in row blocks (pure-jnp reference).

    ``v`` may be (M,) or an (M, k) panel: each streamed Gram block is built
    once and contracted against every column, so extra right-hand sides cost
    GEMM flops only — no extra kernel evaluations.

    ``mask`` — optional per-row weights excluding rows from the quadratic
    form: (n,) applied to every column, or an (n, k) panel giving column j
    its own row subset (exact row-exclusion CV; DESIGN.md §2.4). Column j
    then computes ``K_nM^T diag(mask[:, j]) K_nM v_j`` — one extra
    elementwise multiply on the streamed (block, k) intermediate, applied
    *between* the two Gram contractions so binary masks count excluded rows
    exactly once. ``mask=None`` keeps the original program bit-identical.
    """
    n, m = x.shape[0], z.shape[0]
    pad = (-n) % block
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    nb = xp.shape[0] // block
    valid = (jnp.arange(nb * block) < n).reshape(nb, block)
    if mask is not None:
        mk = jnp.pad(jnp.asarray(mask, x.dtype),
                     ((0, pad),) + ((0, 0),) * (mask.ndim - 1))
        mk = mk.reshape((nb, block) + mk.shape[1:])

    def op(v: Array) -> Array:
        def body(carry, args):
            xb, mb, cb = args
            g = kernel.cross(xb, z) * mb[:, None]
            t = jnp.matmul(g, v, precision=_M_SPACE)
            if cb is not None:
                t = t * (cb if t.ndim == cb.ndim else cb[:, None])
            return carry + jnp.matmul(g.T, t, precision=_M_SPACE), None

        out, _ = jax.lax.scan(body, jnp.zeros((m,) + v.shape[1:], v.dtype),
                              (xp.reshape(nb, block, -1), valid,
                               None if mask is None else mk))
        return out

    return op


def local_knm_t(kernel: Kernel, x: Array, z: Array, y: Array, *, block: int = 8192,
                mask: Array | None = None) -> Array:
    """K_nM^T y, streamed; ``y`` (n,) -> (M,), or an (n, k) panel -> (M, k).

    ``mask`` — optional per-row weights, (n,) or (n, k) matching ``y``:
    computes ``K_nM^T (mask * y)``. Since the mask enters linearly it is
    folded into the targets up front (one elementwise multiply); the
    streamed program is otherwise unchanged.
    """
    if mask is not None:
        y = y * jnp.asarray(mask, y.dtype)
    n, m = x.shape[0], z.shape[0]
    pad = (-n) % block
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    yp = jnp.pad(y, ((0, pad),) + ((0, 0),) * (y.ndim - 1))
    nb = xp.shape[0] // block

    def body(carry, args):
        xb, yb = args
        return carry + jnp.matmul(kernel.cross(xb, z).T, yb, precision=_M_SPACE), None

    out, _ = jax.lax.scan(body, jnp.zeros((m,) + y.shape[1:], x.dtype),
                          (xp.reshape(nb, block, -1),
                           yp.reshape((nb, block) + y.shape[1:])))
    return out


# ---------------------------------------------------------------------------
# Conjugate gradient
# ---------------------------------------------------------------------------


#: Per-column freeze threshold: a column whose squared residual norm has
#: dropped below this fraction of its initial value (or started at exactly
#: zero — padded bucket columns) is converged to fp32 noise; freezing it
#: avoids 0/0 step sizes and needless panel updates while other columns
#: keep iterating. sqrt(1e-14) ~ fp32 eps, so no legitimate progress is cut.
_CG_FREEZE_REL = 1e-14


def cg(matvec: Callable[[Array], Array], b: Array, iters: int,
       callback: Callable[[int, Array], None] | None = None,
       trajectory: bool = False, host: bool = False) -> Array | tuple[Array, Array]:
    """CG on SPD ``matvec``; fixed iteration count (paper uses t ~ log n).

    ``b`` may be a single right-hand side (q,) or an (q, k) panel — the
    multi-RHS block-CG form: one ``matvec`` evaluation per iteration serves
    every column (the operator cost is column-count independent up to GEMM
    flops), while the scalar recurrences (alpha, mu) run per column from
    axis-0 reductions. Columns are individually frozen once converged (see
    ``_CG_FREEZE_REL``); for (q,) inputs the recurrence is exactly plain CG.

    With ``trajectory=True`` returns ``(beta, residuals)`` where
    ``residuals`` is the (iters+1,) — or (iters+1, k) — squared residual
    norm history (row 0 = initial): the raw material for the §9 health
    diagnostics (``health.SolveDiagnostics``). The history is a small
    carried array updated in place; its cost is invisible next to the
    K_nM-streaming matvec. (Frozen columns stop updating r, so their
    recorded residual simply plateaus — the recorded value stays exact.)

    With ``callback`` the loop runs on host (per-iteration metrics for the
    Fig. 4/5 analogues), and so it does with ``host=True``: a ``matvec``
    that dispatches its own compiled pass per call (``Backend.host_loop``)
    is called once per iteration, never traced. Otherwise it is a single
    jitted lax.fori_loop, traced anew on every call (``falkon.cg`` in
    ``runtime.spans``): called outside a jit, as the host-driven fit calls
    it, it is then compiled or loaded from the persistent cache again.
    """
    rs0 = jnp.sum(b * b, axis=0)

    def step(state):
        beta, r, p, rs = state
        ap = matvec(p)
        active = rs > _CG_FREEZE_REL * rs0
        alpha = jnp.where(active,
                          rs / jnp.maximum(jnp.sum(p * ap, axis=0), 1e-30), 0.0)
        beta = beta + alpha * p
        r = r - alpha * ap
        rs_new = jnp.sum(r * r, axis=0)
        mu = jnp.where(active, rs_new / jnp.maximum(rs, 1e-30), 0.0)
        p = jnp.where(active, r + mu * p, p)
        return beta, r, p, jnp.where(active, rs_new, rs)

    state = (jnp.zeros_like(b), b, b, rs0)
    if callback is not None or host:
        resid = [rs0]
        for i in range(iters):
            state = step(state)
            resid.append(state[3])
            if callback is not None:
                callback(i, state[0])
        if trajectory:
            return state[0], jnp.stack(resid)
        return state[0]
    if trajectory:
        traj0 = jnp.zeros((iters + 1,) + rs0.shape, rs0.dtype).at[0].set(rs0)

        def tstep(i, st):
            inner, traj = st
            inner = step(inner)
            return inner, traj.at[i + 1].set(inner[3])

        with spans.retrace("falkon.cg"):
            (beta, *_), traj = jax.lax.fori_loop(0, iters, tstep, (state, traj0))
        return beta, traj
    with spans.retrace("falkon.cg"):
        return jax.lax.fori_loop(0, iters, lambda _, s: step(s), state)[0]


# ---------------------------------------------------------------------------
# Fused whole-fit path (see module docstring / DESIGN.md §2.4)
# ---------------------------------------------------------------------------

def _fit_block(backend) -> int:
    """Stream-block (and row-bucket granularity) for a jit-safe backend."""
    get = getattr(backend, "_block", None)
    return get() if get is not None else 4096


def _k_bucket(k: int) -> int:
    """Column bucket for the fused-fit cache: next power of two >= k.

    One compiled solve serves every RHS count in a bucket (k is padded with
    zero columns that the per-column convergence mask freezes from iteration
    zero), bounding the jit cache at log2(k_max) executables per row bucket.
    """
    return 1 << max(0, k - 1).bit_length()


def _masked_knm_ops(kernel: Kernel, xp: Array, z: Array, yp: Array,
                    row_mask: Array, block: int,
                    col_mask: Array | None = None):
    """(quadratic op, K_nM^T y) over bucket-padded rows with a traced
    validity mask — same math as local_knm_quadratic / local_knm_t, but the
    mask is a tracer so one compiled solve serves every n in the bucket.
    ``yp`` is (n_pad,) for a single-output fit or an (n_pad, kb) panel for
    multi-RHS; the quadratic op consumes matching (M,) / (M, kb) iterates.
    (True vector shapes are kept for kb absent — an (n, 1) panel lowers to
    a markedly slower CPU program than the equivalent matvec.)

    ``col_mask`` — optional per-column row-exclusion weights shaped like
    ``yp``: column j of the quadratic form sees only its masked rows (exact
    k-fold CV), applied as one extra elementwise multiply on the streamed
    (block, kb) intermediate. Padding rows must already be zeroed by the
    caller (falkon_fit pads with zeros). When None, the program is the
    pre-mask one bit-for-bit — a different pytree structure, so masked and
    unmasked fits compile to separate cache entries and the unmasked hot
    path keeps its exact pre-CV executable."""
    m = z.shape[0]
    nb = xp.shape[0] // block
    xb = xp.reshape(nb, block, xp.shape[1])
    mb = row_mask.reshape(nb, block).astype(xp.dtype)
    cmb = (None if col_mask is None
           else col_mask.reshape((nb, block) + yp.shape[1:]))

    def quad(v: Array) -> Array:
        def body(carry, args):
            xblk, mblk, cblk = args
            g = kernel.cross(xblk, z) * mblk[:, None]
            t = g @ v
            if cblk is not None:
                t = t * cblk
            return carry + g.T @ t, None

        out, _ = jax.lax.scan(body, jnp.zeros((m,) + v.shape[1:], v.dtype),
                              (xb, mb, cmb))
        return out

    def body_t(carry, args):
        xblk, yblk = args
        return carry + kernel.cross(xblk, z).T @ yblk, None

    ym = yp * (row_mask if yp.ndim == 1 else row_mask[:, None])
    if col_mask is not None:
        ym = ym * col_mask
    kty, _ = jax.lax.scan(body_t, jnp.zeros((m,) + yp.shape[1:], xp.dtype),
                          (xb, ym.reshape((nb, block) + yp.shape[1:])))
    return quad, kty


@partial(jax.jit, static_argnames=("iters", "backend", "block"),
         donate_argnames=("yp",))
@spans.retrace("falkon.fused_fit")
def _fused_falkon_solve(kernel: Kernel, xp: Array, yp: Array, centers: Array,
                        a_diag: Array, lam: Array, n: Array, *, iters: int,
                        backend, block: int,
                        col_mask: Array | None = None) -> tuple[Array, Array]:
    """Preconditioner + multi-RHS CG + alpha recovery as one compiled program.

    Each trace counts as ``falkon.fused_fit`` (``runtime.spans``): a second
    fit in the same shape bucket adds none, and is then one cached call.

    ``yp`` is the bucket-padded target: (n_pad,) for single-output, or an
    (n_pad, kb) panel for multi-RHS; alpha comes back with matching shape
    and the caller slices the real columns out. Also returns the CG
    residual trajectory for the §9 health diagnostics.

    ``col_mask`` (optional, shaped like ``yp``, zero-padded) gives every
    column its own row subset: column j solves
    ``(K_nM^T diag(m_j) K_nM + lam n_j K_MM) alpha_j = K_nM^T (m_j * y_j)``
    with n_j = sum(m_j) — the per-fold normal equations of exact
    row-exclusion CV. The preconditioner keeps the *global* n: B enters CG
    only as a symmetric congruence, and CG iterates are exactly invariant
    under the (c^2 A, c b) rescaling that a per-column 1/sqrt(n_j) would
    introduce, so the shared factorization changes nothing (DESIGN.md §2.4).
    """
    row_mask = jnp.arange(xp.shape[0]) < n
    prec = make_preconditioner(kernel, centers, a_diag, lam, n)
    kmm = backend.gram_block(kernel, centers, centers)
    quad, kty = _masked_knm_ops(kernel, xp, centers, yp, row_mask, block,
                                col_mask)
    # Per-column effective row count for the lam * n_j * K_MM term; scalar n
    # (the original program) when no mask is given.
    n_eff = n if col_mask is None else jnp.sum(col_mask, axis=0)

    def matvec(v: Array) -> Array:
        u = prec.apply(v)
        w = quad(u) + lam * n_eff * jnp.matmul(kmm, u, precision=_M_SPACE)
        return prec.apply_t(w)

    beta, resid = cg(matvec, prec.apply_t(kty), iters, trajectory=True)
    return prec.apply(beta), resid


# ---------------------------------------------------------------------------
# FALKON estimator
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FalkonModel:
    """A fitted FALKON / Nystrom-KRR predictor: x -> K(x, centers) alpha."""

    centers: Array  # (M, d)
    alpha: Array  # (M,) or (M, k) for multi-output fits
    kernel: Kernel
    #: serving-time contraction backend; set by falkon_fit to the fit-time
    #: choice, overridable per predict call. None -> platform heuristic.
    backend: BackendLike = None
    #: §9 solver health report (CG residual trajectory with lazy
    #: converged/stalled/diverged classification); None for models built
    #: by the direct solvers or hand-assembled.
    diagnostics: "health.SolveDiagnostics | None" = None
    #: fit-time regularization / row count / center weights, recorded by the
    #: solvers so ``predictive_variance`` can rebuild the posterior operator
    #: (K_MM + lam n A); None on hand-assembled models (variance raises).
    lam: float | None = None
    n_train: int | None = None
    a_diag: Array | None = None

    def predictive_variance(self, x: Array, *, backend: BackendLike = None) -> Array:
        """GP-style Nystrom posterior variance per row of ``x``.

        Computes ``k(x, x) - k_xM (K_MM + lam n A)^{-1} k_Mx`` — the
        predictive variance of the degenerate-GP reading of Nystrom-KRR
        (weights A from the sampler; A = I for uniform/exact fits). This is
        exactly ``lam * n`` times the ridge leverage score of x against the
        centers, so it rides the seam's fused ``rls_scores`` path: the
        Pallas backend takes the one-kernel RLS program, ``StreamBackend``
        streams x in host chunks with the (M, M) factorization hoisted out
        of the loop — out-of-core n works unchanged.

        Returns (n,) nonnegative variances (clipped at 0 against fp32
        cancellation; multi-output models share one variance — it does not
        depend on y). Raises ``ValueError`` on models missing the fit
        metadata (lam / n_train), e.g. hand-assembled ones.
        """
        if self.lam is None or self.n_train is None:
            raise ValueError(
                "predictive_variance needs fit metadata (lam, n_train); this "
                "model was built without it — refit via falkon_fit / "
                "nystrom_krr / exact_krr")
        spec = backend if backend is not None else self.backend
        be = resolve_backend(spec, n=x.shape[0])
        m = self.centers.shape[0]
        a = (jnp.ones((m,), jnp.float32) if self.a_diag is None
             else self.a_diag.astype(jnp.float32))
        lam_n = jnp.asarray(self.lam * self.n_train, jnp.float32)
        scores = be.rls_scores(self.kernel, x, self.centers,
                               jnp.ones((m,), bool), lam_n * a, lam_n)
        return jnp.maximum(lam_n * scores, 0.0)

    def predict(self, x: Array, *, backend: BackendLike = None) -> Array:
        """K(x, centers) alpha through the kernel-operator seam.

        Returns (n,) for a single-output model, (n, k) for a multi-output
        one. Both take the fused ``knm_matvec`` panel contraction: K_nM is
        never materialized, each streamed Gram block is evaluated once and
        contracted against every alpha column, so extra outputs cost GEMM
        flops only.

        This is the serving dispatch boundary, so it hosts the chaos
        harness's dispatch-level injection points (inert one-dict-check
        when nothing is armed; see repro/testing/faults.py). It carries no
        finite fence of its own — the serving engines fence per wave where
        the result is materialized anyway, and raw callers opt in via
        ``health.check_finite``.
        """
        spec = backend if backend is not None else self.backend
        be = resolve_backend(spec, n=x.shape[0])
        if faults.active():
            faults.sleep_if(rows=x.shape[0], centers=self.centers.shape[0])
            faults.raise_if()
        out = be.knm_matvec(self.kernel, x, self.centers, self.alpha)
        if faults.active():
            out = faults.corrupt("gram.nan_tile", out)
        return out


def falkon_fit(
    kernel: Kernel,
    x: Array,
    y: Array,
    centers: Array,
    lam: float,
    *,
    a_diag: Array | None = None,
    iters: int = 20,
    backend: BackendLike = None,
    callback: Callable[[int, FalkonModel], None] | None = None,
    fused: bool | None = None,
    check_finite: bool = False,
    row_mask: Array | None = None,
) -> FalkonModel:
    """Fit FALKON (uniform A=I) or FALKON-BLESS (A from Alg. 1/2).

    ``backend`` selects the K_nM operator implementation — an instance, a
    registry name ("jnp" | "pallas" | "sharded"), or None for the platform
    heuristic (repro.core.backend.default_backend).

    ``fused`` selects the whole-fit compilation path (see module docstring):
    None (default) takes it automatically when the backend is jit-safe and no
    ``callback`` needs the host CG loop; True forces it (raising if the
    backend cannot be traced); False forces the host-driven path.

    ``y`` may be (n,) or (n, k): multi-output targets ride ONE multi-RHS
    block-CG against the same centers — the preconditioner, the K_nM
    streaming and (on jit-safe backends) the fused-fit compile are all
    shared across columns, so extra outputs cost only the extra GEMM flops.
    On the fused path k is padded up to a power-of-two column bucket
    (``_k_bucket``) so every RHS count in a bucket shares one executable.

    Every fit records its CG residual trajectory as
    ``model.diagnostics`` (``health.SolveDiagnostics`` — lazy, no device
    sync until a property is read). ``check_finite=True`` arms the §9
    output fence: raise ``health.NonFiniteError`` instead of returning a
    NaN alpha. It defaults off because the check is one blocking device
    round-trip per fit — real cost in the hot sweep paths (fig3 warm-start
    refits, KFoldSweep grids) that dispatch many fits back to back.

    ``row_mask`` — optional per-column row-exclusion weights shaped like
    ``y`` ((n,) or (n, k)): column j is fit on only its masked rows, i.e.
    solves ``(K_nM^T diag(m_j) K_nM + lam n_j K_MM) alpha_j =
    K_nM^T (m_j y_j)`` with n_j = sum(m_j). This is the exact k-fold CV
    mechanism (every fold = one masked RHS column of a single multi-RHS
    solve); the shared preconditioner keeps the global n, which is exact —
    CG iterates are invariant under the per-column rescaling (see
    ``_fused_falkon_solve``). ``row_mask=None`` keeps the pre-mask program
    (and its jit cache entries) bit-for-bit.
    """
    n = x.shape[0]
    m = centers.shape[0]
    backend = resolve_backend(backend, n=n)
    single = y.ndim == 1
    if not single and callback is not None:
        raise ValueError("per-iteration callback is single-output only; "
                         "fit columns separately to trace them")
    if row_mask is not None:
        row_mask = jnp.asarray(row_mask, x.dtype)
        if row_mask.shape != y.shape:
            raise ValueError(f"row_mask shape {row_mask.shape} must match "
                             f"y shape {y.shape}")
    a_diag = jnp.ones((m,), x.dtype) if a_diag is None else a_diag
    if fused is None:
        fused = backend.jit_safe and callback is None
    if fused:
        if not backend.jit_safe:
            raise ValueError(f"fused=True needs a jit-safe backend, got {backend.name!r}")
        if callback is not None:
            raise ValueError("the fused fit has no host CG loop; "
                             "pass fused=False to use callback")
        block = _fit_block(backend)
        pad = (-n) % block
        # Single-output keeps true vector shapes (an (n, 1) panel lowers to
        # a much slower CPU program); k >= 2 pads to the pow2 column bucket.
        col_pad = 0 if single else _k_bucket(y.shape[1]) - y.shape[1]
        # yp is donated by _fused_falkon_solve, so it must be a fresh buffer
        # even when the bucket needs no padding (x is shared, never donated).
        if pad or col_pad:
            yp = jnp.pad(y, ((0, pad),) if single else ((0, pad), (0, col_pad)))
        else:
            yp = y + jnp.zeros((), y.dtype)
        col_mask = None
        if row_mask is not None:
            # Zero-pad like yp: padded rows drop out of the quadratic form
            # and padded columns get n_j = 0 (frozen by the CG mask anyway).
            col_mask = jnp.pad(
                row_mask,
                ((0, pad),) if single else ((0, pad), (0, col_pad)))
        alpha, resid = _fused_falkon_solve(
            kernel, jnp.pad(x, ((0, pad), (0, 0))), yp, centers, a_diag,
            jnp.asarray(lam, jnp.float32), jnp.asarray(n, jnp.int32),
            iters=iters, backend=backend, block=block, col_mask=col_mask)
        alpha = alpha if single else alpha[:, : y.shape[1]]
        resid = resid if single else resid[:, : y.shape[1]]
        if check_finite:
            health.check_finite(alpha, "falkon_fit alpha (fused)")
        return FalkonModel(centers=centers, alpha=alpha, kernel=kernel,
                           backend=backend,
                           diagnostics=health.SolveDiagnostics(resid),
                           lam=float(lam), n_train=n, a_diag=a_diag)
    prec = make_preconditioner(kernel, centers, a_diag, lam, n)
    kmm = backend.gram_block(kernel, centers, centers)
    quad, kty = backend.knm_operators(kernel, x, centers, y, mask=row_mask)
    n_eff = n if row_mask is None else jnp.sum(row_mask, axis=0)

    def matvec(v: Array) -> Array:
        u = prec.apply(v)
        w = quad(u) + lam * n_eff * jnp.matmul(kmm, u, precision=_M_SPACE)
        return prec.apply_t(w)

    b = prec.apply_t(kty)
    cb = None
    if callback is not None:
        def cb(i, beta):  # noqa: E731 — host-side metric hook
            callback(i, FalkonModel(centers=centers, alpha=prec.apply(beta),
                                    kernel=kernel, backend=backend))
    beta, resid = cg(matvec, b, iters, callback=cb, trajectory=True,
                     host=backend.host_loop)
    alpha = prec.apply(beta)
    if check_finite:
        health.check_finite(alpha, "falkon_fit alpha")
    return FalkonModel(centers=centers, alpha=alpha, kernel=kernel,
                       backend=backend,
                       diagnostics=health.SolveDiagnostics(resid),
                       lam=float(lam), n_train=n, a_diag=a_diag)


def falkon_bless_fit(key: Array, kernel: Kernel, x: Array, y: Array, lam_bless: float,
                     lam_falkon: float, *, iters: int = 20, q2: float = 3.0,
                     m_cap: int | None = None, backend: BackendLike = None,
                     callback=None) -> FalkonModel:
    """FALKON-BLESS end-to-end (the paper's lam_bless >> lam_falkon trick,
    Sec. 4). Thin shim over the ``repro.api`` front door — equivalent to
    ``FalkonRegressor(sampler=BlessSampler(lam=lam_bless, ...))`` — kept for
    source compatibility; tests/test_api.py proves the paths bit-identical.

    The upward delegation is deliberate: the sampler+solver *composition*
    has exactly one implementation (the estimator), so shim and front door
    cannot drift. The import is lazy/call-time, keeping module import order
    acyclic (api imports core at module scope, never the reverse).
    """
    from ..api.estimators import FalkonRegressor, FitConfig  # api sits above core
    from ..api.samplers import BlessSampler

    est = FalkonRegressor(
        kernel=kernel,
        sampler=BlessSampler(lam=lam_bless, q2=q2, m_cap=m_cap),
        config=FitConfig(lam=lam_falkon, iters=iters, backend=backend),
    )
    return est.fit(x, y, key=key, callback=callback).model_
