"""BLESS (Alg. 1) and BLESS-R (Alg. 2) — bottom-up leverage score sampling.

Faithful implementations of the paper's Algorithms 1 and 2, restructured so
the host loop touches the device as little as possible. The ladder itself
runs on the host (H ~ log(lam0/lam)/log q levels); each level is two jitted
phases on size-bucketed buffers:

  * a *score* phase — candidate draw, Eq. 3 scoring through the
    ``Backend.rls_scores`` seam (the fused Pallas kernel on TPU), and the
    d_h reduction, all inside one compiled call;
  * a *sample* phase — the with-replacement categorical draw (Alg. 1) or
    acceptance compaction (Alg. 2) and the A_h weights.

  Between the phases the host fetches exactly the scalars it needs to pick
the next static shapes (d_h -> M_h, the distinct-center count -> the next
level's score buffer), so there are O(1) device syncs per level instead of
O(1) per array.

Buffers use *quarter-pow2* buckets (``_bucket``): pow2 up to 32, then the
smallest of {5/8, 3/4, 7/8, 1} * pow2 that fits. Padding waste drops from
<= 2x to <= 1.25x while the jit cache stays O(log) sized — a draw of 1045
candidates runs on a 1280 buffer, not 2048. Each phase counts its traces
as ``bless.<phase>`` in ``runtime.spans`` (``retraces("bless")`` sums them):
repeating a ladder at the same (n, kernel, lam, q*) hits the cache end to
end. Each level on the host is the span ``repro.bless.level``, and its
blocking fetch of the next level's buffer size ``repro.bless.sync``.

Two exact-optimization notes (distributionally identical to the paper's
pseudocode, DESIGN.md §8):

  * when a level wants more uniform candidates than there are points
    (R_h >= n), the score phase evaluates each point once and carries the
    multiplicity c_i of the uniform draw instead of scoring duplicate rows
    (Alg. 1 line 6 at R_h ~ q1 n would score each point ~q1 times);
  * the Alg. 1 center sets are multisets; the *internal* scorer merges
    duplicate centers before the Cholesky via the Woodbury push-through
    (merged reg = harmonic sum of the duplicates' lam n A_jj), shrinking
    the (M, M) factor to the distinct-center count. The public
    ``CenterSet`` keeps the raw multiset — FALKON and Eq. 3 consumers see
    exactly the paper's (J_h, A_h).

Paper-vs-practice constants: Thm. 1's q1/q2 include union-bound log factors
that the paper's own experiments do not use (Sec. 4 reaches M ~ 1e4 centers
at n = 7e4). ``theory_constants(t, q, n, H, delta)`` reproduces Thm. 1's
values; the defaults are the practical ones used in our Fig. 1/2 analogues.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime import spans
from .gram import BackendLike, Kernel, resolve_backend
from .leverage import _SCORE_FLOOR, CenterSet
from .sampling import categorical

Array = jax.Array

#: Kept name: the Alg. 1 line-9 draw is *with replacement*, i.e. the jitted
#: inverse-CDF categorical (see ``repro.core.sampling`` for why it is not a
#: Gumbel-top-k, which samples without replacement).
_multinomial = categorical


@dataclasses.dataclass(frozen=True)
class BlessLevel:
    """One rung of the ladder: accurate scores at scale lam_h."""

    lam: float
    centers: CenterSet  # (J_h, A_h) on a padded buffer
    d_h: float  # n/R_h * sum of candidate scores  (≈ d_eff(lam_h))
    m_h: int  # |J_h|
    r_h: int  # |U_h|


@dataclasses.dataclass(frozen=True)
class BlessResult:
    levels: list[BlessLevel]
    lam_path: list[float]

    @property
    def final(self) -> BlessLevel:
        return self.levels[-1]

    def scores(self, kernel: Kernel, x_all: Array, lam: float | None = None,
               *, backend: BackendLike = None) -> Array:
        """Approximate leverage scores for every point at the final scale."""
        from .leverage import approx_rls_all

        lvl = self.final
        return approx_rls_all(kernel, x_all, lvl.centers, jnp.asarray(lam or lvl.lam),
                              backend=backend)


def theory_constants(t: float, q: float, n: int, h: int, delta: float = 0.1):
    """Thm. 1 (Alg. 1) constants: (q1, q2)."""
    q2 = 12.0 * q * (2 * t + 1) ** 2 / t**2 * (1 + t) * math.log(12 * h * n / delta)
    q1 = 5.0 * q2 / (q * (1 + t))
    return q1, q2


def lam_ladder(lam: float, lam0: float, q: float) -> list[float]:
    """Geometric ladder lam_0 > ... > lam_H = lam (lam_h = lam_{h-1}/q)."""
    h = max(1, math.ceil(math.log(lam0 / lam) / math.log(q)))
    lams = [lam0 / q**i for i in range(1, h)]
    lams.append(lam)  # pin the final level exactly at lam
    return lams


def _pow2(x: int) -> int:
    return 1 << max(0, (int(x) - 1)).bit_length()


def _bucket(x: int) -> int:
    """Quarter-pow2 size bucket: pow2 up to 32, then the smallest of
    {5/8, 3/4, 7/8, 1} * next-pow2 that fits. At most 4 buckets per octave
    keeps the jit cache O(log) while capping padding waste at 25%."""
    x = max(1, int(x))
    p = _pow2(x)
    if p <= 32:
        return p
    for c in (5 * p // 8, 3 * p // 4, 7 * p // 8):
        if c >= x:
            return c
    return p


# =============================================================================
# Shared level machinery
# =============================================================================


def _dedup_centers(centers: CenterSet, lamn: Array, dbuf: int):
    """Merge duplicate centers of an Alg. 1 multiset into a (dbuf,) buffer.

    Exact via the Woodbury push-through: duplicate columns j of the same
    point with regularized diagonals r_j = lam n A_jj collapse to one column
    with r = 1 / sum_j (1/r_j) (harmonic; a singleton is unchanged). The
    caller guarantees dbuf >= the distinct count (it fetched it when the
    level was sampled); surplus duplicates would be silently dropped
    otherwise, so the driver always buckets the fetched count up.
    """
    mbuf = centers.idx.shape[0]
    sentinel = jnp.iinfo(jnp.int32).max
    order = jnp.argsort(jnp.where(centers.mask, centers.idx, sentinel))
    sidx = centers.idx[order]
    svalid = centers.mask[order]
    sinv = jnp.where(svalid, 1.0 / (lamn * centers.weight[order]), 0.0)
    prev = jnp.concatenate([jnp.full((1,), -1, sidx.dtype), sidx[:-1]])
    first = svalid & (sidx != prev)
    seg = jnp.cumsum(first.astype(jnp.int32)) - 1
    n_dd = jnp.sum(first.astype(jnp.int32))
    tgt = jnp.where(svalid, seg, dbuf)  # out-of-bounds scatters drop
    dd_idx = jnp.zeros((dbuf,), jnp.int32).at[tgt].set(sidx, mode="drop")
    dd_inv = jnp.zeros((dbuf,), jnp.float32).at[tgt].add(sinv, mode="drop")
    dd_mask = jnp.arange(dbuf) < n_dd
    dd_reg = jnp.where(dd_mask, 1.0 / jnp.maximum(dd_inv, 1e-30), 1.0)
    return dd_idx, dd_mask, dd_reg


_dedup_centers_jit = partial(jax.jit, static_argnames=("dbuf",))(_dedup_centers)


def _rls_dedup(kernel, x_cand, cand_mask, x_all, centers, lamn, *, backend, dbuf):
    """Eq. 3 scores of candidates against a (possibly multiset) center set,
    deduplicated internally, through ``backend.rls_scores``. Clipped to
    [_SCORE_FLOOR, 1]; 0 on invalid candidate slots.

    Host-resident ``x_all`` (a ``repro.stream.ChunkStore``) takes a Python
    branch instead of ``lax.cond``: the cond traces BOTH branches, and a
    traced center gather would force the whole store onto the device. So
    does a backend that dispatches its own compiled passes
    (``Backend.host_loop``): an eager cond would trace the pass anew, and
    compile it, at every level. Only reachable on non-jit-safe backends
    (the stream driver), so the jitted ladder phases never see it; the
    empty-center case routes through ``rls_scores`` with an all-masked
    buffer (exactly K_ii / lamn) so a chunked ``x_cand`` never meets a raw
    ``kernel.diag``.
    """
    if not isinstance(x_all, jax.Array) or backend.host_loop:
        if int(centers.count) > 0:
            dd_idx, dd_mask, dd_reg = _dedup_centers_jit(centers, lamn, dbuf=dbuf)
            s = backend.rls_scores(kernel, x_cand, x_all[dd_idx], dd_mask,
                                   dd_reg, lamn)
        else:
            s = backend.rls_scores(
                kernel, x_cand, x_all[np.zeros((dbuf,), np.int32)],
                jnp.zeros((dbuf,), bool), jnp.ones((dbuf,), jnp.float32), lamn)
        return jnp.where(cand_mask, jnp.clip(s, _SCORE_FLOOR, 1.0), 0.0)

    def no_centers(_):
        return kernel.diag(x_cand) / lamn

    def with_centers(_):
        dd_idx, dd_mask, dd_reg = _dedup_centers(centers, lamn, dbuf)
        return backend.rls_scores(kernel, x_cand, x_all[dd_idx], dd_mask,
                                  dd_reg, lamn)

    s = jax.lax.cond(centers.count > 0, with_centers, no_centers, None)
    s = jnp.clip(s, _SCORE_FLOOR, 1.0)
    return jnp.where(cand_mask, s, 0.0)


# =============================================================================
# Algorithm 1 — BLESS (with replacement)
# =============================================================================


def _bless_score_impl(k_u, x, kernel, centers, lam_h, r_h, *,
                      backend, rbuf, dbuf, counts):
    """Level score phase: candidate draw + Eq. 3 scores + the d_h reduction.

    ``counts=True`` is the R_h >= n regime: every point is scored once and
    the uniform draw only contributes multiplicities c_i (scatter-add), so
    the quadform runs over n rows instead of R_h > n duplicate rows.
    Returns (cand_idx, s, wvec, tot, d_h) with wvec = c * s the unnormalized
    sampling weights of Alg. 1 line 8.
    """
    n = x.shape[0]
    lamn = lam_h * n
    draws = jax.random.randint(k_u, (rbuf,), 0, n)
    if counts:
        cand_idx = jnp.arange(n, dtype=jnp.int32)
        cand_mask = jnp.ones((n,), bool)
        x_cand = x
        slot = jnp.where(jnp.arange(rbuf) < r_h, draws, n)
        c = jnp.zeros((n,), jnp.float32).at[slot].add(1.0, mode="drop")
    else:
        cand_idx = draws.astype(jnp.int32)
        cand_mask = jnp.arange(rbuf) < r_h
        x_cand = x[cand_idx]
        c = cand_mask.astype(jnp.float32)
    s = _rls_dedup(kernel, x_cand, cand_mask, x, centers, lamn,
                   backend=backend, dbuf=dbuf)
    wvec = c * s
    tot = jnp.maximum(jnp.sum(wvec), 1e-30)
    d_h = n / r_h.astype(jnp.float32) * tot
    return cand_idx, s, wvec, tot, d_h


# The retrace mark goes on what jit traces: host-driven backends call the
# impl eagerly, and an eager call is no retrace.
_bless_score = partial(jax.jit, static_argnames=("backend", "rbuf", "dbuf", "counts"))(
    spans.retrace("bless.score")(_bless_score_impl))


@partial(jax.jit, static_argnames=("mbuf", "n"))
@spans.retrace("bless.sample")
def _bless_sample(k_j, cand_idx, s, wvec, tot, r_h, m_h, *, mbuf, n):
    """Level sample phase (Alg. 1 lines 9-10): M_h categorical draws from
    wvec (with replacement), the A_h weights, and the distinct-center count
    the host needs to size the next level's dedup buffer."""
    pos = categorical(k_j, wvec, mbuf)
    j_mask = jnp.arange(mbuf) < m_h
    scale = r_h.astype(jnp.float32) * m_h.astype(jnp.float32) / n
    w = jnp.where(j_mask, scale * s[pos] / tot, 1.0)
    idx = cand_idx[pos].astype(jnp.int32)
    center_set = CenterSet(
        idx=idx,
        weight=w.astype(jnp.float32),
        mask=j_mask,
        count=m_h.astype(jnp.int32),
    )
    sort_key = jnp.sort(jnp.where(j_mask, idx, jnp.iinfo(jnp.int32).max))
    prev = jnp.concatenate([jnp.full((1,), -1, sort_key.dtype), sort_key[:-1]])
    n_distinct = jnp.sum((sort_key != prev) & (jnp.arange(mbuf) < m_h))
    return center_set, n_distinct


def bless(
    key: Array,
    x: Array,
    kernel: Kernel,
    lam: float,
    *,
    q: float = 2.0,
    q1: float = 3.0,
    q2: float = 3.0,
    lam0: float | None = None,
    t: float = 1.0,
    m_cap: int | None = None,
    backend: BackendLike = None,
) -> BlessResult:
    """Bottom-up Leverage Score Sampling (paper Alg. 1).

    Args:
      key: PRNG key.
      x: (n, d) dataset.
      kernel: bounded PSD kernel.
      lam: target regularization (the paper's lambda).
      q: ladder step (> 1).
      q1: candidate-set multiplier, R_h = q1 * min(kappa^2/lam_h, n).
      q2: center multiplier, M_h = q2 * d_h.
      lam0: ladder start; defaults to the paper's kappa^2/min(t, 1).
      t: target multiplicative accuracy (only sets the default lam0).
      m_cap: optional hard cap on M_h (memory guard for benchmarks).
      backend: kernel-operator backend for the Eq. 3 scorer — an instance,
        a registry name ("jnp" | "pallas" | "sharded"), or None for the
        platform heuristic (repro.core.backend.default_backend).

    Returns:
      BlessResult with one BlessLevel per rung — the whole regularization
      path {lam_h}, the paper's "computed at once" advantage.
    """
    n = x.shape[0]
    kap2 = float(kernel.kappa_sq)
    lam0 = kap2 / min(t, 1.0) if lam0 is None else lam0
    lams = lam_ladder(lam, lam0, q)
    backend = resolve_backend(backend, n=n)
    score_fn = _bless_score if backend.jit_safe else _bless_score_impl

    centers = CenterSet.empty(1)
    dbuf = 1
    levels: list[BlessLevel] = []
    for h, lam_h in enumerate(lams):
        key, k_u, k_j = jax.random.split(key, 3)
        # -- line 4/5: uniform candidates U_h, R_h = q1 * min(kappa^2/lam_h, n)
        r_h = max(8, int(math.ceil(q1 * min(kap2 / lam_h, n))))
        rbuf = _bucket(r_h)
        counts = n <= rbuf  # score each point once, carry multiplicities
        with spans.span("bless.level", h=h, r_h=r_h) as level:
            cand_idx, s, wvec, tot, d_dev = score_fn(
                k_u, x, kernel, centers, jnp.asarray(lam_h, jnp.float32),
                jnp.asarray(r_h, jnp.int32),
                backend=backend, rbuf=rbuf, dbuf=dbuf, counts=counts)
            # -- line 7/8: d_h (a blocking fetch, waits for the scores) -> M_h
            d_h = float(d_dev)
            m_h = max(8, int(math.ceil(q2 * d_h)))
            if m_cap is not None:
                m_h = min(m_h, m_cap)
            level.set_metadata(m_h=m_h)
            mbuf = _bucket(m_h)
            # -- line 9/10: J_h ~ Multinomial(P_h, U_h), A_h weights
            centers, n_distinct = _bless_sample(
                k_j, cand_idx, s, wvec, tot, jnp.asarray(r_h, jnp.int32),
                jnp.asarray(m_h, jnp.int32), mbuf=mbuf, n=n)
            with spans.span("bless.sync", h=h):
                dbuf = _bucket(int(n_distinct))
        levels.append(BlessLevel(lam=lam_h, centers=centers, d_h=d_h, m_h=m_h, r_h=r_h))
    return BlessResult(levels=levels, lam_path=lams)


# =============================================================================
# Algorithm 2 — BLESS-R (rejection sampling, without replacement)
# =============================================================================


@spans.retrace("bless.r_gates")
def _blessr_gates_impl(k_u, betas, n):
    """All H Bernoulli pre-filters (Alg. 2 lines 5-8) in one dispatch:
    per level the survivor-first index order and the survivor count — one
    host fetch of (H,) sizes instead of H gate/argsort round-trips."""
    h = betas.shape[0]
    gate = jax.random.uniform(k_u, (h, n)) < betas[:, None]
    r_vec = jnp.sum(gate, axis=1).astype(jnp.int32)
    orders = jnp.argsort(~gate, axis=1).astype(jnp.int32)  # survivors first
    return orders, r_vec


_blessr_gates = partial(jax.jit, static_argnames=("n",))(_blessr_gates_impl)


def _bucket32(x: int) -> int:
    """Finer (multiple-of-32) bucket for Alg. 2's *internal* center buffers.

    The per-level (M, M) factor + (R, M) quadform are so dbuf-sensitive
    that quarter-pow2 padding (up to 25% extra M) costs more wall time than
    the occasional extra recompile the finer grid admits. Public CenterSet
    buffers keep the coarse ``_bucket`` convention.
    """
    x = max(1, int(x))
    return _bucket(x) if x <= 32 else -(-x // 32) * 32


def _compact_body(u_idx, p, acc, m_h, *, mbuf, m_cap):
    """Compact acceptances into an (mbuf,) CenterSet; with ``m_cap`` keep
    the m_cap highest-probability acceptances (memory guard)."""
    m_h = jnp.asarray(m_h, jnp.int32)
    if m_cap is not None:
        keep = jnp.argsort(jnp.where(acc, -p, jnp.inf))[:m_cap]
        acc = jnp.zeros_like(acc).at[keep].set(True) & acc
        m_h = jnp.minimum(m_h, m_cap)
    sel = jnp.argsort(~acc)[:mbuf]
    j_mask = jnp.arange(mbuf) < m_h
    return CenterSet(
        idx=u_idx[sel].astype(jnp.int32),
        weight=jnp.where(j_mask, p[sel], 1.0).astype(jnp.float32),
        mask=j_mask,
        count=m_h,
    )


@partial(jax.jit, static_argnames=("mbuf", "m_cap"))
@spans.retrace("bless.r_compact")
def _blessr_compact(u_idx, p, acc, m_h, *, mbuf, m_cap):
    """Standalone compaction — only the ladder's final level needs it (every
    other level's compaction is fused into the next level's dispatch)."""
    return _compact_body(u_idx, p, acc, m_h, mbuf=mbuf, m_cap=m_cap)


def _blessr_level_impl(k_a, x, kernel, order_h, pu, pp, pacc, pm, lam_prev,
                       beta, q2v, r_h, *, backend, rbuf, dbuf, m_cap,
                       identity_order):
    """One fused Alg. 2 level: pack the previous level's acceptances into
    its (dbuf,) center set J_{h-1}, then score + accept this level's
    candidates against it (lines 9-12) — a single dispatch per level, with
    the (m_h, sum s) statistics stacked so the driver blocks on exactly one
    2-float fetch.

    ``identity_order=True`` is the beta_h = 1 regime (every point survives
    the Bernoulli pre-filter): the survivor order is the identity, so the
    candidate gather is skipped entirely and rbuf == n.
    """
    n = x.shape[0]
    centers = _compact_body(pu, pp, pacc, pm, mbuf=dbuf, m_cap=m_cap)
    if identity_order:
        assert rbuf == n
        u_idx = jnp.arange(n, dtype=jnp.int32)
        x_cand = x
    else:
        u_idx = order_h[: min(rbuf, n)]
        if rbuf > n:
            u_idx = jnp.pad(u_idx, (0, rbuf - n))
        x_cand = x[u_idx]
    u_mask = jnp.arange(rbuf) < r_h
    lamn = lam_prev * n
    # Alg. 2 center sets are distinct (rejection sampling draws each j at
    # most once), so the Alg. 1 dedup pass is the identity here — score
    # straight against the padded set. An empty set degenerates cleanly:
    # an all-false mask zeroes the quadratic form, s = K_ii/(lam n).
    reg = jnp.where(centers.mask, lamn * centers.weight, 1.0)
    s = backend.rls_scores(kernel, x_cand, x[centers.idx], centers.mask,
                           reg, lamn)
    s = jnp.where(u_mask, jnp.clip(s, _SCORE_FLOOR, 1.0), 0.0)
    p = jnp.minimum(q2v * s, 1.0)
    # -- line 11: accept j with prob p_j / beta  (clipped: see App. C)
    acc = (jax.random.uniform(k_a, (rbuf,)) < jnp.minimum(p / beta, 1.0)) & u_mask
    stats = jnp.stack([jnp.sum(acc.astype(jnp.float32)), jnp.sum(s)])
    return centers, u_idx, p, acc, stats


_blessr_level = partial(jax.jit, static_argnames=(
    "backend", "rbuf", "dbuf", "m_cap", "identity_order"))(
    spans.retrace("bless.r_level")(_blessr_level_impl))


def bless_r(
    key: Array,
    x: Array,
    kernel: Kernel,
    lam: float,
    *,
    q: float = 2.0,
    q2: float = 3.0,
    lam0: float | None = None,
    t: float = 1.0,
    m_cap: int | None = None,
    backend: BackendLike = None,
) -> BlessResult:
    """Bottom-up Leverage Score Sampling without replacement (paper Alg. 2).

    Per level h: a Bernoulli(beta_h) pre-filter plays the role of U_h
    (beta_h = min(q2 kappa^2 / (lam_h n), 1)); each survivor j is kept with
    probability p_{h,j}/beta_h where p_{h,j} = min(q2 * l~_{J_{h-1}}(x_j,
    lam_{h-1}), 1); kept columns get weight A_jj = p_{h,j}.

    The Bernoulli gates of every beta_h < 1 level are drawn in one jitted
    phase up front (one host fetch of the survivor counts; beta_h = 1 levels
    need no gate — everyone survives). Each level then runs exactly one
    fused dispatch (previous level's compaction + this level's score/accept)
    and blocks on exactly one 2-float statistics fetch.
    """
    n = x.shape[0]
    kap2 = float(kernel.kappa_sq)
    lam0 = kap2 / min(t, 1.0) if lam0 is None else lam0
    lams = lam_ladder(lam, lam0, q)
    backend = resolve_backend(backend, n=n)
    level_fn = _blessr_level if backend.jit_safe else _blessr_level_impl

    keys = jax.random.split(key, len(lams) + 1)
    betas_host = [min(q2 * kap2 / (lam_h * n), 1.0) for lam_h in lams]
    gated = [h for h, b in enumerate(betas_host) if b < 1.0]
    r_host = {h: n for h in range(len(lams))}
    if gated:
        orders, r_vec = _blessr_gates(
            keys[-1], jnp.asarray([betas_host[h] for h in gated], jnp.float32),
            n=n)
        r_host.update(zip(gated, np.asarray(r_vec).tolist()))
    row_of = {h: i for i, h in enumerate(gated)}
    no_order = jnp.zeros((0,), jnp.int32)  # beta = 1 levels take no gate order

    # prev = the not-yet-compacted acceptances of the last productive level;
    # the dispatch of level h packs them into J_{h-1} on-device, so
    # ``pending`` carries that level's metadata until its centers exist.
    prev = (jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.float32),
            jnp.zeros((1,), bool), 0)
    pending: dict | None = None
    dbuf = 1
    levels: list[BlessLevel] = []
    lam_prev = lam0
    for h, lam_h in enumerate(lams):
        r_h = r_host[h]
        if r_h == 0:
            lam_prev = lam_h
            continue
        identity = betas_host[h] >= 1.0
        rbuf = n if identity else min(_bucket(r_h), n)
        order_h = no_order if identity else orders[row_of[h]]
        with spans.span("bless.level", h=h, r_h=r_h) as level:
            # -- lines 9-12: J_{h-1} pack + scores at lam_{h-1} + acceptances
            packed, u_idx, p, acc, stats = level_fn(
                keys[h], x, kernel, order_h, *prev, lam_prev, betas_host[h],
                q2, r_h, backend=backend, rbuf=rbuf, dbuf=dbuf, m_cap=m_cap,
                identity_order=identity)
            if pending is not None:
                levels.append(BlessLevel(centers=packed, **pending))
                pending = None
            with spans.span("bless.sync", h=h):
                stats = np.asarray(stats)  # the level's one blocking sync
            m_h = int(stats[0])
            level.set_metadata(m_h=m_h)
        d_h = float(n / r_h * stats[1])
        lam_prev = lam_h
        if m_h == 0:
            continue
        m_kept = m_h if m_cap is None else min(m_h, m_cap)
        prev = (u_idx, p, acc, m_h)
        pending = dict(lam=lam_h, d_h=d_h, m_h=m_kept, r_h=r_h)
        dbuf = _bucket32(m_kept)
    if pending is not None:  # final level: nothing left to fuse it into
        centers = _blessr_compact(*prev[:3], jnp.asarray(prev[3], jnp.int32),
                                  mbuf=_bucket(pending["m_h"]), m_cap=m_cap)
        levels.append(BlessLevel(centers=centers, **pending))
    return BlessResult(levels=levels, lam_path=lams)
