"""Kernel-operator backends — the single seam for every hot contraction.

Four contractions dominate the paper's cost story (BLESS Alg. 1/2 levels,
the Eq. 3 scorer, FALKON's CG in Sec. 3, and serving-side predict):

  * ``gram_block``      — a K(X, Z) block (every ladder level, K_MM)
  * ``masked_quadform`` — Eq. 3's inner term  K_Ji^T (K_JJ + lam n A)^{-1} K_Ji
  * ``knm_quadratic`` / ``knm_t`` — the CG matvec K_nM^T K_nM v and its
    right-hand side K_nM^T y, never materializing K_nM
  * ``knm_matvec``      — K(X, Z) v, the predict / Nystrom-KRR forward pass
    (FalkonModel.predict, nystrom_krr, batched serving)

Each ``Backend`` serves all of them:

  * ``JnpBackend``     — pure-jnp streaming reference. jit-safe (its methods
    can be traced with the kernel bandwidth as a tracer), so it is the one
    used inside the jitted Eq. 3 scorer. Default on CPU.
  * ``PallasBackend``  — the fused Pallas TPU kernels under
    ``repro.kernels.{gram,quadform,falkon_matvec}``; interpret-mode off-TPU
    so CI exercises the exact production code path.
  * ``ShardedBackend`` — shard_map data-parallel over the local device mesh
    (``repro.core.distributed``); X rows sharded, (M, M) state replicated.

Backends are small frozen dataclasses: hashable (usable as static jit
arguments) and comparable by configuration, so the jit cache keys correctly.
Selection is by instance, by registry name ("jnp" | "pallas" | "sharded"),
or ``None`` for the ``default_backend()`` platform + problem-size heuristic
(overridable without code edits via the ``REPRO_BACKEND`` env var).
"""
from __future__ import annotations

import dataclasses
import functools
import os
import warnings
from typing import Callable, ClassVar

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..kernels.falkon_matvec import ops as falkon_ops
from ..kernels.gram import ops as gram_ops
from ..kernels.quadform import ops as quadform_ops
from ..kernels.rls_score import ops as rls_ops
from . import health
from .gram import (Kernel, blocked_cross, get_family, kernel_family_names,
                   register_backend)
from .leverage import _chol_with_jitter

Array = jax.Array
KnmQuadraticOp = Callable[[Array], Array]

#: Precision of every float32 product against a Gram block: a TPU runs a
#: DEFAULT-precision float32 dot as one bf16 pass.
_HIGHEST = jax.lax.Precision.HIGHEST

# ---------------------------------------------------------------------------
# Block-size tables.
#
# jnp streamer: rows per lax.scan block — sized so a (block, m) Gram slab
# stays comfortably in cache (CPU) / HBM working set (accelerators).
# Pallas: (bn, bm) VMEM tiles by problem size; small problems take small
# tiles so interpret-mode CI isn't dominated by padding, large ones take the
# MXU-saturating 512x256 shape (working set ~< 4 MB at d <= 2048).
# ---------------------------------------------------------------------------

STREAM_BLOCK = {"cpu": 2048, "gpu": 8192, "tpu": 8192}

PALLAS_GRAM_TILES = ((1024, (128, 128)), (8192, (256, 256)), (None, (512, 256)))
PALLAS_QUADFORM_TILES = ((1024, (128, 128)), (8192, (256, 256)), (None, (256, 256)))
PALLAS_MATVEC_BN = ((4096, 256), (None, 512))

# Backend-selection thresholds. The baked-in defaults were measured with
# ``tools/autotune_backend.py`` (which sweeps each pair of backends over a
# row grid and reports the timing crossover) on the reference CPU container;
# rerun it on real hardware and either edit these or set the printed
# ``REPRO_*_MIN_ROWS`` env vars — the env always wins (read per call, so
# tests and deploys can flip them without reimports). docs/backends.md has
# the calibration recipe.
_PALLAS_MIN_ROWS = 256  # interpret-mode never crosses over off-TPU; on-TPU floor
_SHARD_MIN_ROWS = 1 << 15  # below this collective latency beats the split
_STREAM_MIN_ROWS = 1 << 21  # hosts whose device reports no memory size


def _threshold(env: str, default: int) -> int:
    """An autotuned threshold with its env override (empty/unset -> default)."""
    raw = os.environ.get(env, "").strip()
    return int(raw) if raw else default


def _stream_min_rows() -> int:
    """Rows past which X stops fitting the device and is streamed instead.

    Where the device reports its memory (TPU, GPU) X may take a quarter of
    it at 512 B a row — fp32 with d padded to the 128 lanes the Pallas
    tiles use — leaving the rest to the padded copies and (block, M) Gram
    tiles; a 16 GB v5e (``bytes_limit`` 16,909,336,064) keeps 8,256,511
    rows in core. Devices that report nothing (the CPU backend) keep the
    fixed ``_STREAM_MIN_ROWS``.
    """
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    return int(limit) // (4 * 512) if limit else _STREAM_MIN_ROWS


def _pick(table, size: int):
    for threshold, value in table:
        if threshold is None or size <= threshold:
            return value
    raise AssertionError("table has no catch-all row")


def _kernel_params(kernel: Kernel) -> tuple[str, float]:
    """(kind, sigma) for the Pallas wrappers; sigma must be concrete here
    because the kernels bake the family's inv_scale into the compiled
    epilogue. The family itself is resolved from the ``repro.families``
    registry — an unknown name raises with every registered family listed,
    not a hard-coded subset."""
    get_family(kernel.name)  # enumerates the registry on typos
    try:
        return kernel.name, float(kernel.sigma)
    except (TypeError, jax.errors.ConcretizationTypeError) as e:
        raise ValueError(
            f"PallasBackend needs a concrete kernel bandwidth for the "
            f"{kernel.name!r} family (registered: {kernel_family_names()}); "
            "call it outside jit (the core entry points already do)"
        ) from e


# ---------------------------------------------------------------------------
# Protocol
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Backend:
    """Abstract kernel-operator backend (see module docstring)."""

    name: ClassVar[str] = "abstract"
    #: True if every method can be traced under jit with traced operands
    #: (including the kernel bandwidth). Non-jit-safe backends are driven by
    #: the host-level code paths instead.
    jit_safe: ClassVar[bool] = False
    #: True if each call of its K_nM operators is a pass dispatched from the
    #: host: a CG loop then calls the operator once per iteration instead of
    #: tracing it into one compiled loop (``falkon.cg``).
    host_loop: ClassVar[bool] = False

    def gram_block(self, kernel: Kernel, x: Array, z: Array) -> Array:
        """K(X, Z) of shape (n, m)."""
        raise NotImplementedError

    def masked_quadform(self, kernel: Kernel, x_cand: Array, z: Array,
                        mask: Array, reg: Array) -> Array:
        """q_i = K_Ji^T (K_JJ ∘ mask + diag(reg))^{-1} K_Ji for each candidate.

        ``z`` (Mbuf, d) are padded center coordinates, ``mask`` (Mbuf,) their
        validity, ``reg`` (Mbuf,) the regularized diagonal (lam n A on valid
        slots, 1 on padding). Returns (Rbuf,) in fp32 precision.
        """
        raise NotImplementedError

    def rls_scores(self, kernel: Kernel, x_cand: Array, z: Array,
                   z_mask: Array, reg: Array, lamn: Array) -> Array:
        """Eq. 3 scores  (K_ii - K_Ji^T (K_JJ + lam n A)^{-1} K_Ji) / (lam n)
        for each candidate row — the BLESS ladder's per-level contraction.

        ``z`` (Mbuf, d) padded centers, ``z_mask`` (Mbuf,) validity, ``reg``
        (Mbuf,) the regularized diagonal (lam n A on valid slots, 1 on
        padding), ``lamn`` the scalar lam * n. Returns (Rbuf,) fp32 scores
        (unclipped, unmasked — the ladder applies its own floor/candidate
        mask). The default composes ``masked_quadform`` with the family
        diagonal; backends override it to fuse the whole chain (Pallas keeps
        the (Rbuf, Mbuf) Gram tile in VMEM for its entire lifetime).
        """
        kdiag = kernel.diag(x_cand)
        quad = self.masked_quadform(kernel, x_cand, z, z_mask, reg)
        return (kdiag - quad) / lamn

    def knm_quadratic(self, kernel: Kernel, x: Array, z: Array, *,
                      mask: Array | None = None) -> KnmQuadraticOp:
        """Build the v -> K_nM^T (K_nM v) operator closure for CG.

        The returned op accepts a single fp32 vector (M,) or an (M, k)
        panel of CG iterates — the multi-RHS block-CG form. Panels reuse
        each streamed Gram block for every column, so extra right-hand
        sides cost GEMM flops, not extra kernel evaluations.

        ``mask`` — optional per-column row-exclusion weights, (n,) for a
        vector op or an (n, k) panel giving column j its own row subset
        (exact k-fold CV): column j computes ``K_nM^T diag(mask[:, j])
        K_nM v_j``, one extra elementwise multiply on the streamed
        (block, k) intermediate. ``mask=None`` is the original program
        bit-for-bit on every backend.
        """
        raise NotImplementedError

    def knm_t(self, kernel: Kernel, x: Array, z: Array, y: Array, *,
              mask: Array | None = None) -> Array:
        """K_nM^T y — the CG right-hand side(s).

        ``y`` is fp32 (n,) -> (M,), or an (n, k) target panel -> (M, k).
        ``mask`` (optional, shaped like ``y``) computes ``K_nM^T (mask *
        y)``; since it enters linearly, backends fold it into the targets
        up front (one elementwise multiply, no new streamed program).
        """
        raise NotImplementedError

    def knm_operators(self, kernel: Kernel, x: Array, z: Array,
                      y: Array, *,
                      mask: Array | None = None) -> tuple[KnmQuadraticOp, Array]:
        """Return (quadratic op, K_nM^T y) together.

        Lets backends that stage data (sharding, device placement) pay the
        staging cost once; ``y`` may be (n,) or an (n, k) panel, ``mask``
        an optional per-column row-exclusion panel applied to both halves
        (see ``knm_quadratic`` / ``knm_t``).
        """
        return (self.knm_quadratic(kernel, x, z, mask=mask),
                self.knm_t(kernel, x, z, y, mask=mask))

    def knm_matvec(self, kernel: Kernel, x: Array, z: Array, v: Array) -> Array:
        """K(X, Z) v — the predict / KRR forward contraction.

        ``v`` is fp32 (M,) -> (n,), or an (M, k) coefficient panel ->
        (n, k) (multi-output predict: one kernel evaluation for all k).
        """
        raise NotImplementedError


# ---------------------------------------------------------------------------
# jnp reference backend
# ---------------------------------------------------------------------------


def _quadform_from_chol(chol: Array, g: Array) -> Array:
    """rowsum(solve(L, g^T)^2) — q_i = g_i^T (L L^T)^{-1} g_i.

    Two algebraically identical strategies, picked by static shape: the
    triangular solve streams g through trsm (O(R M^2) at trsm throughput),
    while ``L^{-1}`` + GEMM pays one (M, M) triangular inversion to move the
    O(R M^2) bulk onto the GEMM path (~3-4x the trsm rate on the target
    container). Measured crossover: GEMM wins once R >= 3 M (inversion
    amortized) and M <= 768 (inversion itself still cheap); trsm elsewhere.
    """
    r, m = g.shape[0], chol.shape[0]
    if r >= 3 * m and m <= 768:
        inv_l = jax.scipy.linalg.solve_triangular(
            chol, jnp.eye(m, dtype=chol.dtype), lower=True)
        v = g @ inv_l.T  # (R, M) GEMM: rows v_i = L^{-1} g_i
        return jnp.sum(v * v, axis=1)
    v = jax.scipy.linalg.solve_triangular(chol, g.T, lower=True)
    return jnp.sum(v * v, axis=0)


@dataclasses.dataclass(frozen=True)
class JnpBackend(Backend):
    """Pure-jnp row-streaming backend (the numerical reference)."""

    name: ClassVar[str] = "jnp"
    jit_safe: ClassVar[bool] = True
    block: int | None = None  # stream rows per block; None -> platform table

    def _block(self) -> int:
        return self.block or STREAM_BLOCK.get(jax.default_backend(), 2048)

    def gram_block(self, kernel: Kernel, x: Array, z: Array) -> Array:
        """K(X, Z) (n, m) fp32, streamed in row blocks of ``_block()``."""
        return blocked_cross(kernel, x, z, block=self._block())

    def masked_quadform(self, kernel: Kernel, x_cand: Array, z: Array,
                        mask: Array, reg: Array) -> Array:
        """Eq. 3 quadratic form on the padded K_JJ; the solve strategy is
        picked from the static (R, M) shape by ``_quadform_from_chol``."""
        m = mask.astype(z.dtype)
        kjj = kernel.cross_unfused(z, z) * (m[:, None] * m[None, :]) + jnp.diag(reg)
        g = kernel.cross_unfused(x_cand, z) * m[None, :]
        chol = _chol_with_jitter(kjj)
        return _quadform_from_chol(chol, g)

    def knm_quadratic(self, kernel: Kernel, x: Array, z: Array, *,
                      mask: Array | None = None) -> KnmQuadraticOp:
        """CG quadratic op over the jnp row streamer ((M,) or (M, k));
        optional per-column row ``mask`` (exact-CV panels)."""
        from .falkon import local_knm_quadratic

        return local_knm_quadratic(kernel, x, z, block=self._block(), mask=mask)

    def knm_t(self, kernel: Kernel, x: Array, z: Array, y: Array, *,
              mask: Array | None = None) -> Array:
        """K_nM^T y, streamed; (n,) -> (M,) or panel (n, k) -> (M, k).
        ``mask`` folds into the targets (K_nM^T (mask * y))."""
        from .falkon import local_knm_t

        return local_knm_t(kernel, x, z, y, block=self._block(), mask=mask)

    def knm_matvec(self, kernel: Kernel, x: Array, z: Array, v: Array) -> Array:
        """K(X, Z) v, jitted streaming (serving hot path): one compiled
        call per (shapes, block); ``v`` (M,) -> (n,), (M, k) -> (n, k)."""
        return _jnp_knm_matvec(kernel, x, z, v, block=self._block())


@functools.partial(jax.jit, static_argnames=("block",))
def _jnp_knm_matvec(kernel: Kernel, x: Array, z: Array, v: Array, *,
                    block: int) -> Array:
    """K(X, Z) v, streaming X in row blocks — the jnp predict contraction.

    ``v`` (M,) or (M, k): each streamed Gram block is contracted against
    every column before being discarded.
    """
    n = x.shape[0]
    if n <= block:
        return jnp.matmul(kernel.cross(x, z), v, precision=_HIGHEST)
    pad = (-n) % block
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    out = jax.lax.map(lambda xb: jnp.matmul(kernel.cross(xb, z), v, precision=_HIGHEST),
                      xp.reshape(-1, block, x.shape[1]))
    return out.reshape((-1,) + v.shape[1:])[:n]


# ---------------------------------------------------------------------------
# Pallas fused-kernel backend
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PallasBackend(Backend):
    """Fused Pallas TPU kernels; interpret-mode anywhere without a TPU.

    ``bf16=True`` is the opt-in mixed-precision mode: every kernel's dominant
    MXU product loads its operands as bf16 and accumulates fp32 (the norms,
    exp epilogues, and second-stage contractions stay fp32). Roughly doubles
    MXU throughput and halves the tile working set on TPU; expect ~1e-2
    relative error on kernel values for unit-scale data (tolerances measured
    in tests/test_backend.py, documented in DESIGN.md §2).
    """

    name: ClassVar[str] = "pallas"
    interpret: bool | None = None  # None -> auto (off-TPU interprets)
    bn: int | None = None  # tile overrides; None -> size tables above
    bm: int | None = None
    bf16: bool = False  # mixed-precision MXU tiles (fp32 accumulation)

    def _gram_tiles(self, n: int, m: int) -> tuple[int, int]:
        bn, bm = _pick(PALLAS_GRAM_TILES, max(n, m))
        return self.bn or bn, self.bm or bm

    def gram_block(self, kernel: Kernel, x: Array, z: Array) -> Array:
        """K(X, Z) (n, m) fp32 from the fused Pallas gram kernel."""
        kind, sigma = _kernel_params(kernel)
        bn, bm = self._gram_tiles(x.shape[0], z.shape[0])
        return gram_ops.gram(x, z, sigma, kind=kind, bn=bn, bm=bm,
                             interpret=self.interpret, bf16=self.bf16)

    def masked_quadform(self, kernel: Kernel, x_cand: Array, z: Array,
                        mask: Array, reg: Array) -> Array:
        """Eq. 3 quadratic form: Pallas gram tiles + the fused quadform
        kernel consuming a dense (M, M) inverse (M ~ d_eff, cheap)."""
        m = mask.astype(x_cand.dtype)
        kjj = self.gram_block(kernel, z, z) * (m[:, None] * m[None, :]) + jnp.diag(reg)
        chol = _chol_with_jitter(kjj)
        # Explicit (M, M) inverse: the Pallas quadform consumes a dense W and
        # fuses rowsum((G W) * G) in VMEM; M ~ d_eff so the inverse is cheap.
        w = jax.scipy.linalg.cho_solve((chol, True), jnp.eye(kjj.shape[0], dtype=kjj.dtype))
        g = self.gram_block(kernel, x_cand, z) * m[None, :]
        bn, bm = self.bn or 0, self.bm or 0
        tbn, tbm = _pick(PALLAS_QUADFORM_TILES, max(g.shape))
        return quadform_ops.quadform(g, w, bn=bn or tbn, bm=bm or tbm,
                                     interpret=self.interpret, bf16=self.bf16)

    def rls_scores(self, kernel: Kernel, x_cand: Array, z: Array,
                   z_mask: Array, reg: Array, lamn: Array) -> Array:
        """Eq. 3 scores through the fused ``rls_score`` kernel: gram tile ->
        quadform -> score epilogue in one dispatch, the (Mbuf, Mbuf) inverse
        and centers VMEM-resident across the candidate grid. Falls back to
        the composed gram + quadform kernels past the VMEM budget."""
        if z.shape[0] > rls_ops.MAX_FUSED_M:
            return super().rls_scores(kernel, x_cand, z, z_mask, reg, lamn)
        kind, sigma = _kernel_params(kernel)
        m = z_mask.astype(x_cand.dtype)
        kjj = self.gram_block(kernel, z, z) * (m[:, None] * m[None, :]) + jnp.diag(reg)
        chol = _chol_with_jitter(kjj)
        w = jax.scipy.linalg.cho_solve(
            (chol, True), jnp.eye(kjj.shape[0], dtype=kjj.dtype))
        bn = self.bn or _pick(PALLAS_QUADFORM_TILES,
                              max(x_cand.shape[0], z.shape[0]))[0]
        return rls_ops.rls_score(x_cand, z, w, m, lamn, sigma, kind=kind,
                                 bn=bn, interpret=self.interpret, bf16=self.bf16)

    def _matvec_bn(self, n: int) -> int:
        return self.bn or _pick(PALLAS_MATVEC_BN, n)

    def knm_quadratic(self, kernel: Kernel, x: Array, z: Array, *,
                      mask: Array | None = None) -> KnmQuadraticOp:
        """CG quadratic op over the fused Pallas sweep; accepts (M,) or an
        (M, k) panel (one Gram tile per step serves every column). A
        ``mask`` panel rides the same grid as one extra VMEM multiply on
        the (bn, k) intermediate (the masked kernel variant)."""
        kind, sigma = _kernel_params(kernel)
        return falkon_ops.make_knm_quadratic_op(
            x, z, sigma, kind=kind, bn=self._matvec_bn(x.shape[0]),
            interpret=self.interpret, bf16=self.bf16, mask=mask)

    def knm_t(self, kernel: Kernel, x: Array, z: Array, y: Array, *,
              mask: Array | None = None) -> Array:
        """K_nM^T y fused in VMEM; (n,) -> (M,) or panel (n, k) -> (M, k).
        ``mask`` folds into the targets (K_nM^T (mask * y))."""
        kind, sigma = _kernel_params(kernel)
        return falkon_ops.knm_t(x, z, y, sigma, kind=kind,
                                bn=self._matvec_bn(x.shape[0]),
                                interpret=self.interpret, bf16=self.bf16,
                                mask=mask)

    def knm_operators(self, kernel: Kernel, x: Array, z: Array,
                      y: Array, *,
                      mask: Array | None = None) -> tuple[KnmQuadraticOp, Array]:
        """Both halves over one padding or packing of X and Z."""
        kind, sigma = _kernel_params(kernel)
        return falkon_ops.knm_operators(x, z, y, sigma, kind=kind,
                                        bn=self._matvec_bn(x.shape[0]),
                                        interpret=self.interpret, bf16=self.bf16,
                                        mask=mask)

    def knm_matvec(self, kernel: Kernel, x: Array, z: Array, v: Array) -> Array:
        """K(X, Z) v fused in VMEM; (M,) -> (n,) or (M, k) -> (n, k)."""
        kind, sigma = _kernel_params(kernel)
        return falkon_ops.knm_matvec(x, z, v, sigma, kind=kind,
                                     bn=self._matvec_bn(x.shape[0]),
                                     interpret=self.interpret, bf16=self.bf16)


# ---------------------------------------------------------------------------
# shard_map data-parallel backend
# ---------------------------------------------------------------------------


def _sharded_gram_local(kernel: Kernel, xl: Array, z: Array) -> Array:
    return kernel.cross(xl, z)


def _sharded_quadform_local(kernel: Kernel, xc: Array, z: Array, m: Array,
                            chol: Array) -> Array:
    g = kernel.cross(xc, z) * m[None, :]
    v = jax.scipy.linalg.solve_triangular(chol, g.T, lower=True)
    return jnp.sum(v * v, axis=0)


@functools.lru_cache(maxsize=None)
def _sharded_gram_fn(mesh: Mesh, axis: str):
    """Jitted shard_map'd Gram, cached per (mesh, axis) so repeated calls at
    the same shapes reuse one compile (Mesh is hashable)."""
    return jax.jit(shard_map(
        _sharded_gram_local, mesh=mesh,
        in_specs=(P(), P(axis, None), P()), out_specs=P(axis, None)))


@functools.lru_cache(maxsize=None)
def _sharded_quadform_fn(mesh: Mesh, axis: str):
    return jax.jit(shard_map(
        _sharded_quadform_local, mesh=mesh,
        in_specs=(P(), P(axis, None), P(), P(), P()), out_specs=P(axis)))


@dataclasses.dataclass(frozen=True)
class ShardedBackend(Backend):
    """Data-parallel over the local device mesh: X rows sharded over ``axis``,
    (M, M) factors replicated, partials psum-ed (DESIGN.md §2)."""

    name: ClassVar[str] = "sharded"
    axis: str = "data"
    mesh: Mesh | None = None  # None -> 1-D mesh over all local devices

    def _mesh(self) -> Mesh:
        from .distributed import data_mesh

        return self.mesh if self.mesh is not None else data_mesh(self.axis)

    def gram_block(self, kernel: Kernel, x: Array, z: Array) -> Array:
        """K(X, Z) with X rows sharded over the mesh, Z replicated."""
        from .distributed import shard_rows

        mesh = self._mesh()
        xs = shard_rows(mesh, x, self.axis)
        return _sharded_gram_fn(mesh, self.axis)(kernel, xs, z)[: x.shape[0]]

    def masked_quadform(self, kernel: Kernel, x_cand: Array, z: Array,
                        mask: Array, reg: Array) -> Array:
        """Eq. 3 quadratic form: candidates row-sharded, the (Mbuf, Mbuf)
        Cholesky factor replicated (<= d_eff^2 by the paper's space bound)."""
        from .distributed import shard_rows

        mesh = self._mesh()
        m = mask.astype(x_cand.dtype)
        kjj = kernel.cross(z, z) * (m[:, None] * m[None, :]) + jnp.diag(reg)
        chol = _chol_with_jitter(kjj)  # replicated: (Mbuf, Mbuf) <= d_eff^2
        xs = shard_rows(mesh, x_cand, self.axis)
        quad = _sharded_quadform_fn(mesh, self.axis)(kernel, xs, z, m, chol)
        return quad[: x_cand.shape[0]]

    def knm_quadratic(self, kernel: Kernel, x: Array, z: Array, *,
                      mask: Array | None = None) -> KnmQuadraticOp:
        """CG quadratic op with X row-sharded and psum-ed (M,)/(M, k)
        partials — the collective schedule of a DP gradient all-reduce.
        A ``mask`` panel is row-sharded alongside X."""
        from .distributed import dist_knm_quadratic, shard_rows

        mesh = self._mesh()
        xs = shard_rows(mesh, x, self.axis)
        ms = None if mask is None else shard_rows(mesh, mask, self.axis)
        return dist_knm_quadratic(mesh, kernel, xs, z, x.shape[0], self.axis,
                                  mask=ms)

    def knm_t(self, kernel: Kernel, x: Array, z: Array, y: Array, *,
              mask: Array | None = None) -> Array:
        """K_nM^T y with X, y row-sharded; (n,) -> (M,), (n, k) -> (M, k).
        ``mask`` folds into the targets before sharding."""
        from .distributed import dist_knm_t, shard_rows

        if mask is not None:
            y = y * jnp.asarray(mask, y.dtype)
        mesh = self._mesh()
        return dist_knm_t(mesh, kernel, shard_rows(mesh, x, self.axis),
                          shard_rows(mesh, y, self.axis), z, x.shape[0], self.axis)

    def knm_operators(self, kernel: Kernel, x: Array, z: Array,
                      y: Array, *,
                      mask: Array | None = None) -> tuple[KnmQuadraticOp, Array]:
        """(quadratic op, K_nM^T y), staging X/y on device exactly once."""
        from .distributed import dist_knm_quadratic, dist_knm_t, shard_rows

        mesh = self._mesh()
        xs = shard_rows(mesh, x, self.axis)  # device_put once, reuse for both
        ym = y if mask is None else y * jnp.asarray(mask, y.dtype)
        ys = shard_rows(mesh, ym, self.axis)
        ms = None if mask is None else shard_rows(mesh, mask, self.axis)
        n = x.shape[0]
        return (dist_knm_quadratic(mesh, kernel, xs, z, n, self.axis, mask=ms),
                dist_knm_t(mesh, kernel, xs, ys, z, n, self.axis))

    def knm_matvec(self, kernel: Kernel, x: Array, z: Array, v: Array) -> Array:
        """K(X, Z) v, row-parallel (no collective); (M,) or (M, k) ``v``."""
        from .distributed import dist_knm_matvec, shard_rows

        mesh = self._mesh()
        return dist_knm_matvec(mesh, kernel, shard_rows(mesh, x, self.axis),
                               z, v, x.shape[0], self.axis)


# ---------------------------------------------------------------------------
# Guarded fallback backend (DESIGN.md §9)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GuardedBackend(Backend):
    """Primary backend with automatic per-dispatch fallback to a reference.

    Wraps a ``primary`` (default: the fused Pallas kernels) and a
    numerically equivalent ``fallback`` (default: the jnp streamer). Every
    seam method tries the primary; a raised dispatch/compile failure is
    recorded in the health event log (``kind="backend_fallback"``), warned
    once per process per method, and the call is re-served by the fallback
    — one bad kernel dispatch degrades that call's *speed*, never the
    process. Registered as ``"guarded"`` so ``REPRO_BACKEND=guarded`` (or
    ``backend="guarded"``) hardens any entry point without code changes.

    Not jit-safe: the try/except needs the host, so fits through it take
    the host-driven CG path (the fallback leg would anyway — mixing traced
    primary dispatch with host recovery inside one jit cannot work).
    """

    name: ClassVar[str] = "guarded"
    jit_safe: ClassVar[bool] = False
    primary: Backend = dataclasses.field(default_factory=lambda: PallasBackend())
    fallback: Backend = dataclasses.field(default_factory=lambda: JnpBackend())

    def _guard(self, method: str, *args):
        try:
            return getattr(self.primary, method)(*args)
        except Exception as e:  # noqa: BLE001 — any dispatch failure falls back
            health.record_event("backend_fallback", method=method,
                                primary=self.primary.name,
                                fallback=self.fallback.name, error=repr(e))
            warnings.warn(
                f"{self.primary.name}.{method} dispatch failed ({e!r}); "
                f"falling back to {self.fallback.name}", RuntimeWarning,
                stacklevel=3)
            return getattr(self.fallback, method)(*args)

    def gram_block(self, kernel: Kernel, x: Array, z: Array) -> Array:
        """K(X, Z) via the primary, re-served by the fallback on failure."""
        return self._guard("gram_block", kernel, x, z)

    def masked_quadform(self, kernel: Kernel, x_cand: Array, z: Array,
                        mask: Array, reg: Array) -> Array:
        """Eq. 3 quadratic form with per-dispatch fallback."""
        return self._guard("masked_quadform", kernel, x_cand, z, mask, reg)

    def rls_scores(self, kernel: Kernel, x_cand: Array, z: Array,
                   z_mask: Array, reg: Array, lamn: Array) -> Array:
        """Eq. 3 scores with per-dispatch fallback."""
        return self._guard("rls_scores", kernel, x_cand, z, z_mask, reg, lamn)

    def knm_quadratic(self, kernel: Kernel, x: Array, z: Array, *,
                      mask: Array | None = None) -> KnmQuadraticOp:
        """CG quadratic op; both construction and every call are guarded."""
        try:
            op = self.primary.knm_quadratic(kernel, x, z, mask=mask)
        except Exception as e:  # noqa: BLE001
            health.record_event("backend_fallback", method="knm_quadratic",
                                primary=self.primary.name,
                                fallback=self.fallback.name, error=repr(e))
            return self.fallback.knm_quadratic(kernel, x, z, mask=mask)
        fb: list[KnmQuadraticOp | None] = [None]

        def guarded_op(v: Array) -> Array:
            try:
                return op(v)
            except Exception as e:  # noqa: BLE001
                health.record_event("backend_fallback", method="knm_quadratic",
                                    primary=self.primary.name,
                                    fallback=self.fallback.name, error=repr(e))
                if fb[0] is None:
                    fb[0] = self.fallback.knm_quadratic(kernel, x, z, mask=mask)
                return fb[0](v)

        return guarded_op

    def knm_t(self, kernel: Kernel, x: Array, z: Array, y: Array, *,
              mask: Array | None = None) -> Array:
        """K_nM^T y with per-dispatch fallback."""
        if mask is not None:
            y = y * jnp.asarray(mask, y.dtype)
        return self._guard("knm_t", kernel, x, z, y)

    def knm_matvec(self, kernel: Kernel, x: Array, z: Array, v: Array) -> Array:
        """K(X, Z) v (the serving contraction) with per-dispatch fallback."""
        return self._guard("knm_matvec", kernel, x, z, v)


# ---------------------------------------------------------------------------
# Selection
# ---------------------------------------------------------------------------


def _stream_backend() -> Backend:
    """Lazy ``StreamBackend`` factory — ``repro.stream`` imports this module,
    so the import has to happen at resolve time, not at module import."""
    from ..stream import StreamBackend

    return StreamBackend()


def default_backend(n: int | None = None) -> Backend:
    """Platform + problem-size heuristic.

    TPU -> fused Pallas kernels (compiled); multiple devices with enough rows
    to amortize the collectives -> shard_map; otherwise the jnp streamer —
    and past ``REPRO_STREAM_MIN_ROWS`` the pick is wrapped in the out-of-core
    ``StreamBackend`` (the chosen backend keeps building each tile, but X is
    streamed chunk-by-chunk instead of staged whole). ``n`` is the dataset
    row count when the caller knows it.

    The ``REPRO_BACKEND`` env var overrides the heuristic entirely — set it
    to a registry name ("jnp" | "pallas" | "sharded" | "stream" | ...) or a
    composite "stream:<inner>" spec to pin a backend on hardware runs
    without code edits ("auto"/"" fall through to the heuristic). The
    thresholds above are autotuned defaults (``tools/autotune_backend.py``);
    ``REPRO_PALLAS_MIN_ROWS`` / ``REPRO_SHARD_MIN_ROWS`` /
    ``REPRO_STREAM_MIN_ROWS`` override them per deployment.
    """
    env = os.environ.get("REPRO_BACKEND", "").strip().lower()
    if env and env != "auto":
        if ":" in env:
            from .gram import resolve_backend

            try:
                return resolve_backend(env)
            except ValueError as e:
                raise ValueError(f"REPRO_BACKEND={env!r}: {e}") from None
        try:
            return _ENV_BACKENDS[env]()
        except KeyError:
            raise ValueError(
                f"REPRO_BACKEND={env!r} is not a registered backend; "
                f"expected one of {sorted(_ENV_BACKENDS)} or 'auto'"
            ) from None
    platform = jax.default_backend()
    picked: Backend | None = None
    if platform == "tpu" and (n is None or n >= _threshold(
            "REPRO_PALLAS_MIN_ROWS", _PALLAS_MIN_ROWS)):
        picked = PallasBackend()
    elif (len(jax.devices()) > 1 and n is not None
          and n >= _threshold("REPRO_SHARD_MIN_ROWS", _SHARD_MIN_ROWS)):
        picked = ShardedBackend()
    else:
        picked = JnpBackend()
    if n is not None and n >= _threshold("REPRO_STREAM_MIN_ROWS",
                                         _stream_min_rows()):
        from ..stream import StreamBackend

        return StreamBackend(inner=picked)
    return picked


_ENV_BACKENDS: dict[str, Callable[[], Backend]] = {
    "jnp": JnpBackend, "pallas": PallasBackend, "sharded": ShardedBackend,
    "guarded": GuardedBackend, "stream": _stream_backend,
}

register_backend("jnp", JnpBackend)
register_backend("pallas", PallasBackend)
register_backend("sharded", ShardedBackend)
register_backend("guarded", GuardedBackend)
register_backend("stream", _stream_backend)
