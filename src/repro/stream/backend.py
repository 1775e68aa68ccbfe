"""``StreamBackend`` — the full ``Backend`` protocol over chunked data.

Every K_nM-shaped contraction is a *pass*: a loop over uniform ``chunk``-row
chunks of X plus one tail chunk, each chunk contracted on the spot through
the ``inner`` backend — reduced into the (M,)/(M, k) accumulator
(``knm_quadratic`` / ``knm_t``), the (R,) score vector (``masked_quadform``
/ ``rls_scores``), or the (n,)/(n, k) prediction (``knm_matvec``) — so no
(n, M) array ever exists, the same tiling argument as memory-efficient
attention. ``gram_block`` is the one protocol method whose *output* is
(n, m); it carries an explicit element-count guard and raises past it
rather than silently materializing.

The K_nM operators contract each chunk through the inner backend's own
fused operator (``inner.knm_quadratic`` / ``knm_t`` / ``knm_matvec``): with
``PallasBackend`` that is one fused kernel call per chunk (the VPU kernels
for one live column) and no (chunk, M) Gram tile in HBM. The scorers build
their (chunk, Mbuf) tile with ``inner.gram_block`` and multiply it by the
inverse of K_JJ's Cholesky factor, hoisted out of the loop (the inner's
fused scorer would refactor it per chunk); every dot left in a chunk step
is HIGHEST precision, as on the in-core path (a DEFAULT float32 dot runs as
one bf16 pass on a TPU).

Two drivers run a pass, chosen by where X lives:

  * device-resident X (a ``jax.Array``): one compiled program per (op,
    shapes, inner, chunk) — a ``lax.fori_loop`` over the uniform chunks,
    each a ``dynamic_slice`` of X (no copy of X), then the tail. The chunk
    step is traced twice (loop body, tail), never once per chunk, so a CG
    loop that calls the pass per iteration does not unroll it;
  * host sources (``ChunkStore`` / numpy): the double-buffered
    ``device_chunks`` loop, one jitted chunk step per chunk (uniform + tail:
    two compiled programs), the copy of chunk i+1 in flight while chunk i
    runs.

Each pass is the span ``repro.stream.pass`` (op, rows, chunk, m, k) and
waits for its result before the span closes, so the span holds the pass's
device work; ``stream.chunks`` counts the chunk steps a pass runs and
``stream.h2d_bytes`` the bytes host sources put on the device
(``runtime.spans``). ``host_loop`` is True: a CG loop calls the operator
once per iteration from the host instead of tracing it (``falkon.cg``).

Accumulation order is the chunk order (row order), fixed and deterministic:
repeated calls on the same data produce bit-identical results. The sum is
associated differently from the jnp streamer's lax.scan (2048-row blocks vs
``chunk``-row chunks), so cross-backend agreement is the documented 1e-4
scale-relative parity, not bit-equality.

``jit_safe`` is False — a pass on host data needs the host — so fits through
this backend take ``falkon_fit``'s host CG path and the BLESS ladder runs
its eager phases, both of which already accept array-likes like
``ChunkStore``. The kernel is a static argument of the compiled passes: its
bandwidth must be concrete, as ``PallasBackend`` requires anyway.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import ClassVar

import jax
import jax.numpy as jnp
import numpy as np
import scipy.linalg

from ..core import health
from ..core.backend import Backend, JnpBackend
from ..core.gram import Kernel
from ..core.leverage import _chol_with_jitter
from ..runtime import spans
from .store import _TRACKER, ChunkStore, default_chunk, device_chunks

Array = jax.Array

#: ``gram_block`` materialization guard: refuse outputs above this many fp32
#: elements (default 2^26 = 256 MB). Small-problem callers (K_MM, ladder
#: levels, parity tests) pass untouched; an accidental (n, M) materialization
#: at out-of-core n raises instead of silently defeating the subsystem.
MATERIALIZE_ELEMS = 1 << 26


# ---------------------------------------------------------------------------
# Per-chunk steps: (kernel, xb, ab, *args, inner) -> the chunk's contribution.
# ``ab`` is the chunk's rows of the aligned operand (targets, mask) or None.
# ---------------------------------------------------------------------------


def _quad_chunk(kernel, xb, ab, z, v, *, inner):
    return inner.knm_quadratic(kernel, xb, z, mask=ab)(v)


def _knmt_chunk(kernel, xb, ab, z, *, inner):
    return inner.knm_t(kernel, xb, z, ab)


def _matvec_chunk(kernel, xb, ab, z, v, *, inner):
    return inner.knm_matvec(kernel, xb, z, v)


def _quadform_chunk(kernel, xb, ab, z, maskf, inv_l, *, inner):
    """q_i = g_i^T (L L^T)^{-1} g_i = ||L^{-1} g_i||^2 for the chunk's rows,
    one GEMM against the hoisted L^{-1} (a per-chunk triangular solve of
    (Mbuf, chunk) needs ~20x its right-hand side in HBM temporaries on a
    TPU: 26.8 GB at Mbuf = 5120)."""
    g = inner.gram_block(kernel, xb, z) * maskf[None, :]
    v = jnp.matmul(g, inv_l.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.sum(v * v, axis=1)


def _rls_chunk(kernel, xb, ab, z, maskf, inv_l, lamn, *, inner):
    q = _quadform_chunk(kernel, xb, ab, z, maskf, inv_l, inner=inner)
    return (kernel.diag(xb) - q) / lamn


def _host_inv_chol(a: np.ndarray) -> np.ndarray:
    """L^{-1} for L L^T = ``a`` in host LAPACK (float64), climbing the same
    trace-scaled jitter ladder as ``health.chol_with_jitter_ladder``; the
    factor of the last rung if none succeeds (NaN, as on the device)."""
    a = np.asarray(a, np.float64)
    eps0 = max(1e-6 * float(np.mean(np.diagonal(a))), 1e-30)
    eye = np.eye(a.shape[0])
    for k in range(health.JITTER_LEVELS):
        try:
            chol = scipy.linalg.cholesky(a + eps0 * 10.0 ** k * eye, lower=True)
            break
        except np.linalg.LinAlgError:
            chol = np.full_like(a, np.nan)
    inv, _ = scipy.linalg.lapack.dtrtri(chol, lower=1)
    return inv.astype(np.float32)


@partial(jax.jit, static_argnames=("kernel", "inner"))
def _inv_chol(z, maskf, reg, *, kernel, inner):
    """L^{-1} for L L^T = K_JJ (masked) + diag(reg), once per scorer pass.
    On a TPU the factor and its inverse run in host LAPACK through a
    callback, as the preconditioner's eigh does: XLA's TPU Cholesky and
    triangular solve compile for their size, 120 s at Mbuf = 5632 for a
    v5e, and a ladder meets a new Mbuf at nearly every level."""
    kjj = inner.gram_block(kernel, z, z) * (maskf[:, None] * maskf[None, :]) + jnp.diag(reg)
    if jax.default_backend() == "tpu":
        return jax.pure_callback(_host_inv_chol, jax.ShapeDtypeStruct(kjj.shape, kjj.dtype), kjj)
    return jax.scipy.linalg.solve_triangular(
        _chol_with_jitter(kjj), jnp.eye(kjj.shape[0], dtype=kjj.dtype), lower=True)


def _fold(acc, out, start, rows: bool):
    """Sum ``out`` into ``acc``, or write it at row ``start`` of ``acc``."""
    if not rows:
        return acc + out
    return jax.lax.dynamic_update_slice_in_dim(acc, out, start, 0)


_STATIC = ("step", "kernel", "inner", "chunk", "rows")


@partial(jax.jit, static_argnames=_STATIC)
@spans.retrace("stream.device_pass")
def _device_pass(x, aux, args, acc, *, step, kernel, inner, chunk, rows):
    """One pass over device-resident ``x``: the uniform chunks in a
    ``fori_loop``, then the tail, folded into ``acc`` in row order."""
    nb, tail = divmod(x.shape[0], chunk)

    def one(a, start, size):
        xb = jax.lax.dynamic_slice_in_dim(x, start, size)
        ab = None if aux is None else jax.lax.dynamic_slice_in_dim(aux, start, size)
        return _fold(a, step(kernel, xb, ab, *args, inner=inner), start, rows)

    if nb:
        acc = jax.lax.fori_loop(0, nb, lambda i, a: one(a, i * chunk, chunk), acc)
    if tail:
        acc = one(acc, nb * chunk, tail)
    return acc


@partial(jax.jit, static_argnames=("step", "kernel", "inner", "rows"))
@spans.retrace("stream.chunk_step")
def _chunk_step(xb, ab, args, acc, *, step, kernel, inner, rows):
    """One chunk of a pass over a host source."""
    out = step(kernel, xb, ab, *args, inner=inner)
    return out if rows else acc + out


def _static_kernel(kernel: Kernel) -> Kernel:
    """The kernel as a hashable static argument (a Python-float bandwidth)."""
    return dataclasses.replace(kernel, sigma=float(kernel.sigma))


@dataclasses.dataclass(frozen=True)
class StreamBackend(Backend):
    """Out-of-core streaming backend (see module docstring).

    Attributes:
      inner: the backend that contracts each chunk; jnp by default,
        Pallas / shard_map via ``"stream:pallas"`` etc.
      chunk: rows per device chunk; None defers to the ``ChunkStore``'s own
        chunk size (or the platform default for device-resident inputs).
      materialize_elems: the ``gram_block`` output-size guard (elements).
    """

    name: ClassVar[str] = "stream"
    jit_safe: ClassVar[bool] = False
    host_loop: ClassVar[bool] = True
    inner: Backend = dataclasses.field(default_factory=JnpBackend)
    chunk: int | None = None
    materialize_elems: int = MATERIALIZE_ELEMS

    def with_inner(self, inner: Backend) -> "StreamBackend":
        """This wrapper with its per-chunk backend swapped — the composition
        hook ``resolve_backend`` uses for ``"stream:<inner>"`` specs."""
        return dataclasses.replace(self, inner=inner)

    def _chunk_rows(self, x) -> int:
        if self.chunk is not None:
            return max(1, int(self.chunk))
        if isinstance(x, ChunkStore):
            return x.chunk
        return default_chunk()

    def _chunk_inner(self, rows: int) -> Backend:
        """The inner backend for chunks of at most ``rows`` rows: a jnp inner
        streams a chunk in blocks of at most ``rows``, so a chunk smaller
        than its block is not padded up to it."""
        if isinstance(self.inner, JnpBackend) and self.inner._block() > rows:
            return dataclasses.replace(self.inner, block=rows)
        return self.inner

    def _pass(self, op: str, kernel: Kernel, x, aux, args: tuple, shape: tuple, *,
              step, rows: bool, m: int, k: int):
        """One pass of ``step`` over the rows of ``x`` (and ``aux``), folded
        into a zero ``shape`` result, as the span ``repro.stream.pass``;
        waits for the result before the span closes."""
        n, c = x.shape[0], self._chunk_rows(x)
        static = dict(step=step, kernel=_static_kernel(kernel),
                      inner=self._chunk_inner(min(c, n)), rows=rows)
        with spans.span("stream.pass", op=op, rows=n, chunk=c, m=m, k=k):
            _TRACKER.note_transient(4 * min(c, n) * m)
            if isinstance(x, jax.Array):
                spans.count("stream.chunks", -(-n // c))
                out = _device_pass(x, None if aux is None else jnp.asarray(aux), args,
                                   jnp.zeros(shape, jnp.float32), chunk=c, **static)
            else:
                out = None if rows else jnp.zeros(shape, jnp.float32)
                parts = []
                for xb, ab in device_chunks(x, aux=aux, chunk=c):
                    spans.count("stream.chunks")
                    if rows:
                        parts.append(_chunk_step(xb, ab, args, None, **static))
                    else:
                        out = _chunk_step(xb, ab, args, out, **static)
                if rows:
                    out = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            if not isinstance(out, jax.core.Tracer):
                out.block_until_ready()
        return out

    # -- protocol -----------------------------------------------------------

    def gram_block(self, kernel: Kernel, x: Array, z: Array) -> Array:
        """K(X, Z) (n, m) fp32, streamed chunk-by-chunk through the inner
        backend — guarded: raises if the *output* exceeds
        ``materialize_elems`` (this method's result is the one (n, m)
        array the protocol cannot avoid)."""
        n, m = x.shape[0], z.shape[0]
        if n * m > self.materialize_elems:
            raise ValueError(
                f"stream backend refuses to materialize a ({n}, {m}) Gram "
                f"block ({n * m} > materialize_elems={self.materialize_elems}"
                "); out-of-core problems must go through the knm_* / "
                "quadform operators, which never build (n, M) — or raise "
                "StreamBackend(materialize_elems=...) if this block is "
                "genuinely meant to exist")
        blocks = []
        for xb, _ in device_chunks(x, chunk=self.chunk):
            _TRACKER.note_transient(4 * xb.shape[0] * m)
            blocks.append(self.inner.gram_block(kernel, xb, z))
        return blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=0)

    def _kjj_inv_chol(self, kernel: Kernel, z: Array, maskf: Array, reg: Array) -> Array:
        return _inv_chol(z, maskf, reg, kernel=_static_kernel(kernel), inner=self.inner)

    def masked_quadform(self, kernel: Kernel, x_cand: Array, z: Array,
                        mask: Array, reg: Array) -> Array:
        """Eq. 3 quadratic form: factor and invert the (Mbuf, Mbuf) K_JJ's
        Cholesky factor once, then stream candidate chunks through one GEMM
        each."""
        maskf = mask.astype(z.dtype)
        inv_l = self._kjj_inv_chol(kernel, z, maskf, reg)
        n = x_cand.shape[0]
        return self._pass("masked_quadform", kernel, x_cand, None, (z, maskf, inv_l),
                          (n,), step=_quadform_chunk,
                          rows=True, m=z.shape[0], k=1)

    def rls_scores(self, kernel: Kernel, x_cand: Array, z: Array,
                   z_mask: Array, reg: Array, lamn: Array) -> Array:
        """Eq. 3 scores with the K_JJ factorization and its inverse factor
        hoisted out of the chunk loop (the inner backend's own fused scorer
        refactors it per call, which would repeat the (Mbuf, Mbuf) Cholesky
        once per chunk)."""
        maskf = z_mask.astype(x_cand.dtype if hasattr(x_cand, "dtype") else jnp.float32)
        inv_l = self._kjj_inv_chol(kernel, z, maskf, reg)
        n = x_cand.shape[0]
        return self._pass("rls_scores", kernel, x_cand, None,
                          (z, maskf, inv_l, jnp.asarray(lamn, jnp.float32)),
                          (n,), step=_rls_chunk,
                          rows=True, m=z.shape[0], k=1)

    def knm_quadratic(self, kernel: Kernel, x: Array, z: Array, *,
                      mask: Array | None = None):
        """CG quadratic op v -> K_nM^T (K_nM v): every call is one pass over
        X (re-streamed from the host for host sources), folding each chunk's
        fused ``inner.knm_quadratic`` into the (M,)/(M, k) accumulator in
        chunk order. An optional ``mask`` ((n,) or (n, k) per-column row
        exclusion — exact CV) rides the pass as the aligned operand, so
        masked ops stay out-of-core: only (chunk, k) mask slices ever reach
        the device."""
        m = z.shape[0]

        def op(v: Array) -> Array:
            return self._pass("knm_quadratic", kernel, x, mask, (z, v),
                              (m,) + v.shape[1:],
                              step=_quad_chunk, rows=False, m=m,
                              k=1 if v.ndim == 1 else v.shape[1])

        return op

    def knm_t(self, kernel: Kernel, x: Array, z: Array, y: Array, *,
              mask: Array | None = None) -> Array:
        """K_nM^T y with y chunked in lockstep with X; (n,) -> (M,) or an
        (n, k) panel -> (M, k), one X sweep serving every column. ``mask``
        folds into the targets (K_nM^T (mask * y)) before chunking."""
        if mask is not None:
            if isinstance(y, jax.Array):
                y = y * jnp.asarray(mask, y.dtype)
            else:  # host-resident targets stay on host (out-of-core n)
                y = np.asarray(y) * np.asarray(mask)
        m = z.shape[0]
        return self._pass("knm_t", kernel, x, y, (z,), (m,) + y.shape[1:], step=_knmt_chunk,
                          rows=False, m=m, k=1 if y.ndim == 1 else y.shape[1])

    def knm_matvec(self, kernel: Kernel, x: Array, z: Array, v: Array) -> Array:
        """K(X, Z) v — predict: per-chunk outputs in row order, (n,) or
        (n, k); the output is the only O(n) device array this path makes."""
        return self._pass("knm_matvec", kernel, x, None, (z, v), (x.shape[0],) + v.shape[1:],
                          step=_matvec_chunk, rows=True, m=z.shape[0],
                          k=1 if v.ndim == 1 else v.shape[1])
