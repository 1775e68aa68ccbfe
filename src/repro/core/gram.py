"""Kernel (Gram) computations and the kernel-operator backend registry.

The paper works with a bounded PSD kernel ``K(x, x') <= kappa^2`` (Eq. 17).
``Kernel`` is a tiny pytree so jitted core functions retrace only when the
kernel *family* changes, not when its bandwidth does. Families themselves
live in the extensible registry ``repro.families`` (re-exported here):
each ``KernelFamily`` contributes the jnp formula *and* the Pallas tile
epilogue, so a registered family runs on all three backends.

The blockwise entry points here are the pure-jnp reference path; the same
contractions are served by the Pallas kernels (``repro.kernels.gram`` /
``repro.kernels.falkon_matvec``) and the shard_map data-parallel path
(``repro.core.distributed``) through the ``Backend`` implementations in
``repro.core.backend``. This module owns only the *registry* so the low
levels (leverage, bless, falkon) can resolve a backend by name without
importing the backend module at import time (it imports all of them).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import TYPE_CHECKING, Callable, Union

import jax
import jax.numpy as jnp

from ..families import (  # noqa: F401 — re-exported public API
    KernelFamily,
    diag_pre,
    get_family,
    kernel_family_names,
    register_kernel_family,
)

if TYPE_CHECKING:  # pragma: no cover — type-only, avoids the import cycle
    from .backend import Backend

BackendLike = Union["Backend", str, None]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Kernel:
    """A bounded positive-definite kernel ``k(x, z)``.

    Attributes:
      name: kernel family, resolved from the ``repro.families`` registry
        (``kernel_family_names()`` enumerates what is available; gaussian,
        laplacian, linear, matern32 and cauchy ship built in).
      sigma: bandwidth (ignored by bandwidth-free families, e.g. "linear").
      kappa_sq: uniform bound on ``k(x, x)`` (1.0 for the unit-diagonal
        families; must be supplied for "linear" if inputs are not normalized).
    """

    name: str = "gaussian"
    sigma: float = 1.0
    kappa_sq: float = 1.0

    # -- pytree plumbing (name/kappa_sq static, sigma traced) ---------------
    def tree_flatten(self):
        return (jnp.asarray(self.sigma),), (self.name, self.kappa_sq)

    @classmethod
    def tree_unflatten(cls, aux, children):
        name, kappa_sq = aux
        return cls(name=name, sigma=children[0], kappa_sq=kappa_sq)

    @property
    def family(self) -> KernelFamily:
        """The registered family (raises with the registry listed on typos)."""
        return get_family(self.name)

    # -- API -----------------------------------------------------------------
    def cross(self, x: jax.Array, z: jax.Array) -> jax.Array:
        """Gram block ``k(x_i, z_j)`` of shape (n, m)."""
        fam = self.family
        if fam.dot_only:
            return fam.epilogue(_dot_t(x, z), fam.inv_scale(self.sigma))
        return fam.epilogue(sq_dists(x, z), fam.inv_scale(self.sigma))

    def cross_unfused(self, x: jax.Array, z: jax.Array) -> jax.Array:
        """``cross`` with the epilogue kept out of the XLA:CPU broadcast
        fusion (see ``_apply_epilogue``) — elementwise-identical, much
        faster for exp-based families on CPU. The extra ``lax.map`` level
        makes it unsafe inside deeply nested control flow (e.g. the CG
        while-loop), so hot *leaf* contractions opt in explicitly."""
        fam = self.family
        pre = _dot_t(x, z) if fam.dot_only else sq_dists(x, z)
        return _apply_epilogue(fam, pre, fam.inv_scale(self.sigma))

    def diag(self, x: jax.Array) -> jax.Array:
        """``k(x_i, x_i)`` of shape (n,)."""
        fam = self.family
        if fam.unit_diag:
            return jnp.ones((x.shape[0],), x.dtype)
        return fam.epilogue(diag_pre(fam, x), fam.inv_scale(self.sigma))

    def gram(self, x: jax.Array) -> jax.Array:
        return self.cross(x, x)


_EPILOGUE_BLOCKS = 8


def _apply_epilogue(fam: KernelFamily, pre: jax.Array, c) -> jax.Array:
    """Apply a family epilogue to a Gram pre-activation block.

    On CPU the epilogue goes through a ``lax.map`` over row blocks rather
    than straight elementwise application: XLA:CPU fuses the epilogue into
    the distance broadcast loop and then emits *scalar* transcendental
    calls (~4x the whole block's cost for exp-based families); the loop
    body boundary keeps the epilogue a standalone op, which lowers to the
    vectorized libm kernels. Elementwise results are identical. Other
    platforms (and shapes the block count doesn't divide) take the plain
    fused path.
    """
    n = pre.shape[0] if pre.ndim == 2 else 0
    if (jax.default_backend() != "cpu" or n < 512
            or n % _EPILOGUE_BLOCKS != 0):
        return fam.epilogue(pre, c)
    nb = _EPILOGUE_BLOCKS
    blocks = pre.reshape(nb, n // nb, pre.shape[1])
    return jax.lax.map(lambda b: fam.epilogue(b, c), blocks).reshape(pre.shape)


def _dot_t(x: jax.Array, z: jax.Array) -> jax.Array:
    """x z^T at full fp32. TPU runs a DEFAULT-precision fp32 matmul as one
    bf16 pass; in the distance expansion below that error (~5e-4 relative
    on a Gram block, measured on v5e) survives the cancellation of the
    norms. The contraction is over d, small next to the (n, m) epilogue."""
    return jnp.matmul(x, z.T, precision=jax.lax.Precision.HIGHEST)


def sq_dists(x: jax.Array, z: jax.Array) -> jax.Array:
    """Pairwise squared Euclidean distances, MXU-friendly form.

    ||x - z||^2 = ||x||^2 + ||z||^2 - 2 x.z  — one (n,d)x(d,m) matmul plus
    rank-1 updates; clamped at 0 against fp cancellation.
    """
    xn = jnp.sum(x * x, axis=-1)[:, None]
    zn = jnp.sum(z * z, axis=-1)[None, :]
    d2 = xn + zn - 2.0 * _dot_t(x, z)
    return jnp.maximum(d2, 0.0)


def make_kernel(name: str = "gaussian", sigma: float = 1.0, kappa_sq: float = 1.0) -> Kernel:
    """Build a ``Kernel`` after validating ``name`` against the family
    registry (unknown names raise with the registry enumerated).

    ``sigma`` is the bandwidth (ignored by bandwidth-free families);
    ``kappa_sq`` the uniform bound on k(x, x) — supply it for "linear" on
    unnormalized inputs (Eq. 17 candidate-set sizing depends on it).
    """
    get_family(name)  # fail fast with the registered families enumerated
    return Kernel(name=name, sigma=sigma, kappa_sq=kappa_sq)


# ---------------------------------------------------------------------------
# Backend registry
#
# ``repro.core.backend`` registers its implementations here on import; the
# callers (leverage / bless / falkon / benchmarks) resolve by name or pass an
# instance through. Keeping the dict in this leaf module breaks the cycle
# backend.py -> {leverage, falkon, distributed} -> gram.
# ---------------------------------------------------------------------------

_BACKEND_REGISTRY: dict[str, Callable[[], "Backend"]] = {}


def register_backend(name: str, factory: Callable[[], "Backend"]) -> None:
    """Register a zero-arg factory for ``resolve_backend(name)``."""
    _BACKEND_REGISTRY[name] = factory


def backend_names() -> list[str]:
    _ensure_backends_loaded()
    return sorted(_BACKEND_REGISTRY)


def resolve_backend(spec: BackendLike = None, *, n: int | None = None) -> "Backend":
    """Resolve a backend spec: instance (passthrough), name, or None (auto).

    ``None`` picks ``backend.default_backend(n)`` — the platform/size
    heuristic — so every core entry point gets hardware-appropriate
    contractions without callers naming one. ``n`` is the dataset row count
    when the caller knows it.

    Composite specs ``"outer:inner"`` (e.g. ``"stream:pallas"``) resolve the
    outer name, then hand it the resolved inner via its ``with_inner`` hook —
    how the out-of-core streamer composes with a per-tile backend. The inner
    part may itself be composite.
    """
    if spec is None:
        _ensure_backends_loaded()
        from .backend import default_backend

        return default_backend(n)
    if isinstance(spec, str):
        _ensure_backends_loaded()
        outer_name, _, inner_spec = spec.partition(":")
        try:
            outer = _BACKEND_REGISTRY[outer_name]()
        except KeyError:
            raise ValueError(
                f"unknown backend {outer_name!r}; registered: {sorted(_BACKEND_REGISTRY)}"
            ) from None
        if not inner_spec:
            return outer
        if not hasattr(outer, "with_inner"):
            raise ValueError(
                f"backend {outer_name!r} is not composable (no with_inner); "
                f"cannot resolve {spec!r}")
        return outer.with_inner(resolve_backend(inner_spec, n=n))
    return spec


def _ensure_backends_loaded() -> None:
    from . import backend  # noqa: F401 — import side effect: registration


@partial(jax.jit, static_argnames=("block",))
def blocked_cross(kernel: Kernel, x: jax.Array, z: jax.Array, *, block: int = 4096) -> jax.Array:
    """Gram ``k(X, Z)`` computed in row blocks of ``x`` to bound peak memory.

    Used when (n, m) is too large for one materialized intermediate; the
    distance matrix per block is (block, m).
    """
    n = x.shape[0]
    if n <= block:
        return kernel.cross_unfused(x, z)
    pad = (-n) % block
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    xb = xp.reshape(-1, block, x.shape[1])
    out = jax.lax.map(lambda xi: kernel.cross_unfused(xi, z), xb)
    return out.reshape(-1, z.shape[0])[:n]
