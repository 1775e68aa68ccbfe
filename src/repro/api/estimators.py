"""sklearn-style estimators over the paper's solvers — the second slot.

Every estimator follows the same contract:

    est = FalkonRegressor(sampler=BlessSampler(lam=1e-3), kernel="gaussian",
                          config=FitConfig(lam=1e-5, iters=20, backend="jnp"))
    est.fit(X, y)          # -> est  (learned attrs get a trailing underscore)
    est.predict(X)         # (n,) or (n, k), through the backend seam
    est.score(X, y)        # R^2 (uniform average over outputs)

``FitConfig`` is a frozen dataclass so a configuration is hashable and
shareable; the estimator itself is mutable sklearn-style (swap ``.config``
between fits for a lambda sweep). ``y`` may be (n,) or (n, k): multi-output
targets ride ONE multi-RHS block-CG against the shared centers — the
preconditioner, the K_nM streaming and the fused-fit compile are shared, so
extra output columns are nearly free (GEMM flops only).

Warm starts: with ``warm_start=True`` a refit on same-shaped X reuses the
previously sampled centers, so consecutive ``fit`` calls ride the PR 2
fused-fit jit cache — same shape bucket, zero recompiles, one fused dispatch
per refit (lam and the kernel bandwidth are traced, so sweeping them is free).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..core.falkon import FalkonModel, falkon_fit
from ..core.gram import BackendLike, Kernel, make_kernel
from ..core.leverage import CenterSet
from ..core.nystrom import exact_krr, nystrom_krr
from ..runtime import spans
from ..stream import ChunkStore
from .samplers import BlessSampler, Sampler

Array = jax.Array


def _as_data(x) -> Array | ChunkStore:
    """Device array for array inputs; a host-resident ``ChunkStore`` passes
    through untouched so the streaming paths (falkon_fit's host CG, the
    samplers, predict) keep X out of device memory. The direct O(n^2+) paths
    (``ExactKrr``) still ``jnp.asarray`` explicitly — materializing there is
    the algorithm, not an accident."""
    return x if isinstance(x, ChunkStore) else jnp.asarray(x)


@dataclasses.dataclass(frozen=True)
class FitConfig:
    """Solver configuration shared by every estimator.

    Attributes:
      lam: the solver's ridge regularization (the paper's lambda; keep it
        well below a BLESS sampler's own lam — Sec. 4).
      iters: CG iteration count (FALKON only; the direct solvers ignore it).
      backend: kernel-operator backend spec — instance, registry name
        ("jnp" | "pallas" | "sharded" | "guarded"), or None for the
        platform heuristic.
      seed: PRNG seed for the sampler when ``fit`` is not given a key.
      check_finite: arm the §9 finite-output fence on FALKON fits (the
        direct solvers are always fenced); costs one host sync per fit, so
        it is off by default on this hot path.
    """

    lam: float = 1e-3
    iters: int = 20
    backend: BackendLike = None
    seed: int = 0
    check_finite: bool = False


def _as_kernel(kernel: Kernel | str, sigma: float) -> Kernel:
    return kernel if isinstance(kernel, Kernel) else make_kernel(kernel, sigma=sigma)


class _KrrEstimator:
    """Shared fit bookkeeping + predict/score for the three estimators."""

    def __init__(self, kernel: Kernel | str = "gaussian", *, sigma: float = 1.0,
                 config: FitConfig | None = None):
        self.kernel = _as_kernel(kernel, sigma)
        self.config = config if config is not None else FitConfig()
        self.model_: FalkonModel | None = None

    # -- sklearn surface -----------------------------------------------------

    def predict(self, x: Array, *, return_std: bool = False) -> Array | tuple[Array, Array]:
        """Predictions through the kernel-operator seam ((n,) or (n, k)).

        With ``return_std=True`` returns ``(pred, std)`` where ``std`` is
        the (n,) square root of the GP-style Nystrom posterior variance
        (``predictive_variance``) — shared across output columns, since it
        does not depend on y.
        """
        if self.model_ is None:
            raise RuntimeError(f"{type(self).__name__} is not fitted; call .fit first")
        pred = self.model_.predict(_as_data(x), backend=self.config.backend)
        if not return_std:
            return pred
        return pred, jnp.sqrt(self.predictive_variance(x))

    def predictive_variance(self, x: Array) -> Array:
        """GP-style posterior variance ``k(x,x) - k_xM (K_MM + lam n A)^{-1}
        k_Mx`` per row of ``x`` ((n,), nonnegative), streamed through the
        backend seam — works at out-of-core n on ``StreamBackend``."""
        if self.model_ is None:
            raise RuntimeError(f"{type(self).__name__} is not fitted; call .fit first")
        return self.model_.predictive_variance(_as_data(x),
                                               backend=self.config.backend)

    def score(self, x: Array, y: Array) -> float:
        """Coefficient of determination R^2 (uniform average over outputs)."""
        y = jnp.asarray(y)
        pred = self.predict(x)
        if y.shape != pred.shape:  # e.g. (n, 1) targets on a (n,) model:
            raise ValueError(       # broadcasting would yield a garbage R^2
                f"y has shape {y.shape} but the model predicts {pred.shape}")
        res = jnp.sum((y - pred) ** 2, axis=0)
        tot = jnp.maximum(jnp.sum((y - jnp.mean(y, axis=0)) ** 2, axis=0), 1e-30)
        return float(jnp.mean(1.0 - res / tot))

    def _key(self, key: Array | None) -> Array:
        return jax.random.PRNGKey(self.config.seed) if key is None else key


class FalkonRegressor(_KrrEstimator):
    """FALKON (Sec. 3) with a pluggable center sampler.

    ``sampler`` fills the pipeline's first slot (defaults to ``BlessSampler``,
    i.e. FALKON-BLESS); the sampled ``CenterSet``'s weights become the
    generalized preconditioner's A (Def. 2). ``warm_start=True`` keeps the
    sampled centers across refits on same-shaped X (see module docstring).
    """

    def __init__(self, kernel: Kernel | str = "gaussian", *,
                 sampler: Sampler | None = None, sigma: float = 1.0,
                 config: FitConfig | None = None, warm_start: bool = False):
        super().__init__(kernel, sigma=sigma, config=config)
        self.sampler = sampler if sampler is not None else BlessSampler()
        self.warm_start = warm_start
        self.centers_: Array | None = None
        self.a_diag_: Array | None = None
        self.center_set_: CenterSet | None = None
        self._fit_shape_: tuple | None = None

    def fit(self, x: Array, y: Array, *, key: Array | None = None,
            center_set: CenterSet | None = None,
            callback: Callable[[int, FalkonModel], None] | None = None,
            row_mask: Array | None = None) -> "FalkonRegressor":
        """Sample centers (unless warm-starting) and solve by preconditioned
        CG. ``center_set`` bypasses the sampler with a precomputed (J, A)
        (e.g. one BLESS ladder shared across estimators); ``callback(i,
        model)`` switches to the host CG loop for per-iteration metrics
        (single-output only). ``row_mask`` (shaped like y) gives each RHS
        column its own training-row subset — the exact row-exclusion CV
        mechanism ``KFoldSweep`` rides (see ``falkon_fit``)."""
        x = _as_data(x)
        y = jnp.asarray(y)
        cfg = self.config
        # warm start contract (sklearn-style): the caller asserts X is the
        # same training set as the previous fit. The guard can only check
        # shape — a different dataset with identical (n, d) is on the
        # caller; pass center_set= (or leave warm_start off) when rotating
        # datasets, e.g. cross-validation folds.
        reuse = (center_set is None and self.warm_start
                 and self.centers_ is not None
                 and self._fit_shape_ == x.shape)
        with spans.span("fit", n=x.shape[0], k=1 if y.ndim == 1 else y.shape[1]) as fit:
            if not reuse:
                cs = center_set if center_set is not None else self.sampler.sample(
                    self._key(key), x, self.kernel, backend=cfg.backend)
                m = int(cs.count)
                self.center_set_ = cs
                self.centers_ = x[cs.idx[:m]]
                self.a_diag_ = cs.weight[:m]
                self._fit_shape_ = x.shape
            fit.set_metadata(m=self.centers_.shape[0])
            self.model_ = falkon_fit(self.kernel, x, y, self.centers_, cfg.lam,
                                     a_diag=self.a_diag_, iters=cfg.iters,
                                     backend=cfg.backend, callback=callback,
                                     check_finite=cfg.check_finite,
                                     row_mask=row_mask)
        return self


class FalkonClassifier(FalkonRegressor):
    """One-vs-rest classification as ONE multi-RHS FALKON solve.

    The k classes become k RHS columns of a single block-CG on shared
    centers (squared loss on +-1 one-hot targets — the least-squares SVM
    reading): the preconditioner, every K_nM stream, and the fused-fit
    compile are paid once, so k-class classification costs the k-output
    regression price, not k independent fits. Warm-start refits ride the
    same fused-fit cache as the regressor.

    ``predict`` returns labels from ``self.classes_`` (argmax of the margin
    panel); ``decision_function`` exposes the raw (n, k) margins;
    ``predict_proba`` is a softmax over the margins — a monotone
    calibration convenience, not a fitted probability model; ``score`` is
    accuracy. Binary problems keep both columns (k = 2) so every class has
    a margin.
    """

    #: sorted unique training labels; set by ``fit``.
    classes_: "np.ndarray | None" = None

    def fit(self, x: Array, y: Array, *, key: Array | None = None,
            center_set: CenterSet | None = None,
            callback: Callable[[int, FalkonModel], None] | None = None,
            row_mask: Array | None = None) -> "FalkonClassifier":
        """Encode labels as a +-1 one-hot panel and fit the multi-RHS solve.

        ``y`` is (n,) labels of any hashable dtype (ints, strings, ...);
        the sorted unique labels become ``self.classes_``. ``callback`` is
        unsupported (the panel fit has no single-output host loop).
        """
        if callback is not None:
            raise ValueError("FalkonClassifier fits a multi-RHS panel; "
                             "per-iteration callback is single-output only")
        labels = np.asarray(y)
        if labels.ndim != 1:
            raise ValueError(f"classifier targets must be (n,) labels, "
                             f"got shape {labels.shape}")
        classes, inv = np.unique(labels, return_inverse=True)
        if classes.shape[0] < 2:
            raise ValueError("need at least 2 classes to classify")
        self.classes_ = classes
        onehot = (inv[:, None] == np.arange(classes.shape[0])[None, :])
        panel = jnp.asarray(np.where(onehot, 1.0, -1.0), jnp.float32)
        super().fit(x, panel, key=key, center_set=center_set,
                    row_mask=row_mask)
        return self

    def decision_function(self, x: Array) -> Array:
        """Raw one-vs-rest margins (n, k) through the panel predict."""
        return super().predict(x)

    def predict(self, x: Array, *, return_std: bool = False):
        """Predicted labels (n,) from ``classes_[argmax(margins)]``; with
        ``return_std=True`` also the (n,) posterior std of the margins."""
        margins = self.decision_function(x)
        labels = self.classes_[np.asarray(jnp.argmax(margins, axis=1))]
        if not return_std:
            return labels
        return labels, jnp.sqrt(self.predictive_variance(x))

    def predict_proba(self, x: Array) -> Array:
        """Softmax over the margins, (n, k) rows summing to 1 — a monotone
        score calibration (ranking-faithful), not fitted probabilities."""
        return jax.nn.softmax(self.decision_function(x), axis=1)

    def score(self, x: Array, y: Array) -> float:
        """Classification accuracy in [0, 1]."""
        return float(np.mean(np.asarray(self.predict(x)) == np.asarray(y)))


class NystromRegressor(_KrrEstimator):
    """Direct Nystrom-KRR (Def. 4) on sampled centers — the O(n M^2) dense
    solve FALKON's CG converges to; same sampler slot, no iteration knob."""

    def __init__(self, kernel: Kernel | str = "gaussian", *,
                 sampler: Sampler | None = None, sigma: float = 1.0,
                 config: FitConfig | None = None):
        super().__init__(kernel, sigma=sigma, config=config)
        self.sampler = sampler if sampler is not None else BlessSampler()
        self.centers_: Array | None = None
        self.center_set_: CenterSet | None = None

    def fit(self, x: Array, y: Array, *, key: Array | None = None) -> "NystromRegressor":
        """Sample centers and solve Def. 4 directly; ``y`` (n,) or (n, k)."""
        x = _as_data(x)
        cs = self.sampler.sample(self._key(key), x, self.kernel,
                                 backend=self.config.backend)
        m = int(cs.count)
        self.center_set_ = cs
        self.centers_ = x[cs.idx[:m]]
        self.model_ = nystrom_krr(self.kernel, x, jnp.asarray(y), self.centers_,
                                  self.config.lam, backend=self.config.backend)
        return self


class ExactKrr(_KrrEstimator):
    """Exact kernel ridge regression (Eq. 12) — the O(n^3) oracle. No
    sampler slot: every training point is a center."""

    def fit(self, x: Array, y: Array, *, key: Array | None = None) -> "ExactKrr":
        """Solve Eq. 12 on the full Gram matrix; ``y`` (n,) or (n, k)."""
        self.model_ = exact_krr(self.kernel, jnp.asarray(x), jnp.asarray(y),
                                self.config.lam, backend=self.config.backend)
        return self


__all__ = ["FitConfig", "FalkonRegressor", "FalkonClassifier",
           "NystromRegressor", "ExactKrr"]
