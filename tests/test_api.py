"""repro.api front door: Sampler protocol, estimator contracts, bit-for-bit
parity with the legacy free functions, multi-output fits, warm-start refits
on the fused-fit cache, and the public-surface guard."""
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.api as api
from repro.api import (BlessRSampler, BlessSampler, ExactKrr, ExactRlsSampler,
                       FalkonRegressor, FitConfig, KrrServer, NystromRegressor,
                       RecursiveRlsSampler, Sampler, SqueakSampler,
                       TwoPassSampler, UniformSampler, make_kernel)
from repro.core import falkon_bless_fit, falkon_fit, nystrom_krr
from repro.core.leverage import CenterSet
from repro.runtime import spans

KERN = make_kernel("gaussian", sigma=1.5)
BACKENDS = ["jnp", "pallas", "sharded"]

SAMPLERS = [
    BlessSampler(lam=1e-2, m_cap=128),
    BlessRSampler(lam=1e-2, m_cap=128),
    UniformSampler(m=48),
    ExactRlsSampler(m=48, lam=1e-2),
    RecursiveRlsSampler(lam=1e-2, m_cap=128),
    SqueakSampler(lam=1e-2, m_cap=128),
    TwoPassSampler(lam=1e-2, m2=48),
]


def _problem(n=400, d=6, seed=0):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (n, d))
    y = jnp.sin(2 * x[:, 0]) + 0.3 * x[:, 1] ** 2
    return x, y


# -- Sampler protocol --------------------------------------------------------


@pytest.mark.parametrize("sampler", SAMPLERS, ids=lambda s: type(s).__name__)
def test_samplers_satisfy_protocol_and_centerset_invariants(sampler):
    assert isinstance(sampler, Sampler)  # runtime_checkable structural check
    x, _ = _problem()
    cs = sampler.sample(jax.random.PRNGKey(3), x, KERN, backend="jnp")
    assert isinstance(cs, CenterSet)
    m = int(cs.count)
    assert 0 < m <= cs.idx.shape[0]
    assert bool(jnp.all(cs.mask == (jnp.arange(cs.idx.shape[0]) < m)))
    assert bool(jnp.all((cs.idx >= 0) & (cs.idx < x.shape[0])))
    # invalid slots carry weight 1 (keeps padded K_JJ + lam n A conditioned)
    assert bool(jnp.all(jnp.where(cs.mask, True, cs.weight == 1.0)))
    assert bool(jnp.all(cs.weight[:m] > 0))


def test_samplers_are_hashable_and_comparable():
    assert BlessSampler() == BlessSampler()
    assert BlessSampler(lam=1e-2) != BlessSampler(lam=1e-3)
    {UniformSampler(m=8), ExactRlsSampler(m=8)}  # hashable


def test_uniform_sampler_weight_modes():
    x, _ = _problem(n=200)
    nys = UniformSampler(m=32).sample(jax.random.PRNGKey(0), x, KERN)
    ident = UniformSampler(m=32, weights="identity").sample(jax.random.PRNGKey(0), x, KERN)
    np.testing.assert_allclose(nys.weight[:32], 32 / 200)
    np.testing.assert_allclose(ident.weight[:32], 1.0)
    with pytest.raises(ValueError, match="weights"):
        UniformSampler(m=8, weights="typo").sample(jax.random.PRNGKey(0), x, KERN)


# -- estimator contracts -----------------------------------------------------


def test_falkon_regressor_fit_predict_score():
    x, y = _problem()
    est = FalkonRegressor(kernel=KERN, sampler=UniformSampler(m=96),
                          config=FitConfig(lam=1e-4, iters=30, backend="jnp"))
    assert est.fit(x, y) is est  # sklearn contract: fit returns self
    assert est.predict(x).shape == (x.shape[0],)
    assert est.score(x, y) > 0.6  # far better than predicting the mean
    assert est.centers_.shape == (96, x.shape[1])
    assert est.a_diag_.shape == (96,)


def test_unfitted_estimator_raises():
    est = FalkonRegressor(kernel=KERN)
    with pytest.raises(RuntimeError, match="not fitted"):
        est.predict(jnp.zeros((3, 6)))


def test_kernel_accepted_by_name():
    x, y = _problem(n=200)
    est = ExactKrr(kernel="matern32", sigma=2.0, config=FitConfig(lam=1e-3))
    assert est.kernel.name == "matern32" and est.kernel.sigma == 2.0
    assert est.fit(x, y).score(x, y) > 0.9


def test_nystrom_regressor_matches_core_solver():
    x, y = _problem()
    sampler = UniformSampler(m=64)
    est = NystromRegressor(kernel=KERN, sampler=sampler,
                           config=FitConfig(lam=1e-3, backend="jnp", seed=5))
    est.fit(x, y)
    cs = sampler.sample(jax.random.PRNGKey(5), x, KERN, backend="jnp")
    ref = nystrom_krr(KERN, x, y, x[cs.idx[: int(cs.count)]], 1e-3, backend="jnp")
    assert bool(jnp.array_equal(est.model_.alpha, ref.alpha))


def test_estimators_rank_as_expected():
    """Oracle >= direct Nystrom ~= FALKON on the same centers."""
    x, y = _problem()
    cfg = FitConfig(lam=1e-4, iters=40, backend="jnp", seed=1)
    sampler = UniformSampler(m=96)
    fk = FalkonRegressor(kernel=KERN, sampler=sampler, config=cfg).fit(x, y)
    ny = NystromRegressor(kernel=KERN, sampler=sampler, config=cfg).fit(x, y)
    ex = ExactKrr(kernel=KERN, config=cfg).fit(x, y)
    assert abs(fk.score(x, y) - ny.score(x, y)) < 1e-2  # CG converged to Def. 4
    assert ex.score(x, y) >= ny.score(x, y) - 1e-3


# -- parity with the legacy entry points (the acceptance bar) ----------------


@pytest.mark.parametrize("name", BACKENDS)
def test_falkon_regressor_reproduces_falkon_bless_fit_bitwise(name):
    x, y = _problem()
    key = jax.random.PRNGKey(11)
    est = FalkonRegressor(kernel=KERN,
                          sampler=BlessSampler(lam=1e-3, q2=3.0, m_cap=200),
                          config=FitConfig(lam=1e-5, iters=15, backend=name))
    est.fit(x, y, key=key)
    ref = falkon_bless_fit(key, KERN, x, y, 1e-3, 1e-5, iters=15, q2=3.0,
                           m_cap=200, backend=name)
    assert bool(jnp.array_equal(est.model_.centers, ref.centers))
    assert bool(jnp.array_equal(est.model_.alpha, ref.alpha))


def test_center_set_bypass_matches_sampler_path():
    x, y = _problem()
    sampler = BlessSampler(lam=1e-2, m_cap=128)
    cs = sampler.sample(jax.random.PRNGKey(0), x, KERN, backend="jnp")
    cfg = FitConfig(lam=1e-4, iters=15, backend="jnp", seed=0)
    via_sampler = FalkonRegressor(kernel=KERN, sampler=sampler, config=cfg).fit(x, y)
    via_cs = FalkonRegressor(kernel=KERN, config=cfg).fit(x, y, center_set=cs)
    assert bool(jnp.array_equal(via_sampler.model_.alpha, via_cs.model_.alpha))


# -- multi-output y ----------------------------------------------------------


@pytest.mark.parametrize("name", BACKENDS)
def test_multi_output_matches_columnwise_fits(name):
    """Multi-output rides ONE multi-RHS block-CG; each column must agree
    with an independent single-RHS fit to CG/fp32 tolerance (the solves
    share the matvec panel, so bitwise equality is not expected)."""
    x, y = _problem()
    Y = jnp.stack([y, jnp.cos(x[:, 2]), -0.5 * y + 1.0], axis=1)
    est = FalkonRegressor(kernel=KERN, sampler=UniformSampler(m=48),
                          config=FitConfig(lam=1e-3, iters=15, backend=name))
    est.fit(x, Y)
    assert est.model_.alpha.shape == (48, 3)
    pred = est.predict(x)
    assert pred.shape == (x.shape[0], 3)
    for j in range(3):
        col = falkon_fit(KERN, x, Y[:, j], est.centers_, 1e-3,
                         a_diag=est.a_diag_, iters=15, backend=name)
        # alpha itself is ill-conditioned (the CG solves reassociate), so
        # parity is norm-relative on alpha and tight on predictions
        rel_a = float(jnp.linalg.norm(est.model_.alpha[:, j] - col.alpha)
                      / jnp.linalg.norm(col.alpha))
        assert rel_a < 5e-3, (name, j, rel_a)
        ref = col.predict(x)
        rel_p = float(jnp.linalg.norm(pred[:, j] - ref) / jnp.linalg.norm(ref))
        assert rel_p < 1e-3, (name, j, rel_p)
    assert est.score(x, Y) > 0.5


def test_multi_output_exact_and_nystrom():
    x, y = _problem(n=250)
    Y = jnp.stack([y, -y], axis=1)
    ex = ExactKrr(kernel=KERN, config=FitConfig(lam=1e-3, backend="jnp")).fit(x, Y)
    ny = NystromRegressor(kernel=KERN, sampler=UniformSampler(m=64),
                          config=FitConfig(lam=1e-3, backend="jnp")).fit(x, Y)
    for est in (ex, ny):
        assert est.predict(x).shape == (250, 2)
    # symmetric targets -> symmetric predictions
    p = ex.predict(x)
    np.testing.assert_allclose(p[:, 0], -p[:, 1], rtol=1e-4, atol=1e-5)


def test_score_rejects_mismatched_target_shape():
    x, y = _problem(n=200)
    est = FalkonRegressor(kernel=KERN, sampler=UniformSampler(m=32),
                          config=FitConfig(lam=1e-3, iters=10, backend="jnp"))
    est.fit(x, y)  # single-output model
    with pytest.raises(ValueError, match="shape"):
        est.score(x, y[:, None])  # (n, 1) would silently broadcast to (n, n)


# -- warm-start refits on the fused-fit cache --------------------------------


def test_warm_start_refit_rides_fused_cache():
    x, y = _problem(n=500)
    est = FalkonRegressor(kernel=KERN, sampler=UniformSampler(m=56),
                          config=FitConfig(lam=1e-3, iters=17, backend="jnp"),
                          warm_start=True)
    est.fit(x, y)
    centers0 = est.centers_
    traces0 = spans.retraces("falkon.fused_fit")
    # refit with new targets and a new lam: centers reused, zero retraces
    est.config = FitConfig(lam=1e-4, iters=17, backend="jnp")
    est.fit(x, jnp.cos(x[:, 0]))
    assert est.centers_ is centers0  # no re-sampling
    assert spans.retraces("falkon.fused_fit") == traces0  # fused-fit cache hit
    # without warm_start the sampler runs again (same draw, new arrays)
    est.warm_start = False
    est.fit(x, y)
    assert est.centers_ is not centers0


def test_warm_start_resamples_on_different_data_shape():
    """Centers are rows of the previous X: a different row count must break
    the warm start even though the feature dim matches."""
    x, y = _problem(n=300)
    x2, y2 = _problem(n=260, seed=4)
    est = FalkonRegressor(kernel=KERN, sampler=UniformSampler(m=32),
                          config=FitConfig(lam=1e-3, iters=10, backend="jnp"),
                          warm_start=True)
    est.fit(x, y)
    centers0 = est.centers_
    est.fit(x2, y2)  # same d, different n -> re-sample from x2
    assert est.centers_ is not centers0
    assert bool(jnp.all(est.center_set_.idx[: int(est.center_set_.count)]
                        < x2.shape[0]))


# -- serving integration -----------------------------------------------------


def test_krr_server_accepts_fitted_estimator_and_multi_output():
    x, y = _problem()
    Y = jnp.stack([y, 2.0 * y], axis=1)
    est = FalkonRegressor(kernel=KERN, sampler=UniformSampler(m=48),
                          config=FitConfig(lam=1e-3, iters=15, backend="jnp"))
    server = KrrServer(est.fit(x, Y), max_wave=256)
    out = server.predict(x[:37])
    assert out.shape == (37, 2)
    np.testing.assert_allclose(out, est.predict(x[:37]), rtol=1e-6, atol=1e-6)


def test_krr_server_rejects_unfitted_estimator():
    with pytest.raises(ValueError, match="fit"):
        KrrServer(FalkonRegressor(kernel=KERN))


# -- API surface guard -------------------------------------------------------


def test_api_all_importable_and_public():
    assert len(api.__all__) == len(set(api.__all__))
    for name in api.__all__:
        assert not name.startswith("_"), name
        assert getattr(api, name) is not None


def test_api_surface_is_exactly_all():
    """No core internals leak through the front door: every public attribute
    of repro.api is either in __all__ or a submodule of the package."""
    public = {n for n in vars(api) if not n.startswith("_")}
    modules = {n for n in public if inspect.ismodule(getattr(api, n))}
    assert modules <= {"estimators", "samplers", "sweep"}, modules
    assert public - modules == set(api.__all__)


def test_api_does_not_leak_core_helpers():
    for leaked in ("local_knm_quadratic", "resolve_backend", "_chol_with_jitter",
                   "blocked_cross", "approx_rls"):
        assert not hasattr(api, leaked), leaked
