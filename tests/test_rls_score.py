"""Ladder-level parity + determinism suite for the fused RLS-score path.

Holds every backend's ``rls_scores`` seam to the pre-fusion oracle
(``repro.kernels.rls_score.ref``) across all registered kernel families,
guards the jitted ladder against retraces, and pins the one-seed-spelling
PRNG convention across every ``repro.api`` sampler.
"""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (BlessRSampler, BlessSampler, ChenYangSampler,
                       SqueakSampler, UniformSampler, as_prng_key,
                       make_kernel)
from repro.core import resolve_backend
from repro.core.chen_yang import fast_spectral_rls

# the package re-exports the *function* bless under the submodule's name;
# the retrace guard runs the module's own ladders
bless_mod = importlib.import_module("repro.core.bless")
from repro.core.sampling import gumbel_topk
from repro.kernels.rls_score import rls_score_ref
from repro.runtime import spans

FAMILIES = ["gaussian", "laplacian", "linear", "matern32", "cauchy"]
BACKENDS = ["jnp", "pallas", "sharded"]


def _problem(seed=0, n=96, m=24, mbuf=32, d=6, lam=1e-2):
    """A candidate set + padded center set exercising mask and reg padding."""
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (n, d))
    idx = jax.random.permutation(jax.random.PRNGKey(seed + 1), n)[:mbuf]
    z = x[idx]
    mask = jnp.arange(mbuf) < m
    weight = jnp.where(mask, 0.5 + jax.random.uniform(
        jax.random.PRNGKey(seed + 2), (mbuf,)), 1.0)
    lamn = jnp.asarray(lam * n, jnp.float32)
    reg = jnp.where(mask, lamn * weight, 1.0)
    return x, z, mask, reg, lamn


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("backend", BACKENDS)
def test_rls_scores_matches_prefusion_ref(family, backend):
    kernel = make_kernel(family, sigma=1.5, kappa_sq=50.0)
    x, z, mask, reg, lamn = _problem()
    got = resolve_backend(backend).rls_scores(kernel, x, z, mask, reg, lamn)
    want = rls_score_ref(kernel, x, z, mask, reg, lamn)
    assert got.shape == want.shape == (x.shape[0],)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-3, atol=5e-4)


@pytest.mark.parametrize("backend", BACKENDS)
def test_rls_scores_empty_center_set_degenerates(backend):
    """All-false mask zeroes the quadform: s = K_ii / (lam n) exactly."""
    kernel = make_kernel("gaussian", sigma=1.5)
    x, z, _, _, lamn = _problem()
    mask = jnp.zeros(z.shape[0], bool)
    reg = jnp.ones(z.shape[0], jnp.float32)
    got = resolve_backend(backend).rls_scores(kernel, x, z, mask, reg, lamn)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(kernel.diag(x) / lamn),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("family", FAMILIES)
def test_cross_unfused_is_elementwise_identical(family):
    """The blocked-epilogue path must not change a single bit of output."""
    kernel = make_kernel(family, sigma=2.0)
    z = jax.random.normal(jax.random.PRNGKey(1), (40, 5))
    for n in (512, 97):  # blocked path (n % 8 == 0, n >= 512) and plain path
        x = jax.random.normal(jax.random.PRNGKey(0), (n, 5))
        fused = jax.jit(kernel.cross)(x, z)
        unfused = jax.jit(kernel.cross_unfused)(x, z)
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(unfused))


def _ladder_data(n=300, d=4, seed=3):
    key = jax.random.PRNGKey(seed)
    centers = jax.random.normal(key, (8, d)) * 3.0
    assign = jax.random.randint(jax.random.PRNGKey(seed + 1), (n,), 0, 8)
    return centers[assign] + 0.3 * jax.random.normal(
        jax.random.PRNGKey(seed + 2), (n, d))


@pytest.mark.parametrize("alg", ["bless", "bless_r"])
def test_ladder_zero_retrace_on_repeat(alg):
    """A second *identical* ladder run must not retrace any jitted level.

    (A different key may legitimately retrace: acceptance counts move the
    bucketed per-level buffer sizes. Identical inputs must be all cache
    hits — the bucketing exists to make the shape set finite, and scalar
    level parameters ride as weak-typed Python scalars.)
    """
    x = _ladder_data()
    kernel = make_kernel("gaussian", sigma=1.5)
    run = getattr(bless_mod, alg)
    run(jax.random.PRNGKey(0), x, kernel, 1e-2, backend="jnp")
    before = spans.retraces("bless")
    out = run(jax.random.PRNGKey(0), x, kernel, 1e-2, backend="jnp")
    assert spans.retraces("bless") == before
    assert int(out.final.centers.count) > 0


SAMPLERS = [
    BlessSampler(lam=3e-2, q2=2.0, q1=2.0),
    BlessRSampler(lam=3e-2, q2=2.0),
    SqueakSampler(lam=3e-2, m_cap=64),
    ChenYangSampler(m=48, lam=3e-2),
    UniformSampler(m=48),
]


@pytest.mark.parametrize("sampler", SAMPLERS,
                         ids=lambda s: type(s).__name__)
def test_sampler_seed_convention(sampler):
    """One PRNG convention: int seed, typed key and legacy PRNGKey all
    draw the identical center set, and re-running a seed is deterministic."""
    x = _ladder_data(n=260)
    kernel = make_kernel("gaussian", sigma=1.5)
    spellings = [7, jax.random.key(7), jax.random.PRNGKey(7)]
    sets = [sampler.sample(k, x, kernel, backend="jnp") for k in spellings]
    ref = sets[0]
    for cs in sets[1:]:
        np.testing.assert_array_equal(np.asarray(cs.idx), np.asarray(ref.idx))
        np.testing.assert_array_equal(np.asarray(cs.weight),
                                      np.asarray(ref.weight))
        assert int(cs.count) == int(ref.count)
    other = sampler.sample(8, x, kernel, backend="jnp")
    assert (other.idx.shape != ref.idx.shape
            or not np.array_equal(np.asarray(other.idx), np.asarray(ref.idx)))


def test_as_prng_key_spellings_agree():
    base = as_prng_key(5)
    assert jnp.issubdtype(base.dtype, jax.dtypes.prng_key)
    for other in (as_prng_key(jax.random.key(5)),
                  as_prng_key(jax.random.PRNGKey(5))):
        assert np.array_equal(
            np.asarray(jax.random.key_data(base)),
            np.asarray(jax.random.key_data(other)))


def test_chen_yang_scores_track_exact_rls():
    """Spectral estimates correlate with exact RLS and land in (0, 1]."""
    from repro.core.leverage import exact_rls

    x = _ladder_data(n=220)
    kernel = make_kernel("gaussian", sigma=1.5)
    lam = 1e-2
    est = fast_spectral_rls(jax.random.key(0), kernel, x, lam, backend="jnp")
    exact = exact_rls(kernel, x, lam)
    est, exact = np.asarray(est), np.asarray(exact)
    assert est.shape == (220,)
    assert np.all(est > 0.0) and np.all(est <= 1.0 + 1e-6)
    ratio = est / exact
    assert 1 / 3 < np.median(ratio) < 3.0
    assert np.corrcoef(est, exact)[0, 1] > 0.5


def test_gumbel_topk_is_a_weighted_distinct_draw():
    w = jnp.asarray([10.0, 1.0, 1.0, 1.0, 10.0, 1.0])
    hits = np.zeros(6)
    for s in range(200):
        sel = np.asarray(gumbel_topk(jax.random.key(s), w, 2))
        assert len(set(sel.tolist())) == 2  # without replacement
        hits[sel] += 1
    assert hits[0] + hits[4] > hits[1] + hits[2] + hits[3] + hits[5]
