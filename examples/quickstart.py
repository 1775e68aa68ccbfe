"""Quickstart: the ``repro.api`` front door in ~40 lines — pluggable
sampler, sklearn-style estimator, swappable kernel family.

    PYTHONPATH=src python examples/quickstart.py

Every entry point below picks its kernel-operator backend by platform
heuristic; pin one without code edits via the env var, e.g.
``REPRO_BACKEND=pallas python examples/quickstart.py`` (the richer examples
also take an explicit ``--backend`` flag).
"""
import jax
import jax.numpy as jnp

from repro.api import (BlessSampler, ExactRlsSampler, FalkonRegressor,
                       FitConfig, KFoldSweep, kernel_family_names, make_kernel)
from repro.core import approx_rls_all, exact_rls
from repro.runtime.compile_cache import enable_compile_cache

enable_compile_cache()

# --- data: clustered inputs => low effective dimension (the regime
# leverage scores are built for) -------------------------------------------
key = jax.random.PRNGKey(0)
kc, ka, kn, ky = jax.random.split(key, 4)
n, d = 2000, 8
centers = jax.random.normal(kc, (10, d)) * 3.0
x = centers[jax.random.randint(ka, (n,), 0, 10)] + 0.4 * jax.random.normal(kn, (n, d))
y = jnp.sin(2 * x[:, 0]) * jnp.tanh(x[:, 1]) + 0.05 * jax.random.normal(ky, (n,))

kern = make_kernel("gaussian", sigma=2.0)
lam = 1e-3

# --- 1. approximate leverage scores with BLESS (Alg. 1) ---------------------
sampler = BlessSampler(lam=lam, q1=4.0, q2=4.0)
res = sampler.ladder(jax.random.PRNGKey(1), x, kern)  # the full lam path
print(f"BLESS: {len(res.levels)} ladder levels, final |J| = {res.final.m_h} "
      f"(d_eff estimate {res.final.d_h:.1f})")

ell = exact_rls(kern, x, lam)  # O(n^3) oracle, for demonstration only
racc = approx_rls_all(kern, x, res.final.centers, jnp.asarray(lam)) / ell
print(f"score accuracy: mean R-ACC {float(racc.mean()):.3f}, "
      f"5th/95th pct {float(jnp.quantile(racc, .05)):.2f}/{float(jnp.quantile(racc, .95)):.2f}")

# --- 2. FALKON-BLESS: sampler slot + estimator slot, composed ---------------
est = FalkonRegressor(kernel=kern,
                      sampler=BlessSampler(lam=1e-3, q2=3.0, m_cap=400),
                      config=FitConfig(lam=1e-5, iters=25, seed=2))
est.fit(x, y)
mse = float(jnp.mean((est.predict(x) - y) ** 2))
print(f"FALKON-BLESS: M = {est.centers_.shape[0]} centers, "
      f"train MSE {mse:.4f} (R^2 {est.score(x, y):.3f})")

# --- 3. the slots are swappable: oracle sampler, another kernel family ------
est_oracle = FalkonRegressor(kernel="matern32", sigma=2.0,
                             sampler=ExactRlsSampler(m=300, lam=lam),
                             config=FitConfig(lam=1e-5, iters=25, seed=3))
est_oracle.fit(x, y)
print(f"matern32 + exact-RLS oracle sampler: R^2 {est_oracle.score(x, y):.3f} "
      f"(families available: {kernel_family_names()})")

# --- 4. multi-output: k targets ride ONE multi-RHS block-CG -----------------
# The K_nM streaming (the dominant fit cost) is shared by every column, so
# the extra outputs below cost GEMM flops, not extra kernel evaluations.
Y = jnp.stack([y, jnp.cos(x[:, 2]) * x[:, 0], -0.5 * y + 1.0], axis=1)
est_multi = FalkonRegressor(kernel=kern,
                            sampler=BlessSampler(lam=1e-3, q2=3.0, m_cap=400),
                            config=FitConfig(lam=1e-5, iters=25, seed=2))
est_multi.fit(x, Y)
print(f"multi-output: alpha {est_multi.model_.alpha.shape}, "
      f"predict {est_multi.predict(x[:5]).shape}, R^2 {est_multi.score(x, Y):.3f}")

# --- 5. KFoldSweep: lambda selection with CV folds as RHS columns -----------
# Per lambda: ONE multi-RHS solve (folds = columns, fold-masked targets) on
# warm-started centers; the whole grid after the first fit is jit cache hits.
sweep = KFoldSweep(kernel=kern, sampler=BlessSampler(lam=1e-3, m_cap=400),
                   lams=(1e-3, 1e-5, 1e-7), folds=5, iters=25)
res = sweep.run(x, y)
scores = ", ".join(f"lam={ell:g}: {float(s):.4f}"
                   for ell, s in zip(res.lams, res.mean_scores))
print(f"KFoldSweep held-out MSE ({scores}) -> best lam {res.best_lam:g} "
      f"[{len(res.lams)} solves instead of {len(res.lams) * 5} fits]")
