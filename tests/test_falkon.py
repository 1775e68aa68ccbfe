"""FALKON: Def. 2 preconditioner identity, CG convergence to the Def. 4
Nystrom solution, FALKON-BLESS end-to-end, Pallas operator parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (cg, exact_krr, falkon_bless_fit, falkon_fit,
                        make_kernel, make_preconditioner, nystrom_krr)

KERN = make_kernel("gaussian", sigma=1.5)


def _problem(n=500, m=80, seed=0):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (n, 6))
    y = jnp.sin(2 * x[:, 0]) + 0.3 * x[:, 1] ** 2
    z = x[jax.random.choice(jax.random.PRNGKey(seed + 1), n, (m,), replace=False)]
    return x, y, z


@settings(max_examples=6, deadline=None)
@given(m=st.integers(16, 64), lam=st.floats(1e-4, 1e-1), seed=st.integers(0, 100))
def test_preconditioner_identity(m, lam, seed):
    """B B^T = (n/M K A^{-1} K + lam n K)^{-1}   (Eq. 15) on random PSD."""
    n = 300
    key = jax.random.PRNGKey(seed)
    z = jax.random.normal(key, (m, 5))
    a = jax.random.uniform(jax.random.PRNGKey(seed + 1), (m,), minval=0.3, maxval=2.0)
    prec = make_preconditioner(KERN, z, a, lam, n)
    b_dense = jax.vmap(prec.apply, in_axes=1, out_axes=1)(jnp.eye(m))
    k = KERN.cross(z, z)
    h = n / m * k @ jnp.diag(1 / a) @ k + lam * n * k
    # the preconditioner's defining property: B^T H B == I (on kept rank)
    w = b_dense.T @ h @ b_dense
    rel = float(jnp.linalg.norm(w - jnp.eye(m)) / np.sqrt(m))
    assert rel < 2e-2, rel


def test_falkon_converges_to_nystrom():
    x, y, z = _problem()
    lam = 1e-3
    fk = falkon_fit(KERN, x, y, z, lam, iters=40)
    ny = nystrom_krr(KERN, x, y, z, lam)
    pf, pn = fk.predict(x), ny.predict(x)
    assert float(jnp.linalg.norm(pf - pn) / jnp.linalg.norm(pn)) < 1e-3


def test_falkon_matches_exact_krr_with_all_centers():
    x, y, _ = _problem(n=250)
    lam = 1e-2
    fk = falkon_fit(KERN, x, y, x, lam, iters=60)
    ex = exact_krr(KERN, x, y, lam)
    pf, pe = fk.predict(x), ex.predict(x)
    assert float(jnp.linalg.norm(pf - pe) / jnp.linalg.norm(pe)) < 5e-3


def test_cg_residual_decreases():
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (40, 40))
    a = a @ a.T / 40.0 + jnp.eye(40)  # well conditioned
    b = jax.random.normal(jax.random.PRNGKey(1), (40,))
    res = []
    cg(lambda v: a @ v, b, 25,
       callback=lambda i, beta: res.append(float(jnp.linalg.norm(a @ beta - b))))
    assert res[-1] < 1e-3 * res[0]


def test_falkon_bless_end_to_end(clustered_data):
    """Low-d_eff (clustered) data — the regime leverage scores are for:
    a few hundred BLESS centers reach near-interpolation."""
    x = clustered_data
    y = jnp.sin(2 * x[:, 0]) + 0.3 * x[:, 1]
    model = falkon_bless_fit(jax.random.PRNGKey(0), KERN, x, y,
                             lam_bless=1e-3, lam_falkon=1e-5, iters=30, m_cap=300)
    pred = model.predict(x)
    base = jnp.mean((y - y.mean()) ** 2)
    assert float(jnp.mean((pred - y) ** 2)) < 0.05 * float(base)


def test_falkon_with_pallas_backend_matches():
    from repro.core import PallasBackend

    x, y, z = _problem(n=400, m=64)
    lam = 1e-3
    fk = falkon_fit(KERN, x, y, z, lam, iters=25,
                    backend=PallasBackend(interpret=True, bn=256))
    ref = falkon_fit(KERN, x, y, z, lam, iters=25, backend="jnp")
    assert float(jnp.linalg.norm(fk.alpha - ref.alpha)
                 / jnp.linalg.norm(ref.alpha)) < 1e-3


def test_preconditioner_host_eigh_matches_xla(monkeypatch):
    # on a TPU the preconditioner's eigh runs in host LAPACK (XLA's TPU
    # eigh does not compile at thousands of centers); same factors here
    from repro.core import falkon as falkon_mod

    x = jax.random.normal(jax.random.PRNGKey(3), (300, 5))
    z, a = x[:96], jnp.linspace(0.5, 2.0, 96)
    kern = make_kernel("gaussian", sigma=1.5)
    want = falkon_mod.make_preconditioner(kern, z, a, 1e-3, 300)
    monkeypatch.setattr(falkon_mod.jax, "default_backend", lambda: "tpu")
    got = jax.jit(lambda zz: falkon_mod.make_preconditioner(kern, zz, a, 1e-3, 300))(z)
    # same spectrum and the same reconstruction Q T^2 Q^T of A^-1/2 K_MM A^-1/2
    # (B B^T itself inverts the near-null eigenvalues, so it is compared
    # through what it is built from)
    np.testing.assert_allclose(got.t_diag, want.t_diag, rtol=1e-4, atol=1e-6)
    recon = lambda p: (p.q_iso * p.t_diag**2) @ p.q_iso.T  # noqa: E731
    np.testing.assert_allclose(recon(got), recon(want), atol=1e-4)
