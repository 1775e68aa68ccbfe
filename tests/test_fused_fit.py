"""Fused whole-fit path (core/falkon.py, DESIGN.md §2.4): one compiled call
per shape bucket, no host-side CG dispatches on repeat fits, numerical
parity with the host-driven path."""
import jax
import jax.numpy as jnp
import pytest

from repro.core import PallasBackend, falkon_fit, make_kernel, nystrom_krr
from repro.runtime import spans

KERN = make_kernel("gaussian", sigma=1.5)


def _problem(n=500, m=64, seed=0):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (n, 6))
    y = jnp.sin(2 * x[:, 0]) + 0.3 * x[:, 1] ** 2
    return x, y, x[:m]


def test_fused_fit_compiles_once_per_bucket():
    """Second fit in the same shape bucket is a single cached compiled call:
    zero retraces, hence zero host-side CG iteration dispatches.

    m=48 / iters=19 are unique to this test so fits compiled by other test
    files (the jit cache is process-wide) cannot mask the first trace.
    """
    x, y, z = _problem(m=48)
    t0 = spans.retraces("falkon.fused_fit")
    m1 = falkon_fit(KERN, x, y, z, 1e-3, iters=19, backend="jnp")
    traces_after_first = spans.retraces("falkon.fused_fit")
    assert traces_after_first == t0 + 1  # first call compiled the bucket
    # same shapes -> cache hit
    falkon_fit(KERN, x, y, z, 1e-3, iters=19, backend="jnp")
    # different n in the same row bucket -> still a cache hit
    falkon_fit(KERN, x[:400], y[:400], z, 1e-3, iters=19, backend="jnp")
    # lam and the kernel bandwidth are traced -> still a cache hit
    falkon_fit(KERN, x, y, z, 1e-4, iters=19, backend="jnp")
    falkon_fit(make_kernel("gaussian", sigma=2.5), x, y, z, 1e-3, iters=19,
               backend="jnp")
    assert spans.retraces("falkon.fused_fit") == traces_after_first
    # different iters is a static key -> recompiles (sanity that the counter
    # actually observes tracing)
    falkon_fit(KERN, x, y, z, 1e-3, iters=18, backend="jnp")
    assert spans.retraces("falkon.fused_fit") == traces_after_first + 1
    assert m1.alpha.shape == (z.shape[0],)


def test_fused_matches_host_path():
    x, y, z = _problem()
    fused = falkon_fit(KERN, x, y, z, 1e-3, iters=25, backend="jnp")
    host = falkon_fit(KERN, x, y, z, 1e-3, iters=25, backend="jnp", fused=False)
    pf, ph = fused.predict(x), host.predict(x)
    assert float(jnp.linalg.norm(pf - ph) / jnp.linalg.norm(ph)) < 1e-3


def test_fused_matches_nystrom_solution():
    """The compiled solve still converges to the Def. 4 direct solution."""
    x, y, z = _problem(n=400)
    fk = falkon_fit(KERN, x, y, z, 1e-3, iters=40, backend="jnp")
    ny = nystrom_krr(KERN, x, y, z, 1e-3)
    pf, pn = fk.predict(x), ny.predict(x)
    assert float(jnp.linalg.norm(pf - pn) / jnp.linalg.norm(pn)) < 1e-3


def test_fused_respects_weighted_preconditioner():
    x, y, z = _problem(n=300, m=32)
    a = jax.random.uniform(jax.random.PRNGKey(3), (32,), minval=0.5, maxval=2.0)
    fused = falkon_fit(KERN, x, y, z, 1e-3, a_diag=a, iters=25, backend="jnp")
    host = falkon_fit(KERN, x, y, z, 1e-3, a_diag=a, iters=25, backend="jnp",
                      fused=False)
    assert float(jnp.linalg.norm(fused.alpha - host.alpha)
                 / jnp.linalg.norm(host.alpha)) < 1e-3


def test_fused_flag_validation():
    x, y, z = _problem(n=200, m=16)
    with pytest.raises(ValueError, match="jit-safe"):
        falkon_fit(KERN, x, y, z, 1e-3, backend=PallasBackend(interpret=True),
                   fused=True)
    with pytest.raises(ValueError, match="callback"):
        falkon_fit(KERN, x, y, z, 1e-3, backend="jnp", fused=True,
                   callback=lambda i, m: None)
    # callback quietly takes the host path when fused is unset
    seen = []
    falkon_fit(KERN, x, y, z, 1e-3, iters=3, backend="jnp",
               callback=lambda i, m: seen.append(i))
    assert seen == [0, 1, 2]
