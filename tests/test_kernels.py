"""Per-kernel Pallas (interpret=True) vs pure-jnp oracle, swept over
shapes (incl. non-divisible tails) and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.gram.ops import gram, gram_reference
from repro.kernels.quadform.ops import quadform, quadform_reference
from repro.kernels.falkon_matvec.ops import falkon_matvec
from repro.kernels.falkon_matvec.ref import falkon_matvec_ref
from repro.kernels.flash_attention.ops import flash_attention, flash_attention_reference


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n,m,d", [(256, 256, 128), (300, 130, 17), (64, 512, 64), (1000, 77, 3)])
@pytest.mark.parametrize("kind", ["gaussian", "laplacian", "linear", "matern32", "cauchy"])
def test_gram_shapes(n, m, d, kind):
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    z = jax.random.normal(jax.random.PRNGKey(1), (m, d))
    out = gram(x, z, 1.3, kind=kind, interpret=True)
    ref = gram_reference(x, z, 1.3, kind=kind)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gram_dtypes(dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (257, 40)).astype(dtype)
    z = jax.random.normal(jax.random.PRNGKey(1), (129, 40)).astype(dtype)
    out = gram(x, z, 2.0, interpret=True).astype(jnp.float32)
    ref = gram_reference(x, z, 2.0).astype(jnp.float32)
    np.testing.assert_allclose(out, ref, **_tol(dtype))


@pytest.mark.parametrize("n,m", [(256, 256), (300, 200), (100, 515), (1024, 64)])
def test_quadform_shapes(n, m):
    g = jax.random.normal(jax.random.PRNGKey(0), (n, m))
    w = jax.random.normal(jax.random.PRNGKey(1), (m, m))
    w = w @ w.T / m
    out = quadform(g, w, interpret=True)
    ref = quadform_reference(g, w)
    np.testing.assert_allclose(out, ref, rtol=3e-5, atol=3e-4)


@pytest.mark.parametrize("n,m,d,bn", [(512, 128, 128, 256), (700, 130, 17, 256), (256, 515, 8, 128),
                                     (600, 200, 90, 256)])
def test_falkon_matvec_shapes(n, m, d, bn):
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    z = jax.random.normal(jax.random.PRNGKey(1), (m, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (m,))
    out = falkon_matvec(x, z, v, 1.5, interpret=True, bn=bn)
    ref = falkon_matvec_ref(x, z, v, 1.0 / (2 * 1.5**2))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4 * float(jnp.abs(ref).max()))


@pytest.mark.parametrize("kind", ["laplacian", "linear", "matern32", "cauchy"])
def test_falkon_matvec_all_families(kind):
    """The fused CG matvec consumes every registered family's epilogue."""
    from repro.families import get_family

    x = jax.random.normal(jax.random.PRNGKey(0), (300, 12))
    z = jax.random.normal(jax.random.PRNGKey(1), (70, 12))
    v = jax.random.normal(jax.random.PRNGKey(2), (70,))
    out = falkon_matvec(x, z, v, 1.5, kind=kind, interpret=True, bn=256)
    ref = falkon_matvec_ref(x, z, v, float(get_family(kind).inv_scale(1.5)), kind=kind)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4 * float(jnp.abs(ref).max()))


@pytest.mark.parametrize("n,m,d", [(700, 130, 17), (600, 200, 90)])
@pytest.mark.parametrize("bf16", [False, True])
@pytest.mark.parametrize("kind", ["gaussian", "laplacian", "linear", "matern32", "cauchy"])
@pytest.mark.parametrize("op", ["falkon_matvec", "knm_t"])
def test_vector_path_matches_panel_column(op, kind, bf16, n, m, d):
    """One live column takes the VPU kernels; it agrees with column 0 of a
    2-column panel (the MXU kernels) on the same inputs, with or without
    bf16 cross products, and with the fp32 oracle when bf16 is off."""
    from repro.families import get_family, kernel_family_names
    from repro.kernels.falkon_matvec import ops
    from repro.kernels.falkon_matvec.ref import knm_t_ref

    assert kind in kernel_family_names()
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d)) / np.sqrt(d)
    z = jax.random.normal(jax.random.PRNGKey(1), (m, d)) / np.sqrt(d)
    rows = m if op == "falkon_matvec" else n
    v = jax.random.normal(jax.random.PRNGKey(2), (rows,))
    w = jax.random.normal(jax.random.PRNGKey(3), (rows,))
    fn = getattr(ops, op)
    kw = dict(kind=kind, interpret=True, bn=256, bf16=bf16)
    vec = fn(x, z, v, 1.5, **kw)
    col = fn(x, z, v[:, None], 1.5, **kw)
    panel = fn(x, z, jnp.stack([v, w], 1), 1.5, **kw)
    assert vec.shape == (m,) and col.shape == (m, 1) and panel.shape == (m, 2)
    scale = float(jnp.abs(panel[:, 0]).max())
    np.testing.assert_allclose(vec, panel[:, 0], rtol=1e-5, atol=1e-5 * scale)
    np.testing.assert_array_equal(col[:, 0], vec)
    if not bf16:
        ref = (falkon_matvec_ref if op == "falkon_matvec" else knm_t_ref)(
            x, z, v, float(get_family(kind).inv_scale(1.5)), kind=kind)
        np.testing.assert_allclose(vec, ref, rtol=1e-4, atol=1e-4 * float(jnp.abs(ref).max()))


@pytest.mark.parametrize(
    "hq,hkv,s,d,causal",
    [(4, 4, 256, 128, True), (8, 2, 300, 64, True), (8, 1, 512, 80, True),
     (4, 4, 300, 64, False), (2, 2, 128, 128, False)],
)
def test_flash_attention_shapes(hq, hkv, s, d, causal):
    q = jax.random.normal(jax.random.PRNGKey(0), (2, hq, s, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, hkv, s, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, hkv, s, d))
    out = flash_attention(q, k, v, causal=causal, bq=128, bk=128, interpret=True)
    ref = flash_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 256, 128)).astype(dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 256, 128)).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 256, 128)).astype(dtype)
    out = flash_attention(q, k, v, interpret=True).astype(jnp.float32)
    ref = flash_attention_reference(q, k, v).astype(jnp.float32)
    np.testing.assert_allclose(out, ref, **_tol(dtype))


def test_falkon_matvec_plugs_into_cg():
    """The fused kernels serve falkon_fit through the Pallas backend."""
    from repro.core import PallasBackend, falkon_fit, make_kernel, nystrom_krr

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (400, 6))
    y = jnp.sin(x[:, 0])
    z = x[:80]
    kern = make_kernel("gaussian", sigma=1.5)
    fk = falkon_fit(kern, x, y, z, 1e-3, iters=25,
                    backend=PallasBackend(interpret=True, bn=256))
    ny = nystrom_krr(kern, x, y, z, 1e-3)
    pf, pn = fk.predict(x), ny.predict(x)
    assert float(jnp.linalg.norm(pf - pn) / jnp.linalg.norm(pn)) < 1e-3


@pytest.mark.parametrize("n,m,d", [(512, 128, 64), (700, 130, 17), (600, 200, 90)])
def test_knm_t_kernel_shapes(n, m, d):
    from repro.kernels.falkon_matvec.ops import knm_t
    from repro.kernels.falkon_matvec.ref import knm_t_ref

    x = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    z = jax.random.normal(jax.random.PRNGKey(1), (m, d))
    y = jax.random.normal(jax.random.PRNGKey(2), (n,))
    out = knm_t(x, z, y, 1.5, interpret=True, bn=256)
    ref = knm_t_ref(x, z, y, 1.0 / (2 * 1.5**2))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4 * float(jnp.abs(ref).max()))


@pytest.mark.parametrize("s,chunk,h,p,n", [(96, 32, 4, 8, 16), (80, 32, 2, 16, 8),
                                           (128, 128, 8, 8, 16)])
def test_ssd_kernel_shapes(s, chunk, h, p, n):
    from repro.kernels.ssd.ops import ssd, ssd_reference

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (2, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    b = jax.random.normal(ks[3], (2, s, n)) * 0.5
    c = jax.random.normal(ks[4], (2, s, n)) * 0.5
    y, st = ssd(x, dt, a, b, c, chunk=chunk, interpret=True)
    yr, str_ = ssd_reference(x, dt, a, b, c, chunk=16)  # 16 divides every s
    np.testing.assert_allclose(y, yr, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(st, str_, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_kernel_dtypes(dtype):
    from repro.kernels.ssd.ops import ssd, ssd_reference

    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (1, 64, 4, 8)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 64, 4))).astype(dtype)
    a = -jnp.exp(jax.random.normal(ks[2], (4,)) * 0.3)
    b = (jax.random.normal(ks[3], (1, 64, 16)) * 0.5).astype(dtype)
    c = (jax.random.normal(ks[4], (1, 64, 16)) * 0.5).astype(dtype)
    y, _ = ssd(x, dt, a, b, c, chunk=32, interpret=True)
    yr, _ = ssd_reference(x, dt, a, b, c, chunk=32)
    tol = dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(y.astype(jnp.float32), yr.astype(jnp.float32), **tol)
