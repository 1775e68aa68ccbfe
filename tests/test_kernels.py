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


@pytest.mark.parametrize("kind", ["linear", "gaussian"])
@pytest.mark.parametrize("d", [1, 18, 21, 22, 28, 90, 128, 130])
def test_packed_cross_product_is_as_exact_as_highest(d, kind):
    """One bf16 contraction of the packed operands against a float64 oracle:
    x.z for a dot-product family, the squared distance for a distance family
    (norms folded in). Its largest error, relative to |x||z| (to ||x||^2 +
    ||z||^2 for the distance), is at most 1.25x that of the fp32 HIGHEST
    product (and the kernel's fp32 distance assembly) on the same data."""
    from repro.kernels.falkon_matvec.falkon_matvec import _contract
    from repro.kernels.falkon_matvec.ops import _pack

    x = jax.random.normal(jax.random.PRNGKey(d), (2048, d))
    z = jax.random.normal(jax.random.PRNGKey(d + 1), (512, d))
    x64, z64 = np.asarray(x, np.float64), np.asarray(z, np.float64)
    xz = x64 @ z64.T
    packed = _contract(_pack(x, 2048, 0, kind), _pack(z, 512, 1, kind), None)
    highest = _contract(x, z, jax.lax.Precision.HIGHEST)
    if kind == "linear":
        ref = xz
        scale = np.linalg.norm(x64, axis=1)[:, None] * np.linalg.norm(z64, axis=1)[None]
    else:
        nx, nz = (x64 ** 2).sum(1)[:, None], (z64 ** 2).sum(1)[None]
        ref, scale = nx + nz - 2 * xz, nx + nz
        highest = (jnp.sum(x * x, -1)[:, None] + jnp.sum(z * z, -1)[None, :]
                   - 2.0 * highest)
    err = [np.max(np.abs(np.asarray(a, np.float64) - ref) / scale) for a in (packed, highest)]
    assert err[0] <= 1.25 * err[1], err


def _packed_case(op: str, x, z, v, w, mask, **kw):
    """(kernel output, oracle) of one K_nM operator form on the same data."""
    from repro.families import get_family
    from repro.kernels.falkon_matvec import ops, ref

    inv = float(get_family(kw["kind"]).inv_scale(1.5))
    panel = jnp.stack([v, w], 1)
    if op == "falkon_matvec":
        return ops.falkon_matvec(x, z, v, 1.5, **kw), ref.falkon_matvec_ref(x, z, v, inv)
    if op == "falkon_matvec_panel":
        return (ops.falkon_matvec(x, z, panel, 1.5, **kw),
                ref.falkon_matvec_ref(x, z, panel, inv))
    if op == "falkon_matvec_masked":
        masks = jnp.stack([mask, 1.0 - mask], 1)
        return (ops.falkon_matvec(x, z, panel, 1.5, mask=masks, **kw),
                ref.falkon_matvec_masked_ref(x, z, panel, masks, inv))
    y = jnp.sin(x[:, 0] * 3.0)
    if op == "knm_t":
        return ops.knm_t(x, z, y, 1.5, **kw), ref.knm_t_ref(x, z, y, inv)
    return ops.knm_matvec(x, z, v, 1.5, **kw), ref.knm_matvec_ref(x, z, v, inv)


@pytest.mark.parametrize("d", [18, 28, 90])
@pytest.mark.parametrize("op", ["falkon_matvec", "falkon_matvec_panel", "falkon_matvec_masked",
                                "knm_t", "knm_matvec"])
def test_packed_operators_match_reference(op, d):
    """Every K_nM operator form on the packed path (d = 18, 28, 90: the
    cells' widths) agrees with its fp32 oracle at the fp32 tolerances."""
    n, m = 600, 200
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d)) / np.sqrt(d)
    z = jax.random.normal(jax.random.PRNGKey(1), (m, d)) / np.sqrt(d)
    v = jax.random.normal(jax.random.PRNGKey(2), (m,))
    w = jax.random.normal(jax.random.PRNGKey(3), (m,))
    mask = (jax.random.uniform(jax.random.PRNGKey(4), (n,)) < 0.7).astype(jnp.float32)
    out, ref = _packed_case(op, x, z, v, w, mask, kind="gaussian", interpret=True, bn=256)
    assert out.shape == ref.shape
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4 * float(jnp.abs(ref).max()))


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("path", ["packed", "bf16"])
def test_packed_fit_moves_predictions_no_more_than_rounding(path, seed, monkeypatch):
    """At HIGGS's conditioning (d = 28, sigma = 22, lam = 1e-8, 20 CG
    iterations), where a change of rounding alone moves held-out
    predictions by ~1e-3, a fit through the packed operator lies no further
    from the fp32 HIGHEST fit than twice what a rounding-only change moves
    it (fp32 at another tile height; the jnp operator); a fit with the
    lossy bf16 cast lies far outside. The preconditioner is the same in
    every fit: host LAPACK eigh, as on a TPU."""
    from repro.core import JnpBackend, PallasBackend, falkon_fit, make_kernel
    from repro.core import falkon as falkon_mod
    from repro.kernels.falkon_matvec import ops

    def host_eigh(a):
        shapes = (jax.ShapeDtypeStruct(a.shape[:1], a.dtype),
                  jax.ShapeDtypeStruct(a.shape, a.dtype))
        return jax.pure_callback(falkon_mod._host_eigh, shapes, a)

    jax.clear_caches()  # no preconditioner traced with another eigh
    monkeypatch.setattr(falkon_mod, "_eigh", host_eigh)
    n, m = 4096, 256
    k = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = jax.random.normal(k[0], (n + 2048, 28))
    y = jnp.sign(jnp.sin(2 * x[:, 0]) + x[:, 1] * x[:, 2]
                 + 0.3 * jax.random.normal(k[1], (n + 2048,)))
    xte, x, y, z = x[n:], x[:n], y[:n], x[:m]
    kern = make_kernel("gaussian", sigma=22.0)

    def predict(be):
        model = falkon_fit(kern, x, y, z, 1e-8, iters=20, backend=be)
        return np.asarray(JnpBackend().knm_matvec(kern, xte, z, model.alpha), np.float64)

    def gap(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    try:
        got = predict(PallasBackend(interpret=True, bn=256, bf16=path == "bf16"))
        jnp_fit = predict(JnpBackend())
        monkeypatch.setattr(ops, "takes_packed", lambda *a: False)
        fp32 = predict(PallasBackend(interpret=True, bn=256))
        rounding = max(gap(predict(PallasBackend(interpret=True, bn=512)), fp32),
                       gap(jnp_fit, fp32))
    finally:
        jax.clear_caches()  # leave no program traced with the host eigh
    assert (gap(got, fp32) <= 2.0 * rounding) == (path == "packed"), (gap(got, fp32), rounding)


@pytest.mark.parametrize("d,bf16,path", [(18, False, "packed"), (28, False, "packed"),
                                         (90, False, "packed"), (128, False, "fp32"),
                                         (440, False, "packed"), (1536, False, "fp32"),
                                         (18, True, "bf16")])
def test_cross_product_path_follows_d(d, bf16, path):
    """The cross product takes the packed operands where they need fewer MXU
    passes than HIGHEST (d = 18, 28, 90, and TIMIT's 440: 21 passes against
    24), not at d = 128 or at ImageNet's 1536 features (73 against 72), nor
    with bf16; each pick is counted as ``kernels.cross.<path>``."""
    from repro.kernels.falkon_matvec import ops
    from repro.runtime import spans

    x = jax.random.normal(jax.random.PRNGKey(0), (300, d)) / np.sqrt(d)
    z = x[:40]
    names = [f"kernels.cross.{p}" for p in ("packed", "fp32", "bf16")]
    before = {k: spans.taken(k) for k in names}
    ops.falkon_matvec(x, z, jnp.ones((40,)), 1.5, interpret=True, bn=256, bf16=bf16)
    got = {k: spans.taken(k) - before[k] for k in names}
    assert got == {k: int(k.endswith(path)) for k in names}
    assert ops.takes_packed(d, "gaussian", bf16) == (path == "packed")


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
