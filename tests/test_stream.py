"""Out-of-core streaming subsystem (repro.stream) — DESIGN.md §10.

Parity is measured against JnpBackend (the numerical reference) at the
documented scale-relative 1e-4 for single contractions; end-to-end FALKON
through the stream backend is held to the CG-reassociation class (rel 1e-3)
because the chunk accumulation order differs from the jnp streamer's scan.
The peak-memory tests are the subsystem's core claim: no (n, M) array.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (JnpBackend, PallasBackend, default_backend, falkon_fit,
                        make_kernel, resolve_backend)
from repro.core.bless import bless
from repro.core.leverage import approx_rls_all, uniform_center_set
from repro.runtime import spans
from repro.stream import (ChunkStore, StreamBackend, device_chunks,
                          peak_device_bytes, reset_peak_device_bytes)

JNP = JnpBackend()
KERN = make_kernel("gaussian", sigma=1.5)


def _close(a, b, tol=1e-4):
    a, b = np.asarray(a), np.asarray(b)
    scale = max(1.0, float(np.max(np.abs(a))))
    np.testing.assert_allclose(a, b, rtol=tol, atol=tol * scale)


def _xy(n, d=5, k=None, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d), dtype=np.float32)
    shape = (n,) if k is None else (n, k)
    y = rng.standard_normal(shape).astype(np.float32)
    return x, y


# ---------------------------------------------------------------------------
# ChunkStore data plane
# ---------------------------------------------------------------------------


def test_chunkstore_surface():
    x, y = _xy(103, d=4)
    store = ChunkStore(x, y, chunk=40)
    assert store.shape == (103, 4) and store.ndim == 2 and len(store) == 103
    assert store.n_chunks == 3  # 40 + 40 + 23: tail carries the remainder
    sl = store.chunk_slices()
    assert sl[0] == slice(0, 40) and sl[-1] == slice(80, 103)
    np.testing.assert_array_equal(np.asarray(store[5]), x[5])
    np.testing.assert_array_equal(np.asarray(store[10:20]), x[10:20])
    idx = jnp.asarray([7, 3, 99])
    np.testing.assert_array_equal(np.asarray(store[idx]), x[[7, 3, 99]])
    np.testing.assert_array_equal(np.asarray(jnp.asarray(store)), x)  # O(n d) hatch


def test_chunkstore_rejects_traced_gather():
    store = ChunkStore(_xy(32)[0])
    with pytest.raises(TypeError, match="concrete"):
        jax.jit(lambda i: store[i])(jnp.asarray([0, 1]))


def test_chunkstore_validates():
    with pytest.raises(ValueError, match=r"\(n, d\)"):
        ChunkStore(np.zeros((4,), np.float32))
    with pytest.raises(ValueError, match="rows"):
        ChunkStore(np.zeros((4, 2), np.float32), np.zeros((5,), np.float32))


def test_device_chunks_cover_exactly():
    x, y = _xy(97, d=3)
    store = ChunkStore(x, chunk=16)
    xs, ys = [], []
    for xb, yb in device_chunks(store, aux=y):
        xs.append(np.asarray(xb))
        ys.append(np.asarray(yb))
    np.testing.assert_array_equal(np.concatenate(xs), x)
    np.testing.assert_array_equal(np.concatenate(ys), y)


# ---------------------------------------------------------------------------
# Tile-size sweep: non-divisible n, chunk=1, chunk > n
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chunk", [1, 16, 37, 137, 500])
def test_chunk_size_sweep(chunk):
    n, m = 137, 12  # prime n: never divisible by the sweep's chunks > 1
    x, y = _xy(n)
    z = jnp.asarray(x[:m])
    xd = jnp.asarray(x)
    store = ChunkStore(x, chunk=chunk)
    sb = StreamBackend()
    v = jnp.linspace(-1.0, 1.0, m)
    _close(sb.knm_matvec(KERN, store, z, v), JNP.knm_matvec(KERN, xd, z, v))
    _close(sb.knm_t(KERN, store, z, jnp.asarray(y)),
           JNP.knm_t(KERN, xd, z, jnp.asarray(y)))
    _close(sb.knm_quadratic(KERN, store, z)(v),
           JNP.knm_quadratic(KERN, xd, z)(v))
    _close(sb.gram_block(KERN, store, z), JNP.gram_block(KERN, xd, z))


def test_backend_chunk_override_beats_store_chunk():
    x, _ = _xy(64)
    store = ChunkStore(x, chunk=8)
    sb = StreamBackend(chunk=50)  # backend chunk wins over the store's
    z = jnp.asarray(x[:6])
    reset_peak_device_bytes()
    sb.knm_matvec(KERN, store, z, jnp.ones((6,)))
    # two 50-row (tail 14) chunks resident at once, plus their tiles
    assert peak_device_bytes() <= 4 * (2 * 50 * x.shape[1] + 50 * 6) + 256


# ---------------------------------------------------------------------------
# Kernel families x multi-RHS panels
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", ["gaussian", "laplacian", "linear",
                                    "matern32", "cauchy"])
@pytest.mark.parametrize("k", [1, 3, 8])
def test_family_multirhs_parity(family, k):
    kern = make_kernel(family, sigma=1.8)
    n, m, d = 193, 14, 4
    x, ym = _xy(n, d=d, k=k, seed=3)
    y = ym[:, 0] if k == 1 else ym  # k=1 exercises the 1-D contract
    z = jnp.asarray(x[:m])
    xd = jnp.asarray(x)
    store = ChunkStore(x, chunk=48)
    sb = StreamBackend()
    rng = np.random.default_rng(4)
    v = jnp.asarray(rng.standard_normal((m,) if k == 1 else (m, k)).astype(np.float32))
    out = sb.knm_matvec(kern, store, z, v)
    assert out.shape == ((n,) if k == 1 else (n, k))
    _close(out, JNP.knm_matvec(kern, xd, z, v))
    kty = sb.knm_t(kern, store, z, jnp.asarray(y))
    assert kty.shape == ((m,) if k == 1 else (m, k))
    _close(kty, JNP.knm_t(kern, xd, z, jnp.asarray(y)))
    _close(sb.knm_quadratic(kern, store, z)(v),
           JNP.knm_quadratic(kern, xd, z)(v))


def test_quadform_and_rls_parity():
    n, mbuf = 211, 16
    x, _ = _xy(n, seed=5)
    xd = jnp.asarray(x)
    store = ChunkStore(x, chunk=64)
    sb = StreamBackend()
    cs = uniform_center_set(jnp.arange(12), n, mbuf)
    z = xd[cs.idx]
    lamn = jnp.asarray(1e-2 * n, jnp.float32)
    reg = jnp.where(cs.mask, lamn * cs.weight, 1.0)
    _close(sb.masked_quadform(KERN, store, z, cs.mask, reg),
           JNP.masked_quadform(KERN, xd, z, cs.mask, reg))
    _close(sb.rls_scores(KERN, store, z, cs.mask, reg, lamn),
           JNP.rls_scores(KERN, xd, z, cs.mask, reg, lamn))


# ---------------------------------------------------------------------------
# End-to-end: falkon_fit / predict / BLESS on a host-resident store
# ---------------------------------------------------------------------------


def test_falkon_fit_predict_parity():
    n, m, k = 1200, 48, 3
    x, ym = _xy(n, d=6, k=k, seed=7)
    z = jnp.asarray(x[:m])
    store = ChunkStore(x, chunk=256)
    lam = 1e-4
    ref = falkon_fit(KERN, jnp.asarray(x), jnp.asarray(ym), z, lam, iters=12,
                     backend=JNP, fused=False)
    mod = falkon_fit(KERN, store, jnp.asarray(ym), z, lam, iters=12,
                     backend=StreamBackend())
    xq = jnp.asarray(x[:200])
    p_ref, p_str = ref.predict(xq, backend=JNP), mod.predict(xq)
    # chunk-order accumulation reassociates the CG sums: rel 1e-3 class
    rel = float(jnp.max(jnp.abs(p_ref - p_str)) / jnp.max(jnp.abs(p_ref)))
    assert rel < 1e-3
    # predict straight off the store as well (the serving path at big n)
    p_store = mod.predict(store)
    assert p_store.shape == (n, k)
    rel = float(jnp.max(jnp.abs(p_store[:200] - p_ref)) / jnp.max(jnp.abs(p_ref)))
    assert rel < 1e-3


def test_bless_on_store_matches_jnp_scale():
    n = 900
    x, _ = _xy(n, d=4, seed=11)
    key = jax.random.PRNGKey(2)
    lam = 2e-3
    res_ref = bless(key, jnp.asarray(x), KERN, lam, backend=JNP, m_cap=300)
    res_str = bless(key, ChunkStore(x, chunk=200), KERN, lam,
                    backend=StreamBackend(), m_cap=300)
    assert len(res_str.levels) == len(res_ref.levels)
    m_ref, m_str = res_ref.final.m_h, res_str.final.m_h
    # same draws up to fp reassociation in the scores: sizes agree closely
    assert 0.5 * m_ref <= m_str <= 2.0 * m_ref
    # the sampled set must score equivalently through both paths
    s_ref = approx_rls_all(KERN, jnp.asarray(x), res_str.final.centers,
                           jnp.asarray(lam), backend=JNP)
    s_str = approx_rls_all(KERN, ChunkStore(x, chunk=200),
                           res_str.final.centers, jnp.asarray(lam),
                           backend=StreamBackend())
    _close(s_str, s_ref, tol=2e-4)


# ---------------------------------------------------------------------------
# The memory claim: no (n, M) materialization
# ---------------------------------------------------------------------------


def test_peak_memory_stays_far_below_knm():
    n, m, d, chunk = 60_000, 64, 8, 4096
    x, y = _xy(n, d=d, seed=13)
    store = ChunkStore(x, y, chunk=chunk)
    z = store[np.arange(m)]
    sb = StreamBackend()
    reset_peak_device_bytes()
    op = sb.knm_quadratic(KERN, store, z)
    v = jnp.ones((m,), jnp.float32)
    jax.block_until_ready(op(v))
    jax.block_until_ready(sb.knm_t(KERN, store, z, jnp.asarray(y)))
    peak = peak_device_bytes()
    knm_bytes = 4 * n * m  # what a materialized K_nM would cost
    working_set = 4 * (2 * chunk * d + chunk * m)  # 2 chunks + 1 tile
    assert peak <= working_set + 4 * 2 * chunk  # slack: y chunks
    assert peak < knm_bytes / 10
    # and the bound is n-independent: double n, same working set
    x2, y2 = _xy(2 * n, d=d, seed=14)
    reset_peak_device_bytes()
    jax.block_until_ready(
        sb.knm_quadratic(KERN, ChunkStore(x2, chunk=chunk), z)(v))
    assert peak_device_bytes() <= working_set + 4 * 2 * chunk


def test_compiled_chunk_step_memory_is_n_independent():
    """Cost-analysis proof: the compiled pass's temp footprint depends on
    (chunk, M), never on n — streaming 10x the rows of a device-resident X
    runs the same chunk step with the same temporary allocations."""
    from repro.stream.backend import _device_pass, _quad_chunk, _static_kernel

    m, d, chunk = 32, 6, 512
    z = jnp.zeros((m, d), jnp.float32)
    v = jnp.zeros((m,), jnp.float32)
    acc = jnp.zeros((m,), jnp.float32)
    temps = []
    for n in (4 * chunk + 100, 40 * chunk + 100):
        x = jnp.zeros((n, d), jnp.float32)
        compiled = _device_pass.lower(
            x, None, (z, v), acc, step=_quad_chunk, kernel=_static_kernel(KERN),
            inner=StreamBackend(inner=JNP)._chunk_inner(chunk), chunk=chunk,
            rows=False).compile()
        analysis = compiled.memory_analysis()
        if analysis is None:  # platform without memory analysis
            pytest.skip("memory_analysis unavailable")
        temps.append(int(analysis.temp_size_in_bytes))
    # the footprint is a few (chunk, m) tiles — nothing anywhere near (n, m)
    assert temps[0] == temps[1]
    assert temps[1] <= 4 * chunk * m * 8


def test_gram_block_materialization_guard():
    x, _ = _xy(4096, d=3)
    store = ChunkStore(x, chunk=1024)
    z = store[np.arange(8)]
    sb = StreamBackend(materialize_elems=4096 * 8 - 1)
    with pytest.raises(ValueError, match="refuses to materialize"):
        sb.gram_block(KERN, store, z)
    # raising the guard (small problems) streams and concatenates fine
    ok = StreamBackend().gram_block(KERN, store, z)
    assert ok.shape == (4096, 8)


# ---------------------------------------------------------------------------
# Registry / composition / selection
# ---------------------------------------------------------------------------


def test_registry_and_composition():
    assert isinstance(resolve_backend("stream"), StreamBackend)
    comp = resolve_backend("stream:pallas")
    assert isinstance(comp, StreamBackend)
    assert comp.inner.name == "pallas"
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend("stream:cuda")
    with pytest.raises(ValueError, match="not composable"):
        resolve_backend("jnp:pallas")


def test_env_stream_spec(monkeypatch):
    monkeypatch.setenv("REPRO_BACKEND", "stream")
    assert isinstance(default_backend(), StreamBackend)
    monkeypatch.setenv("REPRO_BACKEND", "stream:jnp")
    be = default_backend()
    assert isinstance(be, StreamBackend) and isinstance(be.inner, JnpBackend)
    monkeypatch.setenv("REPRO_BACKEND", "stream:cuda")
    with pytest.raises(ValueError, match="REPRO_BACKEND"):
        default_backend()


def test_stream_threshold_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_STREAM_MIN_ROWS", "1000")
    be = default_backend(2000)
    assert isinstance(be, StreamBackend)
    assert isinstance(be.inner, JnpBackend)  # wraps the heuristic's pick
    monkeypatch.setenv("REPRO_STREAM_MIN_ROWS", "100000")
    assert isinstance(default_backend(2000), JnpBackend)


def test_with_inner_is_pure():
    base = StreamBackend(chunk=1000)
    swapped = base.with_inner(JnpBackend(block=64))
    assert swapped.chunk == 1000 and swapped.inner == JnpBackend(block=64)
    assert base.inner == JnpBackend()  # frozen: original untouched
    assert dataclasses.asdict(base)  # still a plain frozen dataclass


# ---------------------------------------------------------------------------
# PR 9 scenarios at out-of-core n: masked ops, classifier, variance
# ---------------------------------------------------------------------------


def test_masked_quadratic_streams_mask_as_aux():
    """The (n, k) mask panel rides the chunk iterator next to X — masked
    ops agree with the jnp seam and never put the whole mask on device."""
    n, m, k, chunk = 40_000, 32, 4, 2048
    x, _ = _xy(n, d=5, seed=19)
    rng = np.random.default_rng(20)
    mask = (rng.uniform(size=(n, k)) > 0.25).astype(np.float32)
    z = jnp.asarray(x[:m])
    v = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    store = ChunkStore(x, chunk=chunk)
    sb = StreamBackend()
    reset_peak_device_bytes()
    out = sb.knm_quadratic(KERN, store, z, mask=mask)(v)
    peak = peak_device_bytes()
    _close(out, JNP.knm_quadratic(KERN, jnp.asarray(x), z,
                                  mask=jnp.asarray(mask))(v))
    # working set: 2 x-chunks + 2 mask-chunks + one (chunk, m) tile — the
    # full (n, k) mask (640 KB) must never be device-resident at once
    working_set = 4 * (2 * chunk * 5 + 2 * chunk * k + chunk * m)
    assert peak <= working_set + 4096
    assert peak < 4 * n * k  # < one full mask panel


def test_classifier_end_to_end_out_of_core():
    """FalkonClassifier on a host-resident ChunkStore: the panel fit, the
    margin predict, and the argmax labels all stream — peak device bytes
    stay in the working-set class, far below any (n, M) array."""
    from repro.api import FalkonClassifier, FitConfig, UniformSampler

    n, d, chunk = 30_000, 5, 2048
    rng = np.random.default_rng(23)
    labels = np.arange(n) % 3
    means = rng.standard_normal((3, d)).astype(np.float32) * 3.0
    x = means[labels] + rng.standard_normal((n, d)).astype(np.float32)
    store = ChunkStore(x, chunk=chunk)
    clf = FalkonClassifier(
        kernel=KERN, sampler=UniformSampler(m=64),
        config=FitConfig(lam=1e-4, iters=8, backend=StreamBackend()))
    reset_peak_device_bytes()
    clf.fit(store, labels)
    pred = clf.predict(store)
    peak = peak_device_bytes()
    assert pred.shape == (n,)
    acc = float(np.mean(pred == labels))
    assert acc > 0.9, acc
    # the fit + predict never materialize K_nM (4 n M = 7.7 MB here); the
    # O(n) device arrays are the (n, 3) margin panel and the fit targets
    assert peak < 4 * n * 64 / 2
    proba = clf.predict_proba(store)
    assert proba.shape == (n, 3)
    np.testing.assert_allclose(np.asarray(jnp.sum(proba, axis=1)), 1.0,
                               rtol=1e-5)


def test_predictive_variance_out_of_core():
    """predictive_variance on a ChunkStore streams the fused RLS scorer:
    parity with the jnp seam, working-set peak memory."""
    from repro.api import FalkonRegressor, FitConfig, UniformSampler

    n, d, chunk = 30_000, 5, 2048
    x, y = _xy(n, d=d, seed=29)
    store = ChunkStore(x, chunk=chunk)
    est = FalkonRegressor(
        kernel=KERN, sampler=UniformSampler(m=64),
        config=FitConfig(lam=1e-4, iters=8, backend=StreamBackend()))
    est.fit(store, jnp.asarray(y))
    reset_peak_device_bytes()
    var = est.predictive_variance(store)
    peak = peak_device_bytes()
    assert var.shape == (n,) and bool(jnp.all(var >= 0.0))
    ref = est.model_.predictive_variance(jnp.asarray(x[:512]), backend="jnp")
    _close(var[:512], ref, tol=1e-4)
    # the scorer holds 2 x-chunks + one (chunk, M) tile + the (n,) output
    working_set = 4 * (2 * chunk * d + chunk * 64 + n)
    assert peak <= working_set + 4096
    # return_std composes on the store too
    pred, std = est.predict(store, return_std=True)
    assert pred.shape == (n,) and std.shape == (n,)
    _close(std, jnp.sqrt(var), tol=1e-6)


def test_estimator_front_door_accepts_store():
    from repro.api import ChunkStore as ApiChunkStore
    from repro.api import FalkonRegressor, FitConfig, UniformSampler

    assert ApiChunkStore is ChunkStore
    n = 600
    x, y = _xy(n, d=4, seed=17)
    est = FalkonRegressor(
        kernel=KERN, sampler=UniformSampler(m=32),
        config=FitConfig(lam=1e-4, iters=8, backend=StreamBackend()))
    est.fit(ChunkStore(x, chunk=128), jnp.asarray(y[:, 0] if y.ndim == 2 else y))
    pred = est.predict(ChunkStore(x, chunk=128))
    assert pred.shape == (n,)
    ref = est.predict(jnp.asarray(x))
    _close(pred, ref, tol=1e-4)


# ---------------------------------------------------------------------------
# The fused streamed pass: inner fused operators, one program per shape,
# HIGHEST-precision chunk steps
# ---------------------------------------------------------------------------

PALLAS = PallasBackend(interpret=True)


def _nystrom_reference(x, y, z, lam):
    """Plain float64 Nystrom KRR on centers z (A = I): predictions at x of
    alpha = (K_nM^T K_nM + lam n K_MM)^+ K_nM^T y."""
    x, y, z = (np.asarray(a, np.float64) for a in (x, y, z))

    def gauss(a, b):
        d2 = (a * a).sum(1)[:, None] + (b * b).sum(1)[None, :] - 2 * a @ b.T
        return np.exp(-np.maximum(d2, 0) / (2 * 1.5 ** 2))

    knm, kmm = gauss(x, z), gauss(z, z)
    h = knm.T @ knm + lam * x.shape[0] * kmm
    return knm @ (np.linalg.pinv(h, rcond=1e-12) @ (knm.T @ y))


@pytest.mark.parametrize("source", ["device", "store"])
@pytest.mark.parametrize("k", [1, 3])
def test_fused_stream_fit_matches_plain_reference(k, source):
    """A fit through ``StreamBackend(inner=PallasBackend)`` on 4 chunks and
    a ragged 37-row tail lands on the plain float64 Nystrom solution."""
    chunk, m, lam = 96, 24, 1e-3
    n = 4 * chunk + 37
    x, ym = _xy(n, d=5, k=3, seed=31)
    y = ym[:, 0] if k == 1 else ym
    z = jnp.asarray(x[:m])
    data = jnp.asarray(x) if source == "device" else ChunkStore(x, chunk=chunk)
    mod = falkon_fit(KERN, data, jnp.asarray(y), z, lam, iters=40,
                     backend=StreamBackend(inner=PALLAS, chunk=chunk))
    want = _nystrom_reference(x, y, x[:m], lam)
    got = np.asarray(mod.predict(jnp.asarray(x), backend=JNP))
    assert got.shape == want.shape
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-3


@pytest.mark.parametrize("source", ["device", "store"])
def test_stream_pass_runs_the_fused_kernels_per_chunk(source):
    """Each CG pass contracts every chunk through the inner backend's fused
    VPU kernels: the vector path is picked, every chunk is counted, and
    only host sources put bytes on the device."""
    chunk, m, iters = 64, 20, 4
    n = 5 * chunk + 11
    x, y = _xy(n, d=3, seed=37)
    data = jnp.asarray(x) if source == "device" else ChunkStore(x, chunk=chunk)
    names = ("kernels.falkon_matvec.vector", "kernels.knm_t.vector", "stream.chunks",
             "stream.h2d_bytes")

    def read():
        return {k: spans.taken(k) + spans.counted(k) for k in names}

    before = read()
    falkon_fit(KERN, data, jnp.asarray(y), jnp.asarray(x[:m]), 1e-3, iters=iters,
               backend=StreamBackend(inner=PALLAS, chunk=chunk))
    got = {k: v - before[k] for k, v in read().items()}
    assert got["kernels.falkon_matvec.vector"] >= 1 and got["kernels.knm_t.vector"] >= 1
    assert got["stream.chunks"] == 6 * (iters + 1)  # 5 chunks + tail, iters + rhs passes
    assert got["stream.h2d_bytes"] == (0 if source == "device"
                                       else (iters + 1) * 4 * n * 3)


def _dots(jaxpr):
    """Every dot_general in ``jaxpr`` and in the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                inner = getattr(sub, "jaxpr", sub)
                if isinstance(inner, jax.extend.core.Jaxpr):
                    yield from _dots(inner)


def _pass_jaxprs(jaxpr):
    """The sub-jaxprs of the streamed passes (``_device_pass``) in ``jaxpr``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("jit", "pjit") and eqn.params["name"] == "_device_pass":
            yield eqn.params["jaxpr"].jaxpr
        else:
            for p in eqn.params.values():
                inner = getattr(p, "jaxpr", p)
                if isinstance(inner, jax.extend.core.Jaxpr):
                    yield from _pass_jaxprs(inner)


@pytest.mark.parametrize("inner", ["pallas", "jnp"])
def test_every_chunk_step_dot_requests_highest(inner):
    """A DEFAULT float32 dot runs as one bf16 pass on a TPU: every dot of a
    streamed pass's chunk step, for each operator, asks for HIGHEST."""
    be = StreamBackend(inner=PALLAS if inner == "pallas" else JNP, chunk=64)
    n, m = 3 * 64 + 9, 16
    x, y = _xy(n, d=4, seed=41)
    x, y = jnp.asarray(x), jnp.asarray(y)
    z, v = x[:m], jnp.linspace(-1.0, 1.0, m)
    mask, reg = jnp.ones((m,), bool), jnp.full((m,), 2.0)
    calls = {
        "knm_quadratic": lambda: be.knm_quadratic(KERN, x, z)(v),
        "knm_quadratic_masked": lambda: be.knm_quadratic(KERN, x, z, mask=y > 0)(v),
        "knm_t": lambda: be.knm_t(KERN, x, z, y),
        "knm_matvec": lambda: be.knm_matvec(KERN, x, z, jnp.stack([v, v], 1)),
        "masked_quadform": lambda: be.masked_quadform(KERN, x, z, mask, reg),
        "rls_scores": lambda: be.rls_scores(KERN, x, z, mask, reg, jnp.float32(2.0)),
    }
    for name, call in calls.items():
        (body,) = _pass_jaxprs(jax.make_jaxpr(call)().jaxpr)
        dots = list(_dots(body))
        assert dots, name
        for eqn in dots:
            prec = eqn.params["precision"]
            prec = prec if isinstance(prec, tuple) else (prec, prec)
            assert prec == (jax.lax.Precision.HIGHEST,) * 2, (name, prec)


@pytest.mark.parametrize("source", ["device", "store"])
def test_traced_programs_do_not_grow_with_chunks(source):
    """A pass over 3 or 30 chunks traces the same programs: on device one
    per shape, holding the chunk step twice (loop body and tail) — the chunk
    loop is a fori_loop, not unrolled; on the host two chunk steps (uniform
    and tail) shared by every n."""
    chunk, m = 32, 8
    name = "stream.device_pass" if source == "device" else "stream.chunk_step"
    traced, steps = [], []
    for nb in (3, 30):
        n = nb * chunk + 5
        x, _ = _xy(n, d=3, seed=43 + nb)
        data = jnp.asarray(x) if source == "device" else ChunkStore(x, chunk=chunk)
        op = StreamBackend(inner=PALLAS, chunk=chunk).knm_quadratic(KERN, data,
                                                                   jnp.asarray(x[:m]))
        before = (spans.retraces(name), spans.taken("kernels.falkon_matvec.vector"))
        for _ in range(3):
            op(jnp.full((m,), 0.5, jnp.float32))
        traced.append(spans.retraces(name) - before[0])
        steps.append(spans.taken("kernels.falkon_matvec.vector") - before[1])
    if source == "device":
        assert traced == [1, 1] and steps == [2, 2]
    else:
        assert traced[0] <= 2 and traced[1] == 0 and steps[1] == 0


def test_scorer_factor_in_host_lapack_matches_the_device_ladder(monkeypatch):
    """On a TPU the scorers' inverse Cholesky factor runs in host LAPACK
    (forced here as on a TPU): it agrees with XLA's, and it climbs the same
    jitter ladder on a singular K_JJ (duplicate centers)."""
    from repro.stream import backend as sb

    x, _ = _xy(64, d=3, seed=47)
    z = jnp.asarray(np.concatenate([x[:20], x[:4]]))  # 4 duplicate centers
    maskf = jnp.ones((24,), jnp.float32)
    kern = sb._static_kernel(KERN)
    host = []
    monkeypatch.setattr(sb, "_host_inv_chol",
                        lambda a, f=sb._host_inv_chol: host.append(1) or f(a))
    for i, reg in enumerate((jnp.full((24,), 0.5), jnp.zeros((24,)))):
        want = sb._inv_chol(z, maskf, reg, kernel=kern, inner=JNP)
        with monkeypatch.context() as mp:
            mp.setattr(sb.jax, "default_backend", lambda: "tpu")
            # a static inner of its own: a trace of its own, on the TPU branch
            got = sb._inv_chol(z, maskf, reg, kernel=kern, inner=JnpBackend(block=99))
        assert len(host) == i + 1
        assert np.isfinite(np.asarray(got)).all()
        kjj = np.asarray(KERN.cross(z, z), np.float64) + np.diag(np.asarray(reg))
        # L^{-1} K_JJ L^{-T}: I where the jitter is negligible, 0 on the
        # duplicates' null space, the same through both factorizations
        prods = [np.asarray(inv, np.float64) @ kjj @ np.asarray(inv, np.float64).T
                 for inv in (got, want)]
        np.testing.assert_allclose(prods[0], prods[1], atol=0.02)
        if float(reg[0]) > 0:
            np.testing.assert_allclose(prods[0], np.eye(24), atol=1e-3)
