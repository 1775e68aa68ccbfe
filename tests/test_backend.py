"""Kernel-operator backend layer: the three hot contractions agree across
jnp / Pallas(interpret) / shard_map to fp32 tolerance, end-to-end BLESS and
FALKON runs included, plus registry/heuristic plumbing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (JnpBackend, PallasBackend, ShardedBackend, backend_names,
                        bless, default_backend, falkon_fit, make_kernel,
                        resolve_backend)
from repro.core.leverage import approx_rls_all

BACKENDS = ["jnp", "pallas", "sharded"]
KERN = make_kernel("gaussian", sigma=1.5)


def _problem(n=400, m=64, d=6, seed=0):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (n, d))
    y = jnp.sin(2 * x[:, 0]) + 0.3 * x[:, 1] ** 2
    z = x[:m]
    return x, y, z


# -- registry / heuristic ----------------------------------------------------


def test_registry_names_and_resolution():
    assert backend_names() == ["guarded", "jnp", "pallas", "sharded", "stream"]
    assert isinstance(resolve_backend("jnp"), JnpBackend)
    assert isinstance(resolve_backend("pallas"), PallasBackend)
    assert isinstance(resolve_backend("sharded"), ShardedBackend)
    from repro.core.backend import GuardedBackend
    assert isinstance(resolve_backend("guarded"), GuardedBackend)
    inst = PallasBackend(interpret=True)
    assert resolve_backend(inst) is inst
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend("cuda")


def test_default_backend_heuristic_off_tpu():
    # the suite runs on 1 CPU device: heuristic must land on the reference
    # in-core, and wrap it in the out-of-core streamer past the row bound
    from repro.stream import StreamBackend
    assert isinstance(default_backend(), JnpBackend)
    assert isinstance(default_backend(1_000_000), JnpBackend)
    big = default_backend(10_000_000)
    assert isinstance(big, StreamBackend)
    assert isinstance(big.inner, JnpBackend)


def test_stream_threshold_follows_device_memory(monkeypatch):
    # a device that reports its memory keeps X in core while it takes at
    # most a quarter of it at 512 B a row: SUSY's 5M rows stay in core on a
    # 16 GB chip, HIGGS-sized 11M rows stream
    from repro.core import backend as backend_mod
    from repro.stream import StreamBackend

    class Device:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    monkeypatch.setattr(jax, "devices", lambda: [Device({"bytes_limit": 16 << 30})])
    assert backend_mod._stream_min_rows() == (16 << 30) // 2048
    assert isinstance(default_backend(5_000_000), JnpBackend)
    assert isinstance(default_backend(11_000_000), StreamBackend)
    monkeypatch.setattr(jax, "devices", lambda: [Device(None)])  # CPU: no stats
    assert backend_mod._stream_min_rows() == backend_mod._STREAM_MIN_ROWS


def test_repro_backend_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_BACKEND", "pallas")
    assert isinstance(default_backend(), PallasBackend)
    assert isinstance(resolve_backend(None), PallasBackend)  # threads through
    monkeypatch.setenv("REPRO_BACKEND", "auto")
    assert isinstance(default_backend(), JnpBackend)  # falls through to heuristic
    monkeypatch.setenv("REPRO_BACKEND", "cuda")
    with pytest.raises(ValueError, match="REPRO_BACKEND"):
        default_backend()


def test_backends_are_hashable_jit_keys():
    assert hash(JnpBackend()) == hash(JnpBackend())
    assert JnpBackend() == JnpBackend()
    assert PallasBackend(bn=256) != PallasBackend()


# -- contraction parity ------------------------------------------------------


ALL_FAMILIES = ["gaussian", "laplacian", "linear", "matern32", "cauchy"]


@pytest.mark.parametrize("name", BACKENDS)
@pytest.mark.parametrize("kind", ALL_FAMILIES)
def test_gram_block_parity(name, kind):
    kern = make_kernel(kind, sigma=1.7, kappa_sq=10.0)
    x, _, _ = _problem(n=300)
    # z disjoint from x: at d2 == 0 the laplacian's sqrt amplifies fp
    # association noise between compiled and eager paths beyond tolerance
    z = jax.random.normal(jax.random.PRNGKey(9), (70, x.shape[1]))
    out = resolve_backend(name).gram_block(kern, x, z)
    np.testing.assert_allclose(out, kern.cross(x, z), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("name", BACKENDS)
@pytest.mark.parametrize("kind", ["matern32", "cauchy"])
def test_new_family_knm_matvec_parity(name, kind):
    """The registry's new families drive the predict contraction on every
    backend from the one KernelFamily definition."""
    kern = make_kernel(kind, sigma=1.3)
    x, _, _ = _problem(n=300)
    z = jax.random.normal(jax.random.PRNGKey(7), (48, x.shape[1]))
    v = jax.random.normal(jax.random.PRNGKey(5), (48,))
    ref = kern.cross(x, z) @ v
    out = resolve_backend(name).knm_matvec(kern, x, z, v)
    np.testing.assert_allclose(out, ref, rtol=1e-4,
                               atol=1e-4 * float(jnp.abs(ref).max()))


@pytest.mark.parametrize("name", BACKENDS)
def test_masked_quadform_parity(name):
    x, _, z = _problem(n=256, m=48)
    mbuf = 64
    mask = jnp.arange(mbuf) < 48
    zbuf = jnp.where(mask[:, None], jnp.pad(z, ((0, mbuf - 48), (0, 0))), 0.0)
    reg = jnp.where(mask, 1e-3 * x.shape[0], 1.0)
    ref = JnpBackend().masked_quadform(KERN, x, zbuf, mask, reg)
    out = resolve_backend(name).masked_quadform(KERN, x, zbuf, mask, reg)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("name", BACKENDS)
def test_knm_operators_parity(name):
    x, y, z = _problem()
    v = jax.random.normal(jax.random.PRNGKey(3), (z.shape[0],))
    g = KERN.cross(x, z)
    quad, kty = resolve_backend(name).knm_operators(KERN, x, z, y)
    np.testing.assert_allclose(quad(v), g.T @ (g @ v), rtol=1e-4,
                               atol=1e-4 * float(jnp.abs(g.T @ (g @ v)).max()))
    np.testing.assert_allclose(kty, g.T @ y, rtol=1e-4,
                               atol=1e-4 * float(jnp.abs(g.T @ y).max()))


@pytest.mark.parametrize("masked", [False, True])
def test_pallas_knm_operators_pack_x_once(masked):
    """The Pallas operator and its right-hand side share one packing of X and
    Z (one ``kernels.cross.packed`` pick, however often the operator is
    called), and give what ``knm_quadratic`` and ``knm_t`` give apart."""
    from repro.runtime import spans

    x, y, z = _problem()
    v = jax.random.normal(jax.random.PRNGKey(3), (z.shape[0],))
    mask = (y > 0) if masked else None
    be = PallasBackend(interpret=True)
    before = spans.taken("kernels.cross.packed")
    quad, kty = be.knm_operators(KERN, x, z, y, mask=mask)
    got = [quad(v), quad(2.0 * v)]
    assert spans.taken("kernels.cross.packed") - before == 1
    np.testing.assert_array_equal(got[0], be.knm_quadratic(KERN, x, z, mask=mask)(v))
    np.testing.assert_array_equal(kty, be.knm_t(KERN, x, z, y, mask=mask))


@pytest.mark.parametrize("name", BACKENDS)
@pytest.mark.parametrize("n", [256, 300])  # tile-aligned and ragged (n % block != 0)
def test_knm_matvec_parity(name, n):
    x, _, _ = _problem(n=n)
    z = jax.random.normal(jax.random.PRNGKey(7), (48, x.shape[1]))
    v = jax.random.normal(jax.random.PRNGKey(5), (48,))
    ref = KERN.cross(x, z) @ v
    out = resolve_backend(name).knm_matvec(KERN, x, z, v)
    assert out.shape == (n,)
    np.testing.assert_allclose(out, ref, rtol=1e-4,
                               atol=1e-4 * float(jnp.abs(ref).max()))


def test_jnp_knm_matvec_multiblock_ragged():
    """The streaming branch: n spans several blocks and overhangs the last."""
    x, _, _ = _problem(n=300)
    z = jax.random.normal(jax.random.PRNGKey(7), (32, x.shape[1]))
    v = jax.random.normal(jax.random.PRNGKey(5), (32,))
    out = JnpBackend(block=128).knm_matvec(KERN, x, z, v)
    np.testing.assert_allclose(out, KERN.cross(x, z) @ v, rtol=1e-5, atol=1e-5)


# -- mixed precision (PallasBackend(bf16=True)) ------------------------------
#
# bf16 MXU operands, fp32 accumulation: only the distance cross-term loses
# precision, so unit-scale data stays within ~3e-2 absolute of fp32
# (DESIGN.md §2.3). These tolerances are the documented contract.

BF16 = PallasBackend(interpret=True, bf16=True)


def test_bf16_is_a_distinct_jit_key():
    assert BF16 != PallasBackend(interpret=True)
    hash(BF16)  # usable as a static jit argument
    assert BF16.bf16 and not PallasBackend().bf16


def test_bf16_gram_tolerance():
    x, _, _ = _problem(n=300)
    z = jax.random.normal(jax.random.PRNGKey(9), (70, x.shape[1]))
    out = BF16.gram_block(KERN, x, z)
    np.testing.assert_allclose(out, KERN.cross(x, z), atol=3e-2)


def test_bf16_knm_matvec_tolerance():
    x, _, _ = _problem(n=300)
    z = jax.random.normal(jax.random.PRNGKey(9), (48, x.shape[1]))
    v = jax.random.normal(jax.random.PRNGKey(5), (48,))
    ref = KERN.cross(x, z) @ v
    out = BF16.knm_matvec(KERN, x, z, v)
    np.testing.assert_allclose(out, ref, atol=3e-2 * float(jnp.abs(ref).max()))


def test_bf16_masked_quadform_tolerance():
    x, _, z = _problem(n=256, m=48)
    mbuf = 64
    mask = jnp.arange(mbuf) < 48
    zbuf = jnp.where(mask[:, None], jnp.pad(z, ((0, mbuf - 48), (0, 0))), 0.0)
    reg = jnp.where(mask, 1e-3 * x.shape[0], 1.0)
    ref = JnpBackend().masked_quadform(KERN, x, zbuf, mask, reg)
    out = BF16.masked_quadform(KERN, x, zbuf, mask, reg)
    np.testing.assert_allclose(out, ref, atol=5e-2 * float(jnp.abs(ref).max()))


# -- end-to-end parity (the acceptance bar) ----------------------------------


@pytest.mark.parametrize("name", ["pallas", "sharded"])
def test_bless_center_sets_match_jnp(name):
    """Identical PRNG path + fp32-close scores => identical center sets."""
    x, _, _ = _problem(n=500)
    ref = bless(jax.random.PRNGKey(0), x, KERN, 1e-3, backend="jnp")
    res = bless(jax.random.PRNGKey(0), x, KERN, 1e-3, backend=name)
    assert [lvl.m_h for lvl in res.levels] == [lvl.m_h for lvl in ref.levels]
    assert bool(jnp.all(res.final.centers.idx == ref.final.centers.idx))
    # 5e-4: the internal center dedup merges duplicate regularizers (harmonic
    # sum), which mildly worsens the (M, M) conditioning the backends' fp32
    # solves amplify — center identity above is still required to be exact
    np.testing.assert_allclose(res.final.centers.weight, ref.final.centers.weight,
                               rtol=5e-4, atol=5e-5)
    s_ref = approx_rls_all(KERN, x, ref.final.centers, jnp.asarray(1e-3), backend="jnp")
    s = approx_rls_all(KERN, x, ref.final.centers, jnp.asarray(1e-3), backend=name)
    np.testing.assert_allclose(s, s_ref, rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("name", ["pallas", "sharded"])
def test_falkon_predictions_match_jnp(name):
    x, y, z = _problem()
    ref = falkon_fit(KERN, x, y, z, 1e-3, iters=25, backend="jnp")
    fk = falkon_fit(KERN, x, y, z, 1e-3, iters=25, backend=name)
    # the model remembers its fit-time backend, so each predict below also
    # exercises that backend's knm_matvec end to end
    assert fk.backend is not None and fk.backend.name == name
    pr, pf = ref.predict(x), fk.predict(x)
    assert float(jnp.max(jnp.abs(pr - pf))) < 1e-4, name
    # per-call override routes the same model through another backend
    po = fk.predict(x, backend="jnp")
    assert float(jnp.max(jnp.abs(po - pr))) < 1e-4, name


@pytest.mark.parametrize("name", ["pallas", "sharded"])
@pytest.mark.parametrize("kind", ["matern32", "cauchy"])
def test_new_family_falkon_predictions_match_jnp(name, kind):
    """End-to-end FALKON parity for the registry's new families."""
    kern = make_kernel(kind, sigma=1.8)
    x, y, z = _problem(n=300, m=40)
    ref = falkon_fit(kern, x, y, z, 1e-3, iters=20, backend="jnp")
    fk = falkon_fit(kern, x, y, z, 1e-3, iters=20, backend=name)
    assert float(jnp.max(jnp.abs(ref.predict(x) - fk.predict(x)))) < 1e-4


def test_unknown_family_error_enumerates_registry():
    import dataclasses

    from repro.core import kernel_family_names

    bad = dataclasses.replace(make_kernel("gaussian"), name="spectral")
    with pytest.raises(ValueError, match="registered"):
        resolve_backend("pallas").gram_block(bad, jnp.zeros((8, 4)), jnp.zeros((8, 4)))
    assert {"gaussian", "laplacian", "linear", "matern32", "cauchy"} <= set(
        kernel_family_names())


def test_pallas_backend_runs_interpret_explicitly():
    """CI path: interpret=True forced (not just the off-TPU default)."""
    x, y, z = _problem(n=300, m=40)
    fk = falkon_fit(KERN, x, y, z, 1e-3, iters=15,
                    backend=PallasBackend(interpret=True))
    ref = falkon_fit(KERN, x, y, z, 1e-3, iters=15, backend="jnp")
    assert float(jnp.max(jnp.abs(fk.predict(x) - ref.predict(x)))) < 1e-4
