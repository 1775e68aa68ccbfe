"""Durable online FALKON (repro/online): incremental append + warm-refit
parity with cold fits, the always-on ingest fence, background center
refresh with delta absorption, ChunkStore growth, and the resumable
streamed fit's checkpoint/refusal contract. The kill/resume chaos
scenarios live in test_chaos.py."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.api import (OnlineFalkon, ResumeMismatchError, UniformSampler,
                       as_prng_key, resumable_streamed_fit)
from repro.checkpoint import checkpoint_extra, latest_step, restore_checkpoint
from repro.core import falkon_fit, health, make_kernel
from repro.runtime import spans
from repro.stream import ChunkStore

KERN = make_kernel("gaussian", sigma=1.5)
# Converged regime on purpose: the accumulator path solves the explicitly
# formed normal equations, so parity with the operator path is only
# meaningful once both CGs have converged (unconverged iterates follow
# different rounding paths); see repro/online/accumulate.py.
LAM, ITERS = 1e-3, 30
N, D, M = 2400, 4, 56


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(N, D)).astype(np.float32)
    y = (np.sin(2 * x[:, 0]) + 0.3 * x[:, 1]).astype(np.float32)
    return x, y


def _pred_rel_err(a, b, xt):
    pa, pb = a.predict(xt), b.predict(xt)
    return float(jnp.max(jnp.abs(pa - pb)) / jnp.max(jnp.abs(pa)))


# -- parity: appends + warm refit vs cold fit on concatenated data -----------


@pytest.mark.parametrize("backend", ["jnp", "stream:pallas"])
def test_append_refit_matches_cold_fit(data, backend):
    x, y = data
    cold = falkon_fit(KERN, jnp.asarray(x), jnp.asarray(y), jnp.asarray(x[:M]),
                      LAM, iters=ITERS)
    of = OnlineFalkon(KERN, x[:M], LAM, x=x[:800], y=y[:800], iters=ITERS,
                      backend=backend, chunk=512)
    for i in range(800, N, 400):
        of.append(x[i:i + 400], y[i:i + 400])
    model = of.refit()
    assert cold.diagnostics.converged and model.diagnostics.converged
    xt = jnp.asarray(np.random.default_rng(1).normal(size=(300, D)),
                     jnp.float32)
    assert _pred_rel_err(cold, model, xt) < 1e-2
    assert of.counters["appends"] == 4 and of.counters["rows"] == N


def test_multi_output_append_refit(data):
    x, y = data
    Y = np.stack([y, -y], axis=1)
    cold = falkon_fit(KERN, jnp.asarray(x), jnp.asarray(Y), jnp.asarray(x[:M]),
                      LAM, iters=ITERS)
    of = OnlineFalkon(KERN, x[:M], LAM, x=x[:1200], y=Y[:1200], iters=ITERS,
                      chunk=512)
    of.append(x[1200:], Y[1200:])
    model = of.refit()
    assert model.alpha.shape == (M, 2)
    xt = jnp.asarray(x[:200])
    assert _pred_rel_err(cold, model, xt) < 1e-2


def test_warm_refit_rides_one_executable(data):
    """Steady-state append+refit cycles must not retrace the accumulator
    solve — each refit is one cached compiled dispatch."""
    x, y = data
    of = OnlineFalkon(KERN, x[:M], LAM, x=x[:1000], y=y[:1000], iters=ITERS,
                      chunk=512)
    of.refit()
    before = spans.retraces("online.acc_solve")
    for i in range(1000, 1800, 200):
        of.append(x[i:i + 200], y[i:i + 200])
        of.refit()
    assert spans.retraces("online.acc_solve") == before
    assert of.counters["refits"] == 5


# -- ingest fence ------------------------------------------------------------


def test_append_rejects_non_finite_batch_untouched(data):
    x, y = data
    of = OnlineFalkon(KERN, x[:M], LAM, x=x[:600], y=y[:600], chunk=256)
    h0, b0 = of._h, of._b
    bad = x[600:700].copy()
    bad[3, 1] = np.nan
    with pytest.raises(health.NonFiniteError):
        of.append(bad, y[600:700])
    assert bool(jnp.all(of._h == h0)) and bool(jnp.all(of._b == b0))
    assert of.store.shape[0] == 600  # store untouched too
    assert of.counters["rejected"] == 1 and of.counters["appends"] == 0
    with pytest.raises(health.NonFiniteError):
        of.append(x[600:700], np.full(100, np.inf, np.float32))
    assert of.counters["rejected"] == 2


def test_append_validates_shapes(data):
    x, y = data
    of = OnlineFalkon(KERN, x[:M], LAM, x=x[:600], y=y[:600])
    with pytest.raises(ValueError, match="append batch"):
        of.append(x[:10, :2], y[:10])
    with pytest.raises(ValueError, match="append targets"):
        of.append(x[:10], y[:9])


# -- center refresh ----------------------------------------------------------


def test_refresh_centers_inline(data):
    x, y = data
    of = OnlineFalkon(KERN, x[:M], LAM, x=x[:1500], y=y[:1500], iters=ITERS,
                      sampler=UniformSampler(m=M), chunk=512)
    of.refresh_centers(as_prng_key(3))
    model = of.refit()
    assert of.counters["refreshes"] == 1
    assert model.centers.shape[0] == M
    # refreshed model still fits the data it absorbed
    xt = jnp.asarray(x[:200])
    ref = falkon_fit(KERN, jnp.asarray(x[:1500]), jnp.asarray(y[:1500]),
                     model.centers, LAM, a_diag=model.a_diag, iters=ITERS)
    assert _pred_rel_err(ref, model, xt) < 2e-2


def test_background_refresh_absorbs_delta(data):
    """Rows appended while a background refresh runs are folded into the
    refreshed accumulators on join — nothing is lost in the handoff."""
    x, y = data
    of = OnlineFalkon(KERN, x[:M], LAM, x=x[:1200], y=y[:1200], iters=ITERS,
                      sampler=UniformSampler(m=M), chunk=512)
    of.refresh_centers(as_prng_key(5), background=True)
    of.append(x[1200:1800], y[1200:1800])  # the delta
    assert of.join_refresh()
    assert of.counters["refreshes"] == 1
    model = of.refit()
    ref = falkon_fit(KERN, jnp.asarray(x[:1800]), jnp.asarray(y[:1800]),
                     model.centers, LAM, a_diag=model.a_diag, iters=ITERS)
    assert _pred_rel_err(ref, model, jnp.asarray(x[:200])) < 2e-2
    assert not of.join_refresh()  # nothing left running


def test_refresh_needs_sampler(data):
    x, y = data
    of = OnlineFalkon(KERN, x[:M], LAM, x=x[:300], y=y[:300])
    with pytest.raises(ValueError, match="sampler"):
        of.refresh_centers(as_prng_key(0))


# -- ChunkStore growth -------------------------------------------------------


def test_chunkstore_append_grows_and_views():
    rng = np.random.default_rng(2)
    x0 = rng.normal(size=(100, 3)).astype(np.float32)
    y0 = rng.normal(size=(100,)).astype(np.float32)
    store = ChunkStore(x0, y0, chunk=64)
    xs, ys = [x0], [y0]
    for r in (1, 50, 300):
        xa = rng.normal(size=(r, 3)).astype(np.float32)
        ya = rng.normal(size=(r,)).astype(np.float32)
        assert store.append(xa, ya) == sum(a.shape[0] for a in xs) + r
        xs.append(xa)
        ys.append(ya)
    np.testing.assert_array_equal(store.x, np.concatenate(xs))
    np.testing.assert_array_equal(store.y, np.concatenate(ys))
    assert store.shape == (451, 3)
    assert store.n_chunks == 8
    assert store.x.flags["C_CONTIGUOUS"]


def test_chunkstore_append_validates():
    store = ChunkStore(np.zeros((4, 3), np.float32), np.zeros(4, np.float32))
    with pytest.raises(ValueError, match="append rows"):
        store.append(np.zeros((2, 5), np.float32), np.zeros(2, np.float32))
    with pytest.raises(ValueError, match="carries y"):
        store.append(np.zeros((2, 3), np.float32))
    xonly = ChunkStore(np.zeros((4, 3), np.float32))
    with pytest.raises(ValueError, match="has no y"):
        xonly.append(np.zeros((2, 3), np.float32), np.zeros(2, np.float32))


# -- resumable streamed fit (happy path; kill/resume lives in test_chaos) ----


def test_resumable_fit_matches_cold_and_checkpoints(data, tmp_path):
    x, y = data
    centers = jnp.asarray(x[:M])
    cold = falkon_fit(KERN, jnp.asarray(x), jnp.asarray(y), centers, LAM,
                      iters=ITERS)
    store = ChunkStore(x, y, chunk=512)
    key = as_prng_key(11)
    model = resumable_streamed_fit(KERN, store, centers=centers, lam=LAM,
                                   iters=ITERS, ckpt_dir=str(tmp_path),
                                   ckpt_every=2, key=key)
    assert _pred_rel_err(cold, model, jnp.asarray(x[:200])) < 1e-2
    # final barrier checkpointed: cursor == n_chunks, PRNG key round-trips
    step = latest_step(str(tmp_path))
    assert step == store.n_chunks
    extra = checkpoint_extra(str(tmp_path), step)
    assert extra["cursor"] == store.n_chunks and extra["rows"] == N
    _, tree = restore_checkpoint(
        str(tmp_path), {"h": jnp.zeros((M, M)), "b": jnp.zeros((M,)),
                        "key": np.zeros((2,), np.uint32)}, step=step)
    np.testing.assert_array_equal(np.asarray(tree["key"]),
                                  np.asarray(jax.random.key_data(key)))


def test_resumable_fit_refuses_config_mismatch(data, tmp_path):
    x, y = data
    centers = jnp.asarray(x[:M])
    store = ChunkStore(x, y, chunk=512)
    resumable_streamed_fit(KERN, store, centers=centers, lam=LAM,
                           iters=ITERS, ckpt_dir=str(tmp_path))
    for kwargs in ({"lam": LAM * 2}, {"iters": ITERS + 1},
                   {"centers": jnp.asarray(x[1:M + 1])}):
        with pytest.raises(ResumeMismatchError, match="refusing"):
            resumable_streamed_fit(
                KERN, store, centers=kwargs.get("centers", centers),
                lam=kwargs.get("lam", LAM), iters=kwargs.get("iters", ITERS),
                ckpt_dir=str(tmp_path))
    with pytest.raises(ResumeMismatchError):
        resumable_streamed_fit(
            KERN, ChunkStore(x, y, chunk=600), centers=centers, lam=LAM,
            iters=ITERS, ckpt_dir=str(tmp_path))  # different chunking
