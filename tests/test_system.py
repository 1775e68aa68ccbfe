"""End-to-end behaviour: the paper pipeline (BLESS -> FALKON) learns; the
LM framework trains (loss falls), checkpoints, restores bit-exactly, and
serves; serving engine decodes coherently with per-slot state."""
import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, smoke
from repro.core import exact_rls, falkon_bless_fit, make_kernel
from repro.data import SyntheticLM
from repro.optim import OptConfig
from repro.serving.engine import ServeEngine, prefill, sample_greedy
from repro.training import make_train_step, train_state_init
from repro.checkpoint import restore_checkpoint, save_checkpoint


def test_paper_pipeline_learns(clustered_data):
    """End-to-end BLESS -> FALKON: explains most variance AND matches the
    direct Nystrom solver on its own centers (the solver contract).
    (At n=900 with-replacement sampling leaves ~2/3 unique centers, so the
    approximation floor is above the paper's n >> M regime — EXPERIMENTS.md
    quantifies this; here we pin the contract, not the asymptotics.)"""
    from repro.core import nystrom_krr

    x = clustered_data
    y = jnp.sin(3 * x[:, 0]) * jnp.tanh(x[:, 1])
    kern = make_kernel("gaussian", sigma=1.0)
    model = falkon_bless_fit(jax.random.PRNGKey(1), kern, x, y,
                             lam_bless=1e-3, lam_falkon=1e-6, iters=30, m_cap=400)
    mse = float(jnp.mean((model.predict(x) - y) ** 2))
    var = float(jnp.var(y))
    assert mse < 0.25 * var, (mse, var)  # >75% variance explained
    ny = nystrom_krr(kern, x, y, model.centers, 1e-6)
    rel = float(jnp.linalg.norm(model.predict(x) - ny.predict(x))
                / jnp.linalg.norm(ny.predict(x)))
    assert rel < 1e-3, rel


def test_lm_trains_checkpoints_and_serves():
    cfg = smoke(get_config("qwen3-32b"))
    opt = OptConfig(peak_lr=3e-3, warmup=5, total_steps=80)
    state = train_state_init(cfg, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, opt, loss_chunks=4))
    pipe = SyntheticLM(cfg.vocab_size, batch=8, seq=64, seed=0, noise=0.05)
    losses = []
    for s in range(60):  # past the lr peak: rule accuracy ~86% (pred correct by ~45)
        state, m = step(state, pipe.batch_at(s))
        losses.append(float(m["loss"]))
    assert losses[-1] < 0.5 * losses[0], losses[::10]

    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 60, state)
        _, restored = restore_checkpoint(d, state)
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            assert bool(jnp.all(a == b))
        # restored state continues identically (determinism)
        s1, m1 = step(state, pipe.batch_at(60))
        s2, m2 = step(restored, pipe.batch_at(60))
        assert float(m1["loss"]) == float(m2["loss"])

    # greedy decode predicts the learned rule
    params = state.params
    perm = pipe._rule()
    t0 = 17
    logits, cache = prefill(params, cfg, jnp.asarray([[t0]]), cache_len=8)
    pred = int(sample_greedy(logits, cfg.vocab_size)[0])
    assert pred == int(perm[t0])


def test_serve_engine_continuous_batching():
    cfg = smoke(get_config("phi3-mini-3.8b"))
    state = train_state_init(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(params=state.params, cfg=cfg, max_len=32, batch_slots=3)
    # an empty prompt has no logits to sample from: clear error, not an
    # unbound-variable crash (and the engine state stays untouched)
    with pytest.raises(ValueError, match="at least one token"):
        eng.add_request(0, [])
    assert not bool(eng.active[0])
    eng.add_request(0, [1, 2, 3])
    eng.add_request(1, [4, 5])
    for _ in range(4):
        eng.step()
    out0, out1 = eng.finish(0), eng.finish(1)
    assert len(out0) == 5 and len(out1) == 5
    assert all(0 <= t < cfg.vocab_size for t in out0 + out1)


def test_train_step_sharded_runs_on_local_mesh():
    """The same pjit train step the dry-run lowers also *runs* on a real
    (1-device) mesh with full sharding machinery engaged."""
    from repro.launch.specs import input_specs
    from repro.launch.mesh import make_local_mesh
    from repro.sharding.rules import MeshCtx, set_mesh_ctx

    cfg = dataclasses.replace(smoke(get_config("gemma-2b")), attn_chunk=64)
    mesh = make_local_mesh(("data", "model"))
    ctx = MeshCtx(mesh=mesh)
    set_mesh_ctx(ctx)
    try:
        from repro.training import make_train_step, train_state_init

        state = train_state_init(cfg, jax.random.PRNGKey(0))
        pipe = SyntheticLM(cfg.vocab_size, batch=4, seq=64, seed=0)
        step = jax.jit(make_train_step(cfg, OptConfig(), loss_chunks=4))
        with jax.set_mesh(mesh):
            state, m = step(state, pipe.batch_at(0))
        assert jnp.isfinite(m["loss"])
    finally:
        set_mesh_ctx(None)
