"""Serve a small model with batched requests (continuous batching) and
demonstrate BLESS leverage-score KV-cache compression — the paper's
technique as a serving feature.

    PYTHONPATH=src python examples/serve_batched.py
"""
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, smoke
from repro.data import SyntheticLM
from repro.models.attention import bless_compress_cache
from repro.optim import OptConfig
from repro.serving.engine import ServeEngine
from repro.training import make_train_step, train_state_init
from repro.runtime.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    cfg = smoke(get_config("qwen3-32b"))
    print(f"arch: {cfg.name} ({cfg.n_layers}L d={cfg.d_model})")

    # brief training so generations follow the synthetic rule
    state = train_state_init(cfg, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, OptConfig(peak_lr=3e-3, warmup=5,
                                                  total_steps=40), loss_chunks=4))
    pipe = SyntheticLM(cfg.vocab_size, batch=8, seq=64, seed=0, noise=0.05)
    for s in range(40):
        state, m = step(state, pipe.batch_at(s))
    print(f"pre-trained 40 steps, loss {float(m['loss']):.3f}")

    # continuous batching: requests arrive at different times
    eng = ServeEngine(params=state.params, cfg=cfg, max_len=64, batch_slots=4)
    perm = pipe._rule()
    eng.add_request(0, [int(perm[7]), int(perm[perm[7]])])
    eng.add_request(1, [3, int(perm[3])])
    t0 = time.time()
    n_steps = 12
    for i in range(n_steps):
        if i == 4:  # a request joins mid-flight
            eng.add_request(2, [11])
        eng.step()
    dt = time.time() - t0
    done = sum(1 for i in range(3))
    for slot in range(3):
        print(f"slot {slot}: {eng.finish(slot)}")
    print(f"{n_steps} decode steps x active slots in {dt:.2f}s "
          f"({n_steps * 3 / dt:.1f} tok/s aggregate)")

    # --- BLESS KV compression: keep the top-RLS keys, decode against M << S
    from repro.models import init_cache

    b, s_full, m_keep = 2, 64, 16
    kv = init_cache(cfg, b, s_full)
    layer0 = kv[next(iter(kv))]
    if "k" in layer0:
        k = jax.random.normal(jax.random.PRNGKey(1), layer0["k"].shape[1:], jnp.bfloat16)
        v = jax.random.normal(jax.random.PRNGKey(2), layer0["v"].shape[1:], jnp.bfloat16)
        kc, vc = bless_compress_cache(k.astype(jnp.float32), v.astype(jnp.float32),
                                      m=m_keep)
        print(f"KV compression: {k.shape} -> {kc.shape} "
              f"({s_full / m_keep:.0f}x less KV traffic per decoded token)")


if __name__ == "__main__":
    main()
