"""Serve a FALKON-BLESS kernel ridge model under bursty request traffic —
the paper's estimator as a production endpoint.

Fits FALKON-BLESS once, then replays a trace of variable-size prediction
requests through ``KrrServer``: requests are packed into waves, padded to
pow2 row buckets, and served by single fused ``knm_matvec`` dispatches
through the kernel-operator backend seam. Compare the dispatch count with
the naive one-dispatch-per-request path it replaces.

    PYTHONPATH=src python examples/serve_krr.py [--backend jnp|pallas|sharded]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.api import (BlessSampler, FalkonRegressor, FitConfig, KrrServer,
                       make_kernel)
from repro.runtime.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4000)
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--backend", choices=["auto", "jnp", "pallas", "sharded", "stream"],
                    default="auto", help="kernel-operator backend override")
    args = ap.parse_args()
    enable_compile_cache()
    backend = None if args.backend == "auto" else args.backend

    # --- fit once (clustered data: the low-d_eff regime BLESS exploits) ----
    key = jax.random.PRNGKey(0)
    kc, ka, kn, ky = jax.random.split(key, 4)
    n, d = args.n, 8
    cl = jax.random.normal(kc, (10, d)) * 3.0
    x = cl[jax.random.randint(ka, (n,), 0, 10)] + 0.4 * jax.random.normal(kn, (n, d))
    y = jnp.sin(2 * x[:, 0]) * jnp.tanh(x[:, 1]) + 0.05 * jax.random.normal(ky, (n,))
    kern = make_kernel("gaussian", sigma=2.0)
    t0 = time.perf_counter()
    est = FalkonRegressor(kernel=kern, sampler=BlessSampler(lam=1e-3, m_cap=400),
                          config=FitConfig(lam=1e-5, iters=20, backend=backend))
    est.fit(x, y, key=jax.random.PRNGKey(1))
    model = est.model_
    print(f"FALKON-BLESS fit: M = {model.centers.shape[0]} centers "
          f"in {time.perf_counter() - t0:.1f}s (backend={model.backend.name})")

    # --- bursty traffic: variable-size requests from the same distribution --
    # (KrrServer accepts the fitted estimator directly)
    server = KrrServer(est, backend=backend, max_wave=2048, min_bucket=64)
    kq = jax.random.PRNGKey(2)
    sizes = [int(s) for s in jax.random.randint(kq, (args.requests,), 1, 65)]
    reqs = []
    for i, r in enumerate(sizes):
        kq, kr = jax.random.split(kq)
        qi = cl[i % 10] + 0.4 * jax.random.normal(kr, (r, d))
        reqs.append(qi)

    for q in reqs:  # warmup: replay the trace once so every wave bucket the
        server.submit(q)  # timed run hits is already compiled
    server.flush()
    server.reset()  # zero the stats for the timed run

    t0 = time.perf_counter()
    rids = [server.submit(q) for q in reqs]
    preds = server.flush()
    jax.block_until_ready(preds[rids[-1]])
    dt = time.perf_counter() - t0

    s = server.stats
    print(f"{s['requests']} requests / {s['rows']} rows in {dt * 1e3:.1f} ms "
          f"({s['rows'] / dt:.0f} rows/s)")
    print(f"{s['dispatches']} fused dispatches (vs {s['requests']} naive), "
          f"buckets {sorted(s['buckets'])}, "
          f"padding overhead {s['padded_rows'] / max(1, s['rows']):.1%}")

    # spot-check one response against the unbatched path
    err = float(jnp.max(jnp.abs(preds[rids[0]] - model.predict(reqs[0]))))
    print(f"batched vs direct max abs diff: {err:.2e}")


if __name__ == "__main__":
    main()
