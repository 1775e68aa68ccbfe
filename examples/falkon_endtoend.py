"""End-to-end driver (the paper's kind of workload): large-scale kernel
ridge classification with the full production pipeline —

  BLESS center selection -> distributed FALKON CG (data-parallel over all
  local devices) -> evaluation -> model checkpoint.

Mirrors the paper's SUSY experiment shape (Sec. 4) at CPU-container scale:
n = 50_000 points, lam_bless >> lam_falkon, ~10^2-10^3 Nystrom centers.

    PYTHONPATH=src python examples/falkon_endtoend.py [--n 50000]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.api import BlessSampler, FalkonRegressor, FitConfig, make_kernel
from repro.checkpoint import save_checkpoint
from repro.core.distributed import data_mesh, falkon_fit_distributed
from repro.runtime.compile_cache import enable_compile_cache


def susy_like(n: int, d: int = 18, seed: int = 0):
    """Two-class data with SUSY-ish dimensionality: a smooth nonlinear
    decision boundary living on a low-dimensional subspace + nuisance dims
    (the low-effective-dimension regime leverage scores exploit)."""
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (n, d))
    w1, w2 = jax.random.normal(k2, (2, d)) / jnp.sqrt(d)
    margin = jnp.tanh(2 * x @ w1) + 0.5 * (x @ w2) ** 2 - 0.5
    y = jnp.sign(margin + 0.1 * jax.random.normal(k3, (n,)))
    return x, jnp.where(y == 0, 1.0, y)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=50_000)
    ap.add_argument("--lam-bless", type=float, default=1e-4)
    ap.add_argument("--lam-falkon", type=float, default=1e-6)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--m-cap", type=int, default=1500)
    ap.add_argument("--ckpt", default="/tmp/falkon_ckpt")
    ap.add_argument("--backend", choices=["auto", "jnp", "pallas", "sharded", "stream"],
                    default="auto",
                    help="kernel-operator backend (auto: BLESS by platform "
                         "heuristic / REPRO_BACKEND env, FALKON data-parallel)")
    args = ap.parse_args()
    enable_compile_cache()
    backend = None if args.backend == "auto" else args.backend

    n_test = 8000
    xa, ya = susy_like(args.n + n_test)  # one rule; held-out split
    x, y, xte, yte = xa[: args.n], ya[: args.n], xa[args.n:], ya[args.n:]
    kern = make_kernel("gaussian", sigma=4.0)  # the paper's SUSY sigma

    sampler = BlessSampler(lam=args.lam_bless, q1=3.0, q2=3.0, m_cap=args.m_cap)
    t0 = time.time()
    res = sampler.ladder(jax.random.PRNGKey(0), x, kern, backend=backend)
    t_bless = time.time() - t0
    m = res.final.m_h
    print(f"BLESS: {len(res.levels)} levels, M = {m} centers in {t_bless:.1f}s "
          f"(n = {args.n}; candidate sets never exceeded "
          f"{max(l.r_h for l in res.levels)} points — the 1/lam bound)")

    t0 = time.time()
    if backend is None or backend == "sharded":
        mesh = data_mesh()
        print(f"FALKON: data-parallel CG over {mesh.devices.size} device(s)")
        model = falkon_fit_distributed(
            mesh, kern, x, y, x[res.final.centers.idx[:m]], args.lam_falkon,
            a_diag=res.final.centers.weight[:m], iters=args.iters)
    else:
        print(f"FALKON: CG on the {backend!r} backend")
        est = FalkonRegressor(kernel=kern, sampler=sampler,
                              config=FitConfig(lam=args.lam_falkon,
                                               iters=args.iters, backend=backend))
        # the ladder above already sampled (J, A): hand it straight to fit
        model = est.fit(x, y, center_set=res.final.centers).model_
    t_falkon = time.time() - t0

    pred_tr = jnp.sign(model.predict(x[:10000]))
    pred_te = jnp.sign(model.predict(xte))
    err_tr = float(jnp.mean(pred_tr != y[:10000]))
    err_te = float(jnp.mean(pred_te != yte))
    print(f"FALKON-BLESS: {args.iters} CG iters in {t_falkon:.1f}s | "
          f"train err {err_tr:.4f} | test err {err_te:.4f}")

    path = save_checkpoint(args.ckpt, 0, {
        "centers": model.centers, "alpha": model.alpha,
        "sigma": jnp.asarray(4.0), "lam": jnp.asarray(args.lam_falkon)})
    print(f"model checkpoint -> {path}")


if __name__ == "__main__":
    main()
