"""Benchmark harness — one function per paper table/figure.

  bench_fig1_raccuracy        Fig. 1: R-ACC of approximate leverage scores
  bench_fig2_runtime_scaling  Fig. 2: runtime vs n (BLESS ~flat in n)
  bench_table1_complexity     Table 1: |J| ~ d_eff(lam), runtime ~ 1/lam
  bench_fig3_lambda_stability Fig. 3: error across lam_falkon grid
  bench_fig45_falkon          Fig. 4/5: FALKON-BLESS vs FALKON-UNI per iter
  bench_multi_rhs             multi-RHS block-CG: k outputs / CV folds in
                              one solve vs the per-column loop
  bench_scenarios             scenario layer: mask-panel tax on the quad op
                              (exact CV), classifier fit, variance scorer
  bench_bigk                  out-of-core: million-row FALKON through the
                              stream backend, peak device bytes recorded
  bench_online                durable online FALKON: append + warm refit
                              vs cold fit (the >=5x CI speedup gate)
  bench_lm_steps              framework: smoke-scale train/decode step times

Prints ``name,us_per_call,derived`` CSV rows (stdout), one per measurement.
CPU-scale sizes; every timing is post-warmup (jit cache hot).

Flags:
  --backend {jnp,pallas,sharded,stream}  pin the kernel-operator backend
  --json PATH      also write the records as a JSON array (the perf
                   trajectory artifact future perf PRs diff against)
  --repeats N      time each measurement N times, report the median
  --only A,B       run only benches whose registry name contains a substring
  --smoke          tiny sizes (CI smoke job: fast, still end-to-end)
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import (BlessRSampler, BlessSampler, ChenYangSampler,
                       FalkonRegressor, FitConfig, KFoldSweep,
                       RecursiveRlsSampler, SqueakSampler, UniformSampler,
                       make_kernel)
from repro.core import exact_rls, falkon_fit
from repro.core.leverage import approx_rls_all
from repro.runtime.compile_cache import enable_compile_cache

_RECORDS: list[dict] = []
_REPEATS = 1


def emit(name: str, us: float, derived: str = "") -> None:
    _RECORDS.append({"name": name, "us_per_call": round(us, 1), "derived": derived})
    print(f"{name},{us:.1f},{derived}", flush=True)


def _ready(out) -> None:
    if hasattr(out, "final"):
        jax.block_until_ready(out.final.centers.idx)
    elif hasattr(out, "idx"):
        jax.block_until_ready(out.idx)
    elif hasattr(out, "alpha"):
        jax.block_until_ready(out.alpha)
    else:
        jax.block_until_ready(out)


def timed(fn):
    """(last result, median us over --repeats runs), after one warmup call."""
    _ready(fn())  # warmup: compile every shape this measurement touches
    times = []
    out = None
    for _ in range(_REPEATS):
        t0 = time.perf_counter()
        out = fn()
        _ready(out)
        times.append((time.perf_counter() - t0) * 1e6)
    return out, float(np.median(times))


def _data(n: int, d: int = 10, seed: int = 0, clusters: int = 12):
    key = jax.random.PRNGKey(seed)
    kc, ka, kn = jax.random.split(key, 3)
    centers = jax.random.normal(kc, (clusters, d)) * 3.0
    assign = jax.random.randint(ka, (n,), 0, clusters)
    return centers[assign] + 0.5 * jax.random.normal(kn, (n, d))


def _classif(n: int, n_test: int, d: int = 8, seed: int = 1):
    """One ground-truth rule; train/test split from the same distribution."""
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (n + n_test, d))
    w = jax.random.normal(k2, (d,))
    margin = jnp.tanh(x @ w + 0.7 * jnp.sin(2 * x[:, 0]) * x[:, 1])
    y = jnp.sign(margin + 0.3 * jax.random.normal(k3, (n + n_test,)))
    y = jnp.where(y == 0, 1.0, y)
    return x[:n], y[:n], x[n:], y[n:]


def _racc_stats(scores, ell):
    r = np.asarray(scores / ell)
    return (float(r.mean()), float(np.quantile(r, 0.05)), float(np.quantile(r, 0.95)))


def bench_fig1_raccuracy(n: int = 2000, lam: float = 1e-3, backend=None) -> None:
    """Every method is a repro.api Sampler: one CenterSet contract, one
    scoring path (Eq. 3 at the target lam), apples-to-apples."""
    x = _data(n)
    kern = make_kernel("gaussian", sigma=2.0)
    ell = exact_rls(kern, x, lam)
    key = jax.random.PRNGKey(0)
    lamj = jnp.asarray(lam)

    def run(tag, sampler):
        cs, us = timed(lambda: sampler.sample(key, x, kern, backend=backend))
        m, q5, q95 = _racc_stats(approx_rls_all(kern, x, cs, lamj, backend=backend), ell)
        emit(f"fig1.{tag}", us, f"racc={m:.3f};q5={q5:.2f};q95={q95:.2f};M={int(cs.count)}")
        return int(cs.count)

    run("bless", BlessSampler(lam=lam, q2=4.0, q1=4.0))
    mref = run("bless_r", BlessRSampler(lam=lam, q2=4.0))
    run("squeak", SqueakSampler(lam=lam, m_cap=mref))
    run("rrls", RecursiveRlsSampler(lam=lam, m_cap=mref))
    run("chen_yang", ChenYangSampler(m=mref, lam=lam))
    run("uniform", UniformSampler(m=mref))


def bench_fig2_runtime_scaling(lam: float = 2e-3, backend=None,
                               sizes=(1000, 2000, 4000, 8000)) -> None:
    key = jax.random.PRNGKey(0)
    kern = make_kernel("gaussian", sigma=2.0)
    samplers = (
        ("bless", BlessSampler(lam=lam, q2=3.0, q1=3.0)),
        ("squeak", SqueakSampler(lam=lam, m_cap=600)),
        ("rrls", RecursiveRlsSampler(lam=lam, m_cap=600)),
    )
    for n in sizes:
        x = _data(n)
        for name, sampler in samplers:
            _, us = timed(lambda: sampler.sample(key, x, kern, backend=backend))
            emit(f"fig2.{name}.n{n}", us, f"n={n}")


def bench_table1_complexity(n: int = 2000, backend=None) -> None:
    """|J_H| tracks q2*d_eff(lam) across lam — the Table 1 / Thm 1(b) claim."""
    x = _data(n)
    kern = make_kernel("gaussian", sigma=2.0)
    key = jax.random.PRNGKey(0)
    q2 = 3.0
    for lam in (1e-2, 3e-3, 1e-3):
        deff = float(jnp.sum(exact_rls(kern, x, lam)))
        sampler = BlessSampler(lam=lam, q2=q2, q1=3.0)
        res, us = timed(lambda: sampler.ladder(key, x, kern, backend=backend))
        emit(f"table1.lam{lam:g}", us,
             f"deff={deff:.1f};M={res.final.m_h};q2*deff={q2 * deff:.1f};H={len(res.levels)}")


def bench_fig45_falkon(n: int = 3000, m_target: int = 250, n_test: int = 800,
                       backend=None) -> None:
    """Error per CG iteration: BLESS centers+weights vs uniform centers.
    Same estimator slot, two samplers — the api's swap-the-sampler story."""
    x, y, xte, yte = _classif(n, n_test)
    kern = make_kernel("gaussian", sigma=2.0)
    lam_falkon, lam_bless = 1e-5, 1e-3

    cs_bless = BlessSampler(lam=lam_bless, q2=3.0, m_cap=m_target).sample(
        jax.random.PRNGKey(0), x, kern, backend=backend)
    mh = int(cs_bless.count)
    cs_uni = UniformSampler(m=mh, replace=False, weights="identity").sample(
        jax.random.PRNGKey(1), x, kern)

    def err_curve(cs, tag):
        est = FalkonRegressor(kernel=kern,
                              config=FitConfig(lam=lam_falkon, iters=20,
                                               backend=backend))

        def run():
            errs = []

            def cb(i, model):
                pred = jnp.sign(model.predict(xte))
                errs.append(float(jnp.mean(pred != yte)))

            est.fit(x, y, center_set=cs, callback=cb)
            return errs

        errs, us = timed(run)
        best5 = min(errs[:5])
        emit(f"fig45.{tag}", us, f"err@5={best5:.4f};err@20={errs[-1]:.4f};M={mh}")

    err_curve(cs_bless, "falkon_bless")
    err_curve(cs_uni, "falkon_uni")


def bench_fig3_lambda_stability(n: int = 2000, m_cap: int = 250, n_test: int = 600,
                                backend=None) -> None:
    """Lambda sweep on fixed centers — warm-start refits riding the fused-fit
    jit cache (lam is traced: every lam after the first is a cache hit)."""
    x, y, xte, yte = _classif(n, n_test)
    kern = make_kernel("gaussian", sigma=2.0)
    cs_bless = BlessSampler(lam=1e-3, q2=3.0, m_cap=m_cap).sample(
        jax.random.PRNGKey(0), x, kern, backend=backend)
    mh = int(cs_bless.count)
    cs_uni = UniformSampler(m=mh, replace=False, weights="identity").sample(
        jax.random.PRNGKey(1), x, kern)
    ests = {tag: FalkonRegressor(kernel=kern, warm_start=True,
                                 config=FitConfig(lam=1e-3, iters=5, backend=backend))
            for tag in ("bless", "uni")}
    ests["bless"].fit(x, y, center_set=cs_bless)  # installs the centers
    ests["uni"].fit(x, y, center_set=cs_uni)
    for lam in (1e-3, 1e-5, 1e-7):
        for tag, est in ests.items():
            est.config = FitConfig(lam=lam, iters=5, backend=backend)
            _, us = timed(lambda: est.fit(x, y))  # warm start: centers reused
            err = float(jnp.mean(jnp.sign(est.predict(xte)) != yte))
            emit(f"fig3.{tag}.lam{lam:g}", us, f"cerr@5it={err:.4f}")


def bench_multi_rhs(n: int = 3000, m: int = 256, k: int = 8, folds: int = 4,
                    iters: int = 20, backend=None) -> None:
    """Multi-RHS block-CG amortization: k outputs (or CV folds) share the
    preconditioner and the K_nM streaming, so fused_k{k} should sit far
    below k x fused_k1 while loop_k{k} (the pre-PR 4 column loop, the
    honest baseline) pays the full k x."""
    x = _data(n)
    kern = make_kernel("gaussian", sigma=2.0)
    key = jax.random.PRNGKey(0)
    cs = UniformSampler(m=m, replace=False, weights="identity").sample(key, x, kern)
    centers = x[cs.idx[:m]]
    cols = [jnp.sin((j + 2) * x[:, j % x.shape[1]]) + 0.1 * j for j in range(k)]
    ymulti = jnp.stack(cols, axis=1)
    lam = 1e-5

    _, us1 = timed(lambda: falkon_fit(kern, x, ymulti[:, 0], centers, lam,
                                      iters=iters, backend=backend))
    emit("multi_rhs.fused_k1", us1, f"n={n};M={m};iters={iters}")
    _, usk = timed(lambda: falkon_fit(kern, x, ymulti, centers, lam,
                                      iters=iters, backend=backend))
    emit(f"multi_rhs.fused_k{k}", usk, f"k={k};xk1={usk / us1:.2f}")

    def column_loop():
        return [falkon_fit(kern, x, ymulti[:, j], centers, lam, iters=iters,
                           backend=backend).alpha for j in range(k)]

    _, usl = timed(lambda: jnp.stack(column_loop(), axis=1))
    emit(f"multi_rhs.loop_k{k}", usl, f"k={k};xk1={usl / us1:.2f}")

    lams = (1e-3, 1e-5, 1e-7)
    sweep = KFoldSweep(kernel=kern, lams=lams, folds=folds, iters=iters,
                       backend=backend)
    y1 = ymulti[:, 0]
    # time the scores array so _ready() blocks on real compute (KFoldResult
    # itself is an unregistered dataclass jax cannot block on)
    _, usf = timed(lambda: sweep.run(x, y1, center_set=cs).scores)
    emit("multi_rhs.kfold", usf,
         f"lams={len(lams)};folds={folds};solves={len(lams)};"
         f"fits_naive={len(lams) * folds}")


def bench_scenarios(n: int = 3000, m: int = 256, k: int = 8, iters: int = 15,
                    n_quad: int | None = None, backend=None) -> None:
    """PR 9 scenario layer: the mask-panel tax on the streamed quadratic op
    (the exact-CV mechanism — gate: masked <= 1.15x unmasked), one-vs-rest
    classification as one panel solve, and the predictive-variance scorer.
    The quad pair is timed back-to-back in one process, so the ratio in the
    derived field is runner-speed independent; ``n_quad`` sizes that pair
    separately so the smoke run keeps its timings above dispatch jitter."""
    from repro.api import FalkonClassifier
    from repro.core import resolve_backend

    kern = make_kernel("gaussian", sigma=2.0)
    key = jax.random.PRNGKey(0)
    nq = n_quad if n_quad is not None else n
    xq = _data(nq)
    be = resolve_backend(backend, n=nq)
    centers = xq[:m]
    v = jax.random.normal(key, (m, k))
    mask = (jax.random.uniform(key, (nq, k)) > 0.25).astype(jnp.float32)

    # jit the ops as the fused fit does — the gate measures the mask
    # multiply's compute tax, not eager dispatch overhead
    quad = jax.jit(be.knm_quadratic(kern, xq, centers))
    _, us_plain = timed(lambda: quad(v))
    emit("scenarios.quad_unmasked", us_plain, f"n={nq};M={m};k={k}")
    mquad = jax.jit(be.knm_quadratic(kern, xq, centers, mask=mask))
    _, us_mask = timed(lambda: mquad(v))
    emit("scenarios.quad_masked", us_mask,
         f"n={nq};M={m};k={k};ratio={us_mask / us_plain:.3f};gate=1.15")

    xtr, ytr, xte, yte = _classif(n, max(200, n // 5))
    labels = np.asarray(jnp.where(ytr > 0, 1, 0))
    clf = FalkonClassifier(kernel=kern, sampler=UniformSampler(m=m),
                           config=FitConfig(lam=1e-5, iters=iters,
                                            backend=backend),
                           warm_start=True)

    def fit_clf():
        clf.fit(xtr, labels)
        return clf.model_

    _, us_fit = timed(fit_clf)
    acc = clf.score(xte, np.asarray(jnp.where(yte > 0, 1, 0)))
    emit("scenarios.classifier_fit", us_fit,
         f"n={n};M={m};classes=2;acc={acc:.4f}")

    _, us_var = timed(lambda: clf.model_.predictive_variance(xte))
    emit("scenarios.variance", us_var, f"n_test={xte.shape[0]};M={m}")


def bench_bigk(n: int = 1_000_000, m: int = 1024, d: int = 10, iters: int = 3,
               backend=None) -> None:
    """Out-of-core FALKON (DESIGN.md §10): fit + predict at n rows through
    the stream backend with X host-resident, emitting the subsystem's peak
    device bytes next to wall time. ``knmMB`` in the derived field is what a
    materialized (n, M) K_nM would cost — the peak staying orders of
    magnitude below it is the whole point. Timed once with no warmup pass:
    the wall time is streaming compute (compile is seconds against minutes),
    and a full-size warmup would double a minutes-long bench.
    """
    from repro.core import resolve_backend
    from repro.stream import (ChunkStore, StreamBackend, peak_device_bytes,
                              reset_peak_device_bytes)

    inner = "jnp" if backend in (None, "stream") else backend
    be = StreamBackend(inner=resolve_backend(inner))
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, d), dtype=np.float32)
    y = np.sin(3.0 * x[:, 0]) * np.cos(x[:, 1])
    store = ChunkStore(x, y.astype(np.float32))
    centers = store[np.linspace(0, n - 1, m).astype(np.int64)]
    kern = make_kernel("gaussian", sigma=2.0)
    knm_mb = 4.0 * n * m / 1e6

    reset_peak_device_bytes()
    t0 = time.perf_counter()
    model = falkon_fit(kern, store, jnp.asarray(y), centers, 1e-6,
                       iters=iters, backend=be)
    jax.block_until_ready(model.alpha)
    us_fit = (time.perf_counter() - t0) * 1e6
    peak_mb = peak_device_bytes() / 1e6
    emit("bigk.falkon_fit", us_fit,
         f"n={n};M={m};iters={iters};peakMB={peak_mb:.1f};knmMB={knm_mb:.0f}")

    reset_peak_device_bytes()
    t0 = time.perf_counter()
    pred = model.predict(store)
    jax.block_until_ready(pred)
    us_pred = (time.perf_counter() - t0) * 1e6
    emit("bigk.predict", us_pred,
         f"n={n};M={m};peakMB={peak_device_bytes() / 1e6:.1f};knmMB={knm_mb:.0f}")


def bench_online(n: int = 50_000, m: int = 384, iters: int = 10,
                 backend=None) -> None:
    """Durable online FALKON: absorb a fresh batch into the streamed
    normal-equation accumulators, then warm-refit — O(batch) + O(M^2·iters),
    n-independent — vs a cold from-scratch fit on the same rows. The warm
    row's speedup is the >=5x gate tools/check_bench.py enforces in CI."""
    from repro.api import OnlineFalkon

    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 10)).astype(np.float32)
    y = np.sin(2.0 * x[:, 0]).astype(np.float32)
    kern = make_kernel("gaussian", sigma=2.0)
    centers = jnp.asarray(x[:m])
    batch = n // 10
    of = OnlineFalkon(kern, centers, 1e-6, x=x[: n - batch], y=y[: n - batch],
                      iters=iters, backend=backend or "stream")
    # return the accumulator so timed() blocks on the absorbed batch
    _, us_app = timed(lambda: (of.append(x[n - batch:], y[n - batch:]),
                               of._h)[1])
    _, us_warm = timed(lambda: of.refit())
    _, us_cold = timed(lambda: falkon_fit(
        kern, jnp.asarray(x), jnp.asarray(y), centers, 1e-6, iters=iters,
        backend=backend or "stream"))
    emit("online.append", us_app, f"n={n};M={m};batch={batch}")
    emit("online.cold_refit", us_cold, f"n={n};M={m};iters={iters}")
    emit("online.warm_refit", us_warm,
         f"n={n};M={m};iters={iters};speedup={us_cold / us_warm:.1f}x")


def bench_lm_steps(backend=None) -> None:
    """Smoke-scale per-arch step timing (framework sanity, not paper)."""
    from repro.configs import get_config, list_archs, smoke
    from repro.data import TokenPipeline
    from repro.optim import OptConfig
    from repro.training import make_train_step, train_state_init

    for name in list_archs():
        cfg = smoke(get_config(name))
        state = train_state_init(cfg, jax.random.PRNGKey(0))
        step = jax.jit(make_train_step(cfg, OptConfig(), loss_chunks=4))
        pipe = TokenPipeline(cfg.vocab_size, batch=4, seq=64)
        if not cfg.embed_inputs:
            mk = lambda s: {"frames": jnp.zeros((4, 64, cfg.d_model), jnp.bfloat16),
                            "labels": pipe.batch_at(s)["labels"]}
        elif cfg.pos == "mrope":
            def mk(s):
                b = pipe.batch_at(s)
                p = jnp.broadcast_to(jnp.arange(64), (4, 64))
                b["mrope_positions"] = jnp.stack([p, p, p], 1)
                b["pixel_embeds"] = jnp.zeros((4, cfg.extra_image_tokens, cfg.d_model),
                                              jnp.bfloat16)
                return b
        else:
            mk = pipe.batch_at
        state, _ = step(state, mk(0))  # compile
        t0 = time.perf_counter()
        state, metrics = step(state, mk(1))
        jax.block_until_ready(metrics["loss"])
        emit(f"lm.train_step.{name}", (time.perf_counter() - t0) * 1e6,
             f"loss={float(metrics['loss']):.3f}")


# registry name -> (full-size call, smoke-size call)
BENCHES = {
    "fig1": (bench_fig1_raccuracy, lambda backend: bench_fig1_raccuracy(n=600, backend=backend)),
    "fig2": (bench_fig2_runtime_scaling,
             lambda backend: bench_fig2_runtime_scaling(backend=backend, sizes=(500, 1000))),
    "table1": (bench_table1_complexity,
               lambda backend: bench_table1_complexity(n=600, backend=backend)),
    "fig45": (bench_fig45_falkon,
              lambda backend: bench_fig45_falkon(n=800, m_target=120, n_test=200,
                                                 backend=backend)),
    "fig3": (bench_fig3_lambda_stability,
             lambda backend: bench_fig3_lambda_stability(n=600, m_cap=120, n_test=200,
                                                         backend=backend)),
    "multi_rhs": (bench_multi_rhs,
                  lambda backend: bench_multi_rhs(n=600, m=96, k=8, iters=12,
                                                  backend=backend)),
    "scenarios": (bench_scenarios,
                  lambda backend: bench_scenarios(n=600, m=96, k=8, iters=10,
                                                  n_quad=6000, backend=backend)),
    "bigk": (bench_bigk,
             lambda backend: bench_bigk(n=20_000, m=256, iters=3,
                                        backend=backend)),
    "online": (bench_online,
               lambda backend: bench_online(n=20_000, m=256, iters=8,
                                            backend=backend)),
    "lm": (bench_lm_steps, bench_lm_steps),
}


def main() -> None:
    global _REPEATS
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backend",
                    choices=["auto", "jnp", "pallas", "sharded", "stream"],
                    default="auto", help="kernel-operator backend for BLESS/FALKON")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write records as a JSON array to PATH")
    ap.add_argument("--repeats", type=int, default=1,
                    help="timed runs per measurement; the median is reported")
    ap.add_argument("--only", default=None,
                    help="comma-separated substrings of bench names to run "
                         f"(registry: {','.join(BENCHES)})")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny problem sizes (CI smoke job)")
    args = ap.parse_args()
    enable_compile_cache()
    backend = None if args.backend == "auto" else args.backend
    _REPEATS = max(1, args.repeats)
    wanted = [w for w in (args.only or "").split(",") if w]
    for w in wanted:  # a typo'd filter must not silently bench nothing
        if not any(w in name for name in BENCHES):
            ap.error(f"--only token {w!r} matches no bench; "
                     f"valid figure names: {', '.join(sorted(BENCHES))} "
                     "(substring match, comma-separated)")
    print("name,us_per_call,derived")
    for name, (full, smoke) in BENCHES.items():
        if wanted and not any(w in name for w in wanted):
            continue
        (smoke if args.smoke else full)(backend=backend)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(_RECORDS, f, indent=1)
        print(f"# wrote {len(_RECORDS)} records -> {args.json}", flush=True)


if __name__ == "__main__":
    main()
