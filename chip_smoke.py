#!/usr/bin/env python3
"""Chip smoke test: the BLESS -> FALKON -> predict -> serve path on a TPU,
at the shape of SUSY (the BLESS paper's large-scale FALKON-BLESS dataset).

    python3 chip_smoke.py             # one chip: the whole main path
    python3 chip_smoke.py --chips 4   # only the data-parallel FALKON fit,
                                      # over a 4-device mesh vs one device

Data are SUSY-shaped synthetic rows made from ``--seed``: d = 18, two
classes, 5,000,000 training rows and 100,000 held-out rows, in core on the
device. Everything goes through the public API with ``backend=None``, so
the platform heuristic picks the fused Pallas kernels on a TPU:

  1. ``BlessSampler(lam=3e-5, m_cap=8192)`` ladder; the final level holds
     more than 4096 centers (past the fused scorer's M <= 1024 and the
     CG kernels' old VMEM limit).
  2. ``FalkonRegressor(config=FitConfig(lam=1e-6, iters=20))`` fit on the
     ladder's centers, then ``predict`` on the held-out rows.
  3. ``KrrServer`` answers 36 requests of 1 to 4096 rows.

Checks (any failure exits non-zero; there is no fallback):

  * the scorer, the CG operator, K_nM^T y and predict lower to Mosaic
    kernels (``tpu_custom_call``), not interpret mode;
  * (a) held-out sign error under 0.25;
  * (b) Pallas predictions and one CG operator application within 1e-3
    relative of a plain fp32 reference on the same centers and inputs:
    ``JnpBackend`` on the same chip under
    ``jax.default_matmul_precision("highest")``; the Pallas fit's held-out
    error within 0.002 of the reference fit's (20 CG iterations at
    lam = 1e-6 amplify summation order to ~1e-3 in the predictions, so two
    fp32 fits are not compared elementwise at 1e-3);
  * (c) Eq. 3 scores of two ladder levels (fused kernel, and the composed
    gram + quadform past M = 1024) within 1e-3 relative of that reference;
  * (d) every served result equals ``predict`` on the same rows.

Times printed per phase are smoke wall times (compiles included), not
benchmarks. The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or without this checkout's ``src/`` beside it, the script
exits non-zero and prints no result. ``REPRO_*`` environment overrides are
refused: they would steer the backend choice this script checks.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from contextlib import contextmanager

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.join(ROOT, "examples"))

N_TRAIN = 5_000_000  # SUSY's row count
N_TEST = 100_000
SIGMA = 4.0  # the FALKON paper's SUSY bandwidth
LAM_BLESS = 3e-5  # ladder ends near M = 6000 at this shape
M_CAP = 8192
M_MIN = 4096  # the final level must pass this
LAM = 1e-6
ITERS = 20
RTOL = 1e-3
MAX_ERR = 0.25
FIT_ERR_TOL = 0.002
REQUEST_ROWS = (1, 7, 64, 300, 1024, 4096, 2000, 33, 513,
                2, 129, 4095, 777, 16, 1500, 250, 3, 3000)  # x2 = 36 requests


def log(msg: str) -> None:
    print(msg, flush=True)


@contextmanager
def phase(name: str):
    t0 = time.perf_counter()
    yield
    log(f"[{name}] smoke wall time {time.perf_counter() - t0:.2f} s")


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")
    log(f"check passed: {what}")


def rel_err(got, want) -> float:
    """||got - want|| / ||want||, on the host in float64 (the two may live
    on different device sets)."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def make_data(seed: int, n: int, n_test: int):
    """SUSY-shaped two-class rows (``examples/falkon_endtoend.susy_like``),
    one draw split into training and held-out rows."""
    import jax
    from falkon_endtoend import susy_like

    xa, ya = susy_like(n + n_test, seed=seed)
    jax.block_until_ready((xa, ya))
    return xa[:n], ya[:n], xa[n:], ya[n:]


def check_lowering(be, kern, d: int) -> None:
    """The scorer (fused and composed), the CG operator, K_nM^T y and
    predict must lower to Mosaic kernels on this platform."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    rows = jax.ShapeDtypeStruct((4096, d), f32)
    vec = lambda k: jax.ShapeDtypeStruct((k,), f32)  # noqa: E731

    def scorer(xc, z):
        m = z.shape[0]
        return be.rls_scores(kern, xc, z, jnp.ones((m,), bool),
                             jnp.ones((m,), f32), jnp.asarray(1.0, f32))

    for m in (512, 2048):  # fused kernel; composed gram + quadform
        text = jax.jit(scorer).lower(rows, jax.ShapeDtypeStruct((m, d), f32)).as_text()
        check("tpu_custom_call" in text, f"scorer at M={m} lowers to a Mosaic kernel")
    z = jax.ShapeDtypeStruct((2048, d), f32)
    ops = {
        "CG operator K_nM^T K_nM v": (lambda x, z, v: be.knm_quadratic(kern, x, z)(v), vec(2048)),
        "K_nM^T y": (lambda x, z, y: be.knm_t(kern, x, z, y), vec(4096)),
        "predict K_nM alpha": (lambda x, z, a: be.knm_matvec(kern, x, z, a), vec(2048)),
    }
    for name, (fn, arg) in ops.items():
        text = jax.jit(fn).lower(rows, z, arg).as_text()
        check("tpu_custom_call" in text, f"{name} lowers to a Mosaic kernel")


def check_scores(be, ref, kern, x, xq, level, n: int) -> float:
    """Eq. 3 scores of ``xq`` against one ladder level's (J, A) at its lam:
    ``be`` against ``ref`` under highest matmul precision."""
    import jax
    import jax.numpy as jnp

    cs = level.centers
    lamn = jnp.asarray(level.lam * n, jnp.float32)
    z = x[cs.idx]
    reg = jnp.where(cs.mask, lamn * cs.weight, 1.0)
    got = be.rls_scores(kern, xq, z, cs.mask, reg, lamn)
    with jax.default_matmul_precision("highest"):
        want = ref.rls_scores(kern, xq, z, cs.mask, reg, lamn)
    return rel_err(got, want)


def ladder_and_centers(x, kern, seed: int):
    """The BLESS ladder through the public sampler; returns (sampler, result)."""
    import jax
    from repro.api import BlessSampler

    sampler = BlessSampler(lam=LAM_BLESS, m_cap=M_CAP)
    with phase("bless ladder"):
        res = sampler.ladder(jax.random.PRNGKey(seed), x, kern)
        jax.block_until_ready(res.final.centers.idx)
    log(f"BLESS: {len(res.levels)} levels, M reached = {res.final.m_h} "
        f"(d_h = {res.final.d_h:.1f}, R = {res.final.r_h} candidates)")
    return sampler, res


def run_one_chip(seed: int, n: int = N_TRAIN, n_test: int = N_TEST,
                 m_min: int = M_MIN) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.api import FalkonRegressor, FitConfig, KrrServer, make_kernel
    from repro.core import JnpBackend, PallasBackend, falkon_fit, resolve_backend

    with phase("data"):
        x, y, xte, yte = make_data(seed, n, n_test)
    log(f"data: X {tuple(x.shape)}, held out {tuple(xte.shape)}")
    kern = make_kernel("gaussian", sigma=SIGMA)
    be = resolve_backend(None, n=n)
    check(type(be) is PallasBackend, f"backend=None resolves to PallasBackend (got {be!r})")
    check_lowering(be, kern, x.shape[1])

    sampler, res = ladder_and_centers(x, kern, seed)
    m = res.final.m_h
    check(m > m_min, f"ladder ends with M = {m} > {m_min}")

    ref = JnpBackend()
    fused = max((lv for lv in res.levels if lv.centers.idx.shape[0] <= 1024),
                key=lambda lv: lv.m_h)
    xq = xte[:8192]
    with phase("score parity"):
        for lv in (fused, res.final):
            err = check_scores(be, ref, kern, x, xq, lv, n)
            log(f"scores at lam={lv.lam:.3e}, M buffer={lv.centers.idx.shape[0]}: "
                f"rel err vs fp32 reference {err:.3e}")
            check(err < RTOL, f"(c) Pallas scores within {RTOL} of the fp32 reference")

    est = FalkonRegressor(kernel=kern, sampler=sampler,
                          config=FitConfig(lam=LAM, iters=ITERS))
    with phase("falkon fit"):
        est.fit(x, y, center_set=res.final.centers)
        jax.block_until_ready(est.model_.alpha)
    check(type(est.model_.backend) is PallasBackend, "the fit ran on PallasBackend")
    with phase("predict"):
        pred = est.predict(xte)
        jax.block_until_ready(pred)
    err = float(jnp.mean(jnp.sign(pred) != yte))
    log(f"held-out sign error {err:.4f} on {n_test} rows")
    check(err < MAX_ERR, f"(a) held-out sign error {err:.4f} < {MAX_ERR}")

    # The reference takes the same host CG loop as the Pallas fit: 20 CG
    # iterations at lam = 1e-6 amplify summation order alone to ~1e-3 (the
    # jnp fused and host loops differ by 3.4e-3 at n = 2e5 on the CPU), so
    # the kernels are held to RTOL on identical inputs, and the two fits to
    # the same held-out error.
    centers, a_diag = est.centers_, est.a_diag_
    with phase("fp32 reference fit + predict"), jax.default_matmul_precision("highest"):
        ref_model = falkon_fit(kern, x, y, centers, LAM, a_diag=a_diag, iters=ITERS,
                               backend=ref, fused=False)
        pred_ref = ref_model.predict(xte)
        jax.block_until_ready(pred_ref)
    with phase("kernel parity at the fit's shapes"):
        v = jax.random.normal(jax.random.PRNGKey(seed + 1), (centers.shape[0],))
        q_pallas = be.knm_quadratic(kern, x, centers)(v)
        with jax.default_matmul_precision("highest"):
            q_ref = ref.knm_quadratic(kern, x, centers)(v)
        qerr = rel_err(q_pallas, q_ref)
        kerr = rel_err(ref_model.predict(xte, backend=be), pred_ref)
    log(f"CG operator K_nM^T K_nM v at n={n}: rel err vs fp32 reference {qerr:.3e}")
    check(qerr < RTOL, f"(b) the Pallas CG operator within {RTOL} of the fp32 reference")
    log(f"predict of the reference's alpha: rel err vs fp32 reference {kerr:.3e}")
    check(kerr < RTOL, f"(b) Pallas predictions within {RTOL} of the fp32 reference")
    err_ref = float(jnp.mean(jnp.sign(pred_ref) != yte))
    log(f"Pallas fit vs fp32 reference fit: predictions rel err "
        f"{rel_err(pred, pred_ref):.3e}, held-out sign error {err:.4f} vs {err_ref:.4f}")
    check(abs(err - err_ref) <= FIT_ERR_TOL,
          f"(b) the Pallas fit's held-out error within {FIT_ERR_TOL} of the reference fit's")

    server = KrrServer(est)
    sizes = REQUEST_ROWS * 2
    offs = np.cumsum((0,) + sizes)
    requests = [xte[o:o + r] for o, r in zip(offs, sizes)]
    served = {}
    with phase("serve"):
        for half in (requests[:len(sizes) // 2], requests[len(sizes) // 2:]):
            rids = [server.submit(r) for r in half]
            out = server.flush()
            served.update({rid: out[rid] for rid in rids})
        jax.block_until_ready(list(served.values()))
    log(f"served {server.stats['requests']} requests, {server.stats['rows']} rows "
        f"in {server.stats['dispatches']} waves, buckets {sorted(server.stats['buckets'])}")
    got = jnp.concatenate([served[i] for i in range(len(sizes))])
    want = est.predict(xte[:int(offs[-1])])
    diff = float(jnp.max(jnp.abs(got - want)))
    log(f"served vs predict: max abs diff {diff:.3e}, bitwise equal "
        f"{bool(jnp.array_equal(got, want))}")
    check(diff <= 1e-6 * float(jnp.max(jnp.abs(want))),
          "(d) every served result equals predict on the same rows")


def run_four_chips(seed: int, n: int = N_TRAIN, n_test: int = N_TEST) -> None:
    """The data-parallel FALKON (README's "sharded" backend through
    ``falkon_fit_distributed``) over a 4-device ``data_mesh``, against the
    same fit on a one-device mesh, on the ladder's centers."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, Mesh
    from repro.api import FalkonRegressor, FitConfig, make_kernel
    from repro.core import JnpBackend
    from repro.core.distributed import data_mesh, falkon_fit_distributed, shard_rows

    with phase("data"):
        x, y, xte, yte = make_data(seed, n, n_test)
    kern = make_kernel("gaussian", sigma=SIGMA)
    sampler, res = ladder_and_centers(x, kern, seed)
    m = res.final.m_h
    centers = x[res.final.centers.idx[:m]]
    a_diag = res.final.centers.weight[:m]

    mesh4 = data_mesh()
    check(mesh4.devices.size == 4, f"data_mesh spans 4 devices ({mesh4.devices.size})")
    xs = shard_rows(mesh4, x)
    per_dev = {s.device: s.data.shape[0] for s in xs.addressable_shards}
    log(f"row shards: {{{', '.join(f'{d.id}: {r}' for d, r in per_dev.items())}}}")
    check(set(per_dev) == set(mesh4.devices.flat)
          and set(per_dev.values()) == {xs.shape[0] // 4},
          "rows are placed on all four devices, a quarter each")
    del xs
    mesh1 = Mesh(np.asarray(jax.devices()[:1]), ("data",), axis_types=(AxisType.Auto,))

    preds = {}
    for name, mesh in (("4-device", mesh4), ("1-device", mesh1)):
        with phase(f"sharded fit + predict, {name} mesh"):
            model = falkon_fit_distributed(mesh, kern, x, y, centers, LAM,
                                           a_diag=a_diag, iters=ITERS)
            preds[name] = model.predict(xte)
            jax.block_until_ready(preds[name])
    err = float(jnp.mean(jnp.sign(preds["4-device"]) != yte))
    log(f"4-device fit: held-out sign error {err:.4f}")
    perr = rel_err(preds["4-device"], preds["1-device"])
    log(f"4-device vs 1-device predictions: rel err {perr:.3e}")
    check(perr < RTOL, f"4-device fit within {RTOL} of the one-device fit")
    with phase("fp32 reference fit + predict"), jax.default_matmul_precision("highest"):
        ref = FalkonRegressor(kernel=kern, sampler=sampler,
                              config=FitConfig(lam=LAM, iters=ITERS, backend=JnpBackend()))
        ref.fit(x, y, center_set=res.final.centers)
        pred_ref = ref.predict(xte)
        jax.block_until_ready(pred_ref)
    log(f"4-device fit vs fp32 reference (information): rel err "
        f"{rel_err(preds['4-device'], pred_ref):.3e}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel fit over a 4-device mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    steering = sorted(k for k in os.environ if k.startswith("REPRO_"))
    if steering:
        raise SystemExit(f"chip_smoke: refusing to run with {steering} set; "
                         "they override the backend choice this smoke checks")

    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX platform {jax.default_backend()!r})")
    devices = jax.devices()
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
                         f"JAX sees {len(devices)}")
    import repro
    from repro.runtime.compile_cache import enable_compile_cache

    if not os.path.abspath(repro.__file__).startswith(os.path.join(ROOT, "src")):
        raise SystemExit(f"chip_smoke: repro imported from {repro.__file__}, "
                         f"not this checkout ({ROOT})")
    log(f"compile cache: {enable_compile_cache()}")
    log(f"jax {jax.__version__}, devices {devices}")

    with phase("total"):
        if args.chips == 4:
            run_four_chips(args.seed)
        else:
            run_one_chip(args.seed)
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
