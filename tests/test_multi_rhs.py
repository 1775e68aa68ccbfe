"""Multi-RHS block-CG (core/falkon.py): per-column parity with independent
single-RHS solves across every kernel family and backend, the k-bucketed
fused-fit cache (zero retraces within a bucket), per-column convergence
masking, the PR 9 mask-panel seam (per-column row exclusion in the
quadratic op), and the exact KFoldSweep scenario vs naive per-fold
refits."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import FitConfig, KFoldSweep, UniformSampler
from repro.core import cg, falkon_fit, make_kernel
from repro.core.gram import resolve_backend
from repro.runtime import spans

BACKENDS = ["jnp", "pallas", "sharded"]
MASK_BACKENDS = ["jnp", "pallas", "sharded", "stream"]
ALL_FAMILIES = ["gaussian", "laplacian", "linear", "matern32", "cauchy"]
MASK_FAMILIES = ["gaussian", "laplacian", "matern32"]


def _problem(n=300, m=32, d=6, k=3, seed=0):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (n, d))
    cols = [jnp.sin(2 * x[:, 0]), jnp.cos(x[:, 1]), 0.3 * x[:, 2] ** 2,
            x[:, 3] * x[:, 0], jnp.tanh(x[:, 1] + x[:, 2]), -x[:, 4],
            jnp.sin(x[:, 5]) * x[:, 0], jnp.abs(x[:, 2])]
    return x, jnp.stack(cols[:k], axis=1), x[:m]


# -- parity: one block-CG vs k independent solves ----------------------------


@pytest.mark.parametrize("name", BACKENDS)
@pytest.mark.parametrize("kind", ALL_FAMILIES)
@pytest.mark.parametrize("k", [1, 3, 8])
def test_multi_rhs_matches_column_loop(name, kind, k):
    """The panel solve shares the preconditioner and the K_nM streaming, but
    every column's solution must match its own single-RHS fit (the PR 3
    column loop) to CG/fp32 tolerance."""
    kern = make_kernel(kind, sigma=1.7, kappa_sq=10.0)
    x, y, z = _problem(k=k)
    multi = falkon_fit(kern, x, y, z, 1e-3, iters=10, backend=name)
    assert multi.alpha.shape == (z.shape[0], k)
    pred = multi.predict(x)
    assert pred.shape == (x.shape[0], k)
    for j in range(k):
        col = falkon_fit(kern, x, y[:, j], z, 1e-3, iters=10, backend=name)
        ref = col.predict(x)
        rel = float(jnp.linalg.norm(pred[:, j] - ref)
                    / jnp.maximum(jnp.linalg.norm(ref), 1e-30))
        assert rel < 1e-3, (kind, name, j, rel)


def test_multi_rhs_host_path_matches_fused():
    """fused=False drives the same panel CG from the host loop."""
    kern = make_kernel("gaussian", sigma=1.5)
    x, y, z = _problem(k=3)
    fused = falkon_fit(kern, x, y, z, 1e-3, iters=20, backend="jnp")
    host = falkon_fit(kern, x, y, z, 1e-3, iters=20, backend="jnp", fused=False)
    rel = float(jnp.linalg.norm(fused.predict(x) - host.predict(x))
                / jnp.linalg.norm(host.predict(x)))
    assert rel < 1e-3


def test_multi_output_callback_rejected():
    kern = make_kernel("gaussian", sigma=1.5)
    x, y, z = _problem(k=2)
    with pytest.raises(ValueError, match="single-output"):
        falkon_fit(kern, x, y, z, 1e-3, callback=lambda i, m: None)


# -- the k-bucketed fused-fit cache ------------------------------------------


def test_fused_cache_k_bucket_zero_retrace():
    """k is padded to a pow2 column bucket: every RHS count in a bucket
    shares one executable (m=44 / iters=13 are unique to this test so other
    files' fits cannot mask the traces)."""
    kern = make_kernel("gaussian", sigma=1.5)
    x, y8, z = _problem(m=44, k=8)
    t0 = spans.retraces("falkon.fused_fit")
    falkon_fit(kern, x, y8[:, :3], z, 1e-3, iters=13, backend="jnp")
    assert spans.retraces("falkon.fused_fit") == t0 + 1  # k=3 compiled bucket kb=4
    falkon_fit(kern, x, y8[:, :4], z, 1e-3, iters=13, backend="jnp")
    assert spans.retraces("falkon.fused_fit") == t0 + 1  # k=4: same bucket, no trace
    falkon_fit(kern, x, y8[:, :5], z, 1e-3, iters=13, backend="jnp")
    assert spans.retraces("falkon.fused_fit") == t0 + 2  # k=5 -> bucket kb=8
    falkon_fit(kern, x, y8, z, 1e-3, iters=13, backend="jnp")
    assert spans.retraces("falkon.fused_fit") == t0 + 2  # k=8 rides the kb=8 bucket
    falkon_fit(kern, x, y8[:, 0], z, 1e-3, iters=13, backend="jnp")
    assert spans.retraces("falkon.fused_fit") == t0 + 3  # single-output: kb=1


def test_k_bucket_padding_columns_are_inert():
    """A k=3 fit runs in the kb=4 bucket with a zero fourth column; its
    presence must not perturb the real columns (vs a k=4 fit whose fourth
    column IS explicitly zero)."""
    kern = make_kernel("gaussian", sigma=1.5)
    x, y, z = _problem(k=3)
    a = falkon_fit(kern, x, y, z, 1e-3, iters=15, backend="jnp")
    b = falkon_fit(kern, x, jnp.pad(y, ((0, 0), (0, 1))), z, 1e-3, iters=15,
                   backend="jnp")
    np.testing.assert_array_equal(a.alpha, b.alpha[:, :3])
    np.testing.assert_array_equal(b.alpha[:, 3], jnp.zeros(z.shape[0]))


# -- the mask-panel seam: per-column row exclusion ---------------------------


def _mask_panel(n, k, seed=5):
    """A (n, k) 0/1 panel with ~25% of rows excluded per column (and one
    all-ones column so the unmasked fast path is exercised in-panel)."""
    key = jax.random.PRNGKey(seed)
    panel = (jax.random.uniform(key, (n, k)) > 0.25).astype(jnp.float32)
    return panel.at[:, 0].set(1.0) if k > 1 else panel


@pytest.mark.parametrize("name", MASK_BACKENDS)
@pytest.mark.parametrize("kind", MASK_FAMILIES)
@pytest.mark.parametrize("k", [1, 3, 8])
def test_masked_quadratic_backend_parity(name, kind, k):
    """Masked K_nM^T diag(m_j) K_nM v_j must agree across every backend
    (including the out-of-core stream) with the jnp reference at the
    documented 1e-4 scale-relative cross-backend parity."""
    kern = make_kernel(kind, sigma=1.7, kappa_sq=10.0)
    x, _, z = _problem(k=k)
    v = jax.random.normal(jax.random.PRNGKey(9), (z.shape[0], k))
    v = v[:, 0] if k == 1 else v
    mask = _mask_panel(x.shape[0], k)
    mask = mask[:, 0] if k == 1 else mask
    be = resolve_backend(name)
    ref = resolve_backend("jnp").knm_quadratic(kern, x, z, mask=mask)(v)
    got = be.knm_quadratic(kern, x, z, mask=mask)(v)
    assert got.shape == ref.shape
    scale = float(jnp.max(jnp.abs(ref)))
    err = float(jnp.max(jnp.abs(got - ref))) / scale
    # the mask multiply must add no error beyond the backend's own unmasked
    # cross-backend noise (laplacian-on-sharded already sits at ~2e-4 from
    # the shard_map |x-z| reduction — pre-existing, not a mask artifact)
    base_ref = resolve_backend("jnp").knm_quadratic(kern, x, z)(v)
    base_got = be.knm_quadratic(kern, x, z)(v)
    base = float(jnp.max(jnp.abs(base_got - base_ref))) / float(jnp.max(jnp.abs(base_ref)))
    assert err < max(1e-4, 2.0 * base), (name, kind, k, err, base)


@pytest.mark.parametrize("name", MASK_BACKENDS)
@pytest.mark.parametrize("k", [1, 3])
def test_masked_knm_t_backend_parity(name, k):
    """knm_t folds the mask into the targets: K_nM^T (mask * y) on every
    backend equals the jnp reference."""
    kern = make_kernel("gaussian", sigma=1.7)
    x, y, z = _problem(k=k)
    mask = _mask_panel(x.shape[0], k)
    mask = mask[:, 0] if k == 1 else mask
    ref = resolve_backend("jnp").knm_t(kern, x, z, y, mask=mask)
    got = resolve_backend(name).knm_t(kern, x, z, y, mask=mask)
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(got - ref))) / scale < 1e-4, (name, k)


@pytest.mark.parametrize("name", MASK_BACKENDS)
def test_all_ones_mask_is_bit_identical(name):
    """mask=ones must produce bit-for-bit the unmasked program's output on
    every backend — the masked path multiplies by 1.0 between the same two
    contractions, in the same order (mask=None additionally skips the
    multiply entirely; this pins that the mask insertion point is exact)."""
    kern = make_kernel("gaussian", sigma=1.7)
    x, y, z = _problem(k=3)
    v = jax.random.normal(jax.random.PRNGKey(9), (z.shape[0], 3))
    be = resolve_backend(name)
    ones = jnp.ones_like(y)
    np.testing.assert_array_equal(
        np.asarray(be.knm_quadratic(kern, x, z, mask=ones)(v)),
        np.asarray(be.knm_quadratic(kern, x, z)(v)))
    np.testing.assert_array_equal(
        np.asarray(be.knm_t(kern, x, z, y, mask=ones)),
        np.asarray(be.knm_t(kern, x, z, y)))


def test_masked_quadratic_equals_dense_reference():
    """Column j of the masked op is literally K_nM^T diag(m_j) K_nM v_j —
    checked against the dense einsum on small shapes."""
    kern = make_kernel("gaussian", sigma=1.7)
    x, _, z = _problem(n=150, m=24, k=3)
    v = jax.random.normal(jax.random.PRNGKey(9), (z.shape[0], 3))
    mask = _mask_panel(x.shape[0], 3)
    g = kern.cross(x, z)
    dense = jnp.einsum("nm,nk,nj,jk->mk", g, mask, g, v)
    got = resolve_backend("jnp").knm_quadratic(kern, x, z, mask=mask)(v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                               rtol=1e-4, atol=1e-4)


def test_mask_none_stays_bit_identical_program():
    """mask=None takes the original (pre-PR 9) program path: repeated calls
    are bit-identical to each other, and falkon_fit without row_mask is
    unchanged by the seam extension."""
    kern = make_kernel("gaussian", sigma=1.5)
    x, y, z = _problem(k=3)
    a = falkon_fit(kern, x, y, z, 1e-3, iters=10, backend="jnp")
    b = falkon_fit(kern, x, y, z, 1e-3, iters=10, backend="jnp")
    np.testing.assert_array_equal(np.asarray(a.alpha), np.asarray(b.alpha))


def test_falkon_fit_row_mask_validation():
    kern = make_kernel("gaussian", sigma=1.5)
    x, y, z = _problem(k=3)
    with pytest.raises(ValueError, match="row_mask"):
        falkon_fit(kern, x, y, z, 1e-3, row_mask=jnp.ones((x.shape[0],)))


def test_falkon_fit_row_mask_equals_subset_fit():
    """A fused panel fit where column j masks out a row block must equal a
    from-scratch fit on the kept rows (fold-local n in the regularization
    — the exact-CV semantics at the falkon_fit level)."""
    kern = make_kernel("gaussian", sigma=1.5)
    x, y, z = _problem(k=2)
    n = x.shape[0]
    keep = jnp.arange(n) >= 60
    mask = jnp.stack([jnp.ones(n), keep.astype(jnp.float32)], axis=1)
    panel = falkon_fit(kern, x, y * mask, z, 1e-2, iters=25, backend="jnp",
                       row_mask=mask)
    sub = falkon_fit(kern, x[keep], y[keep, 1], z, 1e-2, iters=25,
                     backend="jnp")
    full = falkon_fit(kern, x, y[:, 0], z, 1e-2, iters=25, backend="jnp")
    for col, ref in ((1, sub), (0, full)):
        rel = float(jnp.linalg.norm(panel.alpha[:, col] - ref.alpha)
                    / jnp.linalg.norm(ref.alpha))
        assert rel < 1e-4, (col, rel)


# -- per-column convergence masking ------------------------------------------


def test_cg_freezes_converged_columns():
    """A zero RHS column (rs0 = 0) must stay exactly zero while the live
    columns converge; an easy column frozen early must not drift."""
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (40, 40))
    a = a @ a.T / 40.0 + jnp.eye(40)
    b_live = jax.random.normal(jax.random.PRNGKey(1), (40,))
    b = jnp.stack([b_live, jnp.zeros(40)], axis=1)
    sol = cg(lambda v: a @ v, b, 60)
    np.testing.assert_array_equal(sol[:, 1], jnp.zeros(40))
    np.testing.assert_allclose(a @ sol[:, 0], b_live, rtol=1e-4, atol=1e-4)
    # panel solve of the live column agrees with the single-RHS path
    single = cg(lambda v: a @ v, b_live, 60)
    np.testing.assert_allclose(sol[:, 0], single, rtol=1e-4, atol=1e-5)


# -- KFoldSweep: model selection as one multi-RHS solve per lambda -----------


LAMS = (1e-2, 1e-4, 1e-6)


def _sweep_problem(n=400, d=6, seed=0):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (n, d))
    y = (jnp.sin(2 * x[:, 0]) + 0.3 * x[:, 1] ** 2
         + 0.05 * jax.random.normal(jax.random.PRNGKey(seed + 1), (n,)))
    return x, y


def test_kfold_sweep_matches_naive_per_fold_refits():
    """Every (lam, fold) score must equal the naive loop: a full single-RHS
    refit on the fold's TRAINING ROWS ONLY (exact row-exclusion — held-out
    rows contribute nothing to the operator, fold-local n in the
    regularization), scored on the held-out rows. tests/test_scenarios.py
    pins the well-conditioned end of this parity at 1e-6."""
    from repro.api.sweep import fold_ids

    x, y = _sweep_problem()
    folds = 4
    sweep = KFoldSweep(kernel="gaussian", sigma=1.5, sampler=UniformSampler(m=64),
                       lams=LAMS, folds=folds, iters=15, backend="jnp", seed=0)
    res = sweep.run(x, y)
    assert res.scores.shape == (len(LAMS), folds)

    kern = make_kernel("gaussian", sigma=1.5)
    k_sample, k_fold = jax.random.split(jax.random.PRNGKey(0))
    fid = fold_ids(k_fold, x.shape[0], folds)
    np.testing.assert_array_equal(res.fold_id, fid)
    cs = UniformSampler(m=64).sample(k_sample, x, kern, backend="jnp")
    m = int(cs.count)
    centers, a_diag = x[cs.idx[:m]], cs.weight[:m]
    for li, lam in enumerate(LAMS):
        for f in range(folds):
            train = np.asarray(fid != f)
            model = falkon_fit(kern, x[train], y[train], centers, lam,
                               a_diag=a_diag, iters=15, backend="jnp")
            held = np.asarray(fid == f)
            mse = float(jnp.mean((model.predict(x[held]) - y[held]) ** 2))
            got = float(res.scores[li, f])
            assert abs(mse - got) < 1e-3 * max(1.0, abs(mse)), (li, f, mse, got)
    assert res.best_lam == LAMS[res.best_index]
    assert float(res.mean_scores[res.best_index]) == float(jnp.min(res.mean_scores))


def test_kfold_sweep_rides_fused_cache():
    """The whole lambda grid after the first fit is cache hits: fold count
    fixes the k bucket, lam is traced, centers are warm-started."""
    x, y = _sweep_problem(seed=7)
    sweep = KFoldSweep(kernel="gaussian", sigma=1.5, sampler=UniformSampler(m=52),
                       lams=LAMS, folds=4, iters=12, backend="jnp", seed=3)
    res1 = sweep.run(x, y)
    t0 = spans.retraces("falkon.fused_fit")
    res2 = sweep.run(x, y)  # same shapes end to end -> zero retraces
    assert spans.retraces("falkon.fused_fit") == t0
    np.testing.assert_allclose(res1.scores, res2.scores, rtol=1e-6, atol=1e-7)


def test_kfold_sweep_validates_inputs():
    x, y = _sweep_problem(n=40)
    with pytest.raises(ValueError, match="single-output"):
        KFoldSweep(lams=(1e-3,)).run(x, jnp.stack([y, y], axis=1))
    with pytest.raises(ValueError, match="folds"):
        KFoldSweep(lams=(1e-3,), folds=1).run(x, y)


def test_fold_ids_are_balanced():
    from repro.api.sweep import fold_ids

    fid = fold_ids(jax.random.PRNGKey(0), 103, 5)
    sizes = [int(jnp.sum(fid == f)) for f in range(5)]
    assert min(sizes) >= max(sizes) - 1 and sum(sizes) == 103


def test_kfold_sweep_center_set_bypass():
    """center_set= skips the sampler (e.g. one BLESS ladder shared across
    sweeps) and is reused for every lambda."""
    x, y = _sweep_problem(n=300)
    kern = make_kernel("gaussian", sigma=1.5)
    cs = UniformSampler(m=48).sample(jax.random.PRNGKey(5), x, kern, backend="jnp")
    sweep = KFoldSweep(kernel=kern, lams=(1e-3, 1e-5), folds=3, iters=10,
                       backend="jnp")
    res = sweep.run(x, y, center_set=cs)
    assert res.center_set is cs
    assert res.scores.shape == (2, 3)
