"""Program spans and retrace counters (repro.runtime.spans): what a
profiler session records on the fit path and the BLESS ladder."""
import glob
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.api import FalkonRegressor, FitConfig, UniformSampler, make_kernel
from repro.core import PallasBackend, bless, bless_r, falkon_fit
from repro.core import falkon as falkon_mod
from repro.runtime import spans

KERN = make_kernel("gaussian", sigma=1.5)


def _data(n=400, d=5, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, d))
    return x, jnp.sin(2 * x[:, 0]) + 0.3 * x[:, 1]


def _profiled(tmp_path, fn):
    """Run ``fn`` under a profiler session; returns its result and the
    host events named ``repro.*`` as (name, start_s, end_s, stats)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        out = fn()
        jax.block_until_ready(out)
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    events = [(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9, dict(ev.stats))
              for plane in ProfileData.from_file(path).planes if plane.name == "/host:CPU"
              for line in plane.lines for ev in line.events
              if ev.name.startswith(spans.PREFIX)]
    return out, events


def _named(events, name):
    return sorted((e for e in events if e[0] == name), key=lambda e: e[1])


def test_retraces_counts_a_name_and_its_dotted_parts():
    before = spans.retraces("test_spans")
    with spans.retrace("test_spans.a"):
        pass
    with spans.retrace("test_spans.b"):
        pass
    assert spans.retraces("test_spans") == before + 2
    assert spans.retraces("test_spans.a") == spans.retraces("test_spans.b")
    assert spans.retraces("test_span") == 0


def test_same_bucket_fused_fit_emits_no_retrace_span(tmp_path):
    """The retrace mark is on the timeline exactly when the fused fit traces:
    a first fit in a new bucket has one, a second in the same bucket none.
    (m = 36 / iters = 13 are this test's own bucket.)"""
    x, y = _data()
    name = "repro.retrace.falkon.fused_fit"
    t0 = spans.retraces("falkon.fused_fit")
    fit = lambda: falkon_fit(KERN, x, y, x[:36], 1e-3, iters=13, backend="jnp").alpha  # noqa: E731
    _, first = _profiled(tmp_path / "first", fit)
    assert len(_named(first, name)) == 1
    assert spans.retraces("falkon.fused_fit") == t0 + 1
    _, second = _profiled(tmp_path / "second", fit)
    assert _named(second, name) == []
    assert spans.retraces("falkon.fused_fit") == t0 + 1


def test_regressor_fit_has_one_fit_span(tmp_path):
    x, y = _data()
    cs = UniformSampler(m=32).sample(jax.random.PRNGKey(1), x, KERN)
    est = FalkonRegressor(kernel=KERN, config=FitConfig(lam=1e-3, iters=5, backend="jnp"))
    _, events = _profiled(tmp_path, lambda: est.fit(x, y, center_set=cs).model_.alpha)
    (fit,) = _named(events, "repro.fit")
    assert fit[3] == {"n": 400, "m": 32, "k": 1}


def test_host_eigh_span_lies_inside_its_fit(tmp_path, monkeypatch):
    """The host-LAPACK eigh (forced as on a TPU) is one ``repro.precond.eigh``
    span with its M, inside the fit that ran it, on the same clock. The fit
    checks its output, so it waits for the eigh before it returns."""
    x, y = _data()
    cs = UniformSampler(m=44).sample(jax.random.PRNGKey(2), x, KERN)
    monkeypatch.setattr(falkon_mod.jax, "default_backend", lambda: "tpu")
    cfg = FitConfig(lam=1e-3, iters=11, backend="jnp", check_finite=True)
    est = FalkonRegressor(kernel=KERN, config=cfg)
    _, events = _profiled(tmp_path, lambda: est.fit(x, y, center_set=cs).model_.alpha)
    (fit,) = _named(events, "repro.fit")
    (eigh,) = _named(events, "repro.precond.eigh")
    assert eigh[3] == {"m": 44}
    assert fit[1] <= eigh[1] < eigh[2] <= fit[2]


def test_one_output_fit_takes_the_vector_kernels_and_a_panel_does_not():
    """The fused K_nM operators pick their path from the live column count:
    a single-output fit runs the VPU (vector) kernels, a two-output fit the
    MXU panel kernels, and each pick is counted."""
    x, y = _data(n=300)
    cs = UniformSampler(m=40).sample(jax.random.PRNGKey(5), x, KERN)
    cfg = FitConfig(lam=1e-3, iters=3, backend=PallasBackend(interpret=True))
    ops = ("kernels.falkon_matvec", "kernels.knm_t")

    def picks(fit):
        before = {op + p: spans.taken(op + p) for op in ops for p in (".vector", ".panel")}
        fit()
        return {k: spans.taken(k) - c for k, c in before.items()}

    one = picks(lambda: FalkonRegressor(kernel=KERN, config=cfg).fit(x, y, center_set=cs))
    two = picks(lambda: FalkonRegressor(kernel=KERN, config=cfg).fit(
        x, jnp.stack([y, -y], 1), center_set=cs))
    for op in ops:
        assert one[op + ".vector"] >= 1 and one[op + ".panel"] == 0
        assert two[op + ".panel"] >= 1 and two[op + ".vector"] == 0
    assert spans.taken("kernels") >= sum(one.values()) + sum(two.values())


@pytest.mark.parametrize("ladder", [bless, bless_r])
def test_ladder_spans_one_level_and_one_sync_per_level(tmp_path, ladder):
    x, _ = _data(n=300, d=4, seed=3)
    res, events = _profiled(
        tmp_path, lambda: ladder(jax.random.PRNGKey(0), x, KERN, 1e-2, backend="jnp"))
    levels = _named(events, "repro.bless.level")
    syncs = _named(events, "repro.bless.sync")
    assert len(levels) == len(syncs) == len(res.lam_path)
    assert [lv[3]["h"] for lv in levels] == list(range(len(res.lam_path)))
    for lv, sy in zip(levels, syncs):
        assert sy[3]["h"] == lv[3]["h"]
        assert lv[1] <= sy[1] <= sy[2] <= lv[2]
        assert {"r_h", "m_h"} <= set(lv[3])


@pytest.mark.parametrize("ladder,phase", [(bless, "bless.score"), (bless_r, "bless.r_level")])
def test_eager_ladder_phase_marks_no_retrace(tmp_path, ladder, phase):
    """A host-driven backend runs the score / level phase eagerly: that is no
    trace, so it neither counts nor leaves a retrace mark on the timeline."""
    x, _ = _data(n=200, d=4, seed=4)
    before = spans.retraces(phase)
    res, events = _profiled(
        tmp_path, lambda: ladder(jax.random.PRNGKey(0), x, KERN, 5e-2, backend="pallas"))
    assert len(_named(events, "repro.bless.level")) == len(res.lam_path)
    assert _named(events, "repro.retrace." + phase) == []
    assert spans.retraces(phase) == before


def test_solver_import_loads_no_lm_runtime():
    """The spans module is the solver's only runtime dependency: importing
    the KRR stack leaves the LM training loop's runtime modules unloaded."""
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    code = ("import sys, repro.api, repro.core, repro.online; "
            "print(*(m for m in sys.modules if m.startswith('repro.runtime.')))")
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True).stdout.split()
    assert "repro.runtime.spans" in out
    assert not {"repro.runtime.monitor", "repro.runtime.compress"} & set(out)


@pytest.mark.parametrize("source", ["device", "store"])
def test_streamed_fit_spans_each_pass_with_its_shape(tmp_path, source):
    """A fit through ``StreamBackend`` is one ``repro.stream.pass`` span per
    pass, in order: the right-hand side, then one CG operator pass per
    iteration, each with (op, rows, chunk, m, k), inside the fit's span."""
    from repro.stream import ChunkStore, StreamBackend

    x, y = _data(n=333, d=4, seed=6)
    data = x if source == "device" else ChunkStore(jax.device_get(x), chunk=100)
    cs = UniformSampler(m=24).sample(jax.random.PRNGKey(3), x, KERN)
    be = StreamBackend(inner=PallasBackend(interpret=True), chunk=100)
    est = FalkonRegressor(kernel=KERN, config=FitConfig(lam=1e-3, iters=4, backend=be))
    _, events = _profiled(tmp_path, lambda: est.fit(data, y, center_set=cs).model_.alpha)
    (fit,) = _named(events, "repro.fit")
    passes = _named(events, "repro.stream.pass")
    assert [p[3]["op"] for p in passes] == ["knm_t"] + ["knm_quadratic"] * 4
    for p in passes:
        assert {k: p[3][k] for k in ("rows", "chunk", "m", "k")} == {
            "rows": 333, "chunk": 100, "m": 24, "k": 1}
        assert fit[1] <= p[1] < p[2] <= fit[2]
