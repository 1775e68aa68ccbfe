"""GPipe pipeline parallelism: pipelined == sequential, fwd and grad
(subprocess with 4 forced host devices)."""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    from repro.training.pipeline import pipeline_apply, stack_stages

    n_stages, n_mb, mb, d = 4, 8, 2, 16
    n_layers = 8
    from repro.launch.mesh import make_local_mesh
    mesh = make_local_mesh(("pipe",))
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (n_layers, d, d)) * (0.5 / d**0.5)
    x = jax.random.normal(jax.random.PRNGKey(1), (n_mb, mb, d))

    def stage_fn(wstage, xm):
        # wstage: (layers_per_stage, d, d)
        def body(x, wl):
            return jnp.tanh(x @ wl), None
        out, _ = jax.lax.scan(body, xm, wstage[0] if wstage.ndim == 4 else wstage)
        return out

    # sequential reference
    def seq(w, x):
        def body(xc, wl):
            return jnp.tanh(xc @ wl), None
        out, _ = jax.lax.scan(body, x.reshape(-1, d), w)
        return out.reshape(x.shape)

    wst = stack_stages(w, n_stages)  # (4, 2, d, d)
    run = pipeline_apply(stage_fn, n_stages, n_mb, mesh)
    got = jax.jit(run)(wst, x)
    want = seq(w, x)
    err = float(jnp.max(jnp.abs(got - want)))
    assert err < 1e-5, err

    # gradients flow through the schedule (GPipe backward)
    def loss_p(wst, x):
        return jnp.sum(run(wst, x) ** 2)
    def loss_s(w, x):
        return jnp.sum(seq(w, x) ** 2)
    gp = jax.grad(loss_p)(wst, x).reshape(w.shape)
    gs = jax.grad(loss_s)(w, x)
    gerr = float(jnp.max(jnp.abs(gp - gs)))
    assert gerr < 1e-4, gerr
    print("PIPELINE_OK", err, gerr)
""")


@pytest.mark.slow
def test_gpipe_matches_sequential():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "PIPELINE_OK" in out.stdout, out.stdout + out.stderr
