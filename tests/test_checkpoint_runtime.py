"""Checkpointing (atomicity, async, gc, bf16 roundtrip) and fault-tolerance
runtime (straggler detection, restart supervision, gradient compression)."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint import (AsyncCheckpointer, latest_step,
                              restore_checkpoint, save_checkpoint)
from repro.runtime.monitor import FaultTolerantLoop, HeartbeatMonitor
from repro.runtime.compress import int8_compress, int8_decompress


def _tree():
    return {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16),
                  "d": jnp.asarray(3, jnp.int32)}}


def test_roundtrip_including_bf16(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 7, t)
    step, t2 = restore_checkpoint(str(tmp_path), t)
    assert step == 7
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(t2)):
        assert a.dtype == b.dtype
        assert bool(jnp.all(a == b))


def test_latest_step_ignores_tmp(tmp_path):
    save_checkpoint(str(tmp_path), 3, _tree())
    os.makedirs(tmp_path / "step_00000009.tmp")
    assert latest_step(str(tmp_path)) == 3


def test_bf16_manifest_records_uint16_view_and_restores_true_bf16(tmp_path):
    """bf16 round-trip lockdown: the manifest records BOTH the logical
    dtype and the on-disk uint16 view, and restore hands back true bf16
    (not a raw uint16 view) with bit-identical payload."""
    import json

    import ml_dtypes

    t = _tree()
    save_checkpoint(str(tmp_path), 1, t)
    with open(tmp_path / "step_00000001" / "manifest.json") as f:
        manifest = json.load(f)
    rec = manifest["leaves"]["b/c"]
    assert rec["dtype"] == "bfloat16" and rec["stored_dtype"] == "uint16"
    fp32 = manifest["leaves"]["a"]
    assert fp32["dtype"] == fp32["stored_dtype"] == "float32"
    _, t2 = restore_checkpoint(str(tmp_path), t)
    assert t2["b"]["c"].dtype == ml_dtypes.bfloat16
    assert bool(jnp.all(t2["b"]["c"] == t["b"]["c"]))


def test_restore_refuses_tampered_leaf_dtype(tmp_path):
    """A leaf whose on-disk dtype disagrees with the recorded stored_dtype
    (bit rot, incompatible writer) is refused, never reinterpreted."""
    save_checkpoint(str(tmp_path), 1, _tree())
    path = tmp_path / "step_00000001"
    import json

    with open(path / "manifest.json") as f:
        manifest = json.load(f)
    fname = manifest["leaves"]["b/c"]["file"]  # the bf16-as-uint16 leaf
    np.save(path / fname, np.load(path / fname).astype(np.float64))
    with pytest.raises(ValueError, match="stored_dtype"):
        restore_checkpoint(str(tmp_path), _tree())


def test_crash_window_property_every_stage_leaves_loadable_state(tmp_path):
    """Kill save_checkpoint at EVERY filesystem step: whatever step dies,
    ``latest_step`` only ever sees a complete, restorable checkpoint.

    A ``times=0`` fault is a pure hit counter — one armed pass enumerates
    the crash stages; then each stage k is killed via ``skip=k, times=1``.
    """
    from repro.testing import faults

    base = _tree()
    save_checkpoint(str(tmp_path), 1, base)  # the survivor checkpoint
    with faults.fault("ckpt.torn_write", times=0) as probe:
        save_checkpoint(str(tmp_path), 2, base)
    n_stages = probe.seen
    # tmp dir + one per leaf + pre/post rename (CRASH_STAGES contract)
    assert n_stages == len(jax.tree.leaves(base)) + 3
    for k in range(n_stages):
        ckdir = tmp_path / f"kill_{k}"
        os.makedirs(ckdir)
        save_checkpoint(str(ckdir), 1, base)
        tree2 = {"a": jnp.full((2, 3), 9.0), "b": base["b"]}
        with faults.fault("ckpt.torn_write", times=1, skip=k):
            with pytest.raises(faults.FaultInjected):
                save_checkpoint(str(ckdir), 2, tree2)
        step = latest_step(str(ckdir))
        assert step in (1, 2)  # whatever survived must be complete:
        _, loaded = restore_checkpoint(str(ckdir), base)
        want = base if step == 1 else tree2
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(loaded)):
            assert a.dtype == b.dtype and bool(jnp.all(a == b))


def test_async_checkpointer_gc(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        ck.save(s, _tree())
    ck.wait()
    time.sleep(0.1)
    steps = sorted(int(d.split("_")[1]) for d in os.listdir(tmp_path))
    assert steps == [3, 4]


def test_heartbeat_straggler_detection():
    mon = HeartbeatMonitor(threshold=2.0, window=16)
    for i in range(20):
        mon.record(i, 0.1)
    assert mon.record(20, 0.5) is True
    assert mon.record(21, 0.11) is False
    assert len(mon.stragglers) == 1


def test_fault_tolerant_loop_recovers(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path))
    state0 = {"x": jnp.zeros(())}
    ck.save(0, state0)
    ck.wait()
    fails = {7, 13}

    def inject(step):
        if step in fails:
            fails.discard(step)
            raise RuntimeError("boom")

    def step_fn(st, step):
        return {"x": st["x"] + 1}, {}

    def restore():
        s = latest_step(str(tmp_path))
        _, st = restore_checkpoint(str(tmp_path), state0, step=s)
        return s, st

    loop = FaultTolerantLoop(step_fn, ck, ckpt_every=5, failure_injector=inject)
    final, end = loop.run(state0, 0, 20, restore)
    assert end == 20 and loop.restarts == 2
    assert float(final["x"]) >= 15  # replayed segments re-executed


def test_too_many_failures_raises(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path))
    ck.save(0, {"x": jnp.zeros(())})
    ck.wait()

    def inject(step):
        raise RuntimeError("always")

    loop = FaultTolerantLoop(lambda s, i: (s, {}), ck, max_restarts=2,
                             failure_injector=inject)
    with pytest.raises(RuntimeError):
        loop.run({"x": jnp.zeros(())}, 0, 5,
                 lambda: (0, {"x": jnp.zeros(())}))


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 1000), scale=st.floats(1e-4, 1e3))
def test_int8_quantization_error_bound(seed, scale):
    g = jax.random.normal(jax.random.PRNGKey(seed), (64,)) * scale
    err0 = jnp.zeros_like(g)
    q, s, err = int8_compress(g, err0)
    deq = int8_decompress(q, s)
    assert float(jnp.max(jnp.abs(deq - g))) <= float(s) * 0.5 + 1e-6
    # error feedback: residual equals quantization error exactly
    np.testing.assert_allclose(np.asarray(err), np.asarray(g - deq), rtol=1e-5,
                               atol=1e-7 * scale)


def test_error_feedback_reduces_bias():
    """Repeated EF-compressed sums drift less than naive quantization."""
    g = jnp.full((32,), 0.004)  # well below one int8 step at scale ~0.03
    big = jnp.zeros((32,)).at[0].set(4.0)  # forces a coarse scale
    grads = g + big * 0
    err = jnp.zeros_like(grads)
    acc_ef, acc_naive = jnp.zeros_like(grads), jnp.zeros_like(grads)
    gq = grads.at[0].set(4.0)
    for _ in range(50):
        q, s, err = int8_compress(gq, err)
        acc_ef += int8_decompress(q, s)
        q2, s2, _ = int8_compress(gq, jnp.zeros_like(gq))
        acc_naive += int8_decompress(q2, s2)
    true = gq * 50
    assert float(jnp.abs(acc_ef - true)[1:].max()) < float(jnp.abs(acc_naive - true)[1:].max()) + 1e-5
    assert float(jnp.abs(acc_ef - true).max()) < 0.05
