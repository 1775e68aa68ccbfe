"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode on the CPU cannot see what the chip's compiler refuses:
block layouts Mosaic does not accept and more VMEM than a kernel may take.
Each test lowers one ``PallasBackend`` contraction with ``interpret=False``
at the bring-up shapes (n = 2^20 rows, d = 128, M = 8192; M = 1024 for the
fused scorer) for one chip of a ``v5e:2x2`` topology, compiles it with the
TPU compiler installed beside JAX, and asserts the Mosaic kernel is there.
The K_nM operators compile on both of their paths: one live column (the
VPU kernels, also at susy.fit's d = 18, M = 5632 and at M = 16384) and a
two-column panel (the MXU kernels). The streamed passes compile at
higgs.fit's shapes (n = 1.05e7 rows, d = 28, M = 5632, 65536-row chunks;
the ladder's last level scores 327680 candidates) as one loop over the
chunks, within one chip's HBM: the fused kernel appears once for the loop
body and once for a ragged tail, never once per chunk.
Nothing runs, so no chip is needed; where the topology cannot be described
the fixture skips.

The topology is described inside the fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import pytest

import jax
import jax.numpy as jnp

from repro.core import PallasBackend, make_kernel
from repro.kernels.quadform import ops as quadform_ops

N, D, M = 1 << 20, 128, 8192
M_FUSED = 1024  # the fused scorer's largest center buffer (rls_score.ops.MAX_FUSED_M)
N_SCORE = 1 << 17  # candidate rows of one composed ladder level: (N_SCORE, M) fits HBM
D_SUSY, M_SUSY, M_WIDE = 18, 5632, 16384  # susy.fit's width and centers; the widest M
N_HIGGS, D_HIGGS, CHUNK = 10_500_000, 28, 65536  # higgs.fit: 160 chunks and a 14240-row tail
R_LADDER = 327_680  # higgs.fit's last ladder level: 3e5 candidates in a quarter-pow2 buffer


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shapes(topo):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)


def _cases(s):
    be = PallasBackend(interpret=False)
    kern = make_kernel("gaussian", sigma=4.0)
    ones = jnp.ones
    return {
        "rls_score": (lambda x, z: be.rls_scores(kern, x, z, ones((M_FUSED,), bool),
                                                 ones((M_FUSED,)), jnp.float32(1.0)),
                      s(N, D), s(M_FUSED, D)),
        "quadform": (lambda g, w: quadform_ops.quadform(g, w, interpret=False),
                     s(N_SCORE, M), s(M, M)),
        "gram": (lambda x, z: be.gram_block(kern, x, z), s(N_SCORE, D), s(M, D)),
        "falkon_matvec": (lambda x, z, v: be.knm_quadratic(kern, x, z)(v),
                          s(N, D), s(M, D), s(M)),
        "falkon_matvec_masked": (lambda x, z, v, m: be.knm_quadratic(kern, x, z, mask=m)(v),
                                 s(N, D), s(M, D), s(M), s(N)),
        "falkon_matvec_panel": (lambda x, z, v: be.knm_quadratic(kern, x, z)(v),
                                s(N, D), s(M, D), s(M, 2)),
        "falkon_matvec_susy": (lambda x, z, v: be.knm_quadratic(kern, x, z)(v),
                               s(N, D_SUSY), s(M_SUSY, D_SUSY), s(M_SUSY)),
        "falkon_matvec_wide": (lambda x, z, v: be.knm_quadratic(kern, x, z)(v),
                               s(N, D_SUSY), s(M_WIDE, D_SUSY), s(M_WIDE)),
        "knm_t": (lambda x, z, y: be.knm_t(kern, x, z, y), s(N, D), s(M, D), s(N)),
        "knm_t_panel": (lambda x, z, y: be.knm_t(kern, x, z, y), s(N, D), s(M, D), s(N, 2)),
        "knm_t_susy": (lambda x, z, y: be.knm_t(kern, x, z, y),
                       s(N, D_SUSY), s(M_SUSY, D_SUSY), s(N)),
        "knm_t_wide": (lambda x, z, y: be.knm_t(kern, x, z, y),
                       s(N, D_SUSY), s(M_WIDE, D_SUSY), s(N)),
        "knm_matvec": (lambda x, z, a: be.knm_matvec(kern, x, z, a), s(N, D), s(M, D), s(M)),
    }


@pytest.mark.parametrize("name", ["rls_score", "quadform", "gram", "falkon_matvec",
                                  "falkon_matvec_masked", "knm_t", "knm_matvec",
                                  "falkon_matvec_panel", "falkon_matvec_susy",
                                  "falkon_matvec_wide", "knm_t_panel", "knm_t_susy",
                                  "knm_t_wide"])
def test_kernel_compiles_for_v5e(shapes, name):
    fn, *args = _cases(shapes)[name]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("op", ["knm_quadratic", "knm_t", "rls_scores"])
def test_streamed_pass_compiles_for_v5e_without_unrolling(shapes, op):
    """The streamed passes at higgs.fit's shapes, within one chip's HBM: the
    CG operator and right-hand side over 1.05e7 rows, and the BLESS ladder's
    last level (327680 candidates against 5632 centers). The fused kernel
    is there once for the loop body and once for a ragged tail."""
    from repro.stream import StreamBackend

    be = StreamBackend(inner=PallasBackend(interpret=False), chunk=CHUNK)
    kern = make_kernel("gaussian", sigma=22.0)
    z = shapes(M_SUSY, D_HIGGS)
    if op == "knm_quadratic":
        n, fn, rest = N_HIGGS, (lambda x, z, v: be.knm_quadratic(kern, x, z)(v)), (shapes(M_SUSY),)
    elif op == "knm_t":
        n, fn, rest = N_HIGGS, (lambda x, z, y: be.knm_t(kern, x, z, y)), (shapes(N_HIGGS),)
    else:  # the scorer's pass alone, given the hoisted inverse factor
        from repro.stream.backend import _device_pass, _rls_chunk, _static_kernel

        n, rest = R_LADDER, (shapes(M_SUSY), shapes(M_SUSY, M_SUSY))
        fn = lambda x, z, maskf, inv_l: _device_pass(  # noqa: E731
            x, None, (z, maskf, inv_l, jnp.float32(1e4)), jnp.zeros((n,)),
            step=_rls_chunk, kernel=_static_kernel(kern), inner=be.inner, chunk=CHUNK,
            rows=True)
    text = jax.jit(fn).lower(shapes(n, D_HIGGS), z, *rest).compile().as_text()
    calls = 1 + (n % CHUNK > 0)  # the loop body's kernel and the tail's
    assert text.count('custom_call_target="tpu_custom_call"') == calls
