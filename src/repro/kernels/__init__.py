"""Pallas TPU kernels for the paper's compute hot-spots + the LM fast path.

Each subpackage: <name>.py (pl.pallas_call + BlockSpec VMEM tiling),
ops.py (jit'd public wrapper, pad/dispatch/interpret switch), ref.py
(pure-jnp oracle). On a TPU the kernels compile through Mosaic; on the CPU
backend the test suite runs them in interpret mode
(common.default_interpret()), and tests/test_tpu_compile.py compiles the
main-path ones for a described v5e chip.

  gram           k(X, Z) blocked Gram — every BLESS level's bulk work
  quadform       rowsum((G W) * G) — Eq. 3 leverage-score epilogue, fused
  falkon_matvec  K_nM^T (K_nM v) — FALKON CG inner loop, Gram never hits HBM
  flash_attention causal GQA streaming-softmax attention (LM prefill/train)
  ssd            Mamba-2 SSD chunk scan, state carried in VMEM (SSM archs)
"""
from . import falkon_matvec, flash_attention, gram, quadform, ssd  # noqa: F401
