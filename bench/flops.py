"""Work counted from shapes: floating-point operations and bytes.

Counts use the true row width d and center count M, never the padded
sizes a kernel tiles with, so padding shows as a lower share of the
roofline. A multiply-add is two operations; exp, compare and scale are one
each. Bytes are what the algorithm must move between HBM and the chip at
least: each input read once and each output written once, float32.

A Gaussian kernel block k(X, Z) of (n, m) entries for rows of width d:
the cross product 2 n m d, the squared norms 2 (n + m) d, and 5 n m for
assembling the distance, clamping, scaling and the exp.
"""
from __future__ import annotations

import json
import os

F32 = 4


def cross(n: int, m: int, d: int) -> float:
    return 2.0 * n * m * d + 2.0 * (n + m) * d + 5.0 * n * m


def knm_quadratic(n: int, m: int, d: int, k: int = 1) -> tuple[float, float]:
    """The CG operator K_nM^T (K_nM V) for V (M, k): (flops, bytes)."""
    flops = cross(n, m, d) + 4.0 * n * m * k
    return flops, F32 * (n * d + m * d + 2.0 * m * k)


def knm_t(n: int, m: int, d: int, k: int = 1) -> tuple[float, float]:
    """The CG right-hand side K_nM^T Y for Y (n, k): (flops, bytes)."""
    flops = cross(n, m, d) + 2.0 * n * m * k
    return flops, F32 * (n * d + m * d + n * k + m * k)


def cholesky(m: int) -> float:
    return m ** 3 / 3.0


def falkon_fit(n: int, m: int, d: int, iters: int, k: int = 1) -> float:
    """Operations a FALKON fit needs (FALKON paper, Alg. 1): the right-hand
    side and one operator pass per CG iteration; the two-Cholesky
    preconditioner, K_MM (m, m, d), T = chol(K_MM) and R = chol(T T^T / M +
    lam I) with its triangular product, once; and per iteration its four
    triangular solves (4 m^2 per column) plus K_MM u (2 m^2 per column)."""
    rhs, _ = knm_t(n, m, d, k)
    op, _ = knm_quadratic(n, m, d, k)
    precond = cross(m, m, d) + 2.0 * cholesky(m) + m ** 3 / 3.0
    per_iter = op + 6.0 * m * m * k
    return rhs + precond + iters * per_iter


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def peak(device_kind: str) -> dict:
    """Published peaks of one chip, from ``peaks.json``; an unknown
    ``device_kind`` is an error."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"peaks.json knows {sorted(table)}")
    return table[device_kind]
