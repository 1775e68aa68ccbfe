"""SUSY-shaped two-class rows: d = 18, labels +-1.

``susy_like`` is a verbatim copy of ``examples/falkon_endtoend.susy_like``,
kept here so that a change to the program cannot move the benchmark's data.
"""
import jax
import jax.numpy as jnp


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


generate = susy_like
