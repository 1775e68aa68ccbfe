"""MillionSongs-shaped regression rows (UCI YearPredictionMSD): d = 90
standardized audio features and a continuous, standardized target.

The real features are 12 timbre means and 78 timbre covariances; here they
are correlated Gaussian columns (a random mixing of 90 independent ones),
standardized as the FALKON paper standardizes the data. The target is a
smooth function of a low-dimensional projection plus noise, standardized:
the low-effective-dimension regime that Nystrom centers exploit.
"""
import jax
import jax.numpy as jnp


def msd_like(n: int, d: int = 90, seed: int = 0):
    """(x, y) with x (n, d) standardized columns and y (n,) standardized."""
    key = jax.random.PRNGKey(seed)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    mix = jax.random.normal(k1, (d, d)) / jnp.sqrt(d)
    x = jax.random.normal(k2, (n, d)) @ mix
    x = (x - jnp.mean(x, 0)) / jnp.std(x, 0)
    w = jax.random.normal(k3, (3, d)) / jnp.sqrt(d)
    p = x @ w.T
    y = jnp.tanh(p[:, 0]) + 0.5 * jnp.sin(2.0 * p[:, 1]) + 0.25 * p[:, 2] ** 2
    y = y + 0.3 * jax.random.normal(k4, (n,))
    return x, (y - jnp.mean(y)) / jnp.std(y)


generate = msd_like
