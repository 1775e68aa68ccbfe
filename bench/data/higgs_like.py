"""HIGGS-shaped two-class rows (UCI HIGGS): d = 28, labels +-1.

The real rows are simulated collider events: 21 low-level features, the
kinematics of one lepton, the missing transverse energy and four jets
(transverse momentum, pseudorapidity, azimuth, and a b-tag per jet), and 7
high-level features, invariant masses that physicists derive from them
(m_jj, m_jjj, m_lv, m_jlv, m_bb, m_wbb, m_wwbb). About 53% of the events
are signal.

Here each event is drawn the same way in miniature: signal or background
first (53% signal); momenta log-normal, harder in signal; pseudorapidities
Gaussian and azimuths uniform, with signal's two b-jets closer together;
b-tags in {0, 1, 2}, more often set in signal. The masses are computed from
those four-vectors as the real ones are, so they carry the class through
nonlinear functions of the low-level rows. 8% of the labels are flipped,
so the classes overlap as HIGGS's do. Every column is standardized, as the
FALKON paper standardizes the data.
"""
import jax
import jax.numpy as jnp

SIGNAL = 0.53
FLIP = 0.08


def _wrap(a):
    """An angle wrapped into (-pi, pi]."""
    return jnp.arctan2(jnp.sin(a), jnp.cos(a))


def _four_vectors(pt, eta, phi):
    """(E, px, py, pz) of massless objects, each (n, objects)."""
    return (pt * jnp.cosh(eta), pt * jnp.cos(phi), pt * jnp.sin(phi), pt * jnp.sinh(eta))


def _mass(vecs, cols):
    """Invariant mass of the sum of the objects in ``cols``."""
    e, px, py, pz = (jnp.sum(v[:, cols], axis=1) for v in vecs)
    return jnp.sqrt(jnp.maximum(e * e - px * px - py * py - pz * pz, 0.0))


def higgs_like(n: int, d: int = 28, seed=0):
    """(x, y) with x (n, 28) standardized columns and y (n,) in {-1, +1}.

    Objects, in column order: lepton 0, missing energy 1 (no pseudorapidity),
    jets 2-5; jets 4 and 5 are the b-jet pair of signal's h -> bb."""
    if d != 28:
        raise ValueError(f"HIGGS rows have d = 28, not {d}")
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    sig = jax.random.bernoulli(ks[0], SIGNAL, (n,))
    s = sig.astype(jnp.float32)[:, None]
    harder = jnp.array([0.10, 0.05, 0.15, 0.15, 0.30, 0.30], jnp.float32)
    pt = jnp.exp(3.6 + 0.55 * jax.random.normal(ks[1], (n, 6)) + s * harder)
    eta = 1.1 * jax.random.normal(ks[2], (n, 6)) * jnp.array([1, 0, 1, 1, 1, 1], jnp.float32)
    phi = jax.random.uniform(ks[3], (n, 6), minval=-jnp.pi, maxval=jnp.pi)
    # signal's b-jets come from one decay: pull jet 5 toward jet 4
    pull = 0.6 * s[:, 0]
    eta = eta.at[:, 5].set((1 - pull) * eta[:, 5] + pull * eta[:, 4])
    dphi = _wrap(phi[:, 5] - phi[:, 4])
    phi = phi.at[:, 5].set(_wrap(phi[:, 4] + (1 - pull) * dphi))
    p_tag = jnp.where(sig[:, None], jnp.array([0.3, 0.3, 0.7, 0.7]),
                      jnp.array([0.25, 0.25, 0.35, 0.35]))
    u = jax.random.uniform(ks[4], (n, 4))
    btag = (u < p_tag).astype(jnp.float32) * (1.0 + (u < 0.3 * p_tag))
    low = [pt[:, 0], eta[:, 0], phi[:, 0], pt[:, 1], phi[:, 1]]
    for j in range(2, 6):
        low += [pt[:, j], eta[:, j], phi[:, j], btag[:, j - 2]]
    vecs = _four_vectors(pt, eta, phi)
    high = [_mass(vecs, c) for c in ([2, 3], [2, 3, 4], [0, 1], [0, 1, 4], [4, 5],
                                     [2, 3, 4, 5], [0, 1, 2, 3, 4, 5])]
    x = jnp.stack(low + high, axis=1)
    x = (x - jnp.mean(x, 0)) / jnp.std(x, 0)
    flip = jax.random.bernoulli(ks[5], FLIP, (n,))
    y = jnp.where(sig != flip, 1.0, -1.0)
    return x, y


generate = higgs_like
