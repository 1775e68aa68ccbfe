"""The HIGGS-shaped generator: its shape, its label balance, its columns,
and its seed."""
import os

import jax.numpy as jnp
import numpy as np
import pytest

import cell as cells

PATH = os.path.join(cells.BENCH, "data", "higgs_like.py")


@pytest.fixture(scope="module")
def gen():
    return cells.load_module(PATH, "t_higgs_like")


def test_rows_labels_and_columns(gen):
    x, y = gen.generate(40_000, 28, np.uint32(2 ** 31 + 5))
    assert x.shape == (40_000, 28) and y.shape == (40_000,)
    assert set(np.unique(np.asarray(y))) == {-1.0, 1.0}
    # 53% signal, 8% of labels flipped: 0.53 * 0.92 + 0.47 * 0.08 = 52.5% +1
    assert abs(float(jnp.mean(y > 0)) - 0.5252) < 0.01
    np.testing.assert_allclose(np.asarray(jnp.mean(x, 0)), 0.0, atol=1e-4)
    np.testing.assert_allclose(np.asarray(jnp.std(x, 0)), 1.0, atol=1e-4)
    # the masses (columns 21-27) carry the class: signal's m_bb sits apart
    mbb = np.asarray(x[:, 25])
    assert abs(mbb[np.asarray(y) > 0].mean() - mbb[np.asarray(y) < 0].mean()) > 0.1


def test_same_seed_same_rows(gen):
    a = gen.generate(1000, 28, np.uint32(7))
    b = gen.generate(1000, 28, np.uint32(7))
    c = gen.generate(1000, 28, np.uint32(8))
    assert all(np.array_equal(np.asarray(p), np.asarray(q)) for p, q in zip(a, b))
    assert not np.array_equal(np.asarray(a[0]), np.asarray(c[0]))


def test_only_width_28(gen):
    with pytest.raises(ValueError, match="d = 28"):
        gen.generate(100, 18, np.uint32(1))

