"""Work counts against hand counts at small shapes, and the peak table."""
import pytest

import flops


def test_cross_by_hand():
    # n = 2, m = 3, d = 4: the product 2*2*3*4 = 48, the norms 2*(2+3)*4 = 40,
    # the epilogue 5*2*3 = 30.
    assert flops.cross(2, 3, 4) == 48 + 40 + 30


def test_cg_operator_and_rhs_by_hand():
    f, b = flops.knm_quadratic(2, 3, 4)
    assert f == 118 + 4 * 2 * 3 * 1  # two (n, m) contractions with one column
    assert b == 4 * (2 * 4 + 3 * 4 + 2 * 3)
    f, b = flops.knm_t(2, 3, 4)
    assert f == 118 + 2 * 2 * 3
    assert b == 4 * (2 * 4 + 3 * 4 + 2 + 3)


def test_falkon_fit_by_hand():
    n, m, d, it = 10, 4, 3, 2
    rhs = flops.knm_t(n, m, d)[0]
    op = flops.knm_quadratic(n, m, d)[0]
    precond = flops.cross(m, m, d) + 3 * m ** 3 / 3
    assert flops.falkon_fit(n, m, d, it) == pytest.approx(rhs + precond + it * (op + 6 * m * m))


def test_roofline_takes_the_larger_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(200.0, 10.0, peak) == 2.0
    assert flops.roofline_seconds(100.0, 50.0, peak) == 5.0


def test_peaks():
    assert flops.peak("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peak("TPU v99")
