"""The trace reducer: busy time as a union of op intervals, kernel time by
name, idle gaps named by what the host was doing."""
import pytest

import trace as tracing


def reduced(ops, spans=(), host=(), window=(0.0, 10.0)):
    busy = {"/device:TPU:0": tracing.merge([(s, e) for _, s, e in ops])}
    return tracing.Reduced(window=window, ops=list(ops), busy=busy,
                           spans=list(spans), host=list(host))


def test_merge_is_a_union():
    assert tracing.merge([(3, 4), (0, 2), (1, 3), (6, 7)]) == [(0, 4), (6, 7)]
    assert tracing.merge([(0, 1), (2, 3)]) == [(0, 1), (2, 3)]


def test_busy_counts_overlapping_ops_once():
    r = reduced([("a", 0.0, 2.0), ("b", 1.0, 3.0), ("c", 5.0, 6.0)])
    assert r.busy_s() == pytest.approx(4.0)
    assert r.window_s == 10.0


def test_kernel_seconds_and_starts_by_name():
    r = reduced([("falkon_matvec_pallas", 0.0, 2.0), ("fusion", 2.0, 2.5),
                 ("falkon_matvec_pallas", 3.0, 4.0), ("knm_t_pallas", 4.0, 4.5)])
    assert r.kernel_seconds(("falkon_matvec_pallas",)) == pytest.approx(3.0)
    assert r.kernel_seconds(("falkon_matvec_pallas", "knm_t_pallas")) == pytest.approx(3.5)
    assert r.kernel_starts(("falkon_matvec_pallas",)) == [0.0, 3.0]
    assert r.top_ops(2)[0] == ["falkon_matvec_pallas", 3.0]


def test_idle_gaps_are_named_by_the_host():
    r = reduced([("op", 0.0, 1.0), ("op", 6.0, 7.0)],
                spans=[("bench.fit", 0.0, 10.0)],
                host=[("eigh callback", 1.0, 5.9), ("dispatch", 7.0, 7.1)])
    gaps = r.idle_gaps(3)
    assert gaps[0][0] == "eigh callback" and gaps[0][1] == pytest.approx(5.0)
    assert gaps[1][0] == "bench.fit" and gaps[1][1] == pytest.approx(3.0)
    assert len(gaps) == 2


def test_recorded_chip_trace_of_an_msd_fit():
    """One msd.fit window traced on a v5e chip: 20 CG operator passes, one
    right-hand side, and the host eigh that the chip waits through."""
    import os

    import cell as cells
    import flops

    path = os.path.join(os.path.dirname(__file__), "data", "msd_fit.xplane.pb")
    r = tracing.reduce(path, "bench.fit")
    names = [n for n, _, _ in r.ops]
    assert names.count("falkon_matvec_pallas") == 20
    assert names.count("knm_t_pallas") == 1
    assert r.kernel_seconds(("pure_callback:host_wait",)) > 20.0
    assert 0.85 < 1.0 - r.busy_s() / r.window_s < 0.95
    assert r.idle_gaps(1)[0][0] == "pure_callback:host_wait"
    shapes = {"n": 463715, "d": 90, "m": 8192, "iters": 20}
    ctx = cells.Context("fit", r, shapes, 1, r.window_s, flops.peak("TPU v5 lite"))
    read = {}
    for name, lo, hi in (("cg_iter_ms", 90.0, 110.0), ("precond_s", 20.0, 25.0),
                         ("matvec_roofline", 3.0, 4.5), ("idle_share.fit", 85.0, 95.0)):
        read[name] = cells.reader(name).read(ctx)
        assert lo < read[name] < hi, (name, read[name])
    # A reader that finds nothing to read returns nothing, never 0.
    other = cells.Context("other", r, shapes, 1, r.window_s, flops.peak("TPU v5 lite"))
    assert cells.reader("matvec_roofline").read(other) is None
    assert cells.reader("matvec_roofline.msd").read(ctx) == read["matvec_roofline"]
