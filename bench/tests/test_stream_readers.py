"""The readers of the program's streamed passes (``repro.stream.pass``):
``stream_pass_ms``, ``stream_idle_share`` and ``stream_roofline``, on a
synthetic trace and on a chip trace recorded without the spans; and the
accepted readers that ``higgs.fit`` reports as ``.higgs`` parts, on its
recorded window."""
import os

import pytest

import cell as cells
import flops
import trace as tracing

DATA = os.path.join(os.path.dirname(__file__), "data")
READERS = ("stream_pass_ms", "stream_idle_share", "stream_roofline")
PASS = "repro.stream.pass"
SHAPES = {"n": 1000, "d": 4, "m": 64, "iters": 2}
PEAK = {"flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}


def _fit(t0):
    """One fit from ``t0``: a right-hand side pass, then two operator passes
    whose spans hold idle time around and between their kernels."""
    ops = [("knm_t_pallas", t0 + 0.12, t0 + 0.45),
           ("falkon_matvec_pallas", t0 + 1.10, t0 + 1.50),
           ("falkon_matvec_pallas", t0 + 1.60, t0 + 1.90),
           ("falkon_matvec_pallas", t0 + 2.55, t0 + 2.95)]
    host = [(PASS, t0 + 0.10, t0 + 0.50), (PASS, t0 + 1.00, t0 + 2.00),
            (PASS, t0 + 2.50, t0 + 3.00), ("repro.fit", t0, t0 + 3.1)]
    return ops, host


def _ctx(units=2):
    ops, host = [], []
    for t0 in (0.0, 5.0)[:units]:
        o, h = _fit(t0)
        ops += o
        host += h
    busy = {"/device:TPU:0": tracing.merge([(s, e) for _, s, e in ops])}
    r = tracing.Reduced(window=(0.0, 10.0), ops=ops, busy=busy,
                        spans=[("bench.fit", 0.0, 3.2), ("bench.fit", 5.0, 8.2)][:units],
                        host=host)
    return cells.Context("fit", r, SHAPES, units, r.window_s / units, PEAK)


def test_readers_on_a_synthetic_trace():
    ctx = _ctx()
    # operator passes: 1.10 -> 1.90 and 2.55 -> 2.95 in each fit
    assert cells.reader("stream_pass_ms").read(ctx) == pytest.approx(1e3 * (0.8 + 0.4) / 2)
    # their spans last 1.0 + 0.5 s, of which 0.7 + 0.4 s busy
    assert cells.reader("stream_idle_share").read(ctx) == pytest.approx(100 * (1 - 1.1 / 1.5))
    n, m, d, it = SHAPES["n"], SHAPES["m"], SHAPES["d"], SHAPES["iters"]
    need = (it * flops.roofline_seconds(*flops.knm_quadratic(n, m, d), PEAK)
            + flops.roofline_seconds(*flops.knm_t(n, m, d), PEAK))
    # busy inside every pass span, the right-hand side's 0.33 s included
    assert cells.reader("stream_roofline").read(ctx) == pytest.approx(
        100 * 2 * need / (2 * (0.33 + 0.7 + 0.4)))


def test_a_right_hand_side_pass_is_no_operator_pass():
    """Spans that run only ``knm_t`` are left out of the pass time."""
    ctx = _ctx(units=1)
    ctx.trace.ops[:] = [op for op in ctx.trace.ops if op[0] == "knm_t_pallas"]
    assert cells.reader("stream_pass_ms").read(ctx) is None
    assert cells.reader("stream_idle_share").read(ctx) is None
    assert cells.reader("stream_roofline").read(ctx) is not None


def test_readers_return_nothing_without_stream_spans():
    """A program without the stream pass (the recorded msd.fit windows) has
    nothing to read: each reader returns None and does not raise."""
    for name in ("msd_fit.xplane.pb", "msd_fit_spans.xplane.pb"):
        r = tracing.reduce(os.path.join(DATA, name), "bench.fit")
        ctx = cells.Context("fit", r, SHAPES, 1, r.window_s, PEAK)
        for reader in READERS:
            assert cells.reader(reader).read(ctx) is None, (name, reader)
    other = cells.Context("other", _ctx().trace, {}, 2, 1.0, PEAK)
    for reader in READERS:
        assert cells.reader(reader).read(other) is None


def test_readers_on_a_recorded_higgs_fit_window():
    """A traced higgs.fit window on a v5e (two fits): each fit's right-hand
    side and 20 operator passes are spans of their own, and the readers
    read again what the run reported."""
    r = tracing.reduce(os.path.join(DATA, "higgs_fit.xplane.pb"), "bench.fit")
    shapes = {"n": 10_500_000, "d": 28, "m": 5632, "iters": 20}
    ctx = cells.Context("fit", r, shapes, 2, r.window_s / 2, flops.peak("TPU v5 lite"))
    assert len([n for n, _, _ in r.host if n == PASS]) == 42
    assert len(cells.reader("stream_pass_ms").operator_passes(ctx)) == 40
    assert cells.reader("stream_pass_ms").read(ctx) == pytest.approx(536.0547451750001)
    assert cells.reader("stream_idle_share").read(ctx) == pytest.approx(0.3152619301999615)
    assert cells.reader("stream_roofline").read(ctx) == pytest.approx(3.6013723766335217)


def test_higgs_parts_of_accepted_readers_on_the_recorded_window():
    """``eigh_s.higgs``, ``eigh_transfer_s.higgs``, ``idle_share.fit.higgs``
    and ``fit_mfu.higgs`` are read by their prefix's reader: on the
    recorded higgs.fit window the host eigh is 43% of a fit and the chip is
    idle 46% of the window."""
    r = tracing.reduce(os.path.join(DATA, "higgs_fit.xplane.pb"), "bench.fit")
    shapes = {"n": 10_500_000, "d": 28, "m": 5632, "iters": 20}
    ctx = cells.Context("fit", r, shapes, 2, r.window_s / 2, flops.peak("TPU v5 lite"))
    read = {m: cells.reader(m).read(ctx) for m in
            ("eigh_s.higgs", "eigh_transfer_s.higgs", "idle_share.fit.higgs", "fit_mfu.higgs")}
    assert read["eigh_s.higgs"] == pytest.approx(8.9495637685)
    assert read["eigh_transfer_s.higgs"] == pytest.approx(0.3965425795)
    assert read["idle_share.fit.higgs"] == pytest.approx(45.82612486082858)
    assert read["fit_mfu.higgs"] == pytest.approx(1.951629672291821)
    assert read["eigh_s.higgs"] / ctx.unit_s == pytest.approx(0.426, abs=0.01)
