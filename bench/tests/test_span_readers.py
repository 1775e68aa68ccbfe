"""The readers of the program's spans (``repro.*``): ``eigh_s``,
``eigh_transfer_s`` and ``front_door_ms``, on a synthetic trace and on
chip traces recorded with and without the spans."""
import os

import pytest

import cell as cells
import trace as tracing

DATA = os.path.join(os.path.dirname(__file__), "data")
READERS = ("eigh_s", "eigh_transfer_s", "front_door_ms")
WAIT = "pure_callback:host_wait"


def _reduced(ops, spans, host, window=(0.0, 10.0)):
    busy = {"/device:TPU:0": tracing.merge([(s, e) for n, s, e in ops if n != WAIT])}
    return tracing.Reduced(window=window, ops=list(ops), busy=busy,
                           spans=list(spans), host=list(host))


def _fit_ctx(r, units):
    return cells.Context("fit", r, {"n": 1000, "d": 4, "m": 64, "iters": 20}, units,
                         r.window_s / units, None)


def test_readers_on_a_synthetic_trace():
    """Two fits: each a front-door span that holds its thread through the
    chip's wait on the eigh callback (two overlapping waits, counted once)
    around the host eigh."""
    r = _reduced([("knm_t_pallas", 0.1, 0.2), (WAIT, 0.2, 2.0), (WAIT, 1.9, 2.2),
                  ("falkon_matvec_pallas", 2.2, 4.0),
                  ("knm_t_pallas", 5.1, 5.2), (WAIT, 5.2, 7.4),
                  ("falkon_matvec_pallas", 7.4, 9.0)],
                 spans=[("bench.fit", 0.0, 4.0), ("bench.fit", 5.0, 9.0)],
                 host=[("repro.fit", 0.0, 2.3), ("repro.precond.eigh", 0.3, 1.8),
                       ("repro.fit", 5.0, 7.5), ("repro.precond.eigh", 5.3, 7.1),
                       ("repro.retrace.falkon.cg", 0.005, 0.008)])
    ctx = _fit_ctx(r, 2)
    assert cells.reader("eigh_s").read(ctx) == pytest.approx((1.5 + 1.8) / 2)
    # waits: union (0.2, 2.2) and (5.2, 7.4) = 4.2 s, less 3.3 s of eigh
    assert cells.reader("eigh_transfer_s").read(ctx) == pytest.approx((4.2 - 3.3) / 2)
    # fit spans 2.3 + 2.5 s, less the 2.0 + 2.2 s of waits inside them
    assert cells.reader("front_door_ms").read(ctx) == pytest.approx(1e3 * 0.6 / 2)
    other = cells.Context("other", r, {}, 2, 1.0, None)
    for name in READERS:
        assert cells.reader(name + ".msd").read(ctx) == cells.reader(name).read(ctx)
        assert cells.reader(name).read(other) is None


def test_readers_return_nothing_without_program_spans():
    """The recorded msd.fit window predates the program's spans: each
    reader finds nothing to read and returns None, never 0."""
    r = tracing.reduce(os.path.join(DATA, "msd_fit.xplane.pb"), "bench.fit")
    assert not [n for n, _, _ in r.host if n.startswith("repro.")]
    ctx = _fit_ctx(r, 1)
    for name in READERS + tuple(n + ".msd" for n in READERS):
        assert cells.reader(name).read(ctx) is None, name


def test_recorded_chip_trace_with_program_spans():
    """One msd.fit window traced on a v5e chip with the program's spans: each
    host eigh span lies inside the chip's wait on its callback, in the same
    fit (the spans share the device trace's clock), and the readers read it."""
    r = tracing.reduce(os.path.join(DATA, "msd_fit_spans.xplane.pb"), "bench.fit")
    fits = r.span("bench.fit")
    waits = tracing.merge((s, e) for n, s, e in r.ops if n == WAIT)
    eighs = sorted((s, e) for n, s, e in r.host if n == "repro.precond.eigh")
    assert len(fits) == len(eighs) == 2
    for (fs, fe), (s, e) in zip(fits, eighs):
        (ws, we), = [w for w in waits if w[0] <= s and e <= w[1]]
        assert fs <= ws and we <= fe
    ctx = _fit_ctx(r, len(fits))
    for name, lo, hi in (("eigh_s.msd", 20.0, 22.5), ("eigh_transfer_s.msd", 0.1, 1.0),
                         ("front_door_ms.msd", 100.0, 160.0), ("precond_s.msd", 20.0, 25.0),
                         ("cg_iter_ms.msd", 90.0, 110.0)):
        value = cells.reader(name).read(ctx)
        assert lo < value < hi, (name, value)
    # every wait lies inside a front-door span, so the three readers split it
    front = sum(e - s for n, s, e in r.host if n == "repro.fit") / len(fits)
    parts = (cells.reader("front_door_ms.msd").read(ctx) / 1e3
             + cells.reader("eigh_s.msd").read(ctx)
             + cells.reader("eigh_transfer_s.msd").read(ctx))
    assert parts == pytest.approx(front, rel=1e-9)
