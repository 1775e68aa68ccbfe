"""Milliseconds per streamed CG operator pass on the device clock: within
each of the program's ``repro.stream.pass`` spans that runs the CG operator
kernel (a K_nM^T K_nM pass; the right-hand side's pass runs ``knm_t``), the
time from the first start to the last end of the device operations that
start inside it, averaged over the window's such spans. The span waits for
its pass, so it holds the pass's device work. None where the program has
no such span."""

SPAN = "repro.stream.pass"
KERNELS = ("falkon_matvec_pallas",)


def operator_passes(ctx):
    """(start, end) of the window's pass spans that run the CG operator."""
    starts = sorted(s for n, s, _ in ctx.trace.ops if n in KERNELS)
    return [(s, e) for n, s, e in ctx.trace.host
            if n == SPAN and any(s <= t <= e for t in starts)]


def read(ctx):
    if ctx.unit != "fit":
        return None
    times = []
    for s, e in operator_passes(ctx):
        inside = [(os_, oe) for _, os_, oe in ctx.trace.ops if s <= os_ <= e]
        times.append(max(oe for _, oe in inside) - min(os_ for os_, _ in inside))
    return 1e3 * sum(times) / len(times) if times else None
