"""Roofline share of the streamed K_nM passes: the least time the chip needs
for the window's CG operator passes (iters per fit) and right-hand sides
(one per fit), counted from the true shapes as ``matvec_roofline`` counts
them, whatever kernels run them, over the device's busy time inside the
program's ``repro.stream.pass`` spans. None where the program has no such
span."""

SPAN = "repro.stream.pass"


def read(ctx):
    if ctx.unit != "fit" or ctx.peak is None or not ctx.trace.busy:
        return None
    passes = [(s, e) for n, s, e in ctx.trace.host if n == SPAN]
    busy = ctx.trace.busy[sorted(ctx.trace.busy)[0]]
    t = sum(max(0.0, min(e, pe) - max(s, ps)) for ps, pe in passes for s, e in busy)
    if t <= 0:
        return None
    s, f = ctx.shapes, ctx.flops
    op = f.roofline_seconds(*f.knm_quadratic(s["n"], s["m"], s["d"]), ctx.peak)
    rhs = f.roofline_seconds(*f.knm_t(s["n"], s["m"], s["d"]), ctx.peak)
    return 100.0 * ctx.units * (s["iters"] * op + rhs) / t
