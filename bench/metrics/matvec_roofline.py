"""Roofline share of FALKON's K_nM kernels (kernels/falkon_matvec): the
least time the chip needs for the window's CG operator passes (iters per
fit) and right-hand sides (one per fit), counted from the true shapes,
over the device time of those kernels."""

KERNELS = ("falkon_matvec_pallas", "knm_t_pallas")


def read(ctx):
    if ctx.unit != "fit" or ctx.peak is None:
        return None
    t = ctx.trace.kernel_seconds(KERNELS)
    if t <= 0:
        return None
    s, f = ctx.shapes, ctx.flops
    op = f.roofline_seconds(*f.knm_quadratic(s["n"], s["m"], s["d"]), ctx.peak)
    rhs = f.roofline_seconds(*f.knm_t(s["n"], s["m"], s["d"]), ctx.peak)
    return 100.0 * ctx.units * (s["iters"] * op + rhs) / t
