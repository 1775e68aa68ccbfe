"""Milliseconds per CG iteration on the device clock: within each fit, the
time from the first to the last start of the CG operator kernel over
iters - 1, averaged over the window's fits."""

KERNELS = ("falkon_matvec_pallas",)


def read(ctx):
    if ctx.unit != "fit" or ctx.shapes["iters"] < 2:
        return None
    per_fit = []
    for s, e in ctx.trace.span("bench.fit"):
        starts = [t for t in ctx.trace.kernel_starts(KERNELS) if s <= t <= e]
        if len(starts) >= 2:
            per_fit.append((starts[-1] - starts[0]) / (ctx.shapes["iters"] - 1))
    return 1e3 * sum(per_fit) / len(per_fit) if per_fit else None
