"""Seconds from the start of a fit (the benchmark's host span around the
call) to the first start of the CG operator kernel: the preconditioner,
K_MM and the right-hand side. Averaged over the window's fits."""

KERNELS = ("falkon_matvec_pallas",)


def read(ctx):
    if ctx.unit != "fit":
        return None
    out = []
    for s, e in ctx.trace.span("bench.fit"):
        starts = [t for t in ctx.trace.kernel_starts(KERNELS) if s <= t <= e]
        if starts:
            out.append(starts[0] - s)
    return sum(out) / len(out) if out else None
