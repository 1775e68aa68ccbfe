"""The whole fit's share of the chip's peak: the operations a FALKON fit
needs (bench/flops.falkon_fit) over the host-clock time per fit times the
peak."""


def read(ctx):
    if ctx.unit != "fit" or ctx.peak is None:
        return None
    s = ctx.shapes
    need = ctx.flops.falkon_fit(s["n"], s["m"], s["d"], s["iters"])
    return 100.0 * need / (ctx.unit_s * ctx.peak["flops_per_s"])
