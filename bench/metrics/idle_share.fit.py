"""Share of the fit window in which no operation ran on the device:
1 - busy / window, busy being the union of the device's op intervals."""

UNIT = "fit"


def read(ctx):
    if ctx.unit != UNIT or not ctx.trace.busy:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s)
