"""Seconds a fit that the chip waits on the eigh callback beyond the eigh
itself: the union of the device's ``pure_callback:host_wait`` intervals
less the program's ``repro.precond.eigh`` seconds, over the window's fits.
That is the (M, M) transfer each way and the callback's own dispatch.
None where the program has no such span."""

from trace import merge

WAIT = "pure_callback:host_wait"
SPAN = "repro.precond.eigh"


def read(ctx):
    if ctx.unit != "fit":
        return None
    eigh = [e - s for n, s, e in ctx.trace.host if n == SPAN]
    if not eigh:
        return None
    waits = merge((s, e) for n, s, e in ctx.trace.ops if n == WAIT)
    return (sum(e - s for s, e in waits) - sum(eigh)) / ctx.units
