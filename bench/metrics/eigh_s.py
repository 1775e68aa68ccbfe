"""Seconds a fit in the preconditioner's host LAPACK eigh: the program's
``repro.precond.eigh`` spans (on the host thread that runs the callback)
in the window, over the window's fits. None where the program has no such
span."""

SPAN = "repro.precond.eigh"


def read(ctx):
    if ctx.unit != "fit":
        return None
    eigh = [e - s for n, s, e in ctx.trace.host if n == SPAN]
    return sum(eigh) / ctx.units if eigh else None
