"""Milliseconds a fit in the front door: the program's ``repro.fit`` spans
around ``FalkonRegressor.fit`` (center gather, padding, the solve's
dispatch with any retrace or persistent-cache load) in the window, less
the device's ``pure_callback:host_wait`` intervals inside them, over the
window's fits. The eager eigh callback holds the calling thread until it
returns, so without that subtraction the span would read the
preconditioner's wait (``eigh_s`` + ``eigh_transfer_s``). None where the
program has no such span."""

from trace import merge

SPAN = "repro.fit"
WAIT = "pure_callback:host_wait"


def read(ctx):
    if ctx.unit != "fit":
        return None
    fits = [(s, e) for n, s, e in ctx.trace.host if n == SPAN]
    if not fits:
        return None
    waits = merge((s, e) for n, s, e in ctx.trace.ops if n == WAIT)
    held = sum(max(0.0, min(e, fe) - max(s, fs))
               for fs, fe in fits for s, e in waits)
    return 1e3 * (sum(e - s for s, e in fits) - held) / ctx.units
