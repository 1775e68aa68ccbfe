"""Share of the streamed CG operator passes' time in which no operation ran
on the device: 1 - busy / span, summed over the window's
``repro.stream.pass`` spans that run the CG operator kernel (picked as
``stream_pass_ms`` picks them), busy being the union of the device's op
intervals inside them. It is how far the host's per-pass and per-chunk
work holds the chip back. None where the program has no such span."""

import cell


def read(ctx):
    if ctx.unit != "fit" or not ctx.trace.busy:
        return None
    passes = cell.reader("stream_pass_ms").operator_passes(ctx)
    if not passes:
        return None
    busy = ctx.trace.busy[sorted(ctx.trace.busy)[0]]
    held = sum(max(0.0, min(e, pe) - max(s, ps)) for ps, pe in passes for s, e in busy)
    return 100.0 * (1.0 - held / sum(pe - ps for ps, pe in passes))
