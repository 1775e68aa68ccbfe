"""Reduce a JAX profiler trace (``*.xplane.pb``) to what the metrics read.

The trace holds one plane per device (``/device:TPU:<i>``) whose "XLA Ops"
line has one event per operation run on that device, named by its HLO
text (``%falkon_matvec_pallas.8 = f32[...] custom-call(...)``), and a host
plane (``/host:CPU``) with one line per host thread, where the benchmark's
own spans (``jax.profiler.TraceAnnotation``, named ``bench.*``) and the
runtime's dispatch events sit. Device and host events share one clock.

An op is named by its HLO instruction without the numeric suffix
(``falkon_matvec_pallas``, ``while``, ``cond``). A host transfer's
``recv-done``/``send-done`` (a ``pure_callback`` waiting for the host)
runs no work on the chip: it is named ``<name>:host_wait`` and counts as
idle, not busy.

``Reduced`` keeps, for the traced window only:
  * ``ops``: device operations as (name, start, end) in seconds, every chip;
  * ``busy``: the union of each chip's op intervals, host waits left out;
  * ``spans``: the benchmark's host spans, (name, start, end);
  * ``host``: every other host event, (name, start, end).

A per-layer metric takes its kernels' time with ``kernel_seconds`` and a
list of names kept in the metric's own file, and reads nothing else.
"""
from __future__ import annotations

import dataclasses
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
HOST_WAIT = ":host_wait"


@dataclasses.dataclass
class Reduced:
    window: tuple[float, float]
    ops: list[tuple[str, float, float]]
    busy: dict[str, list[tuple[float, float]]]
    spans: list[tuple[str, float, float]]
    host: list[tuple[str, float, float]]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.busy:
            return 0.0
        return sum(_length(iv) for iv in self.busy.values()) / len(self.busy)

    def kernel_seconds(self, names) -> float:
        """Summed device time of the ops named in ``names``, over every chip."""
        return sum(e - s for n, s, e in self.ops if n in names)

    def kernel_starts(self, names) -> list[float]:
        return sorted(s for n, s, _ in self.ops if n in names)

    def span(self, name: str) -> list[tuple[float, float]]:
        return sorted((s, e) for n, s, e in self.spans if n == name)

    def top_ops(self, k: int = 10) -> list[list]:
        """The ``k`` op names that took the most device time, with seconds."""
        tot: dict[str, float] = {}
        for n, s, e in self.ops:
            tot[n] = tot.get(n, 0.0) + (e - s)
        return [[n, t] for n, t in sorted(tot.items(), key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10) -> list[list]:
        """The ``k`` longest idle gaps of the first chip inside the window,
        each named by what the host was doing: a host wait of the device
        that covers at least half of the gap, else the shortest host event
        or span that does, else the one that covers most of it."""
        if not self.busy:
            return []
        busy = self.busy[sorted(self.busy)[0]]
        gaps, t = [], self.window[0]
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < self.window[1]:
            gaps.append((t, self.window[1]))
        gaps.sort(key=lambda g: g[0] - g[1])
        waits = [op for op in self.ops if op[0].endswith(HOST_WAIT)]
        out = []
        for gs, ge in gaps[:k]:
            def covering(events):
                return [(n, min(e, ge) - max(s, gs), e - s)
                        for n, s, e in events if min(e, ge) > max(s, gs)]

            cands = covering(self.spans + self.host)
            half = [c for c in cands if c[1] >= 0.5 * (ge - gs)]
            wait = [c for c in covering(waits) if c[1] >= 0.5 * (ge - gs)]
            if wait:
                name = wait[0][0]
            elif half:
                name = min(half, key=lambda c: c[2])[0]
            elif cands:
                name = max(cands, key=lambda c: c[1])[0]
            else:
                name = "unattributed"
            out.append([name, ge - gs])
        return out


def op_name(hlo: str) -> str:
    """``%quadform_pallas.1 = f32[...] custom-call(...)`` -> ``quadform_pallas``;
    a host transfer's completion gets the ``:host_wait`` suffix."""
    head, _, body = hlo.partition(" = ")
    name = head.strip().lstrip("%").split(".")[0]
    if "is_host_transfer=true" in body and ("recv-done(" in body or "send-done(" in body):
        name += HOST_WAIT
    return name


def _length(iv) -> float:
    return sum(e - s for s, e in iv)


def merge(intervals) -> list[tuple[float, float]]:
    """Union of (start, end) intervals, sorted and merged."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return paths[-1]


def _clip(events, window):
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi]


def reduce(path: str, window_span: str) -> Reduced:
    """Read the xplane file at ``path``; the window runs from the first
    start to the last end of the host spans named ``window_span``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, spans, host = [], [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            chip = plane.name
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(chip, op_name(ev.name), ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                            for ev in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    item = (ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                    (spans if ev.name.startswith(SPAN_PREFIX) else host).append(item)
    marks = [(s, e) for n, s, e in spans if n == window_span]
    if not marks:
        raise ValueError(f"trace has no {window_span!r} span")
    window = (min(s for s, _ in marks), max(e for _, e in marks))
    lo, hi = window
    kept = [(c, n, max(s, lo), min(e, hi)) for c, n, s, e in ops if e > lo and s < hi]
    busy: dict[str, list] = {}
    for c, n, s, e in kept:
        if not n.endswith(HOST_WAIT):
            busy.setdefault(c, []).append((s, e))
    chips = {c for c, *_ in ops}
    busy = {c: merge(busy.get(c, [])) for c in chips}
    return Reduced(window=window, ops=[(n, s, e) for _, n, s, e in kept],
                   busy=busy, spans=_clip(spans, window), host=_clip(host, window))
