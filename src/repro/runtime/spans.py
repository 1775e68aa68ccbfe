"""Program spans, retrace counters and path counters, on the profiler's clock.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``: under an active profiler session (``jax.profiler.trace``)
it lands on the host plane of the same trace as the device ops, on their
clock, with ``meta`` as the event's stats. With no session it records
nothing and costs about a microsecond, so there is no switch.

``retrace(name)`` wraps code where JAX traces a function (a decorator on
the function given to ``jax.jit``, or a ``with`` block around a loop): it
adds one to the count ``retraces(name)`` reads and opens the span
``repro.retrace.<name>``, so a retrace is both a number for tests and a
mark on the timeline that shows which step traced (and then compiled or
loaded from the persistent cache). An eager call of a jitted function's
body (a host-driven backend's ladder phase) is no trace and marks nothing.
``falkon.cg`` wraps an eager ``lax.fori_loop``, which traces its body anew
on every call: a host-driven fit marks it every fit, and the span covers
the trace, the compile or cache load and the dispatch, not the loop's run.

``took(name)`` counts one pick of a code path where the program chooses
between two by the shapes it is given (at trace time, like a retrace, so a
loop traced once counts once); ``taken(name)`` reads it. The fused K_nM
operators count ``kernels.falkon_matvec.{vector,panel}`` and
``kernels.knm_t.{vector,panel}``: one live column or a multi-column panel.
``count(name, amount)`` adds to a counter that ``counted(name)`` reads: the
streamed passes count ``stream.chunks`` (chunk steps a pass runs) and
``stream.h2d_bytes`` (bytes a host source put on the device).

Spans of the fit path and the ladder:

  * ``repro.fit``                ``FalkonRegressor.fit``, dispatch included;
  * ``repro.precond.eigh``       the preconditioner's host LAPACK ``eigh``;
  * ``repro.bless.level``        one BLESS / BLESS-R ladder level on the host;
  * ``repro.bless.sync``         that level's blocking host fetch;
  * ``repro.stream.pass``        one streamed K_nM pass (op, rows, chunk, m,
                                 k), from its dispatch to its result;
  * ``repro.retrace.<name>``     ``falkon.fused_fit``, ``falkon.cg``,
                                 ``bless.<phase>``, ``online.acc_solve``,
                                 ``stream.device_pass``, ``stream.chunk_step``.
"""
from __future__ import annotations

import contextlib
import threading
from collections import Counter

import jax

PREFIX = "repro."

_RETRACES: Counter = Counter()
_PATHS: Counter = Counter()
_COUNTS: Counter = Counter()
_LOCK = threading.Lock()  # a ladder may trace on a background thread


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """A host span ``repro.<name>``; ``meta`` becomes the event's stats
    (more can be added inside it with ``set_metadata``)."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **meta)


@contextlib.contextmanager
def retrace(name: str):
    """Count one trace of ``name`` and mark it as ``repro.retrace.<name>``."""
    with _LOCK:
        _RETRACES[name] += 1
    with span("retrace." + name):
        yield


def _under(counter: Counter, name: str) -> int:
    with _LOCK:
        return sum(c for k, c in counter.items()
                   if k == name or k.startswith(name + "."))


def retraces(name: str) -> int:
    """Traces counted under ``name`` and under every ``name.<sub>``:
    ``retraces("bless")`` sums the ladder's phases."""
    return _under(_RETRACES, name)


def took(name: str) -> None:
    """Count one pick of the code path ``name``."""
    with _LOCK:
        _PATHS[name] += 1


def taken(name: str) -> int:
    """Picks counted under ``name`` and under every ``name.<sub>``."""
    return _under(_PATHS, name)


def count(name: str, amount: int = 1) -> None:
    """Add ``amount`` to the counter ``name``."""
    with _LOCK:
        _COUNTS[name] += amount


def counted(name: str) -> int:
    """The counter ``name`` plus every ``name.<sub>``."""
    return _under(_COUNTS, name)
