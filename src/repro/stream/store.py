"""Host-resident chunked data plane for the out-of-core streaming backend.

``ChunkStore`` keeps X (and optionally y) in **host** memory as C-contiguous
fp32 row chunks and hands them to the device one chunk at a time. Nothing
here assumes X fits in device memory — the device only ever sees a bounded
working set:

  * at most two X chunks (the one being contracted + the prefetched next),
  * one transient (chunk, M) Gram tile per in-flight contraction,
  * the O(M)/(M, k) accumulators and the O(n) prediction output.

``device_chunks`` is the double-buffered copy loop: the host->device copy of
chunk i+1 is *issued* (``jax.device_put`` is asynchronous) before chunk i is
yielded to the consumer, so under JAX's async dispatch the copy of the next
chunk overlaps the contraction of the current one — the same schedule as a
software-pipelined DMA loop.

Every device allocation this subsystem makes is metered by a module-level
byte tracker (``peak_device_bytes``): CPU devices expose no
``memory_stats()``, so the subsystem carries its own honest accounting of
what it put on device, and the bigk benchmark / tests read the peak from
here (plus ``Device.memory_stats()`` where the platform provides it).
"""
from __future__ import annotations

import dataclasses
import threading

import jax
import numpy as np

from ..runtime import spans

Array = jax.Array

#: Default rows per device chunk by platform. Sized so a (chunk, M) Gram
#: tile at M ~ 1k stays ~64 MB (CPU cache/TLB friendly) / fits HBM working
#: sets comfortably with double buffering on accelerators.
STREAM_CHUNK = {"cpu": 16384, "gpu": 65536, "tpu": 65536}


def default_chunk() -> int:
    """Platform default rows-per-chunk (see ``STREAM_CHUNK``)."""
    return STREAM_CHUNK.get(jax.default_backend(), 16384)


# ---------------------------------------------------------------------------
# Device-byte accounting
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _DeviceBytes:
    """Bytes the stream subsystem currently has resident on device, plus the
    high-water mark. Guarded by a lock — the serving engines may stream from
    several host threads."""

    current: int = 0
    peak: int = 0
    lock: threading.Lock = dataclasses.field(default_factory=threading.Lock)

    def add(self, nbytes: int) -> None:
        with self.lock:
            self.current += nbytes
            self.peak = max(self.peak, self.current)

    def sub(self, nbytes: int) -> None:
        with self.lock:
            self.current = max(0, self.current - nbytes)

    def note_transient(self, nbytes: int) -> None:
        """Record a short-lived allocation (a Gram tile inside a compiled
        chunk step) that never outlives one loop iteration: bumps the peak
        without moving ``current``."""
        with self.lock:
            self.peak = max(self.peak, self.current + nbytes)


_TRACKER = _DeviceBytes()


def reset_peak_device_bytes() -> None:
    """Zero the subsystem's device-byte high-water mark (benchmark prologue)."""
    with _TRACKER.lock:
        _TRACKER.current = 0
        _TRACKER.peak = 0


def peak_device_bytes() -> int:
    """High-water mark of device bytes the stream subsystem allocated
    (chunks in flight + transient Gram tiles + accumulators) since the last
    ``reset_peak_device_bytes``. This is the subsystem's own meter — on
    platforms with ``Device.memory_stats()`` compare against
    ``device_memory_stats()`` for the allocator's view."""
    return _TRACKER.peak


def device_memory_stats() -> dict | None:
    """The default device's allocator stats (``peak_bytes_in_use`` etc.),
    or None where the platform does not expose them (CPU)."""
    dev = jax.local_devices()[0]
    stats = getattr(dev, "memory_stats", None)
    return stats() if stats is not None else None


def _nbytes(a) -> int:
    return int(np.prod(a.shape)) * a.dtype.itemsize if hasattr(a, "shape") else 0


# ---------------------------------------------------------------------------
# ChunkStore
# ---------------------------------------------------------------------------


class ChunkStore:
    """Host-resident (X, y) exposed as fixed-size row chunks.

    Array-like enough to flow through the existing seams unchanged: it has
    ``shape``/``ndim``/``dtype``/``len`` and concrete-index ``__getitem__``
    (row gather -> small device array, which is how ``falkon_fit`` and the
    samplers pull center coordinates out of it). It is **not** a jax type —
    anything that would trace it (jit operands, ``lax.cond`` branches)
    raises a ``TypeError`` pointing at the streaming entry points instead of
    silently materializing.

    Attributes:
      x: the host (n, d) fp32 array (C-contiguous; row slices are contiguous
        so each host->device copy is one memcpy — the portable stand-in for
        pinned staging buffers).
      y: optional aligned (n,) or (n, k) fp32 targets, chunked in lockstep.
      chunk: rows per chunk (the last chunk carries the remainder).
    """

    def __init__(self, x, y=None, *, chunk: int | None = None):
        xb = np.ascontiguousarray(np.asarray(x, dtype=np.float32))
        if xb.ndim != 2:
            raise ValueError(f"ChunkStore x must be (n, d), got {xb.shape}")
        self._n = xb.shape[0]
        self._xbuf = xb
        self._ybuf = None
        if y is not None:
            yb = np.ascontiguousarray(np.asarray(y, dtype=np.float32))
            if yb.shape[0] != self._n:
                raise ValueError(
                    f"y rows {yb.shape[0]} != x rows {self._n}")
            self._ybuf = yb
        self.chunk = max(1, int(chunk) if chunk is not None else default_chunk())

    def append(self, x_new, y_new=None) -> int:
        """Append rows to the store (amortized O(1): capacity-doubling host
        buffers, so the online ingest path never re-copies the history per
        batch). Returns the new row count.

        Existing rows never move or change value — ``x``/``y`` are views of
        a prefix that only grows, which is what lets a background center
        refresh read a row-count snapshot while appends continue.
        """
        xb = np.ascontiguousarray(np.asarray(x_new, dtype=np.float32))
        if xb.ndim != 2 or xb.shape[1] != self._xbuf.shape[1]:
            raise ValueError(f"append rows must be (r, {self._xbuf.shape[1]}), "
                             f"got {xb.shape}")
        yb = None
        if self._ybuf is not None:
            if y_new is None:
                raise ValueError("this store carries y; append needs y_new")
            yb = np.ascontiguousarray(np.asarray(y_new, dtype=np.float32))
            if yb.shape[0] != xb.shape[0] or yb.shape[1:] != self._ybuf.shape[1:]:
                raise ValueError(
                    f"y_new shape {yb.shape} does not match {xb.shape[0]} "
                    f"rows of {self._ybuf.shape[1:]} targets")
        elif y_new is not None:
            raise ValueError("this store has no y; cannot append y_new")
        need = self._n + xb.shape[0]
        if need > self._xbuf.shape[0]:
            cap = max(need, 2 * self._xbuf.shape[0])
            grown = np.empty((cap,) + self._xbuf.shape[1:], np.float32)
            grown[: self._n] = self._xbuf[: self._n]
            self._xbuf = grown
            if self._ybuf is not None:
                growny = np.empty((cap,) + self._ybuf.shape[1:], np.float32)
                growny[: self._n] = self._ybuf[: self._n]
                self._ybuf = growny
        self._xbuf[self._n:need] = xb
        if yb is not None:
            self._ybuf[self._n:need] = yb
        self._n = need
        return self._n

    # -- array-like surface --------------------------------------------------

    @property
    def x(self) -> np.ndarray:
        """The host (n, d) fp32 X — a contiguous view of the growth buffer."""
        return self._xbuf[: self._n]

    @property
    def y(self) -> np.ndarray | None:
        """The host (n,) / (n, k) fp32 targets (None when not stored)."""
        return None if self._ybuf is None else self._ybuf[: self._n]

    @property
    def shape(self) -> tuple[int, int]:
        """(n, d) of the stored X."""
        return (self._n, self._xbuf.shape[1])

    @property
    def ndim(self) -> int:
        """Always 2 (rows x features)."""
        return 2

    @property
    def dtype(self):
        """fp32 — the storage and transfer dtype."""
        return self.x.dtype

    def __len__(self) -> int:
        return self.x.shape[0]

    @property
    def n_chunks(self) -> int:
        """Number of row chunks (ceil(n / chunk))."""
        return -(-self.x.shape[0] // self.chunk)

    def chunk_slices(self) -> list[slice]:
        """The row slice of every chunk, in streaming order."""
        n, c = self.x.shape[0], self.chunk
        return [slice(i, min(i + c, n)) for i in range(0, n, c)]

    def __getitem__(self, idx) -> Array:
        """Concrete row gather -> device array (centers, candidate sets).

        Accepts an int, a slice, or an integer index array. Tracers are
        rejected: a traced gather would force the whole host array onto the
        device, which is exactly what this store exists to avoid.
        """
        if isinstance(idx, jax.core.Tracer):
            raise TypeError(
                "ChunkStore rows can only be gathered with concrete indices "
                "(a traced gather would materialize host data on device); "
                "stream through StreamBackend, or gather before tracing")
        if isinstance(idx, (int, np.integer, slice)):
            return jax.numpy.asarray(self.x[idx])
        return jax.numpy.asarray(self.x[np.asarray(idx)])

    def to_device(self) -> Array:
        """The whole X as one device array — O(n d), NOT O(n M); for code
        paths (oracles, tiny problems) that genuinely want X device-resident."""
        return jax.numpy.asarray(self.x)

    def __array__(self, dtype=None, copy=None):
        """numpy protocol: the host X itself. Lets ``jnp.asarray(store)``
        work as an explicit O(n d) escape hatch (the data, never (n, M)) for
        the oracle/direct-solver paths that cannot stream."""
        return self.x if dtype is None else self.x.astype(dtype)


# ---------------------------------------------------------------------------
# Double-buffered chunk iteration
# ---------------------------------------------------------------------------


def _sources(x, aux):
    """Normalize (x, aux) into (host-or-device row source, aligned aux list,
    chunk row count). ``x`` may be a ChunkStore, numpy array, or jax array."""
    if isinstance(x, ChunkStore):
        return x.x, aux, x.chunk
    return x, aux, None


def device_chunks(x, aux=None, *, chunk: int | None = None):
    """Yield ``(xb, auxb)`` device chunks of ``x`` (and the aligned optional
    ``aux`` rows — targets, masks) with one-chunk prefetch.

    Host sources (ChunkStore / numpy) are copied chunk-by-chunk with
    ``jax.device_put``; the put for chunk i+1 is issued before chunk i is
    yielded, so the copy overlaps the consumer's compute under async
    dispatch, and the tracker never sees more than two chunks resident;
    the bytes put count as ``stream.h2d_bytes`` (``runtime.spans``).
    Device-resident ``x`` (small-n parity paths) is sliced lazily with no
    copies and no accounting.
    """
    src, aux, store_chunk = _sources(x, aux)
    c = int(chunk) if chunk is not None else (store_chunk or default_chunk())
    c = max(1, c)
    n = src.shape[0]
    slices = [slice(i, min(i + c, n)) for i in range(0, n, c)]
    host = isinstance(src, np.ndarray)

    def put(sl):
        xb = src[sl]
        ab = None if aux is None else aux[sl]
        if host:
            xb = jax.device_put(xb)
            _TRACKER.add(_nbytes(xb))
            spans.count("stream.h2d_bytes", _nbytes(xb))
            if ab is not None and isinstance(ab, np.ndarray):
                ab = jax.device_put(ab)
                _TRACKER.add(_nbytes(ab))
                spans.count("stream.h2d_bytes", _nbytes(ab))
        return xb, ab

    def drop(pair):
        if host:
            xb, ab = pair
            _TRACKER.sub(_nbytes(xb))
            if ab is not None:
                _TRACKER.sub(_nbytes(ab))

    cur = put(slices[0])
    for sl in slices[1:]:
        nxt = put(sl)  # async H2D for chunk i+1 issued before chunk i runs
        yield cur
        drop(cur)
        cur = nxt
    yield cur
    drop(cur)
