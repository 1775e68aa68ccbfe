"""``repro.stream`` — the out-of-core streaming subsystem (DESIGN.md §10).

``ChunkStore`` keeps (X, y) host-resident in fixed-size row chunks;
``StreamBackend`` serves the full kernel-operator ``Backend`` protocol as
passes over row chunks — one compiled chunk loop over a device-resident X,
double-buffered copies (chunk i+1 in flight while chunk i runs) from a host
source — so FALKON, the BLESS/Chen-Yang samplers, predict and the
estimators run at n far beyond device memory without code changes — no
(n, M) array is ever materialized. Registered as ``"stream"``
(``REPRO_BACKEND=stream``; ``"stream:pallas"`` contracts each chunk with
another backend's fused operators).
"""
from .backend import MATERIALIZE_ELEMS, StreamBackend
from .store import (
    STREAM_CHUNK,
    ChunkStore,
    default_chunk,
    device_chunks,
    device_memory_stats,
    peak_device_bytes,
    reset_peak_device_bytes,
)

__all__ = [
    "ChunkStore",
    "StreamBackend",
    "STREAM_CHUNK",
    "MATERIALIZE_ELEMS",
    "default_chunk",
    "device_chunks",
    "device_memory_stats",
    "peak_device_bytes",
    "reset_peak_device_bytes",
]
