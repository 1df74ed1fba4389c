"""Storage-backend interface for the activation spool (`repro.io`).

The spool's I/O engine (core/spool.py) is backend-agnostic: it hands a
`StorageBackend` opaque byte blobs under string keys and gets them back.
Backends model the storage tiers of the paper's experimental setup and of
the tiered-cache related work (10Cache, MemAscend):

  * `FilesystemBackend` — one directory on one device (the seed behavior)
  * `StripedBackend`    — round-robin chunk striping across N directories
                          (the paper's multi-SSD array), with per-device
                          write accounting for endurance projection
  * `HostMemoryBackend` — CPU-RAM tier
  * `TieredBackend`     — host-RAM first under a byte budget, spilling to
                          a lower backend in backward-access order
  * `AioBackend`        — O_DIRECT-style direct I/O with an aligned
                          buffer pool and depth-N submission (repro.io.aio)

The data plane is vectored and copy-accounted: `write_parts` moves a
serde part list to the device without a monolithic join, `readinto`
fills a caller-owned (pooled) buffer instead of allocating a fresh blob,
and `IoStats.bytes_copied` counts every host-side payload copy the path
could not avoid, so copies-per-byte is a measured number rather than a
claim.

Every backend measures its own `IoStats` (bytes + wall time per
direction), which the adaptive-offloading planner consumes as per-tier
`TierBandwidth` entries instead of a single scalar.

Backends are registered under string keys (`register_backend`) so config
and CLI layers can select them declaratively (`build_backend`,
`backend_from_spec`).
"""
from __future__ import annotations

import abc
import errno
import os
import threading
import time
import urllib.parse
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Type

from repro import obs
from repro.core.adaptive import TierBandwidth

# ------------------------------------------------------- error taxonomy
#
# The spool's retry layer (repro.resilience) needs to know which backend
# failures are worth a second attempt. The split follows what actually
# recovers on real storage:
#
#   transient — the device is still there but momentarily unhappy
#               (interrupted syscall, contended queue, a flaky-media
#               EIO): a bounded retry with backoff routinely succeeds.
#   fatal     — retrying cannot help: the blob is gone (ENOENT), the
#               device is gone or read-only (ENODEV/EROFS/EACCES), the
#               filesystem is out of space (ENOSPC — freeing space is a
#               *placement* decision, not a retry), or the payload
#               itself is malformed (serde ValueError on a torn blob).

#: errno values a bounded retry may ride out
TRANSIENT_ERRNOS = frozenset({
    errno.EINTR, errno.EAGAIN, errno.EBUSY, errno.ETIMEDOUT, errno.EIO,
    errno.ENOBUFS, errno.ENOMEM,
})

#: errno values where retrying the same call is provably pointless
FATAL_ERRNOS = frozenset({
    errno.ENOENT, errno.ENOSPC, errno.ENODEV, errno.ENXIO, errno.EROFS,
    errno.EACCES, errno.EPERM, errno.EISDIR, errno.ENOTDIR,
})


def classify_io_error(exc: BaseException) -> str:
    """Classify a backend failure as ``"transient"`` or ``"fatal"``.

    Unknown `OSError`s without a listed errno default to transient (one
    retry is cheap; losing a step is not); everything that is not an
    OSError — serde ValueError on a truncated blob, KeyError, etc. —
    is fatal because the bytes themselves are wrong, not the device."""
    if isinstance(exc, FileNotFoundError):
        return "fatal"
    if isinstance(exc, OSError):
        if exc.errno in FATAL_ERRNOS:
            return "fatal"
        if exc.errno in TRANSIENT_ERRNOS:
            return "transient"
        return "transient"
    if isinstance(exc, (TimeoutError, InterruptedError)):
        return "transient"
    return "fatal"

# Nominal sequential-write bandwidths (bytes/s) per backend kind, used by
# dry-run projections when no measurement exists yet. fs: one datacenter
# NVMe; striped: the paper's 4x D7-P5810 array; mem/tiered: host DRAM
# reached over PCIe 4.0 x16.
NOMINAL_WRITE_BW: Dict[str, float] = {
    "fs": 2.0e9,
    "striped": 8.0e9,
    "mem": 20.0e9,
    "tiered": 20.0e9,
    # one NVMe reached over O_DIRECT: no page-cache double copy, so the
    # nominal rate is the device's, not the memcpy-throttled buffered one
    "aio": 3.0e9,
}


@dataclass
class IoStats:
    """Measured I/O volume and busy time for one backend (or one tier).

    write_time / read_time are *utilization clocks*: time during which at
    least one writer (reader) was inside the backend. Summing per-call
    wall times would overstate time N-fold under N concurrent spool
    threads and make measured bandwidth look N-fold worse than the
    device's — the adaptive planner would then underoffload."""
    bytes_written: int = 0
    bytes_read: int = 0
    write_time: float = 0.0
    read_time: float = 0.0
    num_writes: int = 0
    num_reads: int = 0
    num_deletes: int = 0
    # host-side payload copies the data plane could not avoid (joins,
    # bounce/staging buffers) — NOT the device transfer itself. The
    # vectored fs path runs at 0; the benchmark asserts <= 1 per byte.
    bytes_copied: int = 0

    @property
    def write_bandwidth(self) -> float:
        return self.bytes_written / self.write_time \
            if self.write_time else float("inf")

    @property
    def read_bandwidth(self) -> float:
        return self.bytes_read / self.read_time \
            if self.read_time else float("inf")

    def as_dict(self) -> Dict[str, float]:
        return {
            "bytes_written": self.bytes_written,
            "bytes_read": self.bytes_read,
            "write_time_s": self.write_time,
            "read_time_s": self.read_time,
            "num_writes": self.num_writes,
            "num_reads": self.num_reads,
            "write_gb_s": (self.write_bandwidth / 1e9
                           if self.write_time else None),
            "read_gb_s": (self.read_bandwidth / 1e9
                          if self.read_time else None),
            "bytes_copied": self.bytes_copied,
            "copies_per_byte": (
                self.bytes_copied
                / (self.bytes_written + self.bytes_read)
                if (self.bytes_written + self.bytes_read) else 0.0),
        }


def blob_filename(key: str, suffix: str) -> str:
    """File name of a blob key on a file-backed backend. Keys may hold
    "/" (the per-shard leases of a mesh, `jit3/s1`), which a file name
    cannot; percent-quoting keeps the mapping one-to-one."""
    return urllib.parse.quote(key, safe="") + suffix


def as_memoryviews(parts) -> List[memoryview]:
    """Normalize a part list to memoryviews without copying payloads.
    Multi-byte / multi-dimensional views are flattened to a byte view so
    `len(part)` is its byte length everywhere downstream."""
    out = []
    for p in parts:
        mv = p if isinstance(p, memoryview) else memoryview(p)
        if mv.itemsize != 1 or mv.ndim != 1:
            mv = mv.cast("B")
        out.append(mv)
    return out


# one iovec batch per syscall; Linux caps at sysconf(_SC_IOV_MAX) >= 1024
_IOV_MAX = 1024


def pwritev_all(fd: int, parts: List[memoryview], offset: int = 0) -> int:
    """`os.pwritev` the whole part list at `offset`, riding out partial
    writes and the IOV_MAX batch cap. Returns the end offset."""
    queue = [p for p in parts if len(p)]
    while queue:
        written = os.pwritev(fd, queue[:_IOV_MAX], offset)
        if written <= 0:
            raise OSError(f"pwritev stalled at offset {offset}")
        offset += written
        while queue and written >= len(queue[0]):
            written -= len(queue[0])
            queue.pop(0)
        if queue and written:
            queue[0] = queue[0][written:]
    return offset


def preadv_all(fd: int, buf: memoryview, offset: int = 0) -> int:
    """Fill `buf` from `fd` starting at `offset`; stops early only at
    EOF. Returns bytes read."""
    got = 0
    while got < len(buf):
        n = os.preadv(fd, [buf[got:]], offset + got)
        if n <= 0:
            break
        got += n
    return got


class StorageBackend(abc.ABC):
    """Key/value blob store with measured per-backend bandwidth.

    Subclasses implement `_write`/`_read`/`_delete`; the public methods
    wrap them with timing so `stats` is always populated. `delete` is
    missing-tolerant (dropping an un-spooled key is a no-op), matching
    the spool's unconditional `drop`.
    """

    #: registry key, set by @register_backend
    kind: str = "?"

    #: True when `read` hands back the stored blob itself with no copy
    #: (RAM-backed stores). Pooled loaders then skip the readinto
    #: staging buffer and deserialize straight over the returned blob.
    zero_copy_read: bool = False

    def __init__(self) -> None:
        self.stats = IoStats()
        self._stats_lock = threading.Lock()
        self._active = {"w": 0, "r": 0}
        self._window_start = {"w": 0.0, "r": 0.0}

    # ------------------------------------------------------- public API

    def _enter(self, side: str) -> None:
        with self._stats_lock:
            if self._active[side] == 0:
                self._window_start[side] = time.perf_counter()
            self._active[side] += 1

    def _exit(self, side: str) -> float:
        """Returns elapsed busy time to credit (0 while others are still
        inside the window)."""
        now = time.perf_counter()
        with self._stats_lock:
            self._active[side] -= 1
            if self._active[side] == 0:
                return now - self._window_start[side]
            return 0.0

    def write(self, key: str, data: bytes) -> None:
        self._enter("w")
        try:
            with obs.span("io.write", cat="io", key=key, kind=self.kind,
                          bytes=len(data)):
                self._write(key, data)
        except BaseException:
            self._exit("w")
            raise
        dt = self._exit("w")
        with self._stats_lock:
            self.stats.bytes_written += len(data)
            self.stats.write_time += dt
            self.stats.num_writes += 1

    def write_parts(self, key: str, parts) -> None:
        """Vectored write: the blob as a list of bytes-like parts, moved
        to the device without a monolithic ``b"".join``. Backends without
        a native scatter path fall back to one (counted) join."""
        parts = as_memoryviews(parts)
        nbytes = sum(len(p) for p in parts)
        self._enter("w")
        try:
            with obs.span("io.write", cat="io", key=key, kind=self.kind,
                          bytes=nbytes):
                self._write_parts(key, parts)
        except BaseException:
            self._exit("w")
            raise
        dt = self._exit("w")
        with self._stats_lock:
            self.stats.bytes_written += nbytes
            self.stats.write_time += dt
            self.stats.num_writes += 1

    def read(self, key: str) -> bytes:
        self._enter("r")
        try:
            with obs.span("io.read", cat="io", key=key,
                          kind=self.kind) as sp:
                data = self._read(key)
                sp.set(bytes=len(data))
        except BaseException:
            self._exit("r")
            raise
        dt = self._exit("r")
        with self._stats_lock:
            self.stats.bytes_read += len(data)
            self.stats.read_time += dt
            self.stats.num_reads += 1
        return data

    def readinto(self, key: str, buf) -> memoryview:
        """Read the blob into the caller's buffer (typically a pooled
        aligned one) and return the filled prefix as a memoryview —
        no per-load blob allocation. `buf` must be at least `size(key)`
        bytes."""
        mv = buf if isinstance(buf, memoryview) else memoryview(buf)
        self._enter("r")
        try:
            with obs.span("io.read", cat="io", key=key,
                          kind=self.kind) as sp:
                n = self._readinto(key, mv)
                sp.set(bytes=n)
        except BaseException:
            self._exit("r")
            raise
        dt = self._exit("r")
        with self._stats_lock:
            self.stats.bytes_read += n
            self.stats.read_time += dt
            self.stats.num_reads += 1
        return mv[:n]

    def size(self, key: str) -> Optional[int]:
        """Stored blob size in bytes, or None when the backend cannot
        answer without reading (callers then fall back to `read`)."""
        return self._size(key)

    def delete(self, key: str) -> None:
        self._delete(key)
        with self._stats_lock:
            self.stats.num_deletes += 1

    def flush(self) -> None:
        """Durability barrier; a no-op for backends without buffering."""

    def reset_stats(self) -> None:
        """Start a fresh measurement window (e.g. before a calibration
        burst, so tier bandwidths reflect only uncontended writes)."""
        with self._stats_lock:
            self.stats = IoStats()

    def calibrate(self, data: bytes, repeats: int = 2) -> None:
        """Measure write bandwidth with a synthetic burst: reset stats,
        write `repeats` copies of `data`, delete them. Composite
        backends override this to exercise *every* tier — a tier the
        burst never reaches would otherwise report infinite bandwidth
        and the planner would treat spill traffic as free."""
        self.reset_stats()
        for i in range(repeats):
            self.write(f"_calibrate{i}", data)
        for i in range(repeats):
            self.delete(f"_calibrate{i}")

    def close(self) -> None:
        self.flush()

    def tier_bandwidths(self) -> List[TierBandwidth]:
        """Measured per-tier write bandwidth for the adaptive planner.

        Flat backends report one unbounded tier; `TieredBackend`
        overrides this to expose its capacity-bounded upper tier plus
        the lower backend's tiers.
        """
        return [TierBandwidth(self.kind, self.stats.write_bandwidth, None)]

    # ---------------------------------------------------- to implement

    def _note_copy(self, nbytes: int) -> None:
        """Record an unavoidable host-side payload copy (join, bounce
        buffer) so copies-per-byte stays a measured quantity."""
        with self._stats_lock:
            self.stats.bytes_copied += nbytes

    def _write_parts(self, key: str, parts: List[memoryview]) -> None:
        """Default scatter path: join once (counted) and defer to
        `_write`. Backends with a real vectored path override this."""
        data = b"".join(parts)
        self._note_copy(len(data))
        self._write(key, data)

    def _readinto(self, key: str, buf: memoryview) -> int:
        """Default gather path: `_read` then one (counted) copy into the
        caller's buffer. Backends with a native scatter-read override."""
        data = self._read(key)
        n = len(data)
        if n > len(buf):
            raise ValueError(f"buffer of {len(buf)} bytes cannot hold "
                             f"{n}-byte blob {key!r}")
        buf[:n] = data
        self._note_copy(n)
        return n

    def _size(self, key: str) -> Optional[int]:
        return None

    @abc.abstractmethod
    def _write(self, key: str, data: bytes) -> None: ...

    @abc.abstractmethod
    def _read(self, key: str) -> bytes: ...

    @abc.abstractmethod
    def _delete(self, key: str) -> None: ...


# ---------------------------------------------------------------- registry

BACKENDS: Dict[str, Type[StorageBackend]] = {}


def register_backend(name: str):
    def deco(cls: Type[StorageBackend]) -> Type[StorageBackend]:
        cls.kind = name
        BACKENDS[name] = cls
        return cls
    return deco


def get_backend_cls(name: str) -> Type[StorageBackend]:
    if name == "managed" and name not in BACKENDS:
        # the cache manager registers itself on import; it lives in
        # repro.cache (which imports this module), so it cannot be
        # imported eagerly here
        import repro.cache.manager  # noqa: F401
    try:
        return BACKENDS[name]
    except KeyError:
        raise KeyError(f"unknown storage backend {name!r}; "
                       f"registered: {sorted(BACKENDS)}") from None
