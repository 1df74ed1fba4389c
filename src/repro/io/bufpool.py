"""Aligned, reusable host buffers for the spool's zero-copy data plane.

MemAscend (arXiv 2505.23254) measures host-memory churn — fresh multi-MB
allocations page-faulted on first touch, then thrown away per store —
as a first-order bottleneck for SSD-offloaded training. The pool fixes
that: page-aligned `mmap` buffers in power-of-two size classes, leased
per I/O job and returned for reuse, so the steady-state store/load loop
performs zero large allocations. Page alignment (4 KiB) is also exactly
what `O_DIRECT` file descriptors require, so one pool serves both the
buffered and the direct-I/O backends.

Leases are explicit (`PooledBuffer.release()`), not GC-driven: a load's
deserialized views borrow the buffer until the spool record is dropped,
and releasing on finalizer time would hand the buffer to a new writer
while those views are still readable.
"""
from __future__ import annotations

import mmap
import threading
from typing import Dict, List, Optional

from repro import obs

#: O_DIRECT-compatible default: one x86 page / the common LBA-format size.
DEFAULT_ALIGNMENT = 4096


def _size_class(nbytes: int, alignment: int) -> int:
    """Smallest power-of-two multiple of `alignment` holding `nbytes`.

    Power-of-two classes bound internal waste at 2x and keep the free
    lists short; every class >= alignment is a multiple of it, so any
    align-rounded write length fits the leased capacity."""
    cap = alignment
    while cap < nbytes:
        cap <<= 1
    return cap


class PooledBuffer:
    """One leased buffer. `mv` is the full-capacity writable memoryview
    (page-aligned base); `data` is the first `nbytes` of it. Release
    returns the buffer to the pool — idempotent, and mandatory before
    the memory can be reused."""

    __slots__ = ("_pool", "_mm", "mv", "capacity", "nbytes", "_released")

    def __init__(self, pool: "AlignedBufferPool", mm: mmap.mmap,
                 capacity: int, nbytes: int):
        self._pool = pool
        self._mm = mm
        self.mv = memoryview(mm)
        self.capacity = capacity
        self.nbytes = nbytes
        self._released = False

    @property
    def data(self) -> memoryview:
        return self.mv[:self.nbytes]

    def release(self) -> None:
        if self._released:
            return
        self._released = True
        self.mv.release()
        self.mv = None
        self._pool._put_back(self._mm, self.capacity)
        self._mm = None

    def __enter__(self) -> "PooledBuffer":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.release()


class AlignedBufferPool:
    """Thread-safe pool of page-aligned buffers in power-of-two size
    classes. `max_bytes` caps the *idle* (free-list) footprint — leased
    bytes are whatever the callers hold; buffers returned beyond the cap
    are freed instead of cached."""

    def __init__(self, *, alignment: int = DEFAULT_ALIGNMENT,
                 max_bytes: int = 256 << 20):
        if alignment <= 0 or (alignment & (alignment - 1)):
            raise ValueError(f"alignment must be a power of two, "
                             f"got {alignment}")
        if alignment > mmap.PAGESIZE:
            # mmap guarantees page alignment and no more; a stricter
            # requirement would need manual over-allocate-and-trim
            raise ValueError(
                f"alignment {alignment} exceeds the page size "
                f"{mmap.PAGESIZE} that mmap-backed buffers guarantee")
        self.alignment = alignment
        self.max_bytes = max_bytes
        self._lock = threading.Lock()
        self._free: Dict[int, List[mmap.mmap]] = {}
        self._free_bytes = 0
        self.hits = 0
        self.misses = 0
        self.trimmed = 0            # returns dropped over max_bytes
        self.bytes_allocated = 0    # lifetime mmap volume (miss cost)

    def acquire(self, nbytes: int) -> PooledBuffer:
        """Lease a buffer of capacity >= max(nbytes, alignment)."""
        cap = _size_class(max(nbytes, 1), self.alignment)
        with self._lock:
            bucket = self._free.get(cap)
            if bucket:
                mm = bucket.pop()
                self._free_bytes -= cap
                self.hits += 1
                return PooledBuffer(self, mm, cap, nbytes)
            self.misses += 1
            self.bytes_allocated += cap
        # a miss is a fresh mmap whose pages fault on first touch — the
        # exact churn MemAscend measures, so it earns a timeline mark
        obs.instant("pool.miss", cat="pool", bytes=cap)
        # mmap outside the lock: faulting fresh pages is the slow part
        return PooledBuffer(self, mmap.mmap(-1, cap), cap, nbytes)

    def _put_back(self, mm: mmap.mmap, cap: int) -> None:
        with self._lock:
            if self._free_bytes + cap <= self.max_bytes:
                self._free.setdefault(cap, []).append(mm)
                self._free_bytes += cap
                return
            self.trimmed += 1
        try:
            mm.close()
        except BufferError:
            pass    # borrower still holds a view; GC reclaims the map

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    @property
    def free_bytes(self) -> int:
        with self._lock:
            return self._free_bytes

    def stats(self) -> Dict[str, float]:
        return {
            "hits": self.hits, "misses": self.misses,
            "hit_rate": round(self.hit_rate, 4),
            "trimmed": self.trimmed,
            "free_bytes": self.free_bytes,
            "bytes_allocated": self.bytes_allocated,
            "alignment": self.alignment,
        }

    def close(self) -> None:
        """Free every idle buffer (leased ones are released by their
        holders)."""
        with self._lock:
            buckets, self._free = self._free, {}
            self._free_bytes = 0
        for bucket in buckets.values():
            for mm in bucket:
                try:
                    mm.close()
                except BufferError:
                    # a borrower still holds a zero-copy view of this
                    # buffer; dropping our reference is enough — the map
                    # is reclaimed when the last view dies
                    pass
