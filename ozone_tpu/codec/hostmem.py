"""Pooled host buffers + process-wide copy accounting for the datapath.

This is the Python half of the zero-copy datapath (the C++ half is the
arena in native/datapath.cpp, exported through the dp_buf_* capsule
API). Everything payload-shaped that crosses the wire or the
buffer->device edge routes through here so that

  * receive buffers are leased from a size-classed, page-aligned pool
    (mmap-backed — anonymous mappings are page-aligned by construction)
    instead of a fresh ``bytearray`` per frame, and
  * every *host copy* of payload bytes is counted in one process-wide
    registry (``metrics.registry("datapath")``), alongside the bytes
    that *moved* without copying, so the copies/moved ratio is a
    scrapeable gauge and an assertable test invariant
    (tests/test_zero_copy.py pins <= 1 host copy per chunk per
    direction).

Reference analog: Netty's PooledByteBufAllocator + refcounted ByteBuf
leases feeding the gRPC datapath in Apache Ozone — the same argument
(allocation reuse + explicit lifetime beats GC'd byte[] churn) applied
to the Python side of the sidecar protocol.

Env knobs:
  OZONE_TPU_POOL_MAX_MIB        total bytes the pool *retains* on free
                                lists (default 256). Leases above the
                                retention budget are released to the OS.
  OZONE_TPU_POOL_MAX_CLASS_MIB  largest size class retained (default
                                256, sized so a whole-block GET slab —
                                one lease spanning a 64+ MiB streaming
                                read — is recycled instead of re-faulted
                                from fresh anonymous pages every
                                request); bigger leases are transient.
  OZONE_TPU_POOL_MIN_CLASS      smallest size class in bytes
                                (default 4096, one page).
"""

from __future__ import annotations

import logging
import mmap
import os
import sys
import threading
import weakref
from typing import Optional, Union

import numpy as np

from ozone_tpu.utils import metrics

log = logging.getLogger(__name__)

METRICS = metrics.registry("datapath")
# Eager creation: the registry renders in prometheus_text() from the
# first scrape, not the first copy.
_COPIES = METRICS.counter("copies")
_BYTES_COPIED = METRICS.counter("bytes_copied")
_BYTES_MOVED = METRICS.counter("bytes_moved")
_RATIO = METRICS.gauge("copy_ratio")
_POOL_LEASED = METRICS.gauge("pool_leased_bytes")
_POOL_FREE = METRICS.gauge("pool_free_bytes")
_POOL_HIGH = METRICS.gauge("pool_high_water_bytes")

_logged_sites: set[str] = set()
_logged_lock = threading.Lock()

BytesLike = Union[bytes, bytearray, memoryview, np.ndarray]


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


def _site(depth: int = 2) -> str:
    """`file.py:lineno` of the caller `depth` frames up — the log-once
    key for hidden-copy warnings."""
    try:
        f = sys._getframe(depth)
        return f"{os.path.basename(f.f_code.co_filename)}:{f.f_lineno}"
    except Exception:
        return "<unknown>"


def _update_ratio() -> None:
    moved = _BYTES_MOVED.value
    _RATIO.set(_BYTES_COPIED.value / moved if moved else 0.0)


def count_copy(nbytes: int, site: Optional[str] = None,
               warn: bool = True) -> None:
    """Record one host copy of `nbytes` payload bytes. Warns once per
    call-site when the copy is unexpected (`warn=True`), so a hidden
    fallback (e.g. a non-contiguous payload forcing
    np.ascontiguousarray) is visible exactly once in the logs and
    forever in the registry."""
    where = site or _site(2)
    _COPIES.inc()
    _BYTES_COPIED.inc(int(nbytes))
    _update_ratio()
    if warn:
        with _logged_lock:
            first = where not in _logged_sites
            if first:
                _logged_sites.add(where)
        if first:
            log.warning(
                "datapath host copy at %s (%d bytes) — payload left the "
                "zero-copy path (counted in datapath.copies)",
                where, nbytes)


def count_move(nbytes: int) -> None:
    """Record `nbytes` of payload that crossed a hop without a host
    copy (kernel<->pool DMA does not count against the budget)."""
    _BYTES_MOVED.inc(int(nbytes))
    _update_ratio()


class Lease:
    """A refcounted slice of pool memory.

    The creator holds one reference; ``array()`` views take another
    each (dropped via weakref.finalize when the ndarray dies), so the
    backing buffer is recycled only after the last view is gone."""

    __slots__ = ("_pool", "_mm", "cap", "size", "_refs", "__weakref__")

    def __init__(self, pool: "HostBufferPool", mm: mmap.mmap,
                 cap: int, size: int):
        self._pool = pool
        self._mm = mm
        self.cap = cap
        self.size = size
        self._refs = 1

    @property
    def view(self) -> memoryview:
        """Writable memoryview over the leased bytes. Only valid while
        at least one reference is held."""
        return memoryview(self._mm)[: self.size]

    def retain(self) -> None:
        with self._pool._lock:
            if self._refs <= 0:
                raise RuntimeError("retain() on a released lease")
            self._refs += 1

    def release(self) -> None:
        with self._pool._lock:
            if self._refs <= 0:
                raise RuntimeError("release() on a released lease")
            self._refs -= 1
            last = self._refs == 0
        if last:
            self._pool._recycle(self._mm, self.cap)

    def array(self, length: Optional[int] = None,
              offset: int = 0) -> np.ndarray:
        """Zero-copy uint8 ndarray over `[offset, offset+length)` of the
        lease. The array pins the buffer: recycling waits until it (and
        every view derived from it) is garbage-collected."""
        n = self.size - offset if length is None else int(length)
        arr = np.frombuffer(self._mm, dtype=np.uint8, count=n,
                            offset=offset)
        self.retain()
        weakref.finalize(arr, self.release)
        return arr

    def __enter__(self) -> "Lease":
        return self

    def __exit__(self, *exc) -> None:
        self.release()


class HostBufferPool:
    """Size-classed free lists of page-aligned mmap buffers.

    Classes are powers of two from `min_class` up; a lease takes the
    smallest class that fits. Released buffers are retained up to
    `max_retained` total bytes (and only for classes up to
    `max_class`); beyond that they are unmapped, so a burst does not
    permanently inflate the process."""

    def __init__(self,
                 max_retained: Optional[int] = None,
                 max_class: Optional[int] = None,
                 min_class: Optional[int] = None):
        self._lock = threading.Lock()
        self.min_class = min_class or _env_int(
            "OZONE_TPU_POOL_MIN_CLASS", 4096)
        self.max_class = max_class or _env_int(
            "OZONE_TPU_POOL_MAX_CLASS_MIB", 256) * (1 << 20)
        self.max_retained = (max_retained if max_retained is not None
                             else _env_int("OZONE_TPU_POOL_MAX_MIB",
                                           256) * (1 << 20))
        self._free: dict[int, list[mmap.mmap]] = {}
        self.leased_bytes = 0
        self.leased_count = 0
        self.free_bytes = 0
        self.high_water_bytes = 0

    def _class_for(self, n: int) -> int:
        cap = self.min_class
        while cap < n:
            cap <<= 1
        return cap

    def lease(self, n: int) -> Lease:
        if n < 0:
            raise ValueError(f"negative lease size {n}")
        cap = self._class_for(max(n, 1))
        mm: Optional[mmap.mmap] = None
        with self._lock:
            lst = self._free.get(cap)
            if lst:
                mm = lst.pop()
                self.free_bytes -= cap
        if mm is None:
            mm = mmap.mmap(-1, cap)  # anonymous => page-aligned
        with self._lock:
            self.leased_bytes += cap
            self.leased_count += 1
            self.high_water_bytes = max(self.high_water_bytes,
                                        self.leased_bytes)
            self._publish_locked()
        return Lease(self, mm, cap, n)

    def _recycle(self, mm: mmap.mmap, cap: int) -> None:
        retain = False
        with self._lock:
            self.leased_bytes -= cap
            self.leased_count -= 1
            if cap <= self.max_class and \
                    self.free_bytes + cap <= self.max_retained:
                self._free.setdefault(cap, []).append(mm)
                self.free_bytes += cap
                retain = True
            self._publish_locked()
        if not retain:
            try:
                mm.close()
            except BufferError:
                # a stray exported view keeps the mapping alive; GC
                # reclaims it when the view dies
                log.debug("pool buffer still exported at recycle; "
                          "deferring unmap to GC")

    def _publish_locked(self) -> None:
        _POOL_LEASED.set(float(self.leased_bytes))
        _POOL_FREE.set(float(self.free_bytes))
        _POOL_HIGH.set(float(self.high_water_bytes))

    def stats(self) -> dict:
        with self._lock:
            return {
                "leased_count": self.leased_count,
                "leased_bytes": self.leased_bytes,
                "free_bytes": self.free_bytes,
                "high_water_bytes": self.high_water_bytes,
            }

    def trim(self) -> None:
        """Drop all retained free buffers (tests, memory pressure)."""
        with self._lock:
            drop = [mm for lst in self._free.values() for mm in lst]
            self._free.clear()
            self.free_bytes = 0
            self._publish_locked()
        for mm in drop:
            try:
                mm.close()
            except BufferError:
                # an exported view pins the mapping; GC unmaps it later
                log.debug("trim: pool buffer still exported; deferring "
                          "unmap to GC")


_pool: Optional[HostBufferPool] = None
_pool_lock = threading.Lock()


def pool() -> HostBufferPool:
    """The process-wide pool (client recv slabs, stream relays, the
    codec service's staging batches)."""
    global _pool
    with _pool_lock:
        if _pool is None:
            _pool = HostBufferPool()
        return _pool


def as_array(data: BytesLike) -> np.ndarray:
    """Flat uint8 view of `data` with *zero copies* on the fast path
    (bytes / bytearray / memoryview / contiguous uint8 ndarray). The
    slow path (non-uint8 dtype, non-contiguous layout, exotic buffer)
    materializes one copy and counts it in the registry.

    This is the single buffer->array helper the wire endpoints
    (dn_service, native_dn, ec_writer) route through, so the copy
    budget lives in exactly one place."""
    if isinstance(data, np.ndarray):
        if data.dtype == np.uint8 and data.flags.c_contiguous:
            return data.reshape(-1)
        count_copy(data.nbytes, site=_site(2))
        return np.ascontiguousarray(data, dtype=np.uint8).reshape(-1)
    if isinstance(data, (bytes, bytearray, memoryview, mmap.mmap)):
        try:
            return np.frombuffer(data, dtype=np.uint8)
        except (ValueError, BufferError):
            # non-contiguous / unusual memoryview: one counted copy
            count_copy(len(data), site=_site(2))
            return np.frombuffer(bytes(data), dtype=np.uint8)
    arr = np.asarray(data)
    if arr.dtype == np.uint8 and arr.flags.c_contiguous:
        return arr.reshape(-1)
    count_copy(int(arr.nbytes), site=_site(2))
    return np.ascontiguousarray(arr, dtype=np.uint8).reshape(-1)


def to_device(data: BytesLike, device=None):
    """Hand host payload to the chip with no intermediate host copy:
    flat uint8 view (zero-copy for pooled/wire buffers) -> one
    jax.device_put. On CPU backends jax aliases the host buffer via
    dlpack when it can, so this edge is free in-process; on real chips
    it is the single host->HBM DMA the architecture budgets for.

    device_put is not a compile — steady-state PUT/GET triggers zero
    new XLA compilations (asserted by the compile-count probes)."""
    import jax  # lazy: keep this module import-light for the lint CLI

    arr = as_array(data)
    count_move(int(arr.nbytes))
    return jax.device_put(arr, device)
