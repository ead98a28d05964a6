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

What the pool holds: the wire slabs of every native read (one lease a
ReadChunks stream), the codec service's staging batches, and the two
per-operation buffers of an EC read: the key's one buffer that the
user's answer is a view of (`OzoneBucket._read_groups_range`) and each
survivor batch a recovery decodes from
(`ECBlockGroupReader._recover_batches_once`: a degraded GET, a repair,
a storm's stream). A lease that finds nothing on its class's free list
maps new anonymous memory, and every page of that is faulted in at its
first touch, inside whatever span writes it (on the benchmark's host,
under eight readers, 1.3-2.3 ms of the writing thread's CPU a MiB on top
of the copy itself, and ~0.06 ms a MiB of `munmap` at its end: PERF.md
section 6, PR 34); a recycled lease holds ANOTHER operation's bytes, so
whoever leases overwrites every byte it hands on. `pool_leases`,
`pool_leases_recycled` and `pool_fresh_bytes` (registry `datapath`) say
how often the pool engages.

How retention was sized: the free lists must hold what a process's
operations give back between two of them, or the next one maps fresh
memory again. Eight concurrent degraded GETs of 40 MiB `rs-10-4` keys
(the benchmark's cell) each cycle a key buffer (40 MiB), a survivor
batch (40 MiB) and ten unit slabs (4 MiB + framing: the 5 MiB class),
130 MiB a reader, 1,040 MiB in all and every byte of it free when all
eight are between GETs: the default budget is 1 GiB (it was 256 MiB,
which those GETs' slabs alone overran). Classes step four times an
octave from 16 KiB up (1, 1.25, 1.5, 1.75 x 2^k; powers of two below):
a 40 MiB key charges the budget 40 MiB, not the 64 of the next power
of two, and a class wastes a quarter at most. The budget is a ceiling
on what a process keeps, not a reservation: a process retains no more
than its own operations once leased at the same time.

Env knobs:
  OZONE_TPU_POOL_MAX_MIB        total bytes the pool *retains* on free
                                lists (default 1024). Leases above the
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
_POOL_LEASES = METRICS.counter("pool_leases")
_POOL_RECYCLED = METRICS.counter("pool_leases_recycled")
#: bytes (of their classes) of the leases that mapped new memory
_POOL_FRESH = METRICS.counter("pool_fresh_bytes")

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
    backing buffer is recycled only after the last view is gone.
    `fresh` says the lease mapped new memory (untouched, zero pages);
    one that did not holds whatever its last holder wrote."""

    __slots__ = ("_pool", "_mm", "cap", "size", "fresh", "_refs",
                 "__weakref__")

    def __init__(self, pool: "HostBufferPool", mm: mmap.mmap,
                 cap: int, size: int, fresh: bool):
        self._pool = pool
        self._mm = mm
        self.cap = cap
        self.size = size
        self.fresh = fresh
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

    Classes are powers of two from `min_class` up and, from four
    pages a step, four to an octave; a lease takes the smallest class
    that fits. Released buffers are retained up to
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
                                           1024) * (1 << 20))
        self._free: dict[int, list[mmap.mmap]] = {}
        self.leased_bytes = 0
        self.leased_count = 0
        self.free_bytes = 0
        self.high_water_bytes = 0

    def _class_for(self, n: int) -> int:
        cap = self.min_class
        while cap < n:
            cap <<= 1
        # between two powers of two, three classes more wherever a step
        # is whole pages: a buffer just over 2^k charges the retention
        # budget a quarter more, not twice
        half, step = cap >> 1, cap >> 3
        if half >= self.min_class and step % mmap.PAGESIZE == 0:
            for c in (half + step, half + 2 * step, half + 3 * step):
                if c >= n:
                    return c
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
        fresh = mm is None
        if fresh:
            mm = mmap.mmap(-1, cap)  # anonymous => page-aligned
        with self._lock:
            self.leased_bytes += cap
            self.leased_count += 1
            self.high_water_bytes = max(self.high_water_bytes,
                                        self.leased_bytes)
            self._publish_locked()
        _POOL_LEASES.inc()
        if fresh:
            _POOL_FRESH.inc(cap)
        else:
            _POOL_RECYCLED.inc()
        return Lease(self, mm, cap, n, fresh)

    def lease_array(self, n: int) -> tuple[np.ndarray, bool]:
        """`n` UNINITIALISED bytes as a flat uint8 array that alone pins
        its lease (the pages go back to the free list when it and every
        view of it are dead), and whether the lease is fresh. The
        per-operation buffers take this door: unless fresh the array
        holds another operation's bytes, so the caller writes every
        byte before it hands any on."""
        if n == 0:
            return np.empty(0, dtype=np.uint8), False
        with self.lease(n) as lease:
            return lease.array(), lease.fresh

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
    codec service's staging batches, an EC read's key buffer and
    survivor batches)."""
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
