"""Chunk IO: file-per-block layout with offset writes.

Mirrors the reference datanode's default chunk layout strategy
(container-service keyvalue/impl/FilePerBlockStrategy.java:69 — one file
per block, chunks written at their block offset) and ChunkUtils
(keyvalue/helpers/ChunkUtils.java: writeData:109-156 with overwrite
validation :285, readData:190-283). Durability via explicit flush+fsync on
commit rather than per-write.

Round-4 host-path work: the write path is zero-copy and open-once — a
bounded per-store fd cache (the reference FilePerBlockStrategy's
OpenFiles cache) plus `os.pwrite(fd, memoryview(data), offset)` replaces
open-per-chunk + `tobytes()` (which paid a 1 MiB copy AND an open/close
per chunk); reads use `os.pread` on the same cached fd. Descriptors are
refcounted so the store lock covers only cache bookkeeping — the actual
pwrite/pread/fsync syscalls run outside it and concurrent readers are
never serialized behind a committing writer's fsync.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from pathlib import Path

import numpy as np

from ozone_tpu.storage.ids import (
    INVALID_WRITE_SIZE,
    IO_EXCEPTION,
    BlockID,
    ChunkInfo,
    StorageError,
)

#: open block-file descriptors kept per store (= per container). Writers
#: touch one or two blocks of a container at a time, so a small cache
#: captures ~all reuse while bounding total fds across many containers.
_FD_CACHE_CAP = 16


class _CachedFd:
    __slots__ = ("fd", "refs", "evicted")

    def __init__(self, fd: int):
        self.fd = fd
        self.refs = 0
        self.evicted = False


class FilePerBlockStore:
    """Chunks of a block live in one file `<chunks_dir>/<local_id>.block`."""

    def __init__(self, chunks_dir: Path, readonly: bool = False):
        self.chunks_dir = Path(chunks_dir)
        self.readonly = readonly
        if not readonly:
            self.chunks_dir.mkdir(parents=True, exist_ok=True)
        self._fds: OrderedDict[int, _CachedFd] = OrderedDict()
        self._lock = threading.Lock()

    def block_path(self, block_id: BlockID) -> Path:
        return self.chunks_dir / f"{block_id.local_id}.block"

    # ------------------------------------------------------------- fd cache
    def _acquire(self, block_id: BlockID, create: bool) -> _CachedFd:
        """Pin a cached descriptor for a block file (FilePerBlockStrategy
        OpenFiles analog). Release with _release; IO on the pinned fd runs
        outside the store lock (pwrite/pread are thread-safe on a shared
        fd), so only cache bookkeeping is ever serialized."""
        lid = block_id.local_id
        with self._lock:
            ent = self._fds.get(lid)
            if ent is None:
                if self.readonly:
                    flags = os.O_RDONLY
                else:
                    flags = os.O_RDWR | (os.O_CREAT if create else 0)
                ent = _CachedFd(os.open(self.block_path(block_id), flags))
                self._fds[lid] = ent
                # evict idle LRU entries past the cap; pinned entries are
                # skipped (the cache may transiently exceed the cap while
                # many blocks are mid-IO)
                idle = [k for k, e in self._fds.items() if e.refs == 0
                        and k != lid]
                for k in idle[: max(0, len(self._fds) - _FD_CACHE_CAP)]:
                    self._close_entry(self._fds.pop(k))
            else:
                self._fds.move_to_end(lid)
            ent.refs += 1
            return ent

    def _release(self, ent: _CachedFd) -> None:
        with self._lock:
            ent.refs -= 1
            if ent.evicted and ent.refs == 0:
                self._close_entry(ent)

    @staticmethod
    def _close_entry(ent: _CachedFd) -> None:
        if ent.fd >= 0:
            try:
                os.close(ent.fd)
            except OSError:  # ozlint: allow[error-swallowing] -- best-effort fd-cache eviction
                pass
            ent.fd = -1

    def _drop_fd(self, local_id: int) -> None:
        """Caller must hold self._lock."""
        ent = self._fds.pop(local_id, None)
        if ent is not None:
            if ent.refs == 0:
                self._close_entry(ent)
            else:
                ent.evicted = True  # last _release closes it

    def close(self) -> None:
        """Release every cached descriptor (container close/delete)."""
        with self._lock:
            for lid in list(self._fds):
                self._drop_fd(lid)

    # ------------------------------------------------------------- chunk IO
    def write_chunk(
        self, block_id: BlockID, info: ChunkInfo, data: np.ndarray | bytes,
        sync: bool = False,
    ) -> None:
        if self.readonly:
            raise StorageError(
                IO_EXCEPTION, f"write {info.name}: store is readonly")
        # zero-copy: bytes/bytearray already support the buffer protocol;
        # ndarrays go through memoryview IFF contiguous uint8 (the hot
        # path), else one normalizing copy
        if isinstance(data, (bytes, bytearray, memoryview)):
            try:
                view = memoryview(data).cast("B")
            except (TypeError, ValueError):
                # non-contiguous / structured memoryview: normalize
                view = memoryview(bytes(data))
        else:
            arr = np.asarray(data)
            if arr.dtype != np.uint8 or not arr.flags.c_contiguous:
                arr = np.ascontiguousarray(arr, dtype=np.uint8)
            view = memoryview(arr.reshape(-1))
        if len(view) != info.length:
            raise StorageError(
                INVALID_WRITE_SIZE,
                f"chunk {info.name}: data {len(view)} != declared "
                f"{info.length}",
            )
        try:
            ent = self._acquire(block_id, create=True)
        except OSError as e:
            raise StorageError(
                IO_EXCEPTION, f"write {self.block_path(block_id)}: {e}"
            ) from e
        try:
            written = 0
            while written < len(view):
                written += os.pwrite(ent.fd, view[written:],
                                     info.offset + written)
            if sync:
                os.fsync(ent.fd)
        except OSError as e:
            raise StorageError(
                IO_EXCEPTION, f"write {self.block_path(block_id)}: {e}"
            ) from e
        finally:
            self._release(ent)

    def read_chunk(self, block_id: BlockID, info: ChunkInfo) -> np.ndarray:
        try:
            ent = self._acquire(block_id, create=False)
        except OSError as e:
            raise StorageError(
                IO_EXCEPTION, f"read {self.block_path(block_id)}: {e}"
            ) from e
        try:
            buf = os.pread(ent.fd, info.length, info.offset)
        except OSError as e:
            raise StorageError(
                IO_EXCEPTION, f"read {self.block_path(block_id)}: {e}"
            ) from e
        finally:
            self._release(ent)
        if len(buf) < info.length:
            # short read: chunk may extend past written data (padding
            # semantics handled by the caller); zero-fill the tail
            buf = buf + b"\x00" * (info.length - len(buf))
        return np.frombuffer(buf, dtype=np.uint8).copy()

    def block_length(self, block_id: BlockID) -> int:
        path = self.block_path(block_id)
        return path.stat().st_size if path.exists() else 0

    def delete_block(self, block_id: BlockID) -> None:
        with self._lock:
            self._drop_fd(block_id.local_id)
        path = self.block_path(block_id)
        if path.exists():
            path.unlink()

    def fsync_block(self, block_id: BlockID) -> None:
        with self._lock:
            ent = self._fds.get(block_id.local_id)
            if ent is not None:
                ent.refs += 1
        if ent is not None:
            try:
                os.fsync(ent.fd)
            finally:
                self._release(ent)
            return
        path = self.block_path(block_id)
        if path.exists():
            fd = os.open(path, os.O_RDONLY)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)
