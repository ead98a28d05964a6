"""Datapath sidecar host: the C++ hot path wired into a Datanode.

The native listener (native/datapath.cpp) owns the per-chunk work —
frame parse, pwrite/pread, CRC32C verify, fsync — while this module
keeps the CONTROL PLANE in Python via three per-stream callbacks:

- auth: token verification (BlockTokenVerifier), layout gate
  (RequestFeatureValidator analog for the batched verb), container
  writability, the single-writer fence, and block-file path resolution.
- done: the piggybacked block commit (``Datanode.put_block``) plus the
  stream/chunk/byte metrics the gRPC verbs maintain.
- fail: read-side checksum failure -> mark the container unhealthy
  (OnDemandContainerDataScanner trigger analog).

Per-chunk semantics match the gRPC verbs byte-for-byte: same file-per-
block layout (``FilePerBlockStore.block_path``), same zero-fill short
reads, same fsync-before-commit discipline (the C++ side fsyncs on a
sync stream before the commit callback runs, so ``put_block`` is handed
already-durable bytes). Role analog of the reference's native-epoll
Netty transport + ChunkUtils mapped IO (GrpcXceiverService.java:42,
ChunkUtils.java:109-156) — the Python interpreter leaves the per-chunk
path entirely.
"""

from __future__ import annotations

import ctypes
import json
import logging
import threading
from typing import Optional

from ozone_tpu.native import build_shared, _HERE
from ozone_tpu.storage.ids import (
    BLOCK_TOKEN_VERIFICATION_FAILED,
    BlockData,
    BlockID,
    StorageError,
)

log = logging.getLogger(__name__)

_SRC = _HERE / "datapath.cpp"
_SO = _HERE / "libdatapath.so"

_AUTH_CB = ctypes.CFUNCTYPE(
    ctypes.c_int32, ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint32,
    ctypes.c_int32, ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint32)
_DONE_CB = ctypes.CFUNCTYPE(
    ctypes.c_int32, ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint32,
    ctypes.c_int32, ctypes.c_uint64, ctypes.c_uint32,
    ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint32)
_FAIL_CB = ctypes.CFUNCTYPE(
    None, ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint32)

_lib_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_tried = False


def load_lib() -> Optional[ctypes.CDLL]:
    """Build-on-demand + load (native/__init__ pattern); None when the
    host has no toolchain — the daemon then serves gRPC only. A
    toolchain that fails to build raises NativeBuildError: the daemon
    refuses to start rather than quietly serve the slow transport (and
    so does every later call in the process)."""
    global _lib, _lib_tried
    with _lib_lock:
        if _lib is not None or _lib_tried:
            return _lib
        so = build_shared(_SRC, _SO,
                          extra=("-O3", "-march=native", "-std=c++17",
                                 "-pthread"))
        _lib_tried = True
        if so is None:
            return None
        try:
            lib = ctypes.CDLL(str(so))
            lib.dp_start.restype = ctypes.c_void_p
            lib.dp_start.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                     _AUTH_CB, _DONE_CB, _FAIL_CB]
            lib.dp_port.restype = ctypes.c_int
            lib.dp_port.argtypes = [ctypes.c_void_p]
            lib.dp_uds.restype = ctypes.c_int
            lib.dp_uds.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.c_int]
            lib.dp_stop.argtypes = [ctypes.c_void_p]
            lib.dp_crc32c.restype = ctypes.c_uint32
            lib.dp_crc32c.argtypes = [ctypes.c_void_p, ctypes.c_int64]
            # buffer-pool capsule API (native arena lease/release)
            lib.dp_buf_lease.restype = ctypes.c_void_p
            lib.dp_buf_lease.argtypes = [ctypes.c_uint64]
            lib.dp_buf_data.restype = ctypes.c_void_p
            lib.dp_buf_data.argtypes = [ctypes.c_void_p]
            lib.dp_buf_cap.restype = ctypes.c_uint64
            lib.dp_buf_cap.argtypes = [ctypes.c_void_p]
            lib.dp_buf_retain.argtypes = [ctypes.c_void_p]
            lib.dp_buf_release.argtypes = [ctypes.c_void_p]
            lib.dp_pool_stat.restype = ctypes.c_uint64
            lib.dp_pool_stat.argtypes = [ctypes.c_int]
            _lib = lib
        except OSError as e:
            log.warning("native datapath unavailable: %s", e)
            _lib = None
        return _lib


def native_pool_stats() -> Optional[dict]:
    """Arena counters from the C++ side of the pool (the Python half
    lives in codec/hostmem.py). None without the native toolchain."""
    lib = load_lib()
    if lib is None:
        return None
    return {
        "leased_bytes": int(lib.dp_pool_stat(0)),
        "free_bytes": int(lib.dp_pool_stat(1)),
        "high_water_bytes": int(lib.dp_pool_stat(2)),
    }


def _pack_out(out, cap: int, ok: bool, body: bytes) -> int:
    n = 1 + len(body)
    if n > cap:
        return -1
    out[0] = 1 if ok else 0
    if body:
        ctypes.memmove(ctypes.addressof(out.contents) + 1, body, len(body))
    return n


def _error_body(code: str, message: str) -> bytes:
    return json.dumps({"error": {"code": code, "message": message}}).encode()


class DatapathSidecar:
    """One native listener per datanode process."""

    def __init__(self, dn, verifier=None, layout=None,
                 host: str = "127.0.0.1", port: int = 0):
        self.dn = dn
        self.verifier = verifier
        self.layout = layout
        self.host = host
        self._want_port = port
        self.port: Optional[int] = None
        #: abstract unix socket name ("@...") for the co-located lane;
        #: None when the native side could not set one up
        self.uds: Optional[str] = None
        self._handle = None
        # CFUNCTYPE wrappers must outlive the listener (GC'd callbacks
        # are a segfault from a C++ thread)
        self._cbs = (_AUTH_CB(self._auth), _DONE_CB(self._done),
                     _FAIL_CB(self._fail))

    # ------------------------------------------------------------ callbacks
    def _hdr(self, hdr, hdr_len: int) -> dict:
        return json.loads(ctypes.string_at(hdr, hdr_len))

    def _auth(self, hdr, hdr_len, is_write, out, out_cap) -> int:
        try:
            m = self._hdr(hdr, hdr_len)
            block_id = BlockID.from_json(m["block_id"])
            if is_write:
                self._gate_layout()
                self._check_token(m, block_id, "WRITE")
                c = self.dn.containers.get(block_id.container_id)
                c.require_writable()
                if c.chunks.readonly:
                    raise StorageError("IO_EXCEPTION", "store is readonly")
                self.dn._fence(c, block_id, m.get("writer"))
            else:
                self._check_token(m, block_id, "READ")
                c = self.dn.containers.get(block_id.container_id)
            return _pack_out(out, out_cap, True,
                             str(c.chunks.block_path(block_id)).encode())
        except StorageError as e:
            return _pack_out(out, out_cap, False,
                             _error_body(e.code, e.msg))
        except Exception as e:  # noqa: BLE001 - must never unwind into C++
            log.exception("datapath auth failed")
            return _pack_out(out, out_cap, False,
                             _error_body("IO_EXCEPTION", str(e)))

    def _done(self, hdr, hdr_len, is_write, nbytes, nchunks,
              out, out_cap) -> int:
        try:
            m = self._hdr(hdr, hdr_len)
            block_id = BlockID.from_json(m["block_id"])
            mx = self.dn.metrics
            if is_write:
                mx.counter("batched_write_streams").inc()
                mx.counter("batched_write_chunks").inc(int(nchunks))
                mx.counter("bytes_written").inc(int(nbytes))
                self.dn.mutation_count += 1
                commit = m.get("commit")
                if commit is not None:
                    bd = BlockData.from_json(commit)
                    if bd.block_id != block_id:
                        raise StorageError(
                            "INVALID_ARGUMENT",
                            f"commit names {bd.block_id}, stream wrote "
                            f"{block_id}")
                    # sync streams were fsynced by the native side
                    # before this callback: put_block gets
                    # already-durable bytes, so sync=False
                    self.dn.put_block(bd, sync=False,
                                      writer=m.get("writer"))
            else:
                mx.counter("batched_read_streams").inc()
                mx.counter("batched_read_chunks").inc(int(nchunks))
                mx.counter("bytes_read").inc(int(nbytes))
            return _pack_out(out, out_cap, True, b"")
        except StorageError as e:
            return _pack_out(out, out_cap, False,
                             _error_body(e.code, e.msg))
        except Exception as e:  # noqa: BLE001 - must never unwind into C++
            log.exception("datapath commit failed")
            return _pack_out(out, out_cap, False,
                             _error_body("IO_EXCEPTION", str(e)))

    def _fail(self, hdr, hdr_len) -> None:
        try:
            m = self._hdr(hdr, hdr_len)
            block_id = BlockID.from_json(m["block_id"])
            c = self.dn.containers.get(block_id.container_id)
            self.dn.metrics.counter("checksum_failures").inc()
            self.dn.on_read_error(c)
        except Exception:  # noqa: BLE001 - must never unwind into C++
            log.exception("datapath fail-report failed")

    def _gate_layout(self) -> None:
        """Native writes are the batched verb: same layout gate as
        WriteChunksCommit (the client's single-chunk write_chunk falls
        back to the ungated gRPC verb on this refusal)."""
        if self.layout is None:
            return
        from ozone_tpu.utils.upgrade import (
            PRE_FINALIZE_ERROR,
            RATIS_STREAMING_WRITE,
        )

        if not self.layout.is_allowed(RATIS_STREAMING_WRITE):
            raise StorageError(
                PRE_FINALIZE_ERROR,
                f"native datapath needs layout feature "
                f"{RATIS_STREAMING_WRITE.name} "
                f"(v{RATIS_STREAMING_WRITE.version}); datanode is at "
                f"layout {self.layout.metadata_version}")

    def _check_token(self, m: dict, block_id: BlockID, mode: str) -> None:
        if self.verifier is None or not self.verifier.enabled:
            return
        from ozone_tpu.utils.security import AccessMode, TokenError

        try:
            self.verifier.verify(m.get("token"), block_id, AccessMode(mode))
        except TokenError as e:
            raise StorageError(BLOCK_TOKEN_VERIFICATION_FAILED, str(e))

    # ------------------------------------------------------------ lifecycle
    def start(self) -> Optional[int]:
        lib = load_lib()
        if lib is None:
            return None
        self._handle = lib.dp_start(self.host.encode(), self._want_port,
                                    *self._cbs)
        if not self._handle:
            log.warning("native datapath failed to bind %s:%d",
                        self.host, self._want_port)
            return None
        self.port = lib.dp_port(self._handle)
        buf = ctypes.create_string_buffer(128)
        n = lib.dp_uds(self._handle, buf, len(buf))
        self.uds = buf.raw[:n].decode() if n > 0 else None
        log.info("native datapath listening on %s:%d uds=%s (dn=%s)",
                 self.host, self.port, self.uds, self.dn.id)
        return self.port

    def advertise(self) -> dict:
        """GetDatapathInfo payload: TCP port plus the abstract unix
        socket a co-located client should prefer."""
        return {"port": self.port, "uds": self.uds}

    def stop(self) -> None:
        if self._handle is not None:
            load_lib().dp_stop(self._handle)
            self._handle = None
            self.port = None
            self.uds = None
