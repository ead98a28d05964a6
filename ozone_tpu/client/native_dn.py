"""Datanode client that rides the native datapath for the hot verbs.

Extends GrpcDatanodeClient: control-plane verbs stay on gRPC; the bulk
verbs (write_chunks_commit / write_chunk / read_chunks / read_chunk) go
over the datanode's native C++ listener (native/datapath.cpp) when the
server advertises one — discovered once per client via the
GetDatapathInfo gRPC verb, the ``XceiverClientSpi`` transport-choice
analog. Any discovery or connect failure disables the native path for
this client and falls back to gRPC silently (the reference's
native-transport probe-and-fallback posture); mid-stream failures
surface as StorageError exactly like gRPC errors so the writers'
exclude/retry machinery is transport-agnostic.

Chaos parity: every native call honors net/partition.py rules keyed by
the datanode's gRPC ADDRESS (the partition vocabulary's node identity),
so injected partitions and delays cover both transports at once.

Wire framing (must match datapath.cpp): frame = u32 len | u8 tag |
body, little-endian. Checksums ride as big-endian-decoded u32 values
(utils/checksum stores 4-byte big-endian CRC words).
"""

from __future__ import annotations

import json
import os
import socket
import struct
import sys
import threading
from typing import Optional

import numpy as np

from ozone_tpu.client import resilience
from ozone_tpu.codec import hostmem
from ozone_tpu.net.dn_service import GrpcDatanodeClient
from ozone_tpu.storage.ids import StorageError

_T_WHDR, _T_CHUNK, _T_END = 0x01, 0x02, 0x03
_T_RHDR, _T_RCHUNK = 0x05, 0x06
_T_STATUS, _T_DATA = 0x81, 0x82

_FRAME = struct.Struct("<IB")
_CHUNK_HDR = struct.Struct("<QI")
_RCHUNK_HDR = struct.Struct("<QIBII")

_MAX_FRAME = 256 * 1024 * 1024  # must match datapath.cpp
try:
    _IOV_MAX = os.sysconf("SC_IOV_MAX")
    if _IOV_MAX <= 0:
        _IOV_MAX = 1024
except (AttributeError, OSError, ValueError):
    _IOV_MAX = 1024

#: sockets kept per client; EC fan-out drives one unit stream per DN so
#: per-DN concurrency is low
_POOL_CAP = 4


def _enabled() -> bool:
    return os.environ.get("OZONE_TPU_NATIVE_DATAPATH", "1") != "0"


def _connect_timeout_s() -> float:
    """Connect budget (env-overridable); the operation deadline caps it
    further in _Conn via resilience.op_timeout."""
    try:
        return float(os.environ.get("OZONE_TPU_CONNECT_TIMEOUT_S", "")
                     or 20.0)
    except ValueError:
        return 20.0


def _io_timeout_s() -> float:
    """Per-request socket read/write budget when no operation deadline
    is ambient (replaces the old hardcoded 120 s create_connection
    timeout that doubled as the forever-IO timeout)."""
    try:
        return float(os.environ.get("OZONE_TPU_IO_TIMEOUT_S", "") or 120.0)
    except ValueError:
        return 120.0


def _sendmsg_all(sock: socket.socket, parts: list) -> None:
    """One gathered ``sendmsg`` for a whole request, IOV_MAX-batched:
    frame headers and payload views leave the process zero-copy in a
    handful of syscalls instead of two writes per chunk. On shared-core
    rigs the per-chunk wakeup this replaces — not bandwidth — dominated
    PUT latency."""
    mv = [p if isinstance(p, memoryview) else memoryview(p) for p in parts]
    i = 0
    while i < len(mv):
        batch = mv[i:i + _IOV_MAX]
        sent = sock.sendmsg(batch)
        j = 0
        while j < len(batch) and sent >= len(batch[j]):
            sent -= len(batch[j])
            j += 1
        i += j
        if j < len(batch) and sent:
            mv[i] = batch[j][sent:]


def _recvmsg_some(sock: socket.socket, bufs: list, i: int) -> tuple[int, int]:
    """One scatter receive into `bufs[i:]` (IOV_MAX at most a call),
    the read twin of `_sendmsg_all`: returns (bytes received, index of
    the first buffer not yet full); a buffer the receive ended inside
    is cut in place to its unwritten rest, so a frame may straddle any
    boundary and the next call carries on where this one stopped."""
    batch = bufs[i:i + _IOV_MAX]
    got = sock.recvmsg_into(batch)[0]
    if got == 0:
        raise ConnectionError("native datapath peer closed")
    left, j = got, 0
    while j < len(batch) and left >= len(batch[j]):
        left -= len(batch[j])
        j += 1
    if left:
        bufs[i + j] = batch[j][left:]
    return got, i + j


class _Conn:
    def __init__(self, host: str, port: int, uds: Optional[str] = None):
        # deadline-derived connect timeout: a spent budget raises
        # DEADLINE_EXCEEDED here instead of queueing a doomed connect
        timeout = resilience.op_timeout(_connect_timeout_s(), "connect")
        self.sock = None
        if uds:
            # co-located lane: the abstract unix socket the sidecar
            # advertised skips the loopback pseudo-NIC entirely
            # (~1.5-2x single-stream on one core). A name minted on
            # another host simply fails to connect -> TCP below.
            try:
                s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
                s.settimeout(timeout)
                s.connect("\0" + uds[1:] if uds.startswith("@") else uds)
                self.sock = s
            except OSError:
                self.sock = None
        if self.sock is None:
            self.sock = socket.create_connection((host, port),
                                                 timeout=timeout)
            self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        # deep buffers: on shared-core rigs every buffer-full forces a
        # client<->server context switch mid-chunk
        for opt in (socket.SO_SNDBUF, socket.SO_RCVBUF):
            try:
                self.sock.setsockopt(socket.SOL_SOCKET, opt, 8 * 1024 * 1024)
            except OSError:  # ozlint: allow[error-swallowing] -- optional buffer tuning; kernel caps/refusals are fine
                pass
        # reusable control-plane receive scratch (recv_exact/recv_frame)
        self._scratch = bytearray(4096)

    def arm(self, verb: str) -> None:
        """Per-request IO timeout: pooled-connection REUSE re-derives it
        from the remaining operation deadline, so a request issued with
        2 s of budget left cannot block the full default IO timeout."""
        self.sock.settimeout(resilience.op_timeout(_io_timeout_s(), verb))

    def send_frame(self, tag: int, body) -> None:
        _sendmsg_all(self.sock, [_FRAME.pack(len(body), tag), body]
                     if len(body) else [_FRAME.pack(0, tag)])

    def send_frames(self, frames: list[tuple[int, object]]) -> None:
        """One gathered sendmsg for a whole request — headers, small
        frames and payload views leave zero-copy, never joined into a
        coalescing bytes()."""
        parts: list[bytes | memoryview] = []
        for tag, body in frames:
            parts.append(_FRAME.pack(len(body), tag))
            if len(body):
                parts.append(body)
        _sendmsg_all(self.sock, parts)

    def recv_exact_into(self, view: memoryview) -> None:
        got, n = 0, len(view)
        while got < n:
            r = self.sock.recv_into(view[got:], n - got)
            if r == 0:
                raise ConnectionError("native datapath peer closed")
            got += r

    def recv_exact(self, n: int) -> memoryview:
        """Control-plane receive into the connection's reusable scratch
        (no per-frame bytes materialized). The returned view is valid
        until the next recv_* call; payload frames never come through
        here — read_chunks scatters them into pooled leases."""
        if n > len(self._scratch):
            self._scratch = bytearray(max(n, 4096))
        view = memoryview(self._scratch)[:n]
        self.recv_exact_into(view)
        return view

    def recv_frame(self) -> tuple[int, memoryview]:
        n, tag = _FRAME.unpack(self.recv_exact(5))
        if n > _MAX_FRAME:
            raise ConnectionError(f"oversized frame {n}")
        return tag, (self.recv_exact(n) if n else memoryview(b""))

    def close(self) -> None:
        try:
            self.sock.close()
        except OSError:  # ozlint: allow[error-swallowing] -- best-effort socket teardown
            pass


class NativeDatanodeClient(GrpcDatanodeClient):
    def __init__(self, dn_id: str, address: str, tokens=None, tls=None):
        super().__init__(dn_id, address, tokens=tokens, tls=tls)
        #: gRPC address — the node identity partition rules key on
        self.address = address
        # native path needs a plaintext side channel; mTLS clusters stay
        # on the (authenticated) gRPC transport
        self._np_enabled = _enabled() and tls is None
        self._np_port: Optional[int] = None
        self._np_uds: Optional[str] = None
        self._np_probed = False
        self._np_lock = threading.Lock()
        self._pool: list[_Conn] = []
        self._host = address.rsplit(":", 1)[0]

    # ------------------------------------------------------------ discovery
    def _native_port(self) -> Optional[int]:
        if not self._np_enabled:
            return None
        with self._np_lock:
            if self._np_probed:
                return self._np_port
            self._np_probed = True
            try:
                m, _ = self._call("GetDatapathInfo", {})
                self._np_port = m.get("port")
                self._np_uds = m.get("uds")
            except (StorageError, OSError):
                # older server without the verb, or unreachable: the
                # caller's normal gRPC path surfaces real errors
                self._np_port = None
                self._np_uds = None
            return self._np_port

    def _disable_native(self) -> None:
        with self._np_lock:
            self._np_port = None
            for c in self._pool:
                c.close()
            self._pool.clear()

    # ------------------------------------------------------------ transport
    def _checkout(self, port: int) -> _Conn:
        with self._np_lock:
            if self._pool:
                return self._pool.pop()
            uds = self._np_uds
        return _Conn(self._host, port, uds=uds)

    def _checkin(self, conn: _Conn) -> None:
        with self._np_lock:
            if len(self._pool) < _POOL_CAP and self._np_port is not None:
                self._pool.append(conn)
                return
        conn.close()

    def _io_fault(self, e: Exception) -> StorageError:
        """What a failed exchange on the native socket raises. Its IO
        timeout is the operation's remaining budget where that is the
        shorter (`_Conn.arm`): a timeout that the spent budget caused is
        DEADLINE_EXCEEDED, the budget's, and not UNAVAILABLE, which
        callers book against the peer (a failed key, an excluded node,
        a breaker)."""
        if isinstance(e, TimeoutError):
            resilience.check_deadline("native_io")
        return StorageError(
            "UNAVAILABLE", f"native datapath to {self.address}: {e}")

    def _check_partition(self, verb: str) -> None:
        """Same chaos vocabulary as RpcChannel: rules key on the gRPC
        address (and verb), so a blocked or slowed datanode behaves
        identically on BOTH transports."""
        from ozone_tpu.net import partition

        drop, d = partition.consult(self.address, verb, None)
        if drop:
            raise StorageError(
                "UNAVAILABLE",
                f"native datapath to {self.address}: injected partition")
        if d > 0:
            import time

            # injected chaos latency, not a retry sleep
            time.sleep(d)  # ozlint: allow[deadline-propagation] -- injected chaos latency must block like a real slow link (partition.py delay rule)

    def _status(self, conn: _Conn, body) -> None:
        # json.loads needs bytes; STATUS is tiny control-plane framing
        m = json.loads(bytes(body)) if len(body) else {}  # ozlint: allow[datapath-no-copy] -- control-plane STATUS JSON, not payload
        err = m.get("error")
        if err:
            raise StorageError(err.get("code", "IO_EXCEPTION"),
                               err.get("message", ""))

    # ------------------------------------------------------------ write path
    def write_chunks_commit(self, block_id, chunks, commit=None,
                            sync=False, writer=None):
        port = self._native_port()
        if port is None:
            return super().write_chunks_commit(
                block_id, chunks, commit=commit, sync=sync, writer=writer)
        self._check_partition("WriteChunksCommit")
        meta = {"op": "write", "block_id": block_id.to_json(),
                "sync": bool(sync), **self._btok(block_id)}
        if writer is not None:
            meta["writer"] = writer
        if commit is not None:
            meta["commit"] = commit.to_json()
        hdr = json.dumps(meta, separators=(",", ":")).encode()
        # validate every chunk length BEFORE any frame leaves: a
        # mid-stream local raise (after WHDR+CHUNK frames, no END) would
        # leave the connection's framing desynchronized — the server
        # still in its chunk loop — so it could never be pooled again
        views = []
        for info, data in chunks:
            view = _payload_view(data)
            if len(view) != info.length:
                raise StorageError(
                    "INVALID_WRITE_SIZE",
                    f"chunk {info.name}: data {len(view)} != "
                    f"declared {info.length}")
            views.append(view)
        try:
            conn = self._checkout(port)
        except OSError:
            # listener gone (older daemon restarted in place): fall back
            self._disable_native()
            return super().write_chunks_commit(
                block_id, chunks, commit=commit, sync=sync, writer=writer)
        completed = False  # STATUS received: framing is in lockstep
        try:
            conn.arm("WriteChunksCommit")
            # the WHOLE request — WHDR, every chunk header, every
            # payload view, END — leaves in one gathered sendmsg
            # (IOV_MAX-batched): zero payload copies and a handful of
            # syscalls per batch instead of two per chunk
            parts: list[bytes | memoryview] = [
                _FRAME.pack(len(hdr), _T_WHDR), hdr]
            payload_bytes = 0
            for (info, _data), view in zip(chunks, views):
                parts.append(_FRAME.pack(12 + info.length, _T_CHUNK)
                             + _CHUNK_HDR.pack(info.offset, info.length))
                if info.length:
                    parts.append(view)
                payload_bytes += info.length
            parts.append(_FRAME.pack(1, _T_END)
                         + (b"\x01" if sync else b"\x00"))
            _sendmsg_all(conn.sock, parts)
            hostmem.count_move(payload_bytes)
            tag, body = conn.recv_frame()
            if tag != _T_STATUS:
                raise ConnectionError(f"unexpected frame tag {tag:#x}")
            completed = True
            self._status(conn, body)
        except (OSError, ConnectionError) as e:
            conn.close()
            raise self._io_fault(e) from e
        except StorageError:
            if completed:
                # server-reported error after a full request/STATUS
                # exchange: the stream is in lockstep, safe to pool
                self._checkin(conn)
            else:
                # locally-raised mid-stream: framing state unknown —
                # pooling it would surface a spurious UNAVAILABLE on
                # the next checkout (same rule as the read path)
                conn.close()
            raise
        else:
            self._checkin(conn)

    def write_chunk(self, block_id, info, data, sync=False, writer=None):
        if self._native_port() is None:
            return super().write_chunk(block_id, info, data, sync=sync,
                                       writer=writer)
        from ozone_tpu.utils.upgrade import PRE_FINALIZE_ERROR

        try:
            return self.write_chunks_commit(
                block_id, [(info, data)], commit=None, sync=sync,
                writer=writer)
        except StorageError as e:
            if e.code == PRE_FINALIZE_ERROR:
                # native writes are the layout-gated batched verb; the
                # plain WriteChunk gRPC verb predates the gate
                return super().write_chunk(block_id, info, data,
                                           sync=sync, writer=writer)
            raise

    # ------------------------------------------------------------- read path
    def _read_request(self, block_id, infos, verify) -> list:
        """The frames of one ReadChunks request: RHDR, a RCHUNK a chunk
        (its CRCs with it where the daemon is to verify), END."""
        meta = {"op": "read", "block_id": block_id.to_json(),
                **self._btok(block_id)}
        frames: list[tuple[int, object]] = [
            (_T_RHDR, json.dumps(meta, separators=(",", ":")).encode())]
        for info in infos:
            frames.append((_T_RCHUNK, _rchunk_body(info, verify)))
        frames.append((_T_END, b""))
        return frames

    def read_chunks(self, block_id, infos, verify=False):
        port = self._native_port()
        if port is None or (verify and not _natively_verifiable(infos)):
            return super().read_chunks(block_id, infos, verify=verify)
        self._check_partition("ReadChunks")
        request = self._read_request(block_id, infos, verify)
        try:
            conn = self._checkout(port)
        except OSError:
            self._disable_native()
            return super().read_chunks(block_id, infos, verify=verify)
        # the whole response stream — DATA frames + trailing STATUS —
        # lands in ONE pooled slab lease; chunk arrays are zero-copy
        # views at their frame offsets (the lease is recycled when the
        # last array dies). Mid-stream errors release it immediately.
        payload_total = sum(int(i.length) for i in infos)
        lease = hostmem.pool().lease(
            payload_total + 5 * (len(infos) + 1) + 256)
        slab = lease.view
        state = {"filled": 0}

        def _fill(upto: int) -> None:
            filled = state["filled"]
            while filled < upto:
                r = conn.sock.recv_into(slab[filled:])
                if r == 0:
                    raise ConnectionError("native datapath peer closed")
                filled += r
            state["filled"] = filled

        def _status_body(pos: int, n: int):
            # STATUS bodies normally fit the slab margin; an outsized
            # error message spills into a transient buffer
            if pos + n <= len(slab):
                _fill(pos + n)
                return slab[pos:pos + n]
            have = state["filled"] - pos
            body = bytearray(n)
            body[:have] = slab[pos:state["filled"]]
            conn.recv_exact_into(memoryview(body)[have:])
            return body

        out = []
        try:
            conn.arm("ReadChunks")
            conn.send_frames(request)
            pos = 0
            for idx in range(len(infos) + 1):
                _fill(pos + 5)
                n, tag = _FRAME.unpack(slab[pos:pos + 5])
                pos += 5
                if n > _MAX_FRAME:
                    raise ConnectionError(f"oversized frame {n}")
                if tag == _T_STATUS:
                    self._status(conn, _status_body(pos, n))  # raises on err
                    if idx != len(infos):
                        raise ConnectionError("short native read stream")
                    break
                if idx == len(infos) or tag != _T_DATA:
                    raise ConnectionError(f"unexpected frame tag {tag:#x}")
                if n != infos[idx].length:
                    raise ConnectionError(
                        f"DATA frame {n}B != requested {infos[idx].length}B")
                _fill(pos + n)
                out.append(lease.array(length=n, offset=pos) if n
                           else np.empty(0, dtype=np.uint8))
                pos += n
            hostmem.count_move(payload_total)
        except (OSError, ConnectionError) as e:
            conn.close()
            out.clear()  # the traceback pins this frame: drop the views
            raise self._io_fault(e) from e
        except StorageError:
            # a mid-stream server error leaves this connection's framing
            # state unknown: don't pool it
            conn.close()
            out.clear()  # the traceback pins this frame: drop the views
            raise
        else:
            self._checkin(conn)
        finally:
            # drop the owner reference: outstanding chunk arrays keep
            # the buffer alive; on error it returns to the pool now
            lease.release()
        return out

    def read_chunk(self, block_id, info, verify=False):
        if self._native_port() is None or (
                verify and not _natively_verifiable([info])):
            return super().read_chunk(block_id, info, verify=verify)
        return self.read_chunks(block_id, [info], verify=verify)[0]

    def read_chunks_into(self, block_id, infos, rows, verify=False):
        """`read_chunks` with the answer RECEIVED where it is wanted:
        the response stream is scattered by the kernel over [frame
        head scratch, rows[0], frame head scratch, rows[1], ..., status
        scratch], so chunk i's payload goes from the socket to
        `rows[i][:infos[i].length]` and passes no slab, no view and no
        copy. A row (one writable C-contiguous uint8 array, at least
        its chunk long) is written from its start; what lies behind
        the chunk's length is left alone. Returns how many rows were
        received in place: all, or the gRPC fallback's none. A row of
        a read that raised may be half written."""
        port = self._native_port()
        if port is None or (verify and not _natively_verifiable(infos)):
            return super().read_chunks_into(block_id, infos, rows,
                                            verify=verify)
        self._check_partition("ReadChunks")
        request = self._read_request(block_id, infos, verify)
        n = len(infos)
        heads = memoryview(bytearray(5 * (n + 1)))
        bufs: list[memoryview] = []  # the response stream's layout
        head_at: list[int] = []  # index in `bufs` of each frame's head
        head_end: list[int] = []  # stream offset where that head ends
        at = 0
        for i, info in enumerate(infos):
            head_at.append(len(bufs))
            bufs.append(heads[5 * i:5 * i + 5])
            at += 5
            head_end.append(at)
            if info.length:
                bufs.append(_row_view(rows[i], info.length))
                at += info.length
        head_at.append(len(bufs))
        bufs.append(heads[5 * n:])
        head_end.append(at + 5)
        bufs.append(memoryview(bytearray(256)))  # the STATUS body, as a rule
        try:
            conn = self._checkout(port)
        except OSError:
            self._disable_native()
            return super().read_chunks_into(block_id, infos, rows,
                                            verify=verify)
        try:
            conn.arm("ReadChunks")
            conn.send_frames(request)
            todo = list(bufs)  # consumed from `nxt`, partial ones cut
            nxt = got = idx = 0
            while True:
                # every head that is whole by now is read before more is
                # asked of the socket: a STATUS where a DATA frame was
                # due (an error mid-stream) is the last thing the daemon
                # sends, and a receive that waited for the rest of the
                # layout would wait for its timeout
                if got < head_end[idx]:
                    r, nxt = _recvmsg_some(conn.sock, todo, nxt)
                    got += r
                    continue
                size, tag = _FRAME.unpack(heads[5 * idx:5 * idx + 5])
                if size > _MAX_FRAME:
                    raise ConnectionError(f"oversized frame {size}")
                if tag == _T_STATUS:
                    break
                if idx == n or tag != _T_DATA:
                    raise ConnectionError(f"unexpected frame tag {tag:#x}")
                if size != infos[idx].length:
                    raise ConnectionError(
                        f"DATA frame {size}B != requested "
                        f"{infos[idx].length}B")
                idx += 1
            # the STATUS body: what of it already came lies in the
            # layout behind its head (a row's start, after an error),
            # the rest is still to come
            have = got - head_end[idx]
            if have > size:
                raise ConnectionError("bytes after the STATUS frame")
            body = bytearray(size)
            filled = 0
            for b in bufs[head_at[idx] + 1:]:
                if filled == have:
                    break
                take = min(len(b), have - filled)
                body[filled:filled + take] = b[:take]
                filled += take
            conn.recv_exact_into(memoryview(body)[have:])
            self._status(conn, body)  # raises on err
            if idx != n:
                raise ConnectionError("short native read stream")
            hostmem.count_move(sum(int(i.length) for i in infos))
        except (OSError, ConnectionError) as e:
            conn.close()
            raise self._io_fault(e) from e
        except StorageError:
            # a mid-stream server error leaves this connection's framing
            # state unknown: don't pool it
            conn.close()
            raise
        else:
            self._checkin(conn)
        return n

    def close(self):
        with self._np_lock:
            for c in self._pool:
                c.close()
            self._pool.clear()
        super().close()


def _payload_view(data) -> memoryview:
    if isinstance(data, (bytes, bytearray, memoryview)):
        return memoryview(data).cast("B")
    arr = np.asarray(data)
    if arr.dtype != np.uint8 or not arr.flags.c_contiguous:
        # hidden full copy (non-contiguous or non-uint8 payload): count
        # it against the copy budget and warn once per call-site
        caller = sys._getframe(1)
        hostmem.count_copy(
            int(arr.nbytes),
            site=(f"{os.path.basename(caller.f_code.co_filename)}:"
                  f"{caller.f_lineno}"))
        arr = np.ascontiguousarray(arr, dtype=np.uint8)
    return memoryview(arr.reshape(-1))


def _row_view(row, length: int) -> memoryview:
    """The first `length` bytes of a receive row, as the socket is to
    write them; a row that is no writable C-contiguous uint8 array of
    that many bytes is the caller's fault, found before a frame
    leaves."""
    if not (isinstance(row, np.ndarray) and row.dtype == np.uint8
            and row.ndim == 1 and row.flags.c_contiguous
            and row.flags.writeable and row.size >= length):
        raise ValueError(
            f"receive row must be a writable contiguous uint8 array of "
            f"at least {length} bytes")
    return memoryview(row)[:length]


def _natively_verifiable(infos) -> bool:
    """The native side verifies CRC32C only; other checksum types fall
    back to the gRPC read path for verification parity."""
    from ozone_tpu.utils.checksum import ChecksumType

    return all(
        i.checksum.type in (ChecksumType.CRC32C, ChecksumType.NONE)
        or not i.checksum.checksums
        for i in infos)


def _rchunk_body(info, verify: bool) -> bytes:
    cks = info.checksum
    crcs: list[int] = []
    vtype = 0
    if verify and cks.checksums:
        from ozone_tpu.utils.checksum import ChecksumType

        if cks.type is ChecksumType.CRC32C:
            vtype = 1
            crcs = [int.from_bytes(c, "big") for c in cks.checksums]
    return _RCHUNK_HDR.pack(info.offset, info.length, vtype,
                            cks.bytes_per_checksum if vtype else 0,
                            len(crcs)) + struct.pack(f"<{len(crcs)}I", *crcs)
