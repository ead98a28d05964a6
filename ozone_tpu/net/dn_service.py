"""Datanode gRPC service + remote client.

The verb surface mirrors DatanodeClientProtocol.proto's Type enum (:82-110)
served the way XceiverServerGrpc -> HddsDispatcher does; the client is a
drop-in DatanodeClient (client/dn_client.py protocol), so the EC writer/
reader and reconstruction coordinator work unchanged across processes.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ozone_tpu import admission
from ozone_tpu.codec import hostmem
from ozone_tpu.net import wire
from ozone_tpu.net.rpc import RpcChannel, RpcServer
from ozone_tpu.storage.datanode import Datanode
from ozone_tpu.storage.ids import (
    BLOCK_TOKEN_VERIFICATION_FAILED,
    BlockData,
    BlockID,
    ChunkInfo,
    ContainerState,
    StorageError,
)

SERVICE = "ozone.tpu.DatanodeService"


class DatanodeGrpcService:
    """The HddsDispatcher boundary: every externally reachable verb is
    authorized here before it touches the container store. `verifier`
    (utils/security.BlockTokenVerifier, shared with the Ratis submit
    surface) enforces block tokens on block verbs and container tokens
    on container verbs, per HddsDispatcher.validateToken +
    BlockTokenVerifier.java semantics: mode, expiry, signature, and
    id match all checked; failure surfaces as
    BLOCK_TOKEN_VERIFICATION_FAILED without executing the verb."""

    def __init__(self, dn: Datanode, server: RpcServer, verifier=None,
                 layout=None, datapath_port=None):
        self.dn = dn
        self.verifier = verifier
        #: LayoutVersionManager of the hosting daemon — verbs introduced
        #: by a layout feature are refused until the datanode finalizes
        #: (the DN side of RequestFeatureValidator-style gating)
        self.layout = layout
        #: callable() -> native datapath port or None: clients discover
        #: the C++ hot-path listener through this verb and fall back to
        #: the gRPC verbs when absent (client/native_dn.py)
        self.datapath_port = datapath_port
        #: optional utils.throttle.Throttle pacing replication transfers
        #: served by this node (ReplicationSupervisor bandwidth limits
        #: analog); the hosting daemon installs it
        self.throttle = None
        server.add_service(
            SERVICE,
            {
                "GetDatapathInfo": self._datapath_info,
                "CreateContainer": self._create_container,
                "CloseContainer": self._close_container,
                "DeleteContainer": self._delete_container,
                "WriteChunk": self._write_chunk,
                "ReadChunk": self._read_chunk,
                "PutBlock": self._put_block,
                "GetBlock": self._get_block,
                "ListBlock": self._list_block,
                "GetCommittedBlockLength": self._committed_len,
                "DeleteBlock": self._delete_block,
                "Echo": lambda req: req,
            },
            stream_methods={
                "StreamWriteBlock": self._stream_write_block,
                "WriteChunksCommit": self._write_chunks_commit,
                "ImportContainer": self._import_container,
            },
            server_stream_methods={
                "ExportContainer": self._export_container,
                "ReadChunks": self._read_chunks,
            },
            # bounded request queue across ALL datapath verbs (unary,
            # streaming writes, streaming reads share one in-flight
            # bound — overload is overload regardless of verb shape).
            # Echo (liveness probes) and datapath discovery stay exempt.
            admission=admission.controller(
                "dn", exempt=frozenset({"Echo", "GetDatapathInfo"})),
        )

    # ------------------------------------------------------------ token gate
    def _require_block(self, m: dict, mode: str,
                       block_id: Optional[BlockID] = None) -> None:
        if self.verifier is None or not self.verifier.enabled:
            return
        from ozone_tpu.utils.security import AccessMode, TokenError

        if block_id is None:
            block_id = BlockID.from_json(m["block_id"])
        try:
            self.verifier.verify(m.get("token"), block_id, AccessMode(mode))
        except TokenError as e:
            raise StorageError(BLOCK_TOKEN_VERIFICATION_FAILED, str(e))

    def _require_container(self, m: dict, container_id: int) -> None:
        if self.verifier is None or not self.verifier.enabled:
            return
        from ozone_tpu.utils.security import TokenError

        try:
            self.verifier.verify_container(m.get("container_token"),
                                           int(container_id))
        except TokenError as e:
            raise StorageError(BLOCK_TOKEN_VERIFICATION_FAILED, str(e))

    def _require_streaming_layout(self, verb: str) -> None:
        """Layout gate shared by the streaming-write verbs (the DN side
        of RequestFeatureValidator gating)."""
        if self.layout is None:
            return
        from ozone_tpu.utils.upgrade import (
            PRE_FINALIZE_ERROR,
            RATIS_STREAMING_WRITE,
        )

        if not self.layout.is_allowed(RATIS_STREAMING_WRITE):
            raise StorageError(
                PRE_FINALIZE_ERROR,
                f"{verb} needs layout feature "
                f"{RATIS_STREAMING_WRITE.name} "
                f"(v{RATIS_STREAMING_WRITE.version}); datanode is at "
                f"layout {self.layout.metadata_version}")

    def _stream_write_block(self, frames) -> bytes:
        """Streaming block write (the Ratis DataStream / StreamInit path:
        KeyValueHandler.java:273, client BlockDataStreamOutput): frame 0 is
        the wire-packed header {block_id, chunk_size, sync, checksum_type,
        bytes_per_checksum}; every following frame is a raw payload slab.
        Chunks are cut server-side at chunk_size, written as they arrive
        (no per-chunk round trip), and one PutBlock commits the lot —
        the response is the committed BlockData."""
        from ozone_tpu.utils.checksum import Checksum, ChecksumType

        self._require_streaming_layout("StreamWriteBlock")
        it = iter(frames)
        header, _ = wire.unpack(next(it))
        block_id = BlockID.from_json(header["block_id"])
        self._require_block(header, "WRITE", block_id)
        chunk_size = int(header.get("chunk_size", 4 * 1024 * 1024))
        if chunk_size <= 0:
            raise StorageError("INVALID_ARGUMENT",
                               f"chunk_size must be positive: {chunk_size}")
        sync = bool(header.get("sync", False))
        cksum = Checksum(
            ChecksumType(header.get("checksum_type", "CRC32C")),
            int(header.get("bytes_per_checksum", 16 * 1024)),
        )

        chunks: list[ChunkInfo] = []
        offset = 0
        # zero-copy chunk cutting: incoming slabs are held as views and
        # sliced at chunk boundaries — a chunk served by ONE slab never
        # materializes (the common case: clients send chunk-aligned
        # slabs); only a boundary-straddling chunk joins its pieces
        # (one counted copy)
        pending: list[memoryview] = []
        pending_bytes = 0

        def cut(n: int) -> np.ndarray:
            nonlocal pending_bytes
            take: list[memoryview] = []
            need = n
            while need:
                v = pending[0]
                if len(v) <= need:
                    take.append(pending.pop(0))
                    need -= len(v)
                else:
                    take.append(v[:need])
                    pending[0] = v[need:]
                    need = 0
            pending_bytes -= n
            if len(take) == 1:
                return hostmem.as_array(take[0])
            hostmem.count_copy(n, site="dn_service._stream_write_block",
                               warn=False)
            return hostmem.as_array(b"".join(take))

        def flush(final: bool) -> None:
            nonlocal offset
            while pending_bytes >= chunk_size or (final and pending_bytes):
                part = cut(min(chunk_size, pending_bytes))
                info = ChunkInfo(
                    name=f"{block_id}_chunk_{len(chunks)}",
                    offset=offset,
                    length=int(part.size),
                    checksum=cksum.compute(part),
                )
                self.dn.write_chunk(
                    block_id, info, part, sync=sync,
                    writer=header.get("writer"))
                chunks.append(info)
                offset += int(part.size)

        for frame in it:
            if len(frame):
                pending.append(memoryview(frame).cast("B"))
                pending_bytes += len(frame)
            flush(final=False)
        flush(final=True)
        bd = BlockData(block_id, chunks)
        self.dn.put_block(bd, sync=sync, writer=header.get("writer"))
        return wire.pack({"block": bd.to_json()})

    def _write_chunks_commit(self, frames) -> bytes:
        """Batched chunk writes with a piggybacked block commit in ONE
        client-streaming RPC (the reference's PutBlock piggybacking —
        BlockOutputStream.allowPutBlockPiggybacking:151,228-234 /
        KeyValueHandler.java:899 — generalized to any number of chunks
        per message): frame 0 is the wire-packed header {block_id,
        writer?, sync?, token?, commit?: BlockData json}; every following
        frame is wire.pack({chunk: ChunkInfo json}, payload). Unlike
        StreamWriteBlock the CLIENT computes checksums and chunk
        boundaries (the EC writer's device-CRC'd cells land untouched);
        the commit applies only after every chunk landed, so a failure
        anywhere aborts the stream before the block record moves."""
        self._require_streaming_layout("WriteChunksCommit")
        it = iter(frames)
        header, _ = wire.unpack(next(it))
        block_id = BlockID.from_json(header["block_id"])
        self._require_block(header, "WRITE", block_id)
        sync = bool(header.get("sync", False))
        writer = header.get("writer")
        self.dn.metrics.counter("batched_write_streams").inc()
        n_chunks = 0
        for frame in it:
            m, payload = wire.unpack(frame)
            self.dn.write_chunk(
                block_id,
                ChunkInfo.from_json(m["chunk"]),
                wire.payload_array(payload),
                sync=sync,
                writer=writer,
            )
            n_chunks += 1
        self.dn.metrics.counter("batched_write_chunks").inc(n_chunks)
        commit = header.get("commit")
        if commit is not None:
            bd = BlockData.from_json(commit)
            if bd.block_id != block_id:
                raise StorageError(
                    "INVALID_ARGUMENT",
                    f"commit names {bd.block_id}, stream wrote {block_id}")
            self.dn.put_block(bd, sync=sync, writer=writer)
        return wire.pack({})

    def _datapath_info(self, req: bytes) -> bytes:
        # providers may return a bare port (older wiring) or a dict
        # carrying the co-located unix-socket lane as well
        # (DatapathSidecar.advertise)
        v = self.datapath_port() if self.datapath_port else None
        if isinstance(v, dict):
            return wire.pack(v)
        return wire.pack({"port": v})

    def _create_container(self, req: bytes) -> bytes:
        m, _ = wire.unpack(req)
        self._require_container(m, m["container_id"])
        self.dn.create_container(
            m["container_id"],
            m.get("replica_index", 0),
            ContainerState(m.get("state", "OPEN")),
        )
        return wire.pack({})

    def _close_container(self, req: bytes) -> bytes:
        m, _ = wire.unpack(req)
        self._require_container(m, m["container_id"])
        self.dn.close_container(m["container_id"])
        return wire.pack({})

    def _delete_container(self, req: bytes) -> bytes:
        m, _ = wire.unpack(req)
        self._require_container(m, m["container_id"])
        self.dn.delete_container(m["container_id"], m.get("force", False))
        return wire.pack({})

    def _write_chunk(self, req: bytes) -> bytes:
        m, payload = wire.unpack(req)
        self._require_block(m, "WRITE")
        self.dn.write_chunk(
            BlockID.from_json(m["block_id"]),
            ChunkInfo.from_json(m["chunk"]),
            wire.payload_array(payload),
            sync=m.get("sync", False),
            writer=m.get("writer"),
        )
        return wire.pack({})

    def _export_container(self, req: bytes):
        """Packed container tarball streamed in frames (the reference's
        GrpcReplicationService download stream: replication/
        GrpcReplicationService.java:51): framing keeps each gRPC message
        bounded. Compression negotiates per transfer from the client's
        `accept` list (CopyContainerCompression analog; legacy clients
        send only the gzip bool). The daemon's replication throttle, if
        configured, paces the frames. Note: the tarball currently
        materializes in memory at both ends, so practical container
        size is bounded by RAM; the state guard and failure cleanup
        live in container_packer, shared with the in-process client."""
        from ozone_tpu.storage.container_packer import (
            export_container,
            negotiate_codec,
        )

        m, _ = wire.unpack(req)
        self._require_container(m, m["container_id"])
        c = self.dn.get_container(int(m["container_id"]))
        if "accept" in m:
            codec = negotiate_codec(m["accept"])
        else:
            codec = "gzip" if m.get("compress", True) else "none"
        data = export_container(c, compression=codec)
        frame = 4 * 1024 * 1024
        yield wire.pack({"container_id": c.id, "size": len(data),
                         "compression": codec})
        for off in range(0, len(data), frame):
            if self.throttle is not None:
                self.throttle.take(min(frame, len(data) - off))
            yield data[off:off + frame]

    def _import_container(self, frames) -> bytes:
        """Unpack a client-streamed container tarball onto this datanode
        (the DownloadAndImportReplicator import half / operator
        restore): frame 0 carries the metadata, the rest the tarball.
        Failure cleanup (remove only a container THIS import created)
        lives in container_packer."""
        from ozone_tpu.storage.container_packer import import_container

        it = iter(frames)
        m, _ = wire.unpack(next(it))
        # authorization names a container id; the packer enforces the
        # tarball actually IS that container before any bytes land
        expect_id = m.get("container_id")
        self._require_container(m, expect_id if expect_id is not None else -1)
        # join accepts the frames (bytes) directly: one assembly copy,
        # no per-frame bytes() materialization
        data = b"".join(it)
        c = import_container(self.dn, data,
                             replica_index=m.get("replica_index"),
                             expect_id=expect_id)
        return wire.pack({"container_id": c.id})

    def _read_chunk(self, req: bytes) -> bytes:
        m, _ = wire.unpack(req)
        self._require_block(m, "READ")
        data = self.dn.read_chunk(
            BlockID.from_json(m["block_id"]),
            ChunkInfo.from_json(m["chunk"]),
            verify=m.get("verify", False),
        )
        return wire.pack({}, data)

    def _read_chunks(self, req: bytes):
        """Server-streamed batch read: one request naming any number of
        chunks of a block, one payload frame back per chunk in request
        order (the read-side twin of WriteChunksCommit — the transport
        round trip is paid once per batch, not per chunk). Purely a
        protocol addition: clients fall back to per-chunk ReadChunk
        against servers without it, so no layout gate is needed."""
        m, _ = wire.unpack(req)
        block_id = BlockID.from_json(m["block_id"])
        self._require_block(m, "READ", block_id)
        verify = m.get("verify", False)
        self.dn.metrics.counter("batched_read_streams").inc()
        self.dn.metrics.counter("batched_read_chunks").inc(
            len(m["chunks"]))
        for ch in m["chunks"]:
            data = self.dn.read_chunk(
                block_id, ChunkInfo.from_json(ch), verify=verify)
            yield wire.pack({}, data)

    def _put_block(self, req: bytes) -> bytes:
        m, _ = wire.unpack(req)
        bd = BlockData.from_json(m["block"])
        self._require_block(m, "WRITE", bd.block_id)
        self.dn.put_block(bd, sync=m.get("sync", False),
                          writer=m.get("writer"))
        return wire.pack({})

    def _get_block(self, req: bytes) -> bytes:
        m, _ = wire.unpack(req)
        self._require_block(m, "READ")
        bd = self.dn.get_block(BlockID.from_json(m["block_id"]))
        return wire.pack({"block": bd.to_json()})

    def _list_block(self, req: bytes) -> bytes:
        m, _ = wire.unpack(req)
        self._require_container(m, m["container_id"])
        blocks = self.dn.list_blocks(m["container_id"])
        return wire.pack({"blocks": [b.to_json() for b in blocks]})

    def _committed_len(self, req: bytes) -> bytes:
        m, _ = wire.unpack(req)
        self._require_block(m, "READ")
        n = self.dn.get_committed_block_length(BlockID.from_json(m["block_id"]))
        return wire.pack({"length": n})

    def _delete_block(self, req: bytes) -> bytes:
        m, _ = wire.unpack(req)
        self._require_block(m, "WRITE")
        self.dn.delete_block(BlockID.from_json(m["block_id"]))
        return wire.pack({})


class GrpcDatanodeClient:
    """Remote DatanodeClient over gRPC (ECXceiverClientGrpc analog).

    `tokens` (client/dn_client.TokenStore, shared across the factory's
    clients) supplies the block/container capability tokens attached to
    each request the way the reference's request builders carry
    encodedToken; absent tokens simply aren't attached (insecure
    clusters ignore them)."""

    #: per-verb default RPC timeouts, all capped by the ambient
    #: operation deadline (client/resilience.op_timeout): a caller with
    #: 2 s of budget left issues 2 s RPCs, not 30 s ones
    _UNARY_TIMEOUT_S = 30.0
    _STREAM_TIMEOUT_S = 120.0
    _BULK_STREAM_TIMEOUT_S = 300.0

    def __init__(self, dn_id: str, address: str, tokens=None, tls=None):
        self.dn_id = dn_id
        self.tokens = tokens
        self._ch = RpcChannel(address, tls=tls)

    @staticmethod
    def _timeout(default: float, verb: str) -> float:
        from ozone_tpu.client.resilience import op_timeout

        return op_timeout(default, verb)

    def _call(self, method: str, meta: dict,
              payload: Optional[np.ndarray] = None) -> tuple[dict, memoryview]:
        resp = self._ch.call(
            SERVICE, method, wire.pack(meta, payload),
            timeout=self._timeout(self._UNARY_TIMEOUT_S, method))
        return wire.unpack(resp)

    def _btok(self, block_id: BlockID) -> dict:
        if self.tokens is None:
            return {}
        tok = self.tokens.block_token(block_id)
        return {"token": tok} if tok is not None else {}

    def _ctok(self, container_id: int) -> dict:
        if self.tokens is None:
            return {}
        tok = self.tokens.container_token(container_id)
        return {"container_token": tok} if tok is not None else {}

    def create_container(self, container_id, replica_index=0,
                         state=ContainerState.OPEN):
        self._call(
            "CreateContainer",
            {
                "container_id": container_id,
                "replica_index": replica_index,
                "state": state.value,
                **self._ctok(container_id),
            },
        )

    def close_container(self, container_id):
        self._call("CloseContainer", {"container_id": container_id,
                                      **self._ctok(container_id)})

    def delete_container(self, container_id, force=False):
        self._call("DeleteContainer", {"container_id": container_id,
                                       "force": force,
                                       **self._ctok(container_id)})

    def write_chunk(self, block_id, info, data, sync=False,
                    writer=None):
        arr = hostmem.as_array(data)
        m = {
            "block_id": block_id.to_json(),
            "chunk": info.to_json(),
            "sync": sync,
            **self._btok(block_id),
        }
        if writer is not None:
            m["writer"] = writer
        self._call("WriteChunk", m, arr)

    def read_chunk(self, block_id, info, verify=False):
        _, payload = self._call(
            "ReadChunk",
            {
                "block_id": block_id.to_json(),
                "chunk": info.to_json(),
                "verify": verify,
                **self._btok(block_id),
            },
        )
        # zero-copy view over the response buffer (read-only; every
        # consumer copies into its own destination or only reads)
        return wire.payload_array(payload)

    def read_chunks(self, block_id, infos, verify=False):
        """Batch read: one server-streamed RPC returns every chunk in
        `infos` (request order). The read-side twin of
        write_chunks_commit."""
        frames = self._ch.call_server_stream(
            SERVICE, "ReadChunks",
            wire.pack({
                "block_id": block_id.to_json(),
                "chunks": [i.to_json() for i in infos],
                "verify": verify,
                **self._btok(block_id),
            }),
            timeout=self._timeout(self._BULK_STREAM_TIMEOUT_S,
                                  "ReadChunks"),
        )
        out = []
        for f in frames:
            _, payload = wire.unpack(f)
            out.append(wire.payload_array(payload))
        if len(out) != len(infos):
            raise StorageError(
                "IO_EXCEPTION",
                f"ReadChunks returned {len(out)}/{len(infos)} frames")
        return out

    def read_chunks_into(self, block_id, infos, rows, verify=False):
        """`read_chunks` with chunk i's bytes written to
        `rows[i][:infos[i].length]` (one writable uint8 row a chunk;
        what lies behind the chunk's length is left alone). Returns how
        many rows the transport received IN PLACE: none here, where
        every frame is copied out of gRPC's buffer; the native datapath
        receives into the rows themselves."""
        for row, data in zip(rows, self.read_chunks(block_id, infos,
                                                    verify=verify)):
            row[:data.size] = data
        return 0

    def put_block(self, block, sync=False, writer=None):
        m = {"block": block.to_json(), "sync": sync,
             **self._btok(block.block_id)}
        if writer is not None:
            m["writer"] = writer
        self._call("PutBlock", m)

    def get_block(self, block_id):
        m, _ = self._call("GetBlock", {"block_id": block_id.to_json(),
                                       **self._btok(block_id)})
        return BlockData.from_json(m["block"])

    def list_blocks(self, container_id):
        m, _ = self._call("ListBlock", {"container_id": container_id,
                                        **self._ctok(container_id)})
        return [BlockData.from_json(b) for b in m["blocks"]]

    def export_container(self, container_id: int,
                         compress: bool = True) -> bytes:
        """Download the packed container tarball, streamed in frames
        (replication-download / operator-backup path). Offers this
        interpreter's full codec matrix; the server picks
        (CopyContainerCompression negotiation) and import sniffs the
        frame magic, so the name never needs plumbing."""
        from ozone_tpu.storage.container_packer import available_codecs

        accept = (list(available_codecs()) if compress
                  else ["none"])
        frames = self._ch.call_server_stream(
            SERVICE, "ExportContainer",
            wire.pack({"container_id": container_id,
                       "compress": compress,
                       "accept": accept,
                       **self._ctok(container_id)}),
            timeout=self._timeout(self._BULK_STREAM_TIMEOUT_S,
                                  "ExportContainer"),
        )
        head = next(iter_frames := iter(frames))
        wire.unpack(head)  # header: {container_id, size, compression}
        # one assembly copy; frames join without per-frame bytes()
        return b"".join(iter_frames)

    def import_container(self, data: bytes,
                         replica_index=None,
                         container_id=None) -> int:
        """Upload + unpack a container tarball, streamed in frames.
        `container_id` (the id the caller believes the tarball holds)
        scopes the authorization on secure clusters; the server rejects
        a tarball whose descriptor names a different container."""
        frame = 4 * 1024 * 1024
        meta = {"replica_index": replica_index}
        if container_id is not None:
            meta.update(container_id=int(container_id),
                        **self._ctok(container_id))

        def gen():
            yield wire.pack(meta)
            for off in range(0, len(data), frame):
                yield data[off:off + frame]

        try:
            out = self._ch.call_streaming(
                SERVICE, "ImportContainer", gen(),
                timeout=self._timeout(self._BULK_STREAM_TIMEOUT_S,
                                      "ImportContainer"))
        except StorageError as e:
            from ozone_tpu.storage.container_packer import (
                UNSUPPORTED_COMPRESSION,
                compress_blob,
                sniff_decompress,
            )

            if e.code != UNSUPPORTED_COMPRESSION:
                raise
            # the peer lacks this tarball's codec: recompress with the
            # wire-default gzip (every node serves it) and retry once
            data = compress_blob("gzip", sniff_decompress(data))

            def gen2():
                yield wire.pack(meta)
                for off in range(0, len(data), frame):
                    yield data[off:off + frame]

            out = self._ch.call_streaming(
                SERVICE, "ImportContainer", gen2(),
                timeout=self._timeout(self._BULK_STREAM_TIMEOUT_S,
                                      "ImportContainer"))
        m, _ = wire.unpack(out)
        return int(m["container_id"])

    def get_committed_block_length(self, block_id):
        m, _ = self._call(
            "GetCommittedBlockLength", {"block_id": block_id.to_json(),
                                        **self._btok(block_id)}
        )
        return m["length"]

    def delete_block(self, block_id):
        self._call("DeleteBlock", {"block_id": block_id.to_json(),
                                   **self._btok(block_id)})

    def stream_write_block(self, block_id, data_frames, chunk_size=4 * 1024 * 1024,
                           sync=False, checksum_type="CRC32C",
                           bytes_per_checksum=16 * 1024):
        """Streaming write of a whole block: `data_frames` yields bytes
        slabs of any size; returns the committed BlockData. The
        BlockDataStreamOutput analog — one ack for the entire block."""

        def frames():
            yield wire.pack({
                "block_id": block_id.to_json(),
                "chunk_size": chunk_size,
                "sync": sync,
                "checksum_type": checksum_type,
                "bytes_per_checksum": bytes_per_checksum,
                **self._btok(block_id),
            })
            # grpc's cython layer only transports immutable bytes, and
            # it copies each frame into a C slice BEFORE pulling the
            # next one — so already-bytes slabs pass through untouched
            # (the old unconditional bytes(f) re-copied every frame)
            # and mutable slabs (bytearray/ndarray/memoryview) are
            # materialized exactly once, counted against the budget.
            # The pooled-lease variant of this relay lives on the
            # native lane (client/native_dn.py read/write paths).
            for f in data_frames:
                if isinstance(f, bytes):
                    yield f
                    continue
                hostmem.count_copy(len(memoryview(f).cast("B")),
                                   site="dn_service.stream_write_block",
                                   warn=False)
                yield bytes(f)  # ozlint: allow[datapath-no-copy] -- the single counted materialization grpc requires

        resp = self._ch.call_streaming(
            SERVICE, "StreamWriteBlock", frames(),
            timeout=self._timeout(self._STREAM_TIMEOUT_S,
                                  "StreamWriteBlock"))
        m, _ = wire.unpack(resp)
        return BlockData.from_json(m["block"])

    def write_chunks_commit(self, block_id, chunks, commit=None,
                            sync=False, writer=None):
        """Write `chunks` ([(ChunkInfo, payload array)]) and optionally
        commit `commit` (a BlockData) in ONE round trip: the PutBlock-
        piggybacking analog, batched. One ack covers the whole batch —
        the transport-dominant per-chunk round trip collapses to one
        per batch."""
        meta = {"block_id": block_id.to_json(), "sync": sync,
                **self._btok(block_id)}
        if writer is not None:
            meta["writer"] = writer
        if commit is not None:
            meta["commit"] = commit.to_json()

        def frames():
            yield wire.pack(meta)
            for info, data in chunks:
                yield wire.pack({"chunk": info.to_json()},
                                hostmem.as_array(data))

        self._ch.call_streaming(
            SERVICE, "WriteChunksCommit", frames(),
            timeout=self._timeout(self._STREAM_TIMEOUT_S,
                                  "WriteChunksCommit"))

    def echo(self, data: bytes = b"ping") -> bytes:
        return self._ch.call(SERVICE, "Echo", data)

    def close(self):
        self._ch.close()
