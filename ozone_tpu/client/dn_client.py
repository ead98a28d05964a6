"""Datanode client abstraction.

Role analog of the reference's XceiverClient family (hadoop-hdds/client
XceiverClientGrpc / ECXceiverClientGrpc.java:49 — one connection per
replica-index datanode for EC). The transport is pluggable: in-process
(tests, single-node), and gRPC (multi-process clusters). All clients expose
the DatanodeClientProtocol verb surface of storage/datanode.py.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Optional, Protocol

import numpy as np

from ozone_tpu.storage.datanode import Datanode
from ozone_tpu.storage.ids import BlockData, BlockID, ChunkInfo, ContainerState


def batch_unsupported(e: Exception) -> bool:
    """True when `e` means the peer cannot serve the batched
    WriteChunksCommit/ReadChunks verbs (pre-finalize layout, or a server
    or duck-typed client without them): callers downgrade to per-chunk
    verbs — the reference's allDataNodesSupportPiggybacking downgrade
    (BlockOutputStream.java:228-234)."""
    from ozone_tpu.storage.ids import StorageError
    from ozone_tpu.utils.upgrade import PRE_FINALIZE_ERROR

    return isinstance(e, StorageError) and (
        e.code == PRE_FINALIZE_ERROR
        or (e.code == "IO_EXCEPTION" and "UNIMPLEMENTED" in e.msg))


def write_unit_batched(client, block_id: "BlockID", pairs,
                       commit: "BlockData",
                       writer: Optional[str] = None) -> None:
    """Land one unit's chunks + block commit: a single WriteChunksCommit
    stream when the peer serves it (one transport round trip for the
    whole unit), per-chunk verbs otherwise. Shared by the reconstruction
    coordinator and the re-encode flow; the key writers keep their own
    downgrade state machines."""
    from ozone_tpu.storage.ids import StorageError

    fn = getattr(client, "write_chunks_commit", None)
    if fn is not None:
        try:
            fn(block_id, pairs, commit=commit, writer=writer)
            return
        except StorageError as e:
            if not batch_unsupported(e):
                raise
    for info, data in pairs:
        client.write_chunk(block_id, info, data, writer=writer)
    client.put_block(commit, writer=writer)


def write_unit_stream(client, block_id: "BlockID", pairs,
                      writer: Optional[str] = None) -> None:
    """Land one BATCH of a unit's chunks with no commit: the streaming
    half of write_unit_batched used by the pipelined reconstruction and
    re-encode flows — batch N's chunks go out while batch N+1 decodes on
    device, and the single put_block commit follows once every batch has
    landed (same all-chunks-before-commit order). Unlike the one-shot
    write_unit_batched this is called once per stripe window, so the
    downgrade is remembered on the client — one failed probe per peer,
    not one per window."""
    from ozone_tpu.storage.ids import StorageError

    fn = getattr(client, "write_chunks_commit", None)
    if fn is not None and not getattr(client, "_stream_downgraded", False):
        try:
            fn(block_id, pairs, commit=None, writer=writer)
            return
        except StorageError as e:
            if not batch_unsupported(e):
                raise
            client._stream_downgraded = True
    for info, data in pairs:
        client.write_chunk(block_id, info, data, writer=writer)


def build_chunk_pairs(block_id: "BlockID", stripes, cells, crcs,
                      unit_len: int, cell: int, bpc: int, checksum,
                      host_checksum) -> list[tuple["ChunkInfo", object]]:
    """(ChunkInfo, data) pairs for one unit's cells of the given stripe
    indexes — cells [len(stripes), cell], crcs [len(stripes), S] device
    CRCs (size 0 to force host checksums). Full cells reuse the
    device-computed CRCs so repaired data is never re-checksummed on
    host; the tail chunk (or a non-dividing bpc) falls back to the host
    checksummer. Shared by the pipelined reconstruction and re-encode
    emit loops so the CRC-eligibility rule and chunk naming cannot
    diverge between the two repair paths."""
    from ozone_tpu.utils.checksum import ChecksumData

    pairs: list[tuple[ChunkInfo, object]] = []
    for bi, s in enumerate(stripes):
        chunk_len = max(0, min(cell, unit_len - s * cell))
        if chunk_len == 0:
            continue
        data = cells[bi, :chunk_len]
        if chunk_len == cell and cell % bpc == 0 and crcs.size:
            cs = ChecksumData(checksum, bpc, tuple(
                int(v).to_bytes(4, "big") for v in crcs[bi].tolist()))
        else:
            cs = host_checksum.compute(data)
        pairs.append((ChunkInfo(
            name=f"{block_id}_chunk_{s}",
            offset=s * cell,
            length=chunk_len,
            checksum=cs,
        ), data))
    return pairs


class TokenStore:
    """Client-side cache of OM/SCM-granted block and container tokens.

    The reference threads an encodedToken through every Xceiver request
    builder; here the store is shared by every client the factory hands
    out, and GrpcDatanodeClient consults it per call. Writers/readers
    register the tokens that arrived with each BlockGroup (put_group).
    `issuer` is the datanode-side fallback: a DN that holds the cluster
    secret keys self-signs tokens for reconstruction/replication traffic
    (ec/reconstruction/TokenHelper.java analog).
    """

    _CAP = 8192  # bounded: tokens expire in minutes anyway

    def __init__(self, issuer=None):
        self.issuer = issuer
        self._blocks: OrderedDict[BlockID, dict] = OrderedDict()
        self._containers: OrderedDict[int, dict] = OrderedDict()
        self._lock = threading.Lock()

    def put_block_token(self, block_id: BlockID, token: dict) -> None:
        with self._lock:
            self._blocks[block_id] = token
            self._blocks.move_to_end(block_id)
            while len(self._blocks) > self._CAP:
                self._blocks.popitem(last=False)

    def put_container_token(self, container_id: int, token: dict) -> None:
        with self._lock:
            self._containers[int(container_id)] = token
            self._containers.move_to_end(int(container_id))
            while len(self._containers) > self._CAP:
                self._containers.popitem(last=False)

    def put_group(self, group) -> None:
        """Register the tokens riding on a BlockGroup (if any)."""
        tok = getattr(group, "token", None)
        if tok is not None:
            self.put_block_token(group.block_id, tok)
        ctok = getattr(group, "container_token", None)
        if ctok is not None:
            self.put_container_token(group.container_id, ctok)

    #: seconds of remaining validity below which a cached token is
    #: treated as missing (re-issued via the issuer where one exists) —
    #: a token must not expire mid-flight
    _EXPIRY_MARGIN = 15.0

    def _fresh(self, tok: Optional[dict]) -> Optional[dict]:
        import time

        if tok is not None and \
                tok.get("expiry", 0) < time.time() + self._EXPIRY_MARGIN:
            return None
        return tok

    def block_token(self, block_id: BlockID) -> Optional[dict]:
        with self._lock:
            tok = self._fresh(self._blocks.get(block_id))
        if tok is None and self.issuer is not None:
            from ozone_tpu.utils.security import AccessMode

            tok = self.issuer.issue(
                block_id, [AccessMode.READ, AccessMode.WRITE], owner="dn")
            if tok is not None:
                self.put_block_token(block_id, tok)
        return tok

    def container_token(self, container_id: int) -> Optional[dict]:
        with self._lock:
            tok = self._fresh(self._containers.get(int(container_id)))
        if tok is None and self.issuer is not None:
            tok = self.issuer.issue_container(container_id, owner="dn")
            if tok is not None:
                self.put_container_token(container_id, tok)
        return tok


class DatanodeClient(Protocol):
    dn_id: str

    def create_container(self, container_id: int, replica_index: int = 0,
                         state: ContainerState = ContainerState.OPEN) -> None: ...
    def close_container(self, container_id: int) -> None: ...
    def delete_container(self, container_id: int, force: bool = False) -> None: ...
    def write_chunk(self, block_id: BlockID, info: ChunkInfo, data,
                    sync: bool = False,
                    writer: Optional[str] = None) -> None: ...
    def read_chunk(self, block_id: BlockID, info: ChunkInfo,
                   verify: bool = False) -> np.ndarray: ...
    def read_chunks(self, block_id: BlockID, infos,
                    verify: bool = False) -> list[np.ndarray]: ...
    def put_block(self, block: BlockData, sync: bool = False,
                  writer: Optional[str] = None) -> None: ...
    def write_chunks_commit(self, block_id: BlockID, chunks,
                            commit: Optional[BlockData] = None,
                            sync: bool = False,
                            writer: Optional[str] = None) -> None: ...
    def get_block(self, block_id: BlockID) -> BlockData: ...
    def list_blocks(self, container_id: int) -> list[BlockData]: ...
    def get_committed_block_length(self, block_id: BlockID) -> int: ...
    def delete_block(self, block_id: BlockID) -> None: ...
    def export_container(self, container_id: int,
                         compress: bool = True) -> bytes: ...
    def import_container(self, data: bytes,
                         replica_index=None,
                         container_id=None) -> int: ...


class LocalDatanodeClient:
    """In-process client wrapping a Datanode instance directly."""

    def __init__(self, dn: Datanode):
        self.dn = dn
        self.dn_id = dn.id

    def create_container(self, container_id, replica_index=0,
                         state=ContainerState.OPEN):
        self.dn.create_container(container_id, replica_index, state)

    def close_container(self, container_id):
        self.dn.close_container(container_id)

    def export_container(self, container_id, compress=True):
        # state guard lives in the packer, shared with the gRPC path
        from ozone_tpu.storage.container_packer import export_container

        return export_container(self.dn.get_container(container_id),
                                compress=compress)

    def import_container(self, data, replica_index=None, container_id=None):
        # failure cleanup lives in the packer, shared with the gRPC path
        from ozone_tpu.storage.container_packer import import_container

        return import_container(self.dn, data,
                                replica_index=replica_index,
                                expect_id=container_id).id

    def delete_container(self, container_id, force=False):
        self.dn.delete_container(container_id, force)

    def write_chunk(self, block_id, info, data, sync=False, writer=None):
        self.dn.write_chunk(block_id, info, data, sync, writer=writer)

    def read_chunk(self, block_id, info, verify=False):
        return self.dn.read_chunk(block_id, info, verify)

    def read_chunks(self, block_id, infos, verify=False):
        # instance verb per chunk so test subclasses injecting read
        # faults cover the batched path too
        return [self.read_chunk(block_id, i, verify) for i in infos]

    def read_chunks_into(self, block_id, infos, rows, verify=False):
        """In-process twin of the transports' verb: chunk i's bytes to
        `rows[i][:infos[i].length]`; returns the rows received in place
        (none: each is copied out of the store's answer). Through the
        instance verb, as `read_chunks` goes through `read_chunk`."""
        for row, data in zip(rows, self.read_chunks(block_id, infos,
                                                    verify)):
            row[:data.size] = data
        return 0

    def put_block(self, block, sync=False, writer=None):
        self.dn.put_block(block, sync, writer=writer)

    def write_chunks_commit(self, block_id, chunks, commit=None,
                            sync=False, writer=None):
        """In-process twin of the batched stream verb: same write-then-
        commit order and all-chunks-before-commit semantics, no
        transport to save. Routes through the instance verbs so test
        subclasses injecting chunk/commit faults cover this path too."""
        for info, data in chunks:
            self.write_chunk(block_id, info, data, sync, writer=writer)
        if commit is not None:
            self.put_block(commit, sync, writer=writer)

    def get_block(self, block_id):
        return self.dn.get_block(block_id)

    def list_blocks(self, container_id):
        return self.dn.list_blocks(container_id)

    def get_committed_block_length(self, block_id):
        return self.dn.get_committed_block_length(block_id)

    def delete_block(self, block_id):
        self.dn.delete_block(block_id)


class DatanodeClientFactory:
    """dn_id -> client resolver (XceiverClientManager pool analog).

    Resolves in-process datanodes first, then remote addresses registered
    via register_remote (gRPC, lazily connected)."""

    def __init__(self):
        self._local: dict[str, DatanodeClient] = {}
        self._addresses: dict[str, str] = {}
        self._remote: dict[str, DatanodeClient] = {}
        #: shared by every remote client this factory creates; writers/
        #: readers register OM-granted tokens here, datanode daemons
        #: install a self-issuer for reconstruction traffic
        self.tokens = TokenStore()
        #: per-datanode health (EWMA latency + circuit breaker), shared
        #: by every reader/writer built over this factory so one
        #: client's observed straggler steers every other client's
        #: survivor choice and reallocation (client/resilience.py)
        from ozone_tpu.client.resilience import HealthRegistry

        self.health = HealthRegistry()
        #: TlsMaterial presented by every remote client (mTLS clusters);
        #: None = plaintext channels
        self.tls = None
        #: network topology view: dn_id -> location path ("/dc/rack"),
        #: learned from the SCM address book; plus this client's own
        #: position for nearest-first replica ordering
        #: (NetworkTopologyImpl sortDatanodes analog)
        self.locations: dict[str, str] = {}
        self.location: Optional[str] = None
        self.node_id: Optional[str] = None
        #: clients retired by a cert rotation, closed at factory close
        self._retired: list[DatanodeClient] = []
        self._tls_ver = None
        # maybe_get runs concurrently from writer/reader worker threads
        # (one per unit stream): the rotation check + cache insert must
        # be atomic or a stale-cert client can be cached past a rotation
        self._remote_lock = threading.Lock()

    def learn_locations(self, locations: dict[str, str]) -> None:
        if locations:
            self.locations.update(locations)

    def nearest_first(self, nodes) -> list[str]:
        """Order datanodes nearest-first from this client's position;
        no topology knowledge = input order unchanged."""
        if not self.locations or (
                self.location is None and self.node_id is None):
            return list(nodes)
        from ozone_tpu.scm.topology import sort_by_distance

        return sort_by_distance(self.location, nodes, self.locations,
                                reader_node=self.node_id)

    def register_local(self, dn: Datanode) -> LocalDatanodeClient:
        c = LocalDatanodeClient(dn)
        self._local[dn.id] = c
        return c

    def register_remote(self, dn_id: str, address: str) -> None:
        self._addresses[dn_id] = address
        self._remote.pop(dn_id, None)  # reconnect on next use

    def update_remote(self, dn_id: str, address: str) -> None:
        """Refresh a remote address if it changed (daemon restarts bind
        new ports; stale channels must be dropped, locals left alone)."""
        if dn_id in self._local:
            return
        if self._addresses.get(dn_id) != address:
            self.register_remote(dn_id, address)

    def get(self, dn_id: str) -> DatanodeClient:
        c = self.maybe_get(dn_id)
        if c is None:
            raise KeyError(f"no client for datanode {dn_id}")
        return c

    def known_ids(self) -> list[str]:
        return sorted(set(self._local) | set(self._addresses))

    def remote_address(self, dn_id: str) -> Optional[str]:
        """Registered RpcServer address of a remote datanode (the ratis
        client factory resolves peers off this same address book)."""
        return self._addresses.get(dn_id)

    def maybe_get(self, dn_id: str) -> Optional[DatanodeClient]:
        c = self._local.get(dn_id)
        if c is not None:
            return c
        with self._remote_lock:
            # cert rotation (RotatingTls.version bump): drop cached
            # remote clients so reconnects present the renewed identity,
            # not a retired cert the peer may no longer trust. Parked,
            # not closed: an in-flight repair RPC may still be on one
            # (closed at factory close()).
            ver = getattr(self.tls, "version", None)
            if ver != getattr(self, "_tls_ver", None):
                self._tls_ver = ver
                self._retired.extend(self._remote.values())
                self._remote.clear()
            c = self._remote.get(dn_id)
            if c is not None:
                return c
            addr = self._addresses.get(dn_id)
            if addr is not None:
                # native-datapath-aware client: hot verbs ride the C++
                # listener when the server advertises one, gRPC
                # otherwise (and always for the control plane)
                from ozone_tpu.client.native_dn import NativeDatanodeClient

                c = NativeDatanodeClient(dn_id, addr, tokens=self.tokens,
                                         tls=self.tls)
                self._remote[dn_id] = c
                return c
        return None

    def close(self) -> None:
        clients = list(self._remote.values()) + self._retired
        self._remote.clear()
        self._retired = []
        for c in clients:
            try:
                c.close()
            except Exception:  # ozlint: allow[error-swallowing] -- best-effort pool teardown; a close failure has no recovery action
                pass
