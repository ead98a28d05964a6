"""Replicated (non-EC) key write/read path.

Capability analog of the reference's Ratis write path (KeyOutputStream ->
BlockOutputStream -> XceiverClientRatis): every chunk goes to all replicas
of the pipeline and a block commit follows the data
(BlockOutputStream.writeChunkToContainer:604 / executePutBlock:515). The
consensus property itself (leader ordering, watchForCommit quorum) is the
job of the replication service; this client writes all replicas directly —
the single-writer-per-block model makes that equivalent for object-store
semantics — and reads fall over between replicas like XceiverClientGrpc's
nearest-replica reads.
"""

from __future__ import annotations

import logging
import uuid
from typing import Callable, Optional

import numpy as np

from ozone_tpu.client.dn_client import (
    DatanodeClientFactory,
    batch_unsupported as _batch_unsupported,
)
from ozone_tpu.client.ec_writer import (
    BlockGroup,
    StripeWriteError,
    call_allocate,
    create_group_containers,
)
from ozone_tpu.storage.ids import BlockData, ChunkInfo, StorageError
from ozone_tpu.utils.checksum import Checksum, ChecksumType

log = logging.getLogger(__name__)


class ReplicatedKeyWriter:
    """Writes a key as replicated blocks: chunks fanned to every pipeline
    node, putBlock commit per block."""

    #: combine each member's chunk write and block commit into ONE
    #: WriteChunksCommit RPC (the reference's PutBlock piggybacking,
    #: BlockOutputStream.allowPutBlockPiggybacking). Subclasses that
    #: order commits through a different path (the Raft ring) disable it.
    _combined_commit = True

    def __init__(
        self,
        allocate_group: Callable[[list[str]], BlockGroup],
        clients: DatanodeClientFactory,
        block_size: int = 16 * 1024 * 1024,
        chunk_size: int = 4 * 1024 * 1024,
        checksum: ChecksumType = ChecksumType.CRC32C,
        bytes_per_checksum: int = 16 * 1024,
        max_retries: int = 3,
    ):
        self.allocate_group = allocate_group
        self.clients = clients
        self.block_size = block_size
        self.chunk_size = chunk_size
        self.checksum = Checksum(checksum, bytes_per_checksum)
        self.max_retries = max_retries
        self._groups: list[BlockGroup] = []
        self._group: Optional[BlockGroup] = None
        self._chunks: list[ChunkInfo] = []
        self._buf = np.zeros(chunk_size, dtype=np.uint8)
        self._buf_fill = 0
        self._excluded: list[str] = []
        #: containers seen CLOSED mid-write: the SCM may re-offer them
        #: until their report lands, so exclusion rides the allocation
        #: (reference ExcludeList container ids)
        self._excluded_containers: list[int] = []
        self._closed = False
        # datanode write-fence identity (Container.bind_writer): one per
        # logical key write, shared by the chunk fan-out and putBlock
        self._writer_id = uuid.uuid4().hex

    def write(self, data) -> None:
        if self._closed:
            raise ValueError("writer is closed")
        arr = np.asarray(
            np.frombuffer(data, dtype=np.uint8)
            if isinstance(data, (bytes, bytearray))
            else data,
            dtype=np.uint8,
        ).reshape(-1)
        pos = 0
        while pos < arr.size:
            take = min(self.chunk_size - self._buf_fill, arr.size - pos)
            self._buf[self._buf_fill : self._buf_fill + take] = arr[
                pos : pos + take
            ]
            self._buf_fill += take
            pos += take
            if self._buf_fill == self.chunk_size:
                self._flush_chunk()

    def _ensure_group(self) -> BlockGroup:
        if self._group is None:
            self._group = call_allocate(
                self.allocate_group, list(self._excluded),
                tuple(self._excluded_containers))
            self._chunks = []
            self._create_containers(self._group)
        return self._group

    def _create_containers(self, group: BlockGroup) -> None:
        """Open the block's container on every member (overridden by the
        Raft path to order the create through the pipeline leader). An
        unreachable member raises StripeWriteError so the chunk retry
        path excludes it instead of failing the whole write."""
        try:
            create_group_containers(self.clients, group,
                                    replica_indexed=False)
        except StripeWriteError:
            self._group = None  # retry must allocate without the failed
            raise

    def _commit_chunk(self, group: BlockGroup, info: ChunkInfo) -> None:
        """Commit point after the chunk bytes reached every member: plain
        fan-out putBlock here; the Raft path orders this via the leader."""
        bd = BlockData(group.block_id, [*self._chunks, info])
        for dn_id in group.pipeline.nodes:
            self.clients.get(dn_id).put_block(bd, writer=self._writer_id)

    def _flush_chunk(self) -> None:
        if self._buf_fill == 0:
            return
        data = self._buf[: self._buf_fill].copy()
        self._buf_fill = 0
        for attempt in range(self.max_retries + 1):
            try:
                group = self._ensure_group()
                if group.length + data.size > self.block_size * 1:
                    # rollover allocation rides the same handler: a
                    # create-time failure here must also exclude+retry
                    self._finalize_group()
                    group = self._ensure_group()
            except StripeWriteError as e:
                log.warning("group allocation failed on %s: %s",
                            e.failed_nodes, e.cause)
                self._excluded.extend(e.failed_nodes)
                if attempt == self.max_retries:
                    raise StorageError(
                        "IO_EXCEPTION", f"write failed: {e.cause}")
                continue
            info = ChunkInfo(
                name=f"{group.block_id}_chunk_{len(self._chunks)}",
                offset=group.length,
                length=int(data.size),
                checksum=self.checksum.compute(data),
            )
            ok, failed, closed, err = self._write_and_commit(
                group, info, data)
            if ok:
                self._chunks.append(info)
                group.length += data.size
                return
            log.warning("chunk write failed on %s: %s", failed or "commit",
                        err)
            self._excluded.extend(failed)
            self._finalize_group()
            if attempt == self.max_retries:
                raise StorageError("IO_EXCEPTION", f"write failed: {err}")

    def _write_and_commit(self, group: BlockGroup, info: ChunkInfo,
                          data) -> tuple:
        """Data fan-out + block commit for one chunk: ONE combined
        WriteChunksCommit RPC per member when every member serves the
        verb; the split write_chunk/commit phases otherwise (and for
        subclasses whose commit is ordered elsewhere). Returns
        (ok, failed_nodes, container_closed, error)."""
        if self._combined_commit:
            out = self._combined_write(group, info, data)
            if out is not None:
                return out
            # a member lacks the verb: downgrade for the rest of this
            # writer. Members that already took the combined call this
            # attempt simply see a same-writer chunk re-write + the same
            # putBlock again — both idempotent — on the split replay.
            self._combined_commit = False
        failed: list[str] = []
        closed = False
        err: Optional[Exception] = None
        for dn_id in group.pipeline.nodes:
            try:
                self.clients.get(dn_id).write_chunk(
                    group.block_id, info, data,
                    writer=self._writer_id)
            except StorageError as e:
                err = e
                if e.code == "INVALID_CONTAINER_STATE":
                    # container closed under us: healthy node,
                    # reallocate without blacklisting anyone — but
                    # never accept the same container again
                    closed = True
                    self._excluded_containers.append(
                        group.container_id)
                else:
                    failed.append(dn_id)
            except (KeyError, OSError) as e:
                failed.append(dn_id)
                err = e
        if not closed and self._data_phase_ok(group, failed):
            try:
                self._commit_chunk(group, info)
                return True, [], False, None
            except (StorageError, KeyError, OSError) as e:
                if isinstance(e, StorageError) \
                        and e.code == "INVALID_CONTAINER_STATE":
                    # closed or gone unhealthy under the commit: the
                    # retry must not be handed this container again
                    self._excluded_containers.append(group.container_id)
                return False, [], False, e  # commit failure: no node
        return False, failed, closed, err  # to exclude

    def _combined_write(self, group: BlockGroup, info: ChunkInfo,
                        data) -> Optional[tuple]:
        """Combined fan-out: chunk frame + piggybacked putBlock per
        member. None when any member lacks the verb (caller downgrades
        to the split phases). On a partial failure the members that
        already took the combined call committed a record including the
        unacked chunk — they roll back to the pre-chunk record (the
        split path never commits until every member has the data, and
        replicas must not disagree on committed length; same invariant
        as the EC run rollback)."""
        failed: list[str] = []
        ok_nodes: list[str] = []
        closed = False
        err: Optional[Exception] = None
        bd = BlockData(group.block_id, [*self._chunks, info])
        for dn_id in group.pipeline.nodes:
            try:
                client = self.clients.get(dn_id)
                fn = getattr(client, "write_chunks_commit", None)
                if fn is None:
                    # downgrade: members that already took the combined
                    # call committed a record including the unacked
                    # chunk — roll them back before the split replay, or
                    # a replay that then fails (node down, new group)
                    # leaves them durably committed above the finalized
                    # length (the inflated-survivor state the EC
                    # rollback tests forbid)
                    self._rollback_combined(group, ok_nodes)
                    return None
                fn(group.block_id, [(info, data)], commit=bd,
                   writer=self._writer_id)
                ok_nodes.append(dn_id)
            except StorageError as e:
                if _batch_unsupported(e):
                    self._rollback_combined(group, ok_nodes)
                    return None
                err = e
                if e.code == "INVALID_CONTAINER_STATE":
                    closed = True
                    self._excluded_containers.append(group.container_id)
                else:
                    failed.append(dn_id)
            except (KeyError, OSError) as e:
                failed.append(dn_id)
                err = e
        ok = not failed and not closed
        if not ok:
            self._rollback_combined(group, ok_nodes)
        return ok, failed, closed, err

    def _rollback_combined(self, group: BlockGroup,
                           ok_nodes: list[str]) -> None:
        """Best-effort return of combined-call members to the pre-chunk
        record, like the EC rollback; a member with no prior record
        keeps its orphan in a group that finalizes below it."""
        if not ok_nodes or not self._chunks:
            return
        prev = BlockData(group.block_id, list(self._chunks))
        for dn_id in ok_nodes:
            try:
                self.clients.get(dn_id).put_block(
                    prev, writer=self._writer_id)
            except (StorageError, KeyError, OSError) as e:
                log.warning("putBlock rollback failed on %s: %s",
                            dn_id, e)

    def _data_phase_ok(self, group: BlockGroup, failed: list[str]) -> bool:
        """Whether the chunk fan-out suffices to commit. Plain replication
        needs every member; the Raft path overrides to a quorum (a dead
        minority member misses the data, fails its apply when it returns,
        and is repaired by the replication manager)."""
        return not failed

    def _finalize_group(self) -> None:
        if self._group is not None and self._group.length > 0:
            self._groups.append(self._group)
        self._group = None
        self._chunks = []

    def hsync(self) -> list[BlockGroup]:
        """Flush buffered bytes to every replica and return the block
        groups covering all bytes written so far; the current block stays
        open for further writes (KeyOutputStream.hsync semantics — the
        durable prefix the OM can commit mid-write)."""
        if self._closed:
            raise ValueError("writer is closed")
        self._flush_chunk()
        groups = list(self._groups)
        if self._group is not None and self._group.length > 0:
            groups.append(self._group)
        return groups

    def close(self) -> list[BlockGroup]:
        if self._closed:
            return self._groups
        self._flush_chunk()
        self._finalize_group()
        self._closed = True
        return self._groups

    @property
    def bytes_written(self) -> int:
        done = sum(g.length for g in self._groups)
        cur = self._group.length if self._group else 0
        return done + cur + self._buf_fill


class ReplicatedKeyReader:
    """Reads replicated blocks with replica failover AND hedging: the
    nearest replica is read first; once it exceeds its P95 latency EWMA
    (or the OZONE_TPU_HEDGE_MS floor) the SAME read fires at the next
    replica — first result wins, the loser's bytes are discarded
    (client/resilience.py HedgeGroup; the reference's hedged-read
    posture over sortDatanodes order). Breaker-open replicas are moved
    to the back of the chain instead of being dialed first."""

    def __init__(self, group: BlockGroup, clients: DatanodeClientFactory,
                 verify: bool = True):
        self.group = group
        self.clients = clients
        if getattr(clients, "tokens", None) is not None:
            clients.tokens.put_group(group)  # READ tokens from the lookup
        self.verify = verify
        import os

        from ozone_tpu.client import resilience

        self._batch_reads = os.environ.get(
            "OZONE_TPU_BATCH_READS", "1") != "0"
        self._health = getattr(clients, "health", None) \
            or resilience.default_registry()

    def read_all(self) -> np.ndarray:
        return self.read(0, self.group.length)

    def read(self, offset: int, length: int) -> np.ndarray:
        """Chunk-granular range read with hedged replica failover: only
        the chunks overlapping [offset, offset+length) move over the
        wire (one batched ReadChunks round trip per replica when it
        serves the verb)."""
        from ozone_tpu.client import resilience

        if offset < 0 or length < 0 or \
                offset + length > self.group.length:
            raise ValueError("range out of bounds")
        if length == 0:
            return np.zeros(0, np.uint8)
        # topology-nearest replica first (XceiverClientGrpc reads via
        # sortDatanodes order in the reference); farther replicas remain
        # the hedge/failover chain. Breaker-refusing replicas drop to
        # the back (stable within each class).
        nodes = self.group.pipeline.nodes
        if getattr(self.clients, "nearest_first", None) is not None:
            nodes = self.clients.nearest_first(nodes)
        # non-claiming check: ordering must not consume half-open probes
        nodes = sorted(nodes, key=lambda dn: not self._health.usable(dn))

        def read_from(dn_id):
            return self._health.observe(
                dn_id, self._read_replica, dn_id, offset, length)

        try:
            win = resilience.HedgeGroup().run(
                lambda: read_from(nodes[0]),
                [(lambda dn: lambda: read_from(dn))(dn)
                 for dn in nodes[1:]],
                delay_s=self._health.hedge_delay_s(nodes[0]))
            return win.value
        except (StorageError, KeyError, OSError) as e:
            if isinstance(e, StorageError) \
                    and e.code == resilience.DEADLINE_EXCEEDED:
                # the operation budget expired, the replicas may be
                # fine: surface the fail-fast signal, never a
                # missing-block verdict
                raise
            raise StorageError("NO_SUCH_BLOCK",
                               f"all replicas failed: {e}")

    def _read_replica(self, dn_id: str, offset: int,
                      length: int) -> np.ndarray:
        """One replica's attempt at the whole range; raises on any
        shortfall so the hedge/failover chain moves on."""
        client = self.clients.get(dn_id)
        bd = client.get_block(self.group.block_id)
        wanted = [c for c in bd.chunks
                  if c.offset < offset + length
                  and c.offset + c.length > offset]
        fn = (getattr(client, "read_chunks", None)
              if len(wanted) > 1 and self._batch_reads
              else None)
        if fn is not None:
            try:
                parts = fn(self.group.block_id, wanted, self.verify)
            except StorageError as e:
                if not _batch_unsupported(e):
                    raise
                fn = None
        if fn is None:
            parts = [
                client.read_chunk(self.group.block_id, info, self.verify)
                for info in wanted
            ]
        out = np.zeros(length, dtype=np.uint8)
        covered = 0
        for info, data in zip(wanted, parts):
            a = max(offset, info.offset)
            b = min(offset + length, info.offset + len(data))
            if a < b:
                out[a - offset : b - offset] = \
                    data[a - info.offset : b - info.offset]
                covered += b - a
        if covered != length:
            # a stale/short replica (missing or truncated chunks) must
            # FAIL OVER, not read back zeros
            raise StorageError(
                "NO_SUCH_BLOCK",
                f"replica {dn_id} covers {covered}/{length} "
                f"bytes of [{offset},{offset + length})")
        return out
