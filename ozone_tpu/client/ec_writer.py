"""EC key write pipeline: cell accumulation -> batched device encode ->
striped chunk writes -> per-stripe commit with rollback.

Semantics mirror the reference's ECKeyOutputStream (hadoop-ozone/client
io/ECKeyOutputStream.java): 1 MiB cells round-robin striped over d data
blocks (handleWrite:339-360), short final cells zero-padded for parity
(padBufferToLimit:561) but written at true length, parity cells always
full, per-stripe commit via putBlock on all d+p streams carrying the
block-group length (commitStripeWrite:207-244, ECBlockOutputStream
putBlock with blockGroupLen :103-195), and on failure: finalize the group
at the last acked stripe, exclude the failed nodes/pipeline, allocate a
fresh block group and replay the failed stripe there
(rollbackAndReset:166, excludePipelineAndFailedDN:246).

TPU-first divergence: the reference encodes one stripe at a time per
client thread; here complete stripes accumulate in a queue and are encoded
(+ CRC'd) in ONE fused device dispatch per batch (vmap over the stripe
axis), with per-chunk checksums coming back from the same pass.

Transport (round 4): each encoded run of stripes bound for one group
travels as ONE WriteChunksCommit stream per unit — all the run's chunk
frames plus the piggybacked putBlock (the PutBlock-piggybacking analog,
BlockOutputStream.allowPutBlockPiggybacking generalized to N chunks) —
so the round trip is paid once per run, not twice per stripe. Ack
watermark and rollback are then run-granular; members that refuse the
verb downgrade the writer to the per-stripe path mid-write.
"""

from __future__ import annotations

import logging
import uuid
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np

from ozone_tpu.client import resilience
from ozone_tpu.client.dn_client import (
    DatanodeClientFactory,
    batch_unsupported,
)
from ozone_tpu.codec import hostmem
from ozone_tpu.codec import service as codec_service
from ozone_tpu.codec.api import CoderOptions
from ozone_tpu.codec.fused import FusedSpec, effective_bpc, make_fused_encoder
from ozone_tpu.parallel import dispatch
from ozone_tpu.scm.pipeline import Pipeline
from ozone_tpu.storage.ids import (
    BlockData,
    BlockID,
    ChunkInfo,
    ContainerState,
    StorageError,
)
from ozone_tpu.utils.checksum import Checksum, ChecksumData, ChecksumType
from ozone_tpu.utils.metrics import registry
from ozone_tpu.utils.tracing import Tracer

log = logging.getLogger(__name__)

#: the client's per-operation registry (`client/ozone_client.py`): a
#: key that ends in a partial stripe books it (`partial_stripes`) and the
#: zero data cells submitted to fill it (`pad_cells`)
OPS = registry("client.ops")
OPS.counter("partial_stripes")
OPS.counter("pad_cells")


@dataclass
class BlockGroup:
    """One logical EC block: the same (container_id, local_id) replicated
    over the pipeline's d+p nodes with per-node replica indexes."""

    container_id: int
    local_id: int
    pipeline: Pipeline
    length: int = 0  # committed user bytes in this group
    #: short-lived capability tokens riding with the allocation/lookup
    #: (AllocatedBlock's token in the reference, ScmBlockLocationProtocol;
    #: never persisted — the OM strips them at commit and re-mints fresh
    #: READ tokens at lookup)
    token: Optional[dict] = None
    container_token: Optional[dict] = None

    @property
    def block_id(self) -> BlockID:
        return BlockID(self.container_id, self.local_id)

    def to_json(self, with_tokens: bool = False) -> dict:
        out = {
            "container_id": self.container_id,
            "local_id": self.local_id,
            "length": self.length,
            "nodes": self.pipeline.nodes,
            "replication": str(self.pipeline.replication),
            # the pipeline's cluster-wide identity must survive the wire:
            # the datanode raft group is named by it (storage/ratis.py
            # group_id), so a client-side re-numbered Pipeline would
            # address a nonexistent group
            "pipeline_id": self.pipeline.id,
        }
        if with_tokens:
            if self.token is not None:
                out["token"] = self.token
            if self.container_token is not None:
                out["container_token"] = self.container_token
        return out

    @classmethod
    def from_json(cls, g: dict) -> "BlockGroup":
        from ozone_tpu.scm.pipeline import ReplicationConfig

        kw = {}
        if g.get("pipeline_id") is not None:
            kw["id"] = int(g["pipeline_id"])
        return cls(
            container_id=g["container_id"],
            local_id=g["local_id"],
            pipeline=Pipeline(
                ReplicationConfig.parse(g["replication"]),
                list(g["nodes"]), **kw,
            ),
            length=g.get("length", 0),
            token=g.get("token"),
            container_token=g.get("container_token"),
        )


class StripeWriteError(Exception):
    def __init__(self, failed_nodes: list[str], cause: Exception):
        super().__init__(f"stripe write failed on {failed_nodes}: {cause}")
        self.failed_nodes = failed_nodes
        self.cause = cause


class _StreamUnsupported(Exception):
    """A pipeline member refused WriteChunksCommit (pre-finalize layout
    or a server without the verb): the writer falls back to per-stripe
    RPCs, the reference's allDataNodesSupportPiggybacking downgrade
    (BlockOutputStream.java:228-234)."""


#: shared downgrade classifier (dn_client.batch_unsupported)
_batch_unsupported = batch_unsupported


def call_allocate(allocate_group, excluded, excluded_containers):
    """Invoke an allocation callback, passing the excluded-container list
    only when the callback accepts it (legacy single-arg callbacks keep
    working; the OM/SCM chain gets the reference ExcludeList semantics)."""
    import inspect

    try:
        two_arg = len(inspect.signature(allocate_group).parameters) >= 2
    except (ValueError, TypeError):  # builtins/partials w/o signature
        two_arg = False
    if two_arg:
        return allocate_group(excluded, excluded_containers)
    return allocate_group(excluded)


def create_group_containers(clients, group: "BlockGroup",
                            replica_indexed: bool) -> None:
    """Create the group's container on every pipeline member, collecting
    unreachable members into one StripeWriteError so writer retry paths
    exclude them and reallocate (shared by the EC and replicated
    writers; a dead member must not kill the whole write). Outcomes
    feed the shared peer-health registry: an unreachable member here
    trips its breaker just like a failed chunk write."""
    tokens = getattr(clients, "tokens", None)
    if tokens is not None:
        tokens.put_group(group)  # capability tokens rode the allocation
    health = getattr(clients, "health", None)
    failed: list[str] = []
    cause: Optional[Exception] = None
    for i, dn_id in enumerate(group.pipeline.nodes):
        try:
            client = clients.get(dn_id)
            if replica_indexed:
                client.create_container(group.container_id,
                                        replica_index=i + 1)
            else:
                client.create_container(group.container_id)
        except StorageError as e:
            if e.code != "CONTAINER_EXISTS":
                failed.append(dn_id)
                cause = e
                if health is not None and resilience.is_transport_fault(e):
                    health.failure(dn_id)
        except (KeyError, OSError) as e:
            failed.append(dn_id)
            cause = e
            if health is not None:
                health.failure(dn_id)
    if failed:
        raise StripeWriteError(failed, cause)


def cell_lengths(group_length: int, stripe: int, k: int, cell: int) -> list[int]:
    """User-data length of each of the k data cells of stripe `stripe`."""
    start = stripe * k * cell
    out = []
    for i in range(k):
        o = start + i * cell
        out.append(max(0, min(cell, group_length - o)))
    return out


def block_lengths(group_length: int, k: int, cell: int) -> list[int]:
    """User-data length of each of the k data blocks of a group."""
    full, rem = divmod(group_length, k * cell)
    out = []
    for i in range(k):
        extra = min(cell, max(0, rem - i * cell))
        out.append(full * cell + extra)
    return out


@dataclass
class _Stripe:
    data: np.ndarray  # [k, C] zero-padded
    lengths: list[int]  # true user-data length per cell
    index: int = -1  # stripe index within its group, assigned at write time


class ECKeyWriter:
    """Writes one key's byte stream as EC block groups.

    allocate_group(excluded_nodes) -> BlockGroup is the OM/SCM allocation
    callback; committed groups (with final lengths) are returned by
    close() for the key-commit step.
    """

    def __init__(
        self,
        options: CoderOptions,
        allocate_group: Callable[[list[str]], BlockGroup],
        clients: DatanodeClientFactory,
        block_size: int = 16 * 1024 * 1024,
        checksum: ChecksumType = ChecksumType.CRC32C,
        bytes_per_checksum: int = 16 * 1024,
        stripe_batch: int = 8,
        max_retries: int = 3,
        batched_rpc: Optional[bool] = None,
        qos_class: str = "interactive",
    ):
        self.opts = options
        self.k, self.p, self.cell = (
            options.data_units,
            options.parity_units,
            options.cell_size,
        )
        if block_size % self.cell:
            raise ValueError("block_size must be a multiple of cell_size")
        self.block_size = block_size
        self.stripes_per_group = block_size // self.cell
        self.allocate_group = allocate_group
        self.clients = clients
        self.checksum_type = checksum
        self.bpc = effective_bpc(self.cell, bytes_per_checksum)
        self.stripe_batch = stripe_batch
        self.max_retries = max_retries
        self._spec = FusedSpec(options, checksum, self.bpc)
        self._fused = make_fused_encoder(self._spec)
        self._host_checksum = Checksum(checksum, self.bpc)
        #: QoS class for the shared codec service, which is resolved
        #: per flush (like the reader) so a writer never holds a stale
        #: handle across a service restart
        self._qos = qos_class

        self._groups: list[BlockGroup] = []
        self._group: Optional[BlockGroup] = None
        self._group_chunks: list[list[ChunkInfo]] = []  # per unit
        # datanode write-fence identity (one per logical key write):
        # every unit stream of this writer carries it, so a duplicate
        # (container, local_id) from another key can never interleave
        # with ours on the datanode (Container.bind_writer)
        self._writer_id = uuid.uuid4().hex
        # batched WriteChunksCommit streams (one RPC per unit per run)
        # unless disabled; flips off permanently when a member refuses
        # the verb (mixed-version cluster)
        if batched_rpc is None:
            import os

            batched_rpc = os.environ.get(
                "OZONE_TPU_BATCH_WRITES", "1") != "0"
        self._stream_writes = batched_rpc
        self._containers_created = False
        self._excluded: list[str] = []
        self._excluded_containers: list[int] = []
        #: shared per-peer health: write outcomes feed the same EWMA +
        #: breaker the readers consult, and reallocation skips
        #: breaker-open peers up front (no retry attempt burned)
        self._health = getattr(clients, "health", None) \
            or resilience.default_registry()
        #: operation deadline, re-activated on RPC-pool worker threads
        self._deadline: Optional[resilience.Deadline] = resilience.current()

        self._buf = np.zeros((self.k, self.cell), dtype=np.uint8)
        self._cell_idx = 0
        self._cell_off = 0
        self._queue: list[_Stripe] = []
        self._stripe_in_group = 0
        self._closed = False
        # one worker per unit stream: the k+p chunk RPCs of a stripe
        # (and the putBlock barrier) go out concurrently — gRPC releases
        # the GIL, so the stripe wall-time is the slowest node, not the
        # sum (the reference's per-stream async BlockOutputStreams)
        self._rpc_pool: Optional[ThreadPoolExecutor] = None
        # encode pipeline: the batch in flight, (stripes, future of its
        # parity and crcs); network writes of batch N overlap the
        # device encode + device->host pull of batch N+1
        self._pending: Optional[tuple] = None

    # ------------------------------------------------------------------ write
    def write(self, data) -> None:
        if self._closed:
            raise ValueError("writer is closed")
        d = resilience.current()
        if d is not None:
            self._deadline = d  # freshest ambient budget wins
        arr = hostmem.as_array(data)
        pos = 0
        while pos < arr.size:
            take = min(self.cell - self._cell_off, arr.size - pos)
            self._buf[self._cell_idx, self._cell_off : self._cell_off + take] = (
                arr[pos : pos + take]
            )
            self._cell_off += take
            pos += take
            if self._cell_off == self.cell:
                self._cell_off = 0
                self._cell_idx += 1
                if self._cell_idx == self.k:
                    self._enqueue_full_stripe()

    def _enqueue_full_stripe(self) -> None:
        self._queue.append(_Stripe(self._buf, [self.cell] * self.k))
        self._buf = np.zeros((self.k, self.cell), dtype=np.uint8)
        self._cell_idx = 0
        if len(self._queue) >= self.stripe_batch:
            self._flush_queue()

    # ------------------------------------------------------------------ flush
    def _flush_queue(self) -> None:
        """Encode all queued stripes in one device dispatch; the batch
        goes in flight (device encode + device->host pull run async) and
        the PREVIOUS in-flight batch's network writes happen now — a
        two-stage pipeline that overlaps accelerator work with the RPC
        fan-out (the role of the reference's async stream executors)."""
        if not self._queue:
            return
        stripes, self._queue = self._queue, []
        batch = np.stack([s.data for s in stripes])  # [B, k, C]
        # a partial batch (the tail of a small PUT) is marked tail so it
        # rides the linger path: it waits up to OZONE_TPU_CODEC_LINGER_MS
        # to share its dispatch with OTHER operations' stripes instead of
        # paying a full batch slot alone (counted in tail_flushes)
        fut = dispatch.submit(
            codec_service.encode_key(self._spec), self._fused, batch,
            width=self.stripe_batch, qos=self._qos,
            tail=len(stripes) < self.stripe_batch,
            deadline=self._deadline)
        prev, self._pending = self._pending, (stripes, fut)
        if prev is not None:
            self._write_pending(prev)

    def _write_pending(self, prev: tuple) -> None:
        stripes, fut = prev
        self._write_batch(stripes, *codec_service.wait_result(fut))

    def _drain_pending(self) -> None:
        prev, self._pending = self._pending, None
        if prev is not None:
            self._write_pending(prev)

    def _write_batch(self, stripes, parity_dev, crcs_dev) -> None:
        """Write one encoded batch. The batched-RPC path writes each run
        of stripes bound for one group as ONE WriteChunksCommit stream
        per unit — all the run's chunk frames plus the piggybacked
        putBlock, so the transport round trip is paid once per run
        instead of twice per stripe (the round trip dominates). Ack
        watermark and rollback move to run granularity, still finer
        than the reference's block-granular streaming mode. Falls back
        to the per-stripe path (commit order defines the ack watermark,
        as in flushStripeFromQueue:526) when a member lacks the verb."""
        pad = sum(n == 0 for s in stripes for n in s.lengths)
        with Tracer.instance().span("ec:flush", stripes=len(stripes),
                                    pad_cells=pad):
            self._write_batch_traced(stripes, parity_dev, crcs_dev)

    def _write_batch_traced(self, stripes, parity_dev, crcs_dev) -> None:
        parity = np.asarray(parity_dev)
        crcs = np.asarray(crcs_dev)  # [B, k+p, S] uint32

        b = 0
        while b < len(stripes):
            if not self._stream_writes:
                stripe = stripes[b]
                for attempt in range(self.max_retries + 1):
                    try:
                        self._write_stripe(stripe, parity[b], crcs[b])
                        break
                    except StripeWriteError as e:
                        log.warning(
                            "stripe %d failed (attempt %d): %s",
                            stripe.index,
                            attempt,
                            e,
                        )
                        if attempt == self.max_retries:
                            raise
                        self._excluded.extend(e.failed_nodes)
                        # finalize the group at its committed length; the
                        # failed stripe replays into a fresh group
                        self._finalize_group()
                b += 1
                continue
            # batched path: the longest run fitting the current group
            if self._group is not None and \
                    self._stripe_in_group >= self.stripes_per_group:
                self._finalize_group()
            for attempt in range(self.max_retries + 1):
                try:
                    self._ensure_group()
                    n = min(len(stripes) - b,
                            self.stripes_per_group - self._stripe_in_group)
                    self._write_stripe_run(
                        stripes[b:b + n], parity[b:b + n], crcs[b:b + n])
                    b += n
                    break
                except _StreamUnsupported:
                    # mixed-version member: the run rolled back cleanly;
                    # replay it per-stripe from here on
                    self._stream_writes = False
                    break
                except StripeWriteError as e:
                    log.warning("stripe run at %d failed (attempt %d): %s",
                                b, attempt, e)
                    if attempt == self.max_retries:
                        raise
                    self._excluded.extend(e.failed_nodes)
                    self._finalize_group()

    def _write_stripe_run(self, run, parity, crcs) -> None:
        """Write `run` (stripes fitting the current group) as ONE
        WriteChunksCommit stream per unit: every stripe's cell as a
        chunk frame, the run's final putBlock piggybacked. On failure,
        survivors (whose streams committed the run-end record) roll
        back to the pre-run record — the same no-unacked-bytes
        invariant as the per-stripe path — and the run replays into a
        fresh group."""
        group = self._group
        for j, s in enumerate(run):
            s.index = self._stripe_in_group + j
        pre_chunks = [list(c) for c in self._group_chunks]
        pre_len = group.length
        len_after = pre_len + sum(sum(s.lengths) for s in run)

        unit_chunks: list[list[tuple[ChunkInfo, np.ndarray]]] = [
            [] for _ in range(self.k + self.p)]
        for j, stripe in enumerate(run):
            for u in range(self.k + self.p):
                is_data = u < self.k
                length = stripe.lengths[u] if is_data else self.cell
                if length == 0:
                    continue
                cell_data = (stripe.data[u] if is_data
                             else parity[j][u - self.k])
                info = ChunkInfo(
                    name=f"{group.block_id}_chunk_{stripe.index}",
                    offset=stripe.index * self.cell,
                    length=length,
                    checksum=self._chunk_checksum(
                        crcs[j][u], length, cell_data),
                )
                unit_chunks[u].append((info, cell_data[:length]))

        def write_unit(u: int):
            new = unit_chunks[u]
            if not new and not pre_chunks[u]:
                return u, None  # nothing written, nothing to re-commit
            bd = BlockData(
                group.block_id,
                pre_chunks[u] + [info for info, _ in new],
                block_group_length=len_after,
            )
            dn_id = group.pipeline.nodes[u]
            try:
                client = self.clients.get(dn_id)
                if new:
                    fn = getattr(client, "write_chunks_commit", None)
                    if fn is None:  # duck-typed client without the verb
                        return u, StorageError(
                            "IO_EXCEPTION",
                            "UNIMPLEMENTED: client lacks write_chunks_commit")
                    self._observed(dn_id, fn, group.block_id, new,
                                   commit=bd, writer=self._writer_id)
                else:
                    # zero new bytes on this unit (short final stripes):
                    # just advance its committed group length
                    self._observed(dn_id, client.put_block, bd,
                                   writer=self._writer_id)
                return u, None
            except (StorageError, KeyError, OSError) as e:
                if isinstance(e, StorageError) \
                        and e.code == resilience.DEADLINE_EXCEEDED:
                    raise  # op budget spent: abort, don't exclude peers
                return u, e

        failed: list[str] = []
        closed = unsupported = False
        cause: Optional[Exception] = None
        ok_units: list[int] = []
        for u, err in self._ensure_pool().map(self._act(write_unit),
                                              range(self.k + self.p)):
            if err is None:
                ok_units.append(u)
            elif _batch_unsupported(err):
                unsupported = True
                cause = err
            elif isinstance(err, StorageError) \
                    and err.code == "INVALID_CONTAINER_STATE":
                # container closed under us: reallocation signal, not a
                # node fault (same classification as the per-stripe path)
                closed = True
                cause = err
                self._excluded_containers.append(group.container_id)
            else:
                failed.append(group.pipeline.nodes[u])
                cause = err
        if not failed and not closed and not unsupported:
            for u in range(self.k + self.p):
                self._group_chunks[u] = pre_chunks[u] + [
                    info for info, _ in unit_chunks[u]]
            group.length = len_after
            self._stripe_in_group += len(run)
            return

        # units whose stream succeeded committed len_after: roll them
        # back to the pre-run record (best-effort, like the per-stripe
        # rollback — a unit with no prior record stays orphaned in a
        # group that finalizes below its data, exactly as there)
        def roll(entry):
            dn_id, bd = entry
            try:
                self.clients.get(dn_id).put_block(bd, writer=self._writer_id)
                return None
            except (StorageError, KeyError, OSError) as e:
                return dn_id, e

        rollbacks = [
            (group.pipeline.nodes[u],
             BlockData(group.block_id, pre_chunks[u],
                       block_group_length=pre_len))
            for u in ok_units if pre_chunks[u]
        ]
        for res in self._ensure_pool().map(self._act(roll), rollbacks):
            if res is not None:
                log.warning("putBlock rollback failed on %s: %s",
                            res[0], res[1])
        if unsupported:
            raise _StreamUnsupported()
        raise StripeWriteError(failed, cause)

    def _chunk_checksum(
        self, device_crcs: np.ndarray, length: int, cell_data: np.ndarray
    ) -> ChecksumData:
        """ChecksumData for one written chunk. Full cells use the device
        CRCs; partial cells fall back to host computation."""
        if self.checksum_type is ChecksumType.NONE:
            return ChecksumData(self.checksum_type, self.bpc)
        if length == self.cell and self.cell % self.bpc == 0:
            sums = tuple(
                int(v).to_bytes(4, "big") for v in device_crcs.tolist()
            )
            return ChecksumData(self.checksum_type, self.bpc, sums)
        return self._host_checksum.compute(cell_data[:length])

    def _write_stripe(
        self, stripe: _Stripe, parity: np.ndarray, crcs: np.ndarray
    ) -> None:
        # group capacity check happens at write time: rollovers renumber
        # stripes, so indexes are assigned here, not at enqueue
        if self._group is not None and self._stripe_in_group >= self.stripes_per_group:
            self._finalize_group()
        group = self._ensure_group()
        stripe.index = self._stripe_in_group
        offset = stripe.index * self.cell
        failed: list[str] = []
        closed = False
        cause: Optional[Exception] = None
        new_chunks: list[Optional[ChunkInfo]] = [None] * (self.k + self.p)

        def write_unit(u: int):
            is_data = u < self.k
            length = stripe.lengths[u] if is_data else self.cell
            if length == 0:
                return u, None, None
            cell_data = stripe.data[u] if is_data else parity[u - self.k]
            info = ChunkInfo(
                name=f"{group.block_id}_chunk_{stripe.index}",
                offset=offset,
                length=length,
                checksum=self._chunk_checksum(crcs[u], length, cell_data),
            )
            dn_id = group.pipeline.nodes[u]
            try:
                self._observed(
                    dn_id, self.clients.get(dn_id).write_chunk,
                    group.block_id, info, cell_data[:length],
                    writer=self._writer_id,
                )
                return u, info, None
            except (StorageError, KeyError, OSError) as e:
                if isinstance(e, StorageError) \
                        and e.code == resilience.DEADLINE_EXCEEDED:
                    raise  # op budget spent: abort, don't exclude peers
                return u, None, e

        # all k+p unit streams in parallel: gRPC releases the GIL, so
        # the stripe costs the slowest node's RPC, not the sum of nine
        for u, info, err in self._ensure_pool().map(
                self._act(write_unit), range(self.k + self.p)):
            if info is not None:
                new_chunks[u] = info
            elif err is not None:
                cause = err
                if isinstance(err, StorageError) \
                        and err.code == "INVALID_CONTAINER_STATE":
                    # container closed under us (filled concurrently /
                    # SCM finalize): the node is healthy — reallocate a
                    # fresh group, never blacklist the whole pipeline;
                    # the closed container itself is excluded so a stale
                    # SCM pool can't hand it straight back
                    closed = True
                    self._excluded_containers.append(group.container_id)
                else:
                    failed.append(group.pipeline.nodes[u])
        if failed or closed:
            raise StripeWriteError(failed, cause)

        # stripe barrier: putBlock on every participating stream —
        # issued concurrently; the barrier is completion of ALL
        stripe_bytes = sum(stripe.lengths)
        group_len_after = group.length + stripe_bytes
        puts: list[tuple[str, BlockData]] = []
        for u in range(self.k + self.p):
            if new_chunks[u] is not None:
                self._group_chunks[u].append(new_chunks[u])
            if not self._group_chunks[u]:
                continue
            puts.append((
                group.pipeline.nodes[u],
                BlockData(
                    group.block_id,
                    list(self._group_chunks[u]),
                    block_group_length=group_len_after,
                ),
            ))

        def put_unit(entry):
            dn_id, bd = entry
            try:
                self._observed(dn_id, self.clients.get(dn_id).put_block,
                               bd, writer=self._writer_id)
                return None
            except (StorageError, KeyError, OSError) as e:
                return dn_id, e

        errors = [r for r in self._ensure_pool().map(self._act(put_unit), puts)
                  if r is not None]
        if errors:
            all_closed = all(
                isinstance(e, StorageError)
                and e.code == "INVALID_CONTAINER_STATE"
                for _, e in errors)
            if all_closed:
                # container filled/closed between the chunk phase and
                # the barrier: a reallocation signal, not a node fault —
                # exclude the closed container (like the chunk phase)
                # and skip the rollback, whose putBlocks against the
                # closed container could only fail the same way
                self._excluded_containers.append(group.container_id)
                raise StripeWriteError([], errors[0][1])
            # putBlock failure fails the whole stripe: the group rolls
            # over and chunks past the committed length are orphaned.
            # The OTHER units' putBlocks (dispatched concurrently) have
            # already recorded the inflated group length, and offline
            # reconstruction trusts datanode metadata — roll the
            # survivors back to the pre-stripe commit so no datanode
            # reports bytes the client never acked (best-effort: a
            # node that also fails the rollback keeps the inflated
            # record, which is no worse than the sequential path's
            # already-committed prefix).
            failed_dns = {dn_id for dn_id, _ in errors}
            rollbacks = []
            for u in range(self.k + self.p):
                dn_id = group.pipeline.nodes[u]
                if dn_id in failed_dns or not self._group_chunks[u]:
                    continue
                prev_chunks = (self._group_chunks[u][:-1]
                               if new_chunks[u] is not None
                               else list(self._group_chunks[u]))
                if not prev_chunks:
                    continue
                rollbacks.append((dn_id, BlockData(
                    group.block_id, prev_chunks,
                    block_group_length=group.length)))
            for res in self._ensure_pool().map(self._act(put_unit), rollbacks):
                if res is not None:
                    log.warning("putBlock rollback failed on %s: %s",
                                res[0], res[1])
            # A closed container is a reallocation signal, not a node
            # failure — exclude nobody for those.
            bad = [d for d, e in errors
                   if not (isinstance(e, StorageError)
                           and e.code == "INVALID_CONTAINER_STATE")]
            raise StripeWriteError(bad, errors[0][1])
        group.length = group_len_after
        self._stripe_in_group += 1

    def _ensure_pool(self) -> ThreadPoolExecutor:
        if self._rpc_pool is None:
            self._rpc_pool = ThreadPoolExecutor(
                max_workers=self.k + self.p,
                thread_name_prefix="ec-writer")
        return self._rpc_pool

    def _act(self, fn):
        """Wrap a pool callable so the operation deadline AND trace
        context are ambient on the worker thread (RPC timeouts derive
        from the deadline; per-hop spans join the operation's trace)."""
        d = self._deadline
        ctx = Tracer.instance().handoff()
        if d is None and not ctx:
            return fn

        def wrapped(*a):
            with resilience.activate(d), Tracer.instance().activate(ctx):
                return fn(*a)

        return wrapped

    def _observed(self, dn_id: str, fn, *a, **kw):
        """Health-recording RPC: one shared classification
        (resilience.is_transport_fault — which already exempts the
        batch-unsupported UNIMPLEMENTED downgrade and application
        outcomes like a closed container) so the writer can never move
        a peer's breaker differently than the read paths do. Every hop
        gets a span: the per-unit RPC is the "network" stage a slow
        PUT's critical path attributes to."""
        with Tracer.instance().span(
                f"net:{getattr(fn, '__name__', 'rpc')}", dn=dn_id):
            return self._health.observe(dn_id, fn, *a, **kw)

    # ------------------------------------------------------------------ groups
    def _ensure_group(self) -> BlockGroup:
        if self._group is None:
            excluded = list(self._excluded)
            # breaker consult at allocation: a peer mid-outage is
            # excluded up front, so the reallocation can never land on
            # it and burn a retry attempt discovering the outage with a
            # failed stripe write (transient — a recovered peer leaves
            # this list the moment its half-open probe succeeds)
            extra = [dn for dn in self._health.open_peers()
                     if dn not in excluded]
            try:
                self._group = call_allocate(
                    self.allocate_group, excluded + extra,
                    tuple(self._excluded_containers))
            except Exception as e:  # noqa: BLE001 - advisory exclusion
                if not extra or (isinstance(e, StorageError)
                                 and e.code == resilience.DEADLINE_EXCEEDED):
                    raise  # spent budget: no second doomed allocation
                # the breaker-extended exclusion starved placement
                # (small cluster / wide outage): the breaker is
                # ADVISORY — retry with only the hard excludes and let
                # the write discover which peers actually answer
                log.warning(
                    "allocation with breaker-open peers %s excluded "
                    "failed (%s); retrying without the advisory "
                    "exclusions", extra, e)
                self._group = call_allocate(
                    self.allocate_group, excluded,
                    tuple(self._excluded_containers))
            self._group_chunks = [[] for _ in range(self.k + self.p)]
            self._create_containers(self._group)
        return self._group

    def _create_containers(self, group: BlockGroup) -> None:
        """Create the replica-indexed container on each node if absent;
        unreachable members surface as StripeWriteError so the stripe
        retry path excludes them and reallocates (excludePipelineAnd
        FailedDN semantics from the first touch of the pipeline)."""
        try:
            create_group_containers(self.clients, group,
                                    replica_indexed=True)
        except StripeWriteError:
            # discard the group before any data hits it: the retry path
            # must allocate afresh without the failed members
            self._group = None
            raise

    def _finalize_group(self) -> None:
        if self._group is not None and self._group.length > 0:
            self._groups.append(self._group)
        self._group = None
        self._group_chunks = []
        self._stripe_in_group = 0

    def hsync(self) -> list[BlockGroup]:
        """EC keys do not support hsync, matching the reference
        (ECKeyOutputStream rejects hflush/hsync: a partial stripe cannot
        be made durable without writing throwaway parity)."""
        raise StorageError("NOT_SUPPORTED_OPERATION",
                           "hsync is not supported for EC keys")

    # ------------------------------------------------------------------ close
    def close(self) -> list[BlockGroup]:
        """Flush the final (possibly partial) stripe and return the
        committed block groups in key order."""
        if self._closed:
            return self._groups
        d = resilience.current()
        if d is not None:
            self._deadline = d  # freshest ambient budget wins
        try:
            # partial stripe: pad for parity, write true lengths
            if self._cell_idx > 0 or self._cell_off > 0:
                lengths = [
                    self.cell if i < self._cell_idx
                    else (self._cell_off if i == self._cell_idx else 0)
                    for i in range(self.k)
                ]
                OPS.counter("partial_stripes").inc()
                OPS.counter("pad_cells").inc(lengths.count(0))
                self._queue.append(_Stripe(self._buf, lengths))
                self._buf = np.zeros((self.k, self.cell), dtype=np.uint8)
                self._cell_idx = 0
                self._cell_off = 0
            self._flush_queue()
            self._drain_pending()  # the last in-flight encoded batch
            self._finalize_group()
            self._closed = True
        finally:
            if self._rpc_pool is not None:
                self._rpc_pool.shutdown(wait=True)
                self._rpc_pool = None
        return self._groups

    @property
    def bytes_written(self) -> int:
        done = sum(g.length for g in self._groups)
        cur = self._group.length if self._group else 0
        queued = sum(sum(s.lengths) for s in self._queue)
        inflight = (sum(sum(s.lengths) for s in self._pending[0])
                    if self._pending is not None else 0)
        partial = self._cell_idx * self.cell + self._cell_off
        return done + cur + queued + inflight + partial
