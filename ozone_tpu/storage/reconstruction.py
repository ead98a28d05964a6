"""Offline EC reconstruction coordinator.

Mirrors the reference's ECReconstructionCoordinator flow (container-service
ec/reconstruction/ECReconstructionCoordinator.java:81-97 flow doc,
reconstructECContainerGroup:146): driven by an SCM ReconstructECContainers
command carrying source replica-index->node and target index->node maps
(server-scm ECUnderReplicationHandler.processAndSendCommands:107), the
executing datanode

  1. lists blocks on the source nodes,
  2. creates RECOVERING containers on the targets,
  3. per block: recovers the missing units' cells from any k survivors
     (ECBlockReconstructedStripeInputStream.recoverChunks analog — here a
     depth-1 pipeline of batched device decodes: batch N's recovered
     chunks stream to the targets while batch N+1 reads survivors and
     decodes on device),
  4. putBlock + closeContainer on the targets,
  5. on any failure deletes the RECOVERING containers (:193-220).

TPU-first: decode+CRC of recovered cells happen in one fused device pass;
recovered chunks carry device-computed checksums.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass

from ozone_tpu.client import resilience
from ozone_tpu.client.dn_client import (
    DatanodeClientFactory,
    build_chunk_pairs,
    write_unit_stream,
)
from ozone_tpu.client.ec_reader import ECBlockGroupReader, unit_true_lengths
from ozone_tpu.client.ec_writer import BlockGroup
from ozone_tpu.codec.api import CoderOptions
from ozone_tpu.codec.fused import effective_bpc
from ozone_tpu.scm.pipeline import Pipeline, ReplicationConfig
from ozone_tpu.storage.ids import (
    BlockData,
    ChunkInfo,
    ContainerState,
    StorageError,
)
from ozone_tpu.utils.checksum import Checksum, ChecksumType
from ozone_tpu.utils.metrics import MetricsRegistry
from ozone_tpu.utils.tracing import Tracer

log = logging.getLogger(__name__)

MISSING_NODE = "__missing__"


@dataclass(frozen=True)
class ReconstructionCommand:
    """SCM -> DN command (ReconstructECContainersCommand analog)."""

    container_id: int
    replication: CoderOptions
    sources: dict[int, str]  # replica index (1-based) -> dn_id
    targets: dict[int, str]  # missing replica index (1-based) -> dn_id


class ECReconstructionCoordinator:
    def __init__(
        self,
        clients: DatanodeClientFactory,
        checksum: ChecksumType = ChecksumType.CRC32C,
        bytes_per_checksum: int = 16 * 1024,
        mesh=None,
        use_ring: bool = False,
        max_parallel_blocks: int = 2,
        executor=None,
    ):
        self.clients = clients
        self.checksum = checksum
        self.bpc = bytes_per_checksum
        #: blocks of a container group repair in flight at once — each
        #: block's read+decode+write chain is independent, so a small
        #: pool overlaps one block's survivor reads with another's
        #: target writes (memory-bounded: each holds its cell batch)
        self.max_parallel_blocks = max(1, int(max_parallel_blocks))
        #: device mesh for the decode: stripe-parallel (DP) by default,
        #: survivor-sharded ring (SP) with use_ring — the reference runs
        #: its codec inside this same repair flow
        #: (ECReconstructionCoordinator.java:98,146); here the flow is
        #: the one that owns the mesh
        self.mesh = mesh
        self.use_ring = use_ring
        #: persistent mesh executor (parallel/mesh_executor.py): decode
        #: batches from EVERY block and container this coordinator
        #: repairs join one submission queue and coalesce into
        #: full-width mesh dispatches — the fleet-storm datapath
        self.executor = executor
        self.metrics = MetricsRegistry("ec.reconstruction")
        #: shared peer health: source selection skips breaker-open
        #: peers while alternatives exist, and the reader's survivor
        #: choice/straggler hedging below rides the same registry
        self.health = getattr(clients, "health", None) \
            or resilience.default_registry()

    def reconstruct_container_group(self, cmd: ReconstructionCommand) -> None:
        # reconstruction-job boundary: one deadline (operator opt-in via
        # OZONE_TPU_OP_DEADLINE_S) covers listing, every block's
        # recover+write chain, and the target close/cleanup
        # The root span is the flight recorder's unit for a repair.
        with Tracer.instance().operation(
                "repair:container", container=cmd.container_id,
                lost=sorted(cmd.targets)) as sp, \
                resilience.start("reconstruction"):
            sp.tags["bytes"] = self._reconstruct_container_group(cmd)

    def _reconstruct_container_group(self,
                                     cmd: ReconstructionCommand) -> int:
        """Returns the bytes rebuilt onto the targets."""
        tracer = Tracer.instance()
        targets = sorted(cmd.targets)
        created: list[tuple[str, int]] = []
        try:
            with tracer.span("repair:prepare"):
                # 2. RECOVERING containers on targets
                for idx in targets:
                    dn = cmd.targets[idx]
                    self.clients.get(dn).create_container(
                        cmd.container_id,
                        replica_index=idx,
                        state=ContainerState.RECOVERING,
                    )
                    created.append((dn, idx))

                # 1. block list from any source
                blocks = self._list_blocks(cmd)

            # 3.-4. per block: recover + write + putBlock. Independent
            # chains run through a small pool so survivor reads of one
            # block overlap target writes of another; any failure fails
            # the group (RECOVERING cleanup below)
            if self.max_parallel_blocks > 1 and len(blocks) > 1:
                from concurrent.futures import ThreadPoolExecutor

                # pool threads start with no span of their own: carry
                # the deadline and the trace context over, as
                # ec_writer._act does
                deadline, ctx = resilience.current(), tracer.handoff()

                def one(bd: BlockData) -> int:
                    with resilience.activate(deadline), \
                            tracer.activate(ctx):
                        return self._reconstruct_block(cmd, bd, targets)

                with ThreadPoolExecutor(
                        max_workers=self.max_parallel_blocks,
                        thread_name_prefix="ec-recon") as pool:
                    rebuilt = sum(pool.map(one, blocks))
            else:
                rebuilt = sum(self._reconstruct_block(cmd, bd, targets)
                              for bd in blocks)

            with tracer.span("repair:close"):
                for idx in targets:
                    self.clients.get(cmd.targets[idx]).close_container(
                        cmd.container_id
                    )
            self.metrics.counter("groups_reconstructed").inc()
            return rebuilt
        except Exception:
            # 5. cleanup RECOVERING containers on failure
            for dn, _idx in created:
                try:
                    self.clients.get(dn).delete_container(
                        cmd.container_id, force=True
                    )
                except (StorageError, KeyError, OSError) as e:
                    log.warning("cleanup of %s on %s failed: %s",
                                cmd.container_id, dn, e)
            self.metrics.counter("groups_failed").inc()
            raise

    def _list_blocks(self, cmd: ReconstructionCommand) -> list[BlockData]:
        last_err: Exception | None = None
        # health-ordered: breaker-allowing, fastest-EWMA sources first;
        # a tripped source is still LAST-resort dialed rather than
        # failing the job when it is the only replica left
        for dn in self.health.preferred(
                [cmd.sources[idx] for idx in sorted(cmd.sources)]):
            try:
                return self.health.observe(
                    dn, self.clients.get(dn).list_blocks,
                    cmd.container_id)
            except (StorageError, KeyError, OSError) as e:
                last_err = e
        raise StorageError(
            "CONTAINER_NOT_FOUND",
            f"no source could list blocks for {cmd.container_id}: {last_err}",
        )

    def _group_for(self, cmd: ReconstructionCommand, bd: BlockData) -> BlockGroup:
        """Synthesize the block-group view from the command's source map;
        indexes with no live source get a placeholder node the client
        factory cannot resolve (treated as unavailable by the reader)."""
        opts = cmd.replication
        nodes = [
            cmd.sources.get(i + 1, MISSING_NODE) for i in range(opts.all_units)
        ]
        length = bd.block_group_length
        if length is None:
            raise StorageError(
                "NO_SUCH_BLOCK", f"block {bd.block_id} has no group length"
            )
        return BlockGroup(
            container_id=cmd.container_id,
            local_id=bd.block_id.local_id,
            pipeline=Pipeline(ReplicationConfig.from_ec(opts), nodes),
            length=length,
        )

    def _reconstruct_block(
        self, cmd: ReconstructionCommand, bd: BlockData, targets: list[int]
    ) -> int:
        """Rebuild one block group's lost units; returns their bytes.
        Under `repair:block` the reader spans its survivor reads
        (net:read_chunks) and its decode (codec:queue_wait,
        codec:dispatch); `repair:write` is the targets' share."""
        with Tracer.instance().span("repair:block",
                                    block=bd.block_id.local_id):
            return self._reconstruct_block_traced(cmd, bd, targets)

    def _book_reads(self, plan) -> None:
        """What the block's recovery planned and read: the `kind` /
        `width` / `widened` of its `repair:block` span (the current
        one) and this registry's counters. A
        repair is `local` where it read its lost units' groups alone,
        `widened` where it set out to and ended reading outside them,
        `global` otherwise (Reed-Solomon's are all that)."""
        if plan is None or not plan.kind:
            return  # nothing to rebuild: the reader planned no read
        Tracer.instance().current().tags.update(
            plan.tags(), units_read=len(plan.units))
        self.metrics.counter(
            "repairs_widened" if plan.widened
            else "repairs_local" if plan.kind == "local"
            else "repairs_global").inc()
        self.metrics.counter("survivor_units_read").inc(len(plan.units))
        self.metrics.counter("survivor_bytes_read").inc(plan.bytes)

    def _reconstruct_block_traced(
        self, cmd: ReconstructionCommand, bd: BlockData, targets: list[int]
    ) -> int:
        tracer = Tracer.instance()
        opts = cmd.replication
        cell = opts.cell_size
        bpc = effective_bpc(cell, self.bpc)
        group = self._group_for(cmd, bd)
        reader = ECBlockGroupReader(
            group,
            opts,
            self.clients,
            checksum=self.checksum,
            bytes_per_checksum=bpc,
            mesh=self.mesh,
            use_ring=self.use_ring,
            qos_class="bulk",  # repair storms defer to interactive reads
            executor=self.executor,
        )
        target_units = [idx - 1 for idx in targets]  # 0-based unit indexes
        lengths = unit_true_lengths(group, opts)
        host_checksum = Checksum(self.checksum, bpc)

        # Streaming repair through the reader's depth-1 decode pipeline:
        # batch N's recovered chunks land on the targets while batch N+1
        # reads survivors and decodes on device (one device dispatch per
        # stripe batch). Chunk records are keyed by stripe so a
        # mid-stream recovery restart simply overwrites — the single
        # put_block commit per target below runs only after every batch
        # landed (same all-chunks-before-commit order as before).
        written: list[dict[int, ChunkInfo]] = [{} for _ in targets]
        for sb, (cells, crcs) in reader.recover_cells_iter(target_units):
            for ti, idx in enumerate(targets):
                u = idx - 1
                pairs = build_chunk_pairs(
                    group.block_id, sb, cells[:, ti], crcs[:, ti],
                    lengths[u], cell, bpc, self.checksum, host_checksum)
                for info, _ in pairs:
                    written[ti][info.offset // cell] = info
                if pairs:
                    # one batched stream per rebuilt unit per batch when
                    # the target serves it, per-chunk verbs against
                    # older/pre-finalize targets
                    with tracer.span("repair:write", unit=u,
                                     chunks=len(pairs)):
                        write_unit_stream(
                            self.clients.get(cmd.targets[idx]),
                            group.block_id, pairs)
        self._book_reads(reader.recovery)

        rebuilt = 0
        for ti, idx in enumerate(targets):
            dn = self.clients.get(cmd.targets[idx])
            infos = [written[ti][s] for s in sorted(written[ti])]
            with tracer.span("repair:write", unit=idx - 1, commit=True):
                dn.put_block(BlockData(
                    group.block_id, infos,
                    block_group_length=group.length,
                ))
            nbytes = sum(i.length for i in infos)
            rebuilt += nbytes
            self.metrics.counter("blocks_reconstructed").inc()
            self.metrics.counter("bytes_reconstructed").inc(nbytes)
        return rebuilt
