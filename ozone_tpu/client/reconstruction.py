"""Fleet-wide EC reconstruction storms over the persistent mesh executor.

When a datanode dies, every EC container it held a replica of needs a
decode — the f4 (OSDI '14) design point where RECOVERY bandwidth across
the fleet, not single-node codec speed, bounds mean time to
re-protection. The SCM's ReplicationManager repairs those containers one
heartbeat-command at a time; this module is the storm-shaped datapath
for the same work: enumerate every container the dead node touched,
build the per-container ReconstructionCommands the same way
`scm/replication_manager.py:_emit_reconstruction` does (first live
source per index, targets by the SCM's own rack-scatter rule,
`scm/placement.rack_scatter`, excluding every present holder), from the
SCM's container and node LISTINGS — the same plain values whether `scm`
is the in-process StorageContainerManager or a GrpcScmClient on a served
cluster — and run them CONCURRENTLY through one shared
`ECReconstructionCoordinator` wired to the mesh executor — so decode
batches from different containers (same erasure pattern, which a
homogeneous cluster guarantees) coalesce into full-width mesh dispatches
on long-lived SPMD programs instead of per-container dribbles.

The report carries the dispatch accounting that proves the coalescing
happened: `mesh_dispatches` vs `decode_batches_submitted` — a storm
that did NOT coalesce shows dispatches >= batches.
"""

from __future__ import annotations

import logging
import random
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Optional

from ozone_tpu.scm.pipeline import ReplicationConfig, ReplicationType
from ozone_tpu.scm.placement import PlacementError, rack_scatter
from ozone_tpu.storage.reconstruction import (
    ECReconstructionCoordinator,
    ReconstructionCommand,
)
from ozone_tpu.utils.checksum import ChecksumType
from ozone_tpu.utils.metrics import registry

log = logging.getLogger(__name__)

METRICS = registry("client.reconstruction")


@dataclass
class StormReport:
    """What one `repair_datanode` pass did, with the mesh-executor
    dispatch accounting for the coalescing proof."""

    dead_dn: str
    containers_planned: int = 0
    containers_repaired: int = 0
    containers_failed: int = 0
    containers_unrecoverable: int = 0
    elapsed_s: float = 0.0
    #: mesh-executor counter deltas across the storm (zeros when the
    #: storm ran on the single-chip fallback path)
    mesh_dispatches: int = 0
    mesh_stripes: int = 0
    mesh_coalesced_ops: int = 0
    mesh_multi_op_dispatches: int = 0
    mesh_max_inflight: int = 0
    failures: list[tuple[int, str]] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return (self.containers_failed == 0
                and self.containers_repaired == self.containers_planned)


class ReconstructionStorm:
    """Repair every EC container a dead datanode held, data-parallel
    across the mesh.

    `scm` answers `list_containers()` and `list_nodes()`: an in-process
    StorageContainerManager or a GrpcScmClient, planned from alike;
    `clients` is the DatanodeClientFactory reaching the surviving nodes.
    `executor` is the mesh executor the storm REPORTS on (quiesce, the
    `mesh_*` deltas of its report) and hands, through its coordinator,
    to the door with every decode stream; it defaults to the
    process-wide one. Which queue a decode joins is the door's decision
    (`parallel/dispatch.py`): with no mesh the storm still runs,
    through the single-chip codec service.
    """

    def __init__(self, scm, clients, executor=None,
                 checksum: ChecksumType = ChecksumType.CRC32C,
                 bytes_per_checksum: int = 16 * 1024,
                 max_parallel_containers: int = 4,
                 max_parallel_blocks: int = 2):
        from ozone_tpu.parallel import mesh_executor

        self.scm = scm
        self.clients = clients
        self.executor = (executor if executor is not None
                         else mesh_executor.maybe_executor())
        #: reconstruction streams (upstream's
        #: hdds.datanode.replication.streams.limit): containers
        #: repairing at once. Each stream reads its own survivors and
        #: writes its own targets while ALL their decode batches meet in
        #: the shared mesh lanes — the concurrency here is what FILLS
        #: the mesh-wide batches
        self.max_parallel_containers = max(1, int(max_parallel_containers))
        self.coordinator = ECReconstructionCoordinator(
            clients,
            checksum=checksum,
            bytes_per_checksum=bytes_per_checksum,
            max_parallel_blocks=max_parallel_blocks,
            executor=self.executor,
        )

    # ------------------------------------------------------------- plan
    def plan(self, dead_dn_id: str) -> list[ReconstructionCommand]:
        """ReconstructionCommands for every EC container with a replica
        on the dead node, built the `_emit_reconstruction` way: first
        surviving holder per index as source, targets excluding every
        present holder AND the dead node. Containers with too few
        survivors are skipped (and counted by the caller as
        unrecoverable) — a storm must never wedge on a lost cause.

        Commands come back sorted by recoverability, fewest surviving
        indexes first: the stripes closest to losing data permanently
        repair earliest, so a second failure mid-storm costs the least
        (carry-over fix: PR 12's planner ordered containers by SCM
        enumeration order)."""
        nodes = sorted(self.scm.list_nodes(), key=lambda n: n["dn_id"])
        known = {n["dn_id"] for n in nodes}
        # the placement policies' candidate set, once for the whole plan
        eligible = [(n["rack"], n["dn_id"]) for n in nodes
                    if n["state"] == "HEALTHY"
                    and n["op_state"] == "IN_SERVICE"
                    and n["healthy_volumes"] != 0]
        cmds: list[tuple[int, ReconstructionCommand]] = []
        for c in self.scm.list_containers():
            replication = ReplicationConfig.parse(c["replication"])
            if replication.type is not ReplicationType.EC:
                continue
            if c["state"] == "DELETED":
                continue
            if all(r["dn_id"] != dead_dn_id for r in c["replicas"]):
                continue
            present: dict[int, list[str]] = {}
            for r in c["replicas"]:
                if r["dn_id"] == dead_dn_id:
                    continue
                if r["state"] in ("UNHEALTHY", "DELETED", "INVALID"):
                    continue
                if r["dn_id"] not in known:
                    continue
                present.setdefault(r["replica_index"], []).append(
                    r["dn_id"])
            ec = replication.ec
            missing = sorted(
                set(range(1, ec.all_units + 1)) - set(present))
            if not missing:
                continue  # dead replica's index survives elsewhere
            if ec.codec == "lrc":
                # LRC recoverability is pattern-shaped, not a survivor
                # count: ask the repair planner whether the missing set
                # is reachable from the surviving indexes (0-based)
                from ozone_tpu.codec import lrc_math

                try:
                    lrc_math.plan_valid(
                        ec, [i - 1 for i in missing],
                        [i - 1 for i in present])
                    recoverable = True
                except ValueError:
                    recoverable = False
            else:
                recoverable = len(present) >= ec.data_units
            if not recoverable:
                METRICS.counter("unrecoverable").inc()
                log.warning(
                    "storm: container %s unrecoverable (%d/%d indexes "
                    "survive)", c["id"], len(present), ec.data_units)
                continue
            sources = {i: dns[0] for i, dns in present.items()}
            exclude = {dn for dns in present.values() for dn in dns}
            exclude.add(dead_dn_id)
            try:
                # the SCM's own choosing rule; the draw is seeded by the
                # container, so one listing gives one plan wherever it
                # is read
                chosen = rack_scatter(
                    [e for e in eligible if e[1] not in exclude],
                    len(missing), random.Random(c["id"]))
            except PlacementError:
                METRICS.counter("placement_failures").inc()
                log.warning("storm: no targets for container %s", c["id"])
                continue
            cmds.append((len(present), ReconstructionCommand(
                container_id=c["id"],
                replication=ec,
                sources=sources,
                targets=dict(zip(missing, chosen)),
            )))
        # most-at-risk first: ascending surviving-index count, container
        # id as the deterministic tiebreak
        cmds.sort(key=lambda sc: (sc[0], sc[1].container_id))
        return [cmd for _survivors, cmd in cmds]

    # ------------------------------------------------------------ drive
    def repair_container(self, cmd: ReconstructionCommand) -> None:
        """One stream's unit of work: rebuild one container's lost
        replicas through the shared coordinator, so that its decode
        batches meet every other stream's in the mesh lanes. The
        coordinator's `repair:container` operation is the root of the
        repair's trace (its stage record is the flight recorder's).
        `repair_datanode` drives this over a plan; a time-bounded drill
        drives it over commands of its own, on up to
        `max_parallel_containers` threads."""
        self.coordinator.reconstruct_container_group(cmd)

    def repair_datanode(self, dead_dn_id: str) -> StormReport:
        """The storm: plan, then repair containers concurrently through
        the shared coordinator. Returns the report with mesh dispatch
        deltas (how few mesh dispatches the whole fleet repair took)."""
        from ozone_tpu.parallel import mesh_executor as me

        report = StormReport(dead_dn=dead_dn_id)
        unrec0 = METRICS.counter("unrecoverable").value
        cmds = self.plan(dead_dn_id)
        report.containers_planned = len(cmds)
        report.containers_unrecoverable = int(
            METRICS.counter("unrecoverable").value - unrec0)
        if not cmds:
            return report
        snap0 = me.METRICS.snapshot() if self.executor is not None else {}
        t0 = time.monotonic()
        METRICS.counter("storms").inc()
        METRICS.gauge("containers_in_flight").set(0)

        def repair(cmd: ReconstructionCommand) -> Optional[str]:
            try:
                self.repair_container(cmd)
                return None
            except Exception as e:  # noqa: BLE001 - per-container fault isolation
                log.exception("storm: container %s repair failed",
                              cmd.container_id)
                return f"{type(e).__name__}: {e}"

        with ThreadPoolExecutor(
                max_workers=self.max_parallel_containers,
                thread_name_prefix="storm") as pool:
            for cmd, err in zip(cmds, pool.map(repair, cmds)):
                if err is None:
                    report.containers_repaired += 1
                    METRICS.counter("containers_repaired").inc()
                else:
                    report.containers_failed += 1
                    METRICS.counter("containers_failed").inc()
                    report.failures.append((cmd.container_id, err))
        report.elapsed_s = time.monotonic() - t0
        if self.executor is not None:
            self.executor.quiesce()
            snap1 = me.METRICS.snapshot()

            def delta(name: str) -> int:
                return int(snap1.get(name, 0)) - int(snap0.get(name, 0))

            report.mesh_dispatches = delta("dispatches")
            report.mesh_stripes = delta("stripes_dispatched")
            report.mesh_coalesced_ops = delta("coalesced_operations")
            report.mesh_multi_op_dispatches = delta("multi_op_dispatches")
            report.mesh_max_inflight = self.executor._max_inflight
        return report

