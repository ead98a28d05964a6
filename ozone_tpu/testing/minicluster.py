"""MiniOzoneCluster analog: full in-process cluster for integration tests.

Mirrors the reference's MiniOzoneClusterImpl (integration-test
MiniOzoneClusterImpl.java — real OM + SCM + N datanodes in one process,
loopback transport): here a StorageContainerManager, an OzoneManager, and
N Datanodes wired through the in-process client factory, with a manual or
background heartbeat pump and a command-dispatch loop that executes SCM
commands (reconstruction, replica deletion) on the datanodes the way
DatanodeStateMachine's command handlers do.
"""

from __future__ import annotations

import logging
import threading
import time
from pathlib import Path
from typing import Optional

from ozone_tpu.client.dn_client import DatanodeClientFactory
from ozone_tpu.client.ozone_client import OzoneClient
from ozone_tpu.om.om import OzoneManager
from ozone_tpu.scm.replication_manager import (
    DeleteReplicaCommand,
    ReplicateCommand,
)
from ozone_tpu.scm.scm import StorageContainerManager
from ozone_tpu.storage.datanode import Datanode
from ozone_tpu.storage.ids import BlockData, BlockID, StorageError
from ozone_tpu.storage.reconstruction import (
    ECReconstructionCoordinator,
    ReconstructionCommand,
)

log = logging.getLogger(__name__)


class MiniOzoneCluster:
    def __init__(
        self,
        root: Path,
        num_datanodes: int = 5,
        racks: int = 1,
        block_size: int = 16 * 1024 * 1024,
        container_size: int = 256 * 1024 * 1024,
        stale_after_s: float = 9.0,
        dead_after_s: float = 30.0,
        placement_seed: Optional[int] = 42,
    ):
        self.root = Path(root)
        self.scm = StorageContainerManager(
            min_datanodes=min(num_datanodes, 1),
            container_size=container_size,
            placement_seed=placement_seed,
            stale_after_s=stale_after_s,
            dead_after_s=dead_after_s,
        )
        self.clients = DatanodeClientFactory()
        self.datanodes: list[Datanode] = []
        for i in range(num_datanodes):
            dn = Datanode(self.root / f"dn{i}", dn_id=f"dn{i}")
            self.datanodes.append(dn)
            self.clients.register_local(dn)
            rack = f"/rack{i % racks}" if racks > 1 else "/default-rack"
            self.scm.register_datanode(dn.id, rack=rack,
                                       capacity_bytes=10 * container_size)
        self.om = OzoneManager(
            self.root / "om" / "om.db",
            self.scm,
            clients=self.clients,
            block_size=block_size,
        )
        from ozone_tpu.parallel import mesh_executor

        # repair decodes: the coordinator is handed the host's mesh
        # executor where one can exist (it coalesces batches across
        # containers on long-lived programs) and hands it to the door
        self.reconstruction = ECReconstructionCoordinator(
            self.clients, executor=mesh_executor.maybe_executor())
        self._stopped_dns: set[str] = set()
        self._hb_thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    # ----------------------------------------------------------------- client
    def client(self) -> OzoneClient:
        return OzoneClient(self.om, self.clients)

    def datanode(self, dn_id: str) -> Datanode:
        return next(d for d in self.datanodes if d.id == dn_id)

    # -------------------------------------------------------------- liveness
    def stop_datanode(self, dn_id: str) -> None:
        """Simulate a crash: stop heartbeating and unregister the client so
        IO to this node fails."""
        self._stopped_dns.add(dn_id)
        self.clients._local.pop(dn_id, None)

    def restart_datanode(self, dn_id: str) -> None:
        self._stopped_dns.discard(dn_id)
        self.clients.register_local(self.datanode(dn_id))

    # -------------------------------------------------------------- heartbeat
    def heartbeat_all(self, with_reports: bool = True) -> None:
        """One heartbeat round: every live DN reports and executes returned
        commands (DatanodeStateMachine heartbeat + command handler loop)."""
        for dn in self.datanodes:
            if dn.id in self._stopped_dns:
                continue
            report = dn.container_report() if with_reports else None
            commands = self.scm.heartbeat(dn.id, container_report=report)
            for cmd in commands:
                self._execute_command(dn, cmd)

    def _execute_command(self, dn: Datanode, cmd) -> None:
        from ozone_tpu.scm.block_deletion import DeleteBlocksCommand

        try:
            if isinstance(cmd, DeleteBlocksCommand):
                for bid in cmd.blocks:
                    try:
                        dn.delete_block(bid)
                    except StorageError:
                        pass
                self.scm.deleted_blocks.ack(dn.id, cmd.tx_ids)
            elif isinstance(cmd, ReconstructionCommand):
                self.reconstruction.reconstruct_container_group(cmd)
                for idx in cmd.targets:
                    self.scm.replication.op_completed(cmd.container_id, idx)
            elif isinstance(cmd, DeleteReplicaCommand):
                dn.delete_container(cmd.container_id, force=True)
            elif isinstance(cmd, ReplicateCommand):
                self._replicate_container(cmd)
                self.scm.replication.op_completed(cmd.container_id)
            else:
                log.debug("ignoring command %r", cmd)
        except Exception:
            log.exception("command %r failed on %s", cmd, dn.id)
            if isinstance(cmd, ReconstructionCommand):
                for idx in cmd.targets:
                    self.scm.replication.op_completed(cmd.container_id, idx)
            elif isinstance(cmd, ReplicateCommand):
                self.scm.replication.op_completed(cmd.container_id)

    def _replicate_container(self, cmd: ReplicateCommand) -> None:
        """Container copy (DownloadAndImportReplicator analog, in-process)."""
        src = self.clients.get(cmd.source)
        dst = self.clients.get(cmd.target)
        blocks = src.list_blocks(cmd.container_id)
        try:
            dst.create_container(cmd.container_id, cmd.replica_index)
        except StorageError as e:
            if e.code != "CONTAINER_EXISTS":
                raise
        for bd in blocks:
            for info in bd.chunks:
                data = src.read_chunk(bd.block_id, info)
                dst.write_chunk(bd.block_id, info, data)
            dst.put_block(
                BlockData(bd.block_id, bd.chunks, bd.block_group_length)
            )
        dst.close_container(cmd.container_id)

    def tick(self, rounds: int = 1) -> None:
        """heartbeats + SCM control loops, n times (deterministic tests)."""
        for _ in range(rounds):
            self.heartbeat_all()
            self.scm.run_background_once()
            self.heartbeat_all()  # deliver commands emitted by the scan

    def start_heartbeats(self, interval_s: float = 0.5) -> None:
        def loop():
            while not self._stop.wait(interval_s):
                try:
                    self.tick()
                except Exception:
                    log.exception("heartbeat tick failed")

        self._hb_thread = threading.Thread(
            target=loop, name="mini-heartbeats", daemon=True
        )
        self._hb_thread.start()

    # ----------------------------------------------------------------- admin
    def close(self) -> None:
        self._stop.set()
        if self._hb_thread:
            self._hb_thread.join(timeout=5)
        self.scm.stop()
        self.om.close()
        for dn in self.datanodes:
            dn.close()


def free_ports(n: int) -> list[int]:
    """Reserve n distinct loopback ports (bind, record, release)."""
    import socket

    socks = []
    for _ in range(n):
        s = socket.socket()
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        socks.append(s)
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


class MiniOzoneHACluster:
    """Multi-replica metadata ring + real-gRPC datanodes in one process.

    Role analog of the reference's MiniOzoneHAClusterImpl
    (integration-test MiniOzoneHAClusterImpl.java — multiple OMs/SCMs on
    real consensus with loopback RPC). Boots N ScmOmDaemon replicas on
    one raft ring (net/daemons HA mode, everything over real gRPC),
    M datanode daemons heartbeating every replica, and hands out
    failover-aware clients. Replicas can be stopped and revived by id
    for failover tests.
    """

    def __init__(self, root: Path, num_meta: int = 3,
                 num_datanodes: int = 5,
                 block_size: int = 256 * 1024,
                 heartbeat_interval_s: float = 0.15):
        from ozone_tpu.net.daemons import DatanodeDaemon, ScmOmDaemon

        self.root = Path(root)
        self.block_size = block_size
        self.peers = {
            f"m{i}": f"127.0.0.1:{p}"
            for i, p in enumerate(free_ports(num_meta))
        }
        self.metas: dict[str, ScmOmDaemon] = {}
        for mid in self.peers:
            d = self._make_meta(mid)
            d.start()
            self.metas[mid] = d
        self.await_leader()
        self.datanodes = []
        scm_addrs = ",".join(self.peers.values())
        for i in range(num_datanodes):
            d = DatanodeDaemon(self.root / f"dn{i}", f"dn{i}", scm_addrs,
                               heartbeat_interval_s=heartbeat_interval_s)
            d.start()
            self.datanodes.append(d)

    def _make_meta(self, mid: str):
        from ozone_tpu.net.daemons import ScmOmDaemon

        return ScmOmDaemon(
            self.root / mid / "om.db",
            port=int(self.peers[mid].rsplit(":", 1)[1]),
            block_size=self.block_size,
            stale_after_s=1000.0,
            dead_after_s=2000.0,
            background_interval_s=0.2,
            ha_id=mid,
            ha_peers=self.peers,
        )

    # ------------------------------------------------------------ control
    def await_leader(self, timeout: float = 15.0) -> str:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            leaders = [mid for mid, d in self.metas.items()
                       if d.ha is not None and d.ha.is_leader]
            if len(leaders) == 1:
                return leaders[0]
            time.sleep(0.05)
        raise AssertionError(f"no single leader among {list(self.metas)}")

    def stop_meta(self, mid: str) -> None:
        self.metas.pop(mid).stop()

    def revive_meta(self, mid: str) -> None:
        d = self._make_meta(mid)
        d.start()
        self.metas[mid] = d

    # ------------------------------------------------------------ clients
    def client(self) -> OzoneClient:
        from ozone_tpu.net.om_service import GrpcOmClient
        from ozone_tpu.net.ratis_service import RatisClientFactory
        from ozone_tpu.net.scm_service import GrpcScmClient

        clients = DatanodeClientFactory()
        om = GrpcOmClient(",".join(self.peers.values()), clients=clients)
        # seed datanode addresses up front so a read-before-write client
        # can resolve replicas (tools/cli._client does the same)
        try:
            scm = GrpcScmClient(",".join(self.peers.values()))
            for dn_id, addr in scm.node_addresses().items():
                clients.register_remote(dn_id, addr)
            scm.close()
        except StorageError:
            pass  # learned lazily from allocate responses instead
        ratis = RatisClientFactory(address_source=clients.remote_address)
        return OzoneClient(om, clients, ratis_clients=ratis)

    def shutdown(self) -> None:
        for d in self.datanodes:
            d.stop()
        for d in list(self.metas.values()):
            d.stop()
        self.metas.clear()
        self.datanodes = []


class MiniShardedCluster:
    """Sharded metadata plane over real gRPC: one single-replica
    ScmOmDaemon per shard, each booted with its replicated
    InstallShardConfig ownership row and a copy of the root shard map
    (served ungated via GetShardMap), plus shard-aware GrpcOmClients
    that route by the cached map and retry through SHARD_MOVED.

    Metadata-only by design: each daemon embeds its own SCM, so block
    allocation across shards would hand out colliding container ids —
    data-path drills run on the in-process ShardedMetaPlane, which
    shares one SCM (om/sharding/plane.py).
    """

    def __init__(self, root: Path, n_shards: int = 2,
                 slot_count: int = 64, block_size: int = 256 * 1024):
        from ozone_tpu.net.daemons import ScmOmDaemon
        from ozone_tpu.om.sharding.shardmap import ShardMap

        self.root = Path(root)
        self.shard_ids = [f"s{i}" for i in range(n_shards)]
        addresses = {
            sid: f"127.0.0.1:{p}"
            for sid, p in zip(self.shard_ids, free_ports(n_shards))
        }
        self.map = ShardMap.uniform(self.shard_ids, epoch=1,
                                    addresses=addresses,
                                    slot_count=slot_count)
        self.daemons: dict[str, ScmOmDaemon] = {}
        for sid in self.shard_ids:
            d = ScmOmDaemon(
                self.root / sid / "om.db",
                port=int(addresses[sid].rsplit(":", 1)[1]),
                block_size=block_size,
                stale_after_s=1000.0,
                dead_after_s=2000.0,
                background_interval_s=0.2,
                shard_config={
                    "epoch": 1, "shard_id": sid,
                    "slot_count": slot_count,
                    "owned": self.map.owned_slots(sid),
                },
                shard_map=self.map.to_json(),
            )
            d.start()
            self.daemons[sid] = d

    def om_client(self):
        """A shard-aware remote OM client (discovers the map itself)."""
        from ozone_tpu.net.om_service import GrpcOmClient

        return GrpcOmClient(",".join(self.map.addresses.values()),
                            shard_aware=True)

    def move_slot(self, slot: int, to_sid: str):
        """Operator rebalance: fence the source, copy the slot's rows,
        grant the target, publish the bumped map on every daemon.
        Clients holding the old map get SHARD_MOVED and refetch."""
        from ozone_tpu.om.sharding.shardmap import (
            ImportRow,
            InstallShardConfig,
            InstallShardMap,
            slot_for,
        )

        new_map = self.map.move_slot(slot, to_sid)
        from_sid = self.map.shards[self.map.slots[slot]]
        src, dst = self.daemons[from_sid].om, self.daemons[to_sid].om
        src.submit(InstallShardConfig(
            epoch=new_map.epoch, shard_id=from_sid,
            slot_count=new_map.slot_count,
            owned=new_map.owned_slots(from_sid)))
        for vk, _ in list(src.store.iterate("volumes")):
            for bk, brow in list(src.store.iterate("buckets", vk + "/")):
                if slot_for(brow["volume"], brow["name"],
                            new_map.slot_count) != slot:
                    continue
                dst.submit(ImportRow("buckets", bk, brow))
                for table in ("keys", "open_keys", "deleted_keys",
                              "multipart", "dirs", "files",
                              "deleted_dirs"):
                    for k, row in list(src.store.iterate(table,
                                                         bk + "/")):
                        dst.submit(ImportRow(table, k, row))
        dst.submit(InstallShardConfig(
            epoch=new_map.epoch, shard_id=to_sid,
            slot_count=new_map.slot_count,
            owned=new_map.owned_slots(to_sid)))
        for d in self.daemons.values():
            d.om.submit(InstallShardMap(new_map.to_json()))
        self.map = new_map
        return new_map

    def shutdown(self) -> None:
        for d in self.daemons.values():
            d.stop()
        self.daemons.clear()


def make_meta_daemon(tmp_path, i: int, peers: dict, **overrides):
    """One metadata-ring replica (ScmOmDaemon) with test-friendly
    defaults; peers maps 'm<i>' -> host:port. Shared by the HA suites."""
    from ozone_tpu.net.daemons import ScmOmDaemon

    kw = dict(
        stale_after_s=1000.0,
        dead_after_s=2000.0,
        background_interval_s=0.2,
        ha_id=f"m{i}",
        ha_peers=peers,
    )
    kw.update(overrides)
    return ScmOmDaemon(
        tmp_path / f"meta{i}" / "om.db",
        port=int(peers[f"m{i}"].rsplit(":", 1)[1]),
        **kw,
    )


def await_meta_leader(metas: dict, timeout: float = 10.0, among=None):
    """Wait until exactly one replica (optionally restricted to `among`)
    reports leadership; returns its id."""
    import time

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        leaders = [mid for mid, d in metas.items()
                   if (among is None or mid in among)
                   and d.ha is not None and d.ha.is_leader]
        if len(leaders) == 1:
            return leaders[0]
        time.sleep(0.05)
    raise AssertionError(f"no single leader among {among or list(metas)}")
