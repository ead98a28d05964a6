"""Distributed cluster tests: real gRPC transport between daemons
(in one process, loopback sockets — the multi-process topology without the
test overhead). Covers EC write/read through remote OM + datanodes, the
datanode heartbeat/command loop, and reconstruction across the wire.
"""

import time

import numpy as np
import pytest

from ozone_tpu.client.dn_client import DatanodeClientFactory
from ozone_tpu.client.ozone_client import OzoneClient
from ozone_tpu.net.daemons import DatanodeDaemon, ScmOmDaemon
from ozone_tpu.net.om_service import GrpcOmClient
from ozone_tpu.storage.ids import BlockID, ChunkInfo, StorageError

EC = "rs-3-2-4096"


@pytest.fixture
def cluster(tmp_path):
    meta = ScmOmDaemon(
        tmp_path / "om.db",
        block_size=4 * 4096,
        container_size=1024 * 1024,
        stale_after_s=1000.0,
        dead_after_s=2000.0,
        background_interval_s=0.2,
    )
    meta.start()
    dns = []
    for i in range(6):
        d = DatanodeDaemon(
            tmp_path / f"dn{i}", f"dn{i}", meta.address,
            heartbeat_interval_s=0.2,
        )
        d.start()
        dns.append(d)
    yield meta, dns
    for d in dns:
        d.stop()
    meta.stop()


def _client(meta) -> OzoneClient:
    clients = DatanodeClientFactory()
    om = GrpcOmClient(meta.address, clients=clients)
    return OzoneClient(om, clients)


def test_grpc_echo_roundtrip(cluster):
    meta, dns = cluster
    from ozone_tpu.net.dn_service import GrpcDatanodeClient

    c = GrpcDatanodeClient("dn0", dns[0].address)
    assert c.echo(b"hello") == b"hello"
    c.close()


def test_remote_chunk_io(cluster):
    meta, dns = cluster
    from ozone_tpu.net.dn_service import GrpcDatanodeClient
    from ozone_tpu.utils.checksum import Checksum, ChecksumType

    c = GrpcDatanodeClient("dn0", dns[0].address)
    c.create_container(99)
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, 10_000, dtype=np.uint8)
    cs = Checksum(ChecksumType.CRC32C, 4096).compute(data)
    info = ChunkInfo("c0", 0, data.size, cs)
    bid = BlockID(99, 1)
    c.write_chunk(bid, info, data)
    got = c.read_chunk(bid, info, verify=True)
    assert np.array_equal(got, data)
    c.close()


def test_ec_key_over_grpc(cluster):
    meta, dns = cluster
    oz = _client(meta)
    b = oz.create_volume("v").create_bucket("b", replication=EC)
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, 60_000, dtype=np.uint8)
    b.write_key("k", data)
    got = b.read_key("k")
    assert np.array_equal(got, data)
    # degraded read over the wire: stop one datanode hosting the key
    info = oz.om.lookup_key("v", "b", "k")
    victim_id = info["block_groups"][0]["nodes"][0]
    victim = next(d for d in dns if d.dn.id == victim_id)
    victim.server.stop()
    got2 = b.read_key("k")
    assert np.array_equal(got2, data)




def test_fresh_client_reads_via_located_lookup(cluster):
    """A client (or gateway) that never wrote and never fetched the SCM
    topology must still read: key lookups carry the datanode address
    book (the OmKeyLocationInfo DatanodeDetails analog)."""
    meta, dns = cluster
    writer = _client(meta)
    b = writer.create_volume("lv").create_bucket("lb", replication=EC)
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, 40_000, dtype=np.uint8)
    b.write_key("k", data)

    reader = _client(meta)  # fresh factory: EMPTY address book
    rb = reader.get_volume("lv").get_bucket("lb")
    assert np.array_equal(rb.read_key("k"), data)
    # positioned read on another fresh client
    reader2 = _client(meta)
    got = reader2.get_volume("lv").get_bucket("lb").read_key_range(
        "k", 10_000, 5_000)
    assert np.array_equal(got, data[10_000:15_000])


def _await_replica_rebuild(meta, groups, victim_id,
                           timeout_s: float = 20.0) -> None:
    """Wait until every group's full replica-index set exists off the
    victim (the reconstruction convergence condition both repair tests
    share)."""
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if all(
            {r.replica_index
             for dn_id, r in
             meta.scm.containers.get(g.container_id).replicas.items()
             if dn_id != victim_id} == {1, 2, 3, 4, 5}
            for g in groups
        ):
            return
        time.sleep(0.2)
    raise AssertionError("reconstruction did not complete in time")


def _repoint_groups(meta, groups, victim_id) -> None:
    """Point each group's unit slots at the post-repair replica homes.
    NOTE: reads here bypass OM placement refresh on purpose — the OM
    hands out the placement captured at write time; repair-aware reads
    go through SCM container state, which is what this mimics."""
    for g in groups:
        c = meta.scm.containers.get(g.container_id)
        for dn_id, r in c.replicas.items():
            if r.replica_index and dn_id != victim_id:
                g.pipeline.nodes[r.replica_index - 1] = dn_id


def test_reconstruction_over_grpc(cluster):
    meta, dns = cluster
    # the daemons' coordinators repair on the device mesh (8 virtual
    # devices under the test harness) — the production multi-chip path
    # fed by real gRPC datanode reads
    assert all(d.reconstruction.mesh is not None
               and d.reconstruction.mesh.devices.size == 8 for d in dns)
    oz = _client(meta)
    b = oz.create_volume("v").create_bucket("b", replication=EC)
    rng = np.random.default_rng(2)
    data = rng.integers(0, 256, 40_000, dtype=np.uint8)
    b.write_key("k", data)

    info = oz.om.lookup_key("v", "b", "k")
    groups = oz.om.key_block_groups(info)
    # close the containers so the replication manager treats them
    for g in groups:
        for dn in dns:
            if dn.dn.id in g.pipeline.nodes:
                try:
                    dn.dn.close_container(g.container_id)
                except Exception:
                    pass

    victim_id = groups[0].pipeline.nodes[1]
    victim = next(d for d in dns if d.dn.id == victim_id)
    victim.stop()
    # age out only the victim: an ancient heartbeat exceeds dead_after
    meta.scm.nodes.get(victim_id).last_heartbeat = -1e9
    meta.scm.nodes.check_liveness()

    # wait for reconstruction driven by background loop + heartbeats
    _await_replica_rebuild(meta, groups, victim_id)

    # repoint groups at live replicas and verify bytes
    _repoint_groups(meta, groups, victim_id)
    from ozone_tpu.client.ec_reader import ECBlockGroupReader
    from ozone_tpu.codec.api import CoderOptions

    clients = oz.clients
    for dn_id, addr in meta.scm_service.addresses.items():
        if clients.maybe_get(dn_id) is None:
            clients.register_remote(dn_id, addr)
    parts = [
        ECBlockGroupReader(
            g, CoderOptions.parse(EC), clients, bytes_per_checksum=16 * 1024
        ).read_all()
        for g in groups
    ]
    assert np.array_equal(np.concatenate(parts), data)


def test_container_close_converges(tmp_path):
    """A full container goes CLOSING on the SCM, the close command
    reaches every replica over heartbeats, replicas close and report
    back, and the SCM marks it CLOSED (CloseContainerCommand round
    trip) — making it scannable for the background scrubber."""
    from ozone_tpu.client.dn_client import DatanodeClientFactory
    from ozone_tpu.client.ozone_client import OzoneClient
    from ozone_tpu.net.om_service import GrpcOmClient
    from ozone_tpu.storage.ids import ContainerState

    meta = ScmOmDaemon(
        tmp_path / "om.db",
        block_size=64 * 1024,
        container_size=128 * 1024,  # two blocks fill a container
        stale_after_s=1000.0,
        dead_after_s=2000.0,
        background_interval_s=0.2,
    )
    meta.start()
    dns = [
        DatanodeDaemon(tmp_path / f"dn{i}", f"dn{i}", meta.address,
                       heartbeat_interval_s=0.1)
        for i in range(5)
    ]
    for d in dns:
        d.start()
    try:
        clients = DatanodeClientFactory()
        oz = OzoneClient(GrpcOmClient(meta.address, clients=clients),
                         clients)
        oz.create_volume("v")
        b = oz.get_volume("v").create_bucket("b",
                                             replication="rs-3-2-4096")
        payload = np.random.default_rng(8).integers(
            0, 256, 64 * 1024, dtype=np.uint8).tobytes()
        for i in range(4):  # spans multiple containers
            b.write_key(f"k{i}", payload)
        deadline = time.monotonic() + 15
        closed = []
        while time.monotonic() < deadline:
            closed = [c for c in meta.scm.containers.containers()
                      if c.state is ContainerState.CLOSED]
            if closed:
                break
            time.sleep(0.2)
        assert closed, [
            (c.id, c.state.value)
            for c in meta.scm.containers.containers()
        ]
        # the replicas themselves are closed on the datanodes
        cid = closed[0].id
        on_dns = [d for d in dns
                  if d.dn.containers.get_or_none(cid) is not None]
        assert on_dns
        for d in on_dns:
            assert d.dn.containers.get(cid).state in (
                ContainerState.CLOSED, ContainerState.QUASI_CLOSED)
        # read-back still works from closed containers
        for i in range(4):
            assert b.read_key(f"k{i}").tobytes() == payload
    finally:
        for d in dns:
            d.stop()
        meta.stop()


def test_ratis_container_close_rides_the_raft_ring(tmp_path):
    """Closing a RATIS container is ordered through the pipeline raft
    group (never a bare per-replica close racing replicated writes), and
    a writer that hits the closed container reallocates instead of
    blacklisting healthy nodes."""
    from ozone_tpu.client.dn_client import DatanodeClientFactory
    from ozone_tpu.client.ozone_client import OzoneClient
    from ozone_tpu.net.om_service import GrpcOmClient
    from ozone_tpu.net.ratis_service import RatisClientFactory
    from ozone_tpu.net.scm_service import GrpcScmClient
    from ozone_tpu.storage.ids import ContainerState

    meta = ScmOmDaemon(
        tmp_path / "om.db",
        block_size=64 * 1024,
        container_size=128 * 1024,
        stale_after_s=1000.0,
        dead_after_s=2000.0,
        background_interval_s=0.2,
    )
    meta.start()
    dns = [
        DatanodeDaemon(tmp_path / f"dn{i}", f"dn{i}", meta.address,
                       heartbeat_interval_s=0.1)
        for i in range(3)
    ]
    for d in dns:
        d.start()
    try:
        clients = DatanodeClientFactory()
        om = GrpcOmClient(meta.address, clients=clients)
        for dn_id, addr in GrpcScmClient(
                meta.address).node_addresses().items():
            clients.register_remote(dn_id, addr)
        ratis = RatisClientFactory(address_source=clients.remote_address)
        oz = OzoneClient(om, clients, ratis_clients=ratis)
        oz.create_volume("v")
        b = oz.get_volume("v").create_bucket("b",
                                             replication="RATIS/THREE")
        payload = np.random.default_rng(9).integers(
            0, 256, 64 * 1024, dtype=np.uint8).tobytes()
        # enough keys to fill and roll containers while writing
        for i in range(5):
            b.write_key(f"k{i}", payload)
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            closed = [c for c in meta.scm.containers.containers()
                      if c.state is ContainerState.CLOSED]
            if closed:
                break
            time.sleep(0.2)
        assert closed
        # datanode replicas of the closed container converge to CLOSED
        cid = closed[0].id
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            states = {d.dn.id: d.dn.containers.get_or_none(cid)
                      for d in dns}
            vals = [c.state for c in states.values() if c is not None]
            if vals and all(
                    s in (ContainerState.CLOSED,
                          ContainerState.QUASI_CLOSED) for s in vals):
                break
            time.sleep(0.2)
        assert vals and all(
            s in (ContainerState.CLOSED, ContainerState.QUASI_CLOSED)
            for s in vals), states
        for i in range(5):
            assert b.read_key(f"k{i}").tobytes() == payload
    finally:
        for d in dns:
            d.stop()
        meta.stop()


def test_decommission_survives_scm_restart(tmp_path):
    """The node persists its operational state (set-op-state command)
    and echoes it at registration, so a restarted SCM relearns an
    in-progress drain (persistedOpState round trip)."""
    from ozone_tpu.net.scm_service import GrpcScmClient

    # huge background interval: the decommission monitor must not
    # finalize the (container-less) node to DECOMMISSIONED mid-test
    metas = [ScmOmDaemon(tmp_path / "om.db", stale_after_s=1000.0,
                         dead_after_s=2000.0,
                         background_interval_s=1000.0)]
    metas[0].start()
    dns = [
        DatanodeDaemon(tmp_path / f"dn{i}", f"dn{i}", metas[0].address,
                       heartbeat_interval_s=0.1)
        for i in range(3)
    ]
    for d in dns:
        d.start()
    try:
        port = int(metas[0].address.rsplit(":", 1)[1])
        scm = GrpcScmClient(metas[0].address)
        scm.admin("decommission", "dn1")
        # wait for the set-op-state command to reach and persist on dn1
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if dns[1]._op_state == "DECOMMISSIONING":
                break
            time.sleep(0.1)
        assert dns[1]._op_state == "DECOMMISSIONING"
        scm.close()

        metas.pop().stop()
        meta2 = ScmOmDaemon(tmp_path / "om.db", port=port,
                            stale_after_s=1000.0, dead_after_s=2000.0,
                            background_interval_s=1000.0)
        metas.append(meta2)
        meta2.start()
        # the restarted SCM's durable store already knows the drain —
        # before any datanode even re-registers
        assert meta2.scm.nodes._seeded_op.get("dn1") == "DECOMMISSIONING"
        deadline = time.monotonic() + 10
        node = None
        while time.monotonic() < deadline:
            node = meta2.scm.nodes.get("dn1")
            if node is not None:
                break
            time.sleep(0.1)
        assert node is not None
        assert node.op_state.value == "DECOMMISSIONING"
        # healthy nodes come back IN_SERVICE
        assert meta2.scm.nodes.get("dn0") is None or \
            meta2.scm.nodes.get("dn0").op_state.value == "IN_SERVICE"
    finally:
        for d in dns:
            d.stop()
        for m in metas:
            m.stop()


def test_hsync_and_recover_lease_over_grpc(cluster):
    """hsync/recover-lease ride the remote OM protocol (GrpcOmClient
    CommitKey hsync flag + RecoverLease verb)."""
    meta, dns = cluster
    oz = _client(meta)
    oz.create_volume("hv")
    b = oz.get_volume("hv").create_bucket("hb", replication="RATIS/THREE")
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, 30_000, dtype=np.uint8)
    h = b.open_key("k")
    h.write(data[:20_000])
    h.hsync()
    assert np.array_equal(b.read_key("k"), data[:20_000])
    out = oz.om.recover_lease("hv", "hb", "k")
    assert out["recovered"] is True
    assert np.array_equal(b.read_key("k"), data[:20_000])
    # fenced: the stale writer's close fails against the sealed key
    h.write(data[20_000:])
    with pytest.raises(StorageError) as ei:
        h.close()
    assert ei.value.code == "KEY_NOT_FOUND"
    assert np.array_equal(b.read_key("k"), data[:20_000])


def test_reconstruction_of_encrypted_key(cluster):
    """TDE composes with EC repair: reconstruction operates on
    ciphertext units (no DEK anywhere near the datanodes), and the
    repaired key decrypts byte-exactly. Placement is repointed from
    SCM container state like the sibling test — OM-served post-repair
    placement is NOT what is covered here."""
    # client-side AES-CTR rides the optional `cryptography` module
    pytest.importorskip("cryptography")
    meta, dns = cluster
    oz = _client(meta)
    meta.om.kms_create_key("reck")
    oz.create_volume("ev")
    meta.om.create_bucket("ev", "enc", EC, encryption_key="reck")
    b = oz.get_volume("ev").get_bucket("enc")
    rng = np.random.default_rng(9)
    data = rng.integers(0, 256, 40_000, dtype=np.uint8)
    b.write_key("k", data)

    info = oz.om.lookup_key("ev", "enc", "k")
    assert "edek" in info["encryption"]
    groups = oz.om.key_block_groups(info)
    for g in groups:
        for dn in dns:
            if dn.dn.id in g.pipeline.nodes:
                try:
                    dn.dn.close_container(g.container_id)
                except Exception:
                    pass
    victim_id = groups[0].pipeline.nodes[0]  # a DATA unit this time
    victim = next(d for d in dns if d.dn.id == victim_id)
    victim.stop()
    meta.scm.nodes.get(victim_id).last_heartbeat = -1e9
    meta.scm.nodes.check_liveness()

    _await_replica_rebuild(meta, groups, victim_id)

    # fresh client + fresh lookup; placement then repointed from SCM
    oz2 = _client(meta)
    for dn_id, addr in meta.scm_service.addresses.items():
        if oz2.clients.maybe_get(dn_id) is None:
            oz2.clients.register_remote(dn_id, addr)
    info2 = oz2.om.lookup_key("ev", "enc", "k")
    g2 = oz2.om.key_block_groups(info2)
    _repoint_groups(meta, g2, victim_id)
    info2["block_groups"] = [g.to_json() for g in g2]
    got = oz2.get_volume("ev").get_bucket("enc").read_key_info(info2)
    assert np.array_equal(got, data)


def test_volume_failure_triggers_reconstruction(cluster):
    """Disk-death flow end-to-end: a datanode volume fails its disk
    check, the replicas drop out of the next full container report, the
    SCM's accounting sees the loss, and the replication manager repairs
    the missing EC unit on another node (the reference's failed-volume
    -> ICR -> ReplicationManager chain)."""
    import shutil

    meta, dns = cluster
    oz = _client(meta)
    b = oz.create_volume("vvf").create_bucket("b", replication=EC)
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, 40_000, dtype=np.uint8)
    b.write_key("k", data)

    info = oz.om.lookup_key("vvf", "b", "k")
    groups = oz.om.key_block_groups(info)
    for g in groups:
        for dn in dns:
            if dn.dn.id in g.pipeline.nodes:
                try:
                    dn.dn.close_container(g.container_id)
                except Exception:
                    pass

    # kill the DISK (not the node) under one data unit
    victim_id = groups[0].pipeline.nodes[1]
    victim = next(d for d in dns if d.dn.id == victim_id)
    vol = victim.dn.volumes[0]
    shutil.rmtree(vol.root)
    assert victim.dn.check_volumes() == [str(vol.root)]
    assert victim.dn.container_report() == []  # all replicas were there

    # the victim node stays alive and heartbeating; repair must come
    # from the report delta, not a dead-node event
    _await_replica_rebuild(meta, groups, victim_id)

    _repoint_groups(meta, groups, victim_id)
    from ozone_tpu.client.ec_reader import ECBlockGroupReader
    from ozone_tpu.codec.api import CoderOptions

    clients = oz.clients
    for dn_id, addr in meta.scm_service.addresses.items():
        if clients.maybe_get(dn_id) is None:
            clients.register_remote(dn_id, addr)
    parts = [
        ECBlockGroupReader(
            g, CoderOptions.parse(EC), clients, bytes_per_checksum=16 * 1024
        ).read_all()
        for g in groups
    ]
    assert np.array_equal(np.concatenate(parts)[: data.size], data)


def test_a_slow_command_does_not_hold_the_datanodes_heartbeat(tmp_path):
    """The SCM's commands run on a thread of their own once the daemon's
    loops run: while a replication or a reconstruction takes its seconds
    the datanode keeps heartbeating (a late heartbeat made it STALE, and
    the SCM then refused every allocation that needs all nodes), and the
    commands still run one at a time, in the order they came."""
    import threading
    import time

    from ozone_tpu.testing.minicluster import MiniOzoneHACluster

    ha = MiniOzoneHACluster(tmp_path, num_meta=1, num_datanodes=1,
                            heartbeat_interval_s=0.05)
    try:
        d = ha.datanodes[0]
        started, release = threading.Event(), threading.Event()
        done: list[str] = []
        beats: list[float] = []
        real_execute, real_beat = d._execute, d.scm.heartbeat

        def execute(cmd):
            if cmd == {"type": "test-slow"}:
                started.set()
                assert release.wait(10)
                done.append("slow")
            elif cmd == {"type": "test-after"}:
                done.append("after")
            else:
                real_execute(cmd)

        def heartbeat(*a, **kw):
            out = list(real_beat(*a, **kw))
            beats.append(time.monotonic())
            if len(beats) == 1:
                out += [{"type": "test-slow"}, {"type": "test-after"}]
            return out

        d._execute, d.scm.heartbeat = execute, heartbeat
        assert started.wait(5)
        n = len(beats)
        deadline = time.monotonic() + 5
        while len(beats) < n + 3 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert len(beats) >= n + 3  # it kept beating under the command
        assert done == []           # which has not ended, nor the next
        release.set()
        deadline = time.monotonic() + 5
        while len(done) < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert done == ["slow", "after"]
        # ticking by hand (tests, drills) still executes before returning
        d._stop.set()
        d._hb.join(5)
        assert not d._hb.is_alive()
        d.scm.heartbeat = lambda *a, **kw: [{"type": "test-after"}]
        d.heartbeat_once()
        assert done == ["slow", "after", "after"]
    finally:
        ha.shutdown()
