"""Datanode Raft write pipeline + gRPC raft transport.

The reference covers this surface with ContainerStateMachine unit tests
and the MiniOzoneCluster Ratis write-path suites (TestXceiverServerRatis,
TestContainerStateMachine, watchForCommit tests in hadoop-hdds/client):
pipeline writes ordered through a per-pipeline Raft group, chunk data
persisted in the data phase and validated at the metadata commit point,
all-replica watch watermarks, and leader failover mid-stream.
"""

import threading

import itertools

import numpy as np
import pytest

from ozone_tpu.client.dn_client import DatanodeClientFactory
from ozone_tpu.client.ec_writer import BlockGroup
from ozone_tpu.client.ratis_client import RatisKeyWriter, XceiverClientRatis
from ozone_tpu.client.replicated import ReplicatedKeyReader
from ozone_tpu.consensus.raft import InProcessTransport, RaftConfig, RaftNode
from ozone_tpu.net.raft_transport import GrpcRaftTransport, RaftRpcService
from ozone_tpu.net.ratis_service import RatisClientFactory
from ozone_tpu.net.rpc import RpcServer
from ozone_tpu.scm.pipeline import Pipeline, ReplicationConfig
from ozone_tpu.storage.datanode import Datanode
from ozone_tpu.storage.ids import (
    BlockData,
    BlockID,
    ChunkInfo,
    ContainerState,
    StorageError,
)
from ozone_tpu.storage.ratis import ContainerStateMachine, RatisXceiverServer

FAST = RaftConfig(heartbeat_interval_s=0.05,
                  election_timeout_s=(0.15, 0.3))


# ------------------------------------------------------ state machine unit
def make_dn(tmp_path, name="dn0"):
    return Datanode(tmp_path / name, dn_id=name)


def test_state_machine_verbs(tmp_path):
    dn = make_dn(tmp_path)
    sm = ContainerStateMachine(dn)
    assert sm.apply({"verb": "create_container", "container_id": 1})["ok"]
    # idempotent re-apply (log replay after restart)
    assert sm.apply({"verb": "create_container", "container_id": 1})["ok"]

    bid = BlockID(1, 1)
    data = np.arange(100, dtype=np.uint8)
    info = ChunkInfo("c0", 0, 100)
    dn.write_chunk(bid, info, data)  # data phase
    out = sm.apply({"verb": "write_chunk_commit",
                    "block_id": bid.to_json(), "offset": 0, "length": 100})
    assert out["ok"]
    bd = BlockData(bid, [info])
    out = sm.apply({"verb": "put_block", "block": bd.to_json()})
    assert out["committed_length"] == 100
    assert sm.apply({"verb": "close_container", "container_id": 1})["ok"]
    assert dn.get_block(bid).committed
    dn.close()


def test_state_machine_missing_data_marks_unhealthy(tmp_path):
    """A member that missed the data phase must fail the commit apply and
    poison its replica for the replication manager."""
    dn = make_dn(tmp_path)
    sm = ContainerStateMachine(dn)
    sm.apply({"verb": "create_container", "container_id": 1})
    bid = BlockID(1, 1)
    with pytest.raises(StorageError) as ei:
        sm.apply({"verb": "write_chunk_commit",
                  "block_id": bid.to_json(), "offset": 0, "length": 4096})
    assert ei.value.code == "CHUNK_DATA_MISSING"
    assert dn.containers.get(1).state is ContainerState.UNHEALTHY
    dn.close()


# ------------------------------------------------- in-process pipeline ring
@pytest.fixture
def ring(tmp_path):
    """Three datanodes sharing one pipeline raft group, in-process."""
    transport = InProcessTransport()
    dns, xceivers = [], []
    ids = ["dn0", "dn1", "dn2"]
    peers = {i: "" for i in ids}
    for name in ids:
        dn = make_dn(tmp_path, name)
        xc = RatisXceiverServer(dn, tmp_path / name, "", config=FAST,
                                auto_timers=False)
        dns.append(dn)
        xceivers.append(xc)
    pipeline = Pipeline(ReplicationConfig.ratis(3), ids)
    for xc in xceivers:
        xc.join(pipeline.id, peers, transport=transport)
    # deterministic leadership: dn0
    assert xceivers[0].get(pipeline.id).start_election()
    yield dns, xceivers, pipeline
    for xc in xceivers:
        xc.stop()
    for dn in dns:
        dn.close()


#: module-global so successive write_key calls never re-issue a local
#: id — the datanode write fence (Container.bind_writer) now refuses a
#: second writer streaming into an existing block file, which is exactly
#: what a per-call counter restarting at 1 would do
_alloc_count = itertools.count(1)


def write_key(dns, xceivers, pipeline, payload, **kw):
    clients = DatanodeClientFactory()
    ratis = RatisClientFactory()
    for dn, xc in zip(dns, xceivers):
        clients.register_local(dn)
        ratis.register_local(xc, dn.id)
    alloc_count = _alloc_count

    def allocate_group(excluded):
        assert not set(pipeline.nodes) & set(excluded), \
            "pipeline members excluded mid-test"
        return BlockGroup(container_id=1, local_id=next(alloc_count),
                          pipeline=pipeline)

    w = RatisKeyWriter(allocate_group, clients, ratis, **kw)
    w.write(payload)
    groups = w.close()
    return groups, clients


def test_pipeline_write_replicates_to_all(ring):
    dns, xceivers, pipeline = ring
    payload = np.random.default_rng(7).integers(
        0, 256, 300_000, dtype=np.uint8)
    groups, clients = write_key(dns, xceivers, pipeline, payload,
                                chunk_size=64 * 1024)
    # read back through the normal replica-failover reader
    out = np.concatenate(
        [ReplicatedKeyReader(g, clients).read_all() for g in groups])
    assert np.array_equal(out, payload)
    # every member holds identical committed metadata (ordered history)
    for g in groups:
        lengths = {dn.id: dn.get_committed_block_length(g.block_id)
                   for dn in dns}
        assert set(lengths.values()) == {g.length}, lengths
        for dn in dns:
            assert dn.get_block(g.block_id).committed


def test_not_leader_rejected_and_hint_followed(ring):
    dns, xceivers, pipeline = ring
    # direct submit on a follower is rejected with the leader hint
    with pytest.raises(StorageError) as ei:
        xceivers[1].submit(pipeline.id, {"verb": "create_container",
                                         "container_id": 9})
    assert ei.value.code == "NOT_LEADER"
    assert ei.value.msg == "dn0"
    # the client-side xceiver follows the hint transparently
    ratis = RatisClientFactory()
    for dn, xc in zip(dns, xceivers):
        ratis.register_local(xc, dn.id)
    x = XceiverClientRatis(pipeline, ratis)
    x._leader = "dn1"  # wrong guess on purpose
    assert x.submit({"verb": "create_container", "container_id": 9})["ok"]
    assert x._leader == "dn0"


def test_watch_all_vs_majority(ring):
    dns, xceivers, pipeline = ring
    leader = xceivers[0].get(pipeline.id)
    transport = leader.transport
    # partition dn2 away from the leader: quorum (dn0+dn1) still commits
    transport.partition("dn0", "dn2")
    out = xceivers[0].submit(pipeline.id, {"verb": "create_container",
                                           "container_id": 2})
    idx = out["index"]
    # ALL cannot complete while dn2 is cut off...
    with pytest.raises(StorageError) as ei:
        xceivers[0].watch(pipeline.id, idx, policy="ALL", timeout=0.5)
    assert ei.value.code == "TIMEOUT"
    # ...MAJORITY can
    assert xceivers[0].watch(pipeline.id, idx, policy="MAJORITY",
                             timeout=5)["index"] == idx
    # heal: replication catches dn2 up and ALL completes
    transport.heal()
    assert xceivers[0].watch(pipeline.id, idx, policy="ALL",
                             timeout=5)["index"] == idx
    assert dns[2].containers.get_or_none(2) is not None


def test_leader_failover_mid_stream(ring):
    dns, xceivers, pipeline = ring
    payload = np.random.default_rng(3).integers(
        0, 256, 100_000, dtype=np.uint8)
    groups, clients = write_key(dns, xceivers, pipeline, payload,
                                chunk_size=32 * 1024)
    # depose dn0; dn1 takes over; further writes go through the new leader
    n0 = xceivers[0].get(pipeline.id)
    n1 = xceivers[1].get(pipeline.id)
    n0._step_down(n0.storage.term + 1)
    assert n1.start_election()
    more, _ = write_key(dns, xceivers, pipeline, payload,
                        chunk_size=32 * 1024)
    out = np.concatenate(
        [ReplicatedKeyReader(g, clients).read_all()
         for g in groups + more])
    assert np.array_equal(out, np.concatenate([payload, payload]))


def test_write_succeeds_with_minority_member_down(ring):
    """Raft availability: one of three members dead -> data phase reaches
    a quorum, commit goes through, watch degrades to MAJORITY."""
    dns, xceivers, pipeline = ring
    leader = xceivers[0].get(pipeline.id)
    transport = leader.transport
    transport.down.add("dn2")

    class DeadClient:
        dn_id = "dn2"

        def __getattr__(self, name):
            def boom(*a, **k):
                raise StorageError("IO_EXCEPTION", "dn2 is down")

            return boom

    clients = DatanodeClientFactory()
    ratis = RatisClientFactory()
    for dn, xc in zip(dns[:2], xceivers[:2]):
        clients.register_local(dn)
        ratis.register_local(xc, dn.id)
    clients._local["dn2"] = DeadClient()

    def allocate_group(excluded):
        return BlockGroup(container_id=1, local_id=1, pipeline=pipeline)

    payload = np.random.default_rng(9).integers(
        0, 256, 100_000, dtype=np.uint8)
    w = RatisKeyWriter(allocate_group, clients, ratis, chunk_size=32 * 1024,
                       watch_timeout_s=0.5)
    w.write(payload)
    groups = w.close()
    # the two live replicas hold the committed data
    out = ReplicatedKeyReader(groups[0], clients).read_all()
    assert np.array_equal(out, payload)
    for dn in dns[:2]:
        assert dn.get_committed_block_length(groups[0].block_id) \
            == groups[0].length
    # dn2 never saw the data; when it comes back and applies the log, the
    # commit apply poisons its replica for repair
    transport.heal()
    leader.tick()
    assert dns[2].containers.get(1).state is ContainerState.UNHEALTHY
    # the degrade is sticky: later watches skip the ALL timeout
    assert w._xceivers[pipeline.id]._degraded


def test_a_blocks_watch_is_sent_at_its_end_not_at_the_keys_close(tmp_path):
    """A full container closes through its ring after the writes it
    holds, and its (1:1) pipeline retires a heartbeat or two later: a
    key of many blocks must have watched its early blocks' commits by
    then, or its close finds their raft group gone from every member."""
    dns, xceivers, pipes, clients, ratis = _two_pipelines(tmp_path)
    allocated = []

    def allocate_group(excluded):
        if allocated:
            # the first block's container has closed and its pipeline
            # retired: every member has left that raft group
            for xc in xceivers:
                xc.leave(pipes[0].id)
        allocated.append(len(allocated) + 1)
        return BlockGroup(container_id=len(allocated),
                          local_id=next(_alloc_count),
                          pipeline=pipes[len(allocated) - 1])

    payload = np.random.default_rng(11).integers(
        0, 256, 3 * 64 * 1024, dtype=np.uint8)
    w = RatisKeyWriter(allocate_group, clients, ratis,
                       block_size=2 * 64 * 1024, chunk_size=64 * 1024)
    try:
        w.write(payload)
        groups = w.close()
        assert [g.pipeline.id for g in groups] == [p.id for p in pipes]
        out = np.concatenate(
            [ReplicatedKeyReader(g, clients).read_all() for g in groups])
        assert np.array_equal(out, payload)
    finally:
        for xc in xceivers:
            xc.stop()
        for dn in dns:
            dn.close()


def _two_pipelines(tmp_path):
    ids = ["dn0", "dn1", "dn2"]
    peers = {i: "" for i in ids}
    dns = [make_dn(tmp_path, name) for name in ids]
    xceivers = [RatisXceiverServer(dn, tmp_path / dn.id, "", config=FAST,
                                   auto_timers=False) for dn in dns]
    pipes = [Pipeline(ReplicationConfig.ratis(3), ids) for _ in range(2)]
    for p in pipes:
        transport = InProcessTransport()
        for xc in xceivers:
            xc.join(p.id, peers, transport=transport)
        assert xceivers[0].get(p.id).start_election()
    clients, ratis = DatanodeClientFactory(), RatisClientFactory()
    for dn, xc in zip(dns, xceivers):
        clients.register_local(dn)
        ratis.register_local(xc, dn.id)
    return dns, xceivers, pipes, clients, ratis


def test_a_block_of_a_retired_pipeline_is_reallocated_and_nobody_excluded(
        tmp_path, monkeypatch):
    """A block allocated before its container filled: by the time the
    writer opens it the container has closed and its pipeline retired.
    Every member answers NO_SUCH_RAFT_GROUP for good; the writer learns
    it within the join grace (not the whole failover ladder), asks for a
    block elsewhere, excludes that CONTAINER and none of the healthy
    members."""
    import time

    dns, xceivers, pipes, clients, ratis = _two_pipelines(tmp_path)
    monkeypatch.setattr(XceiverClientRatis, "JOIN_GRACE_S", 0.3)
    for xc in xceivers:
        xc.leave(pipes[0].id)  # retired before the writer's first verb
    asked = []

    def allocate_group(excluded, excluded_containers):
        asked.append((list(excluded), list(excluded_containers)))
        return BlockGroup(container_id=len(asked),
                          local_id=next(_alloc_count),
                          pipeline=pipes[len(asked) - 1])

    payload = np.random.default_rng(12).integers(
        0, 256, 100_000, dtype=np.uint8)
    w = RatisKeyWriter(allocate_group, clients, ratis,
                       chunk_size=64 * 1024)
    try:
        t0 = time.monotonic()
        w.write(payload)
        groups = w.close()
        assert time.monotonic() - t0 < 4.0
        assert asked == [([], []), ([], [1])]
        assert [g.pipeline.id for g in groups] == [pipes[1].id]
        out = np.concatenate(
            [ReplicatedKeyReader(g, clients).read_all() for g in groups])
        assert np.array_equal(out, payload)
    finally:
        for xc in xceivers:
            xc.stop()
        for dn in dns:
            dn.close()


def test_a_container_refused_at_the_commit_is_not_taken_again(tmp_path):
    """INVALID_CONTAINER_STATE from the commit (the replica closed or
    went unhealthy between the data phase and the ring's apply): the
    retry excludes the container, or the SCM, which still has it OPEN,
    hands it out until the writer runs out of tries."""
    dns, xceivers, pipes, clients, ratis = _two_pipelines(tmp_path)
    asked = []

    def allocate_group(excluded, excluded_containers):
        asked.append(list(excluded_containers))
        cid = 1 if 1 not in excluded_containers else 2
        return BlockGroup(container_id=cid, local_id=next(_alloc_count),
                          pipeline=pipes[cid - 1])

    payload = np.arange(50_000, dtype=np.uint32).astype(np.uint8)
    w = RatisKeyWriter(allocate_group, clients, ratis,
                       chunk_size=64 * 1024)
    real = w._commit_chunk

    def commit(group, info):
        if group.container_id == 1:
            raise StorageError("INVALID_CONTAINER_STATE",
                               "container 1 is UNHEALTHY, not writable")
        real(group, info)

    w._commit_chunk = commit
    try:
        w.write(payload)
        groups = w.close()
        assert asked == [[], [1]]
        assert [g.container_id for g in groups] == [2]
    finally:
        for xc in xceivers:
            xc.stop()
        for dn in dns:
            dn.close()


def test_join_replaces_group_with_changed_membership(tmp_path):
    """Defense in depth: a served group whose announced membership
    differs is stale metadata — it must be replaced, never reused."""
    transport = InProcessTransport()
    dn = make_dn(tmp_path, "dnA")
    xc = RatisXceiverServer(dn, tmp_path / "dnA", "", config=FAST,
                            auto_timers=False)
    n1 = xc.join(77, {"dnA": "", "dnB": "", "dnC": ""},
                 transport=transport)
    assert set(n1.peer_ids) == {"dnB", "dnC"}
    n2 = xc.join(77, {"dnA": "", "dnB": "", "dnD": ""},
                 transport=InProcessTransport())
    assert n2 is not n1
    assert set(n2.peer_ids) == {"dnB", "dnD"}
    xc.stop()
    dn.close()


def test_pipeline_ids_survive_scm_restart(tmp_path):
    """Pipeline ids are persisted and the allocator advances past them on
    recovery: a restarted SCM can never re-issue an id a datanode still
    serves a raft group under."""
    from ozone_tpu.scm.container_manager import ContainerManager
    from ozone_tpu.scm.node_manager import NodeManager
    from ozone_tpu.scm.placement import RandomPlacement

    def make_cm():
        nodes = NodeManager(stale_after_s=1e6, dead_after_s=2e6)
        for i in range(3):
            nodes.register(f"dn{i}", "/r1", 0)
        return ContainerManager(nodes, RandomPlacement(nodes),
                                db_path=tmp_path / "scm.db")

    cm = make_cm()
    g = cm.allocate_block(ReplicationConfig.ratis(3), 1024)
    pid = g.pipeline.id

    cm2 = make_cm()  # restart on the same db
    recovered = {p.id: p for p in cm2.pipelines()}
    assert pid in recovered
    assert recovered[pid].nodes == g.pipeline.nodes
    g2 = cm2.allocate_block(ReplicationConfig.ratis(3), 1024)
    # same still-open container (and pipeline) is reused after recovery
    assert g2.pipeline.id == pid
    # forcing a new pipeline allocates a strictly fresh id
    cm2.finalize_container(g2.container_id)
    g3 = cm2.allocate_block(ReplicationConfig.ratis(3), 1024)
    assert g3.pipeline.id > pid


def test_closed_pipeline_is_retired(tmp_path):
    """Closing a container fires the pipeline-closed hook exactly once
    and drops the pipeline from the live set (the leave-pipeline path)."""
    from ozone_tpu.scm.container_manager import ContainerManager
    from ozone_tpu.scm.node_manager import NodeManager
    from ozone_tpu.scm.placement import RandomPlacement

    nodes = NodeManager(stale_after_s=1e6, dead_after_s=2e6)
    for i in range(3):
        nodes.register(f"dn{i}", "/r1", 0)
    cm = ContainerManager(nodes, RandomPlacement(nodes))
    closed = []
    cm.on_pipeline_closed = closed.append
    g = cm.allocate_block(ReplicationConfig.ratis(3), 1024)
    assert cm.pipelines() and not closed
    cm.finalize_container(g.container_id)
    cm.mark_closed(g.container_id)  # idempotent second transition
    assert [p.id for p in closed] == [g.pipeline.id]
    assert g.pipeline.id not in {p.id for p in cm.pipelines()}


# -------------------------------------------------------- full daemon wiring
def test_daemon_cluster_ratis_key_roundtrip(tmp_path):
    """SCM announces the pipeline, datanode daemons join the raft group
    over heartbeat commands, and a RATIS/THREE key write is ordered
    through the elected leader — the whole deployment shape."""
    import time as _time

    from ozone_tpu.client.ozone_client import OzoneClient
    from ozone_tpu.net.daemons import DatanodeDaemon, ScmOmDaemon
    from ozone_tpu.net.om_service import GrpcOmClient
    from ozone_tpu.net.ratis_service import RatisClientFactory

    meta = ScmOmDaemon(tmp_path / "om.db", block_size=256 * 1024,
                       stale_after_s=1000.0, dead_after_s=2000.0,
                       background_interval_s=0.2)
    meta.start()
    dns = []
    try:
        for i in range(3):
            d = DatanodeDaemon(tmp_path / f"dn{i}", f"dn{i}", meta.address,
                               heartbeat_interval_s=0.1)
            d.start()
            dns.append(d)
        for _ in range(50):
            if not meta.scm.safemode.in_safemode():
                break
            _time.sleep(0.1)

        clients = DatanodeClientFactory()
        om = GrpcOmClient(meta.address, clients=clients)
        from ozone_tpu.net.scm_service import GrpcScmClient

        for dn_id, addr in GrpcScmClient(
                meta.address).node_addresses().items():
            clients.register_remote(dn_id, addr)
        ratis = RatisClientFactory(address_source=clients.remote_address)
        oz = OzoneClient(om, clients, ratis_clients=ratis)

        oz.create_volume("v")
        b = oz.get_volume("v").create_bucket("b", replication="RATIS/THREE")
        payload = np.random.default_rng(5).integers(
            0, 256, 200_000, dtype=np.uint8).tobytes()
        b.write_key("k", payload)
        out = b.read_key("k")
        assert out.tobytes() == payload

        # each daemon serves the pipeline group; replicas agree
        served = [d.xceiver_ratis.pipelines() for d in dns]
        assert all(served[0] == s and s for s in served), served
        info = om.lookup_key("v", "b", "k")
        for g in om.key_block_groups(info):
            lengths = {d.dn.id: d.dn.get_committed_block_length(g.block_id)
                       for d in dns}
            assert set(lengths.values()) == {g.length}, lengths

        # restart a datanode: it rejoins its groups from local state
        dns[1].stop()
        d1 = DatanodeDaemon(tmp_path / "dn1", "dn1", meta.address,
                            heartbeat_interval_s=0.1)
        d1.start()
        dns[1] = d1
        assert d1.xceiver_ratis.pipelines() == served[0]
        b.write_key("k2", payload)
        assert b.read_key("k2").tobytes() == payload
    finally:
        for d in dns:
            d.stop()
        meta.stop()


# ------------------------------------------------------- grpc raft transport
def test_grpc_raft_transport_election_and_commit(tmp_path):
    """Three raft peers on real RpcServers: elect, commit, route around a
    stopped peer — the multi-process deployment path of consensus."""
    ids = ["a", "b", "c"]
    servers, services = {}, {}
    for nid in ids:
        srv = RpcServer("127.0.0.1", 0)
        services[nid] = RaftRpcService(srv)
        srv.start()
        servers[nid] = srv
    addrs = {nid: servers[nid].address for nid in ids}

    states = {nid: [] for nid in ids}
    nodes = {}
    for nid in ids:
        tr = GrpcRaftTransport("g1", addrs)
        node = RaftNode(
            node_id=nid, peer_ids=[p for p in ids if p != nid],
            storage_dir=tmp_path / nid,
            apply_fn=states[nid].append, config=FAST, transport=tr,
        )
        services[nid].register("g1", node)
        nodes[nid] = node
    try:
        assert nodes["a"].start_election()
        assert nodes["a"].propose({"op": "put", "k": 1}) is None
        nodes["a"].tick()
        assert states["a"] == [{"op": "put", "k": 1}]
        assert states["b"] == [{"op": "put", "k": 1}]
        assert states["c"] == [{"op": "put", "k": 1}]
        # peer c goes away: quorum continues
        services["c"].unregister("g1")
        servers["c"].stop()
        assert nodes["a"].propose({"op": "put", "k": 2}) is None
        nodes["a"].tick()  # push the commit index to b
        assert states["b"][-1] == {"op": "put", "k": 2}
    finally:
        for nid in ids:
            nodes[nid].stop()
        for nid in ("a", "b"):
            servers[nid].stop()


def test_grpc_ratis_pipeline_end_to_end(tmp_path):
    """Full remote shape: three datanodes with RatisXceiverServers over
    real gRPC (raft RPCs and client submit/watch both on the wire)."""
    from ozone_tpu.net.ratis_service import RatisGrpcService

    ids = ["dn0", "dn1", "dn2"]
    dns, xcs, rpc_servers = [], [], []
    for name in ids:
        dn = Datanode(tmp_path / name, dn_id=name)
        srv = RpcServer("127.0.0.1", 0)
        raft_svc = RaftRpcService(srv)
        xc = RatisXceiverServer(dn, tmp_path / name, "", rpc_service=raft_svc,
                                config=FAST)
        RatisGrpcService(xc, srv)
        srv.start()
        dns.append(dn)
        xcs.append(xc)
        rpc_servers.append(srv)
    addrs = {name: srv.address for name, srv in zip(ids, rpc_servers)}
    pipeline = Pipeline(ReplicationConfig.ratis(3), ids)
    try:
        for xc in xcs:
            xc.join(pipeline.id, addrs)
        assert xcs[0].get(pipeline.id).start_election()

        clients = DatanodeClientFactory()
        ratis = RatisClientFactory()
        for dn in dns:
            clients.register_local(dn)  # data phase stays in-process here
        for name, srv in zip(ids, rpc_servers):
            ratis.register_remote(name, srv.address)

        payload = np.random.default_rng(11).integers(
            0, 256, 150_000, dtype=np.uint8)

        def allocate_group(excluded):
            return BlockGroup(container_id=1, local_id=1, pipeline=pipeline)

        w = RatisKeyWriter(allocate_group, clients, ratis,
                           chunk_size=64 * 1024)
        w.write(payload)
        groups = w.close()
        out = np.concatenate(
            [ReplicatedKeyReader(g, clients).read_all() for g in groups])
        assert np.array_equal(out, payload)
        for dn in dns:
            assert dn.get_committed_block_length(groups[0].block_id) \
                == groups[0].length
    finally:
        for xc in xcs:
            xc.stop()
        for srv in rpc_servers:
            srv.stop()
        for dn in dns:
            dn.close()
