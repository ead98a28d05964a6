"""The node-loss repair storm on a mesh of 4 of the 8 forced host
devices: the jitted sharded decode THROUGH `MeshExecutor` against the
benchmark's plain reference for every lost unit of rs-6-3, the storm's
plan from the SCM's RPC listing against its plan from the in-process
SCM, and ten streams over nine erasure patterns rebuilding every replica
byte-exact with `mesh:*` stages in each repair's stage record."""

import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np
import pytest

from ozone_tpu.client.reconstruction import ReconstructionStorm
from ozone_tpu.codec import service as codec_service
from ozone_tpu.codec.api import CoderOptions
from ozone_tpu.codec.fused import FusedSpec
from ozone_tpu.parallel import mesh_executor
from ozone_tpu.parallel.mesh_executor import MeshExecutor
from ozone_tpu.parallel.sharded import make_mesh
from ozone_tpu.storage.reconstruction import ReconstructionCommand
from ozone_tpu.testing.minicluster import MiniOzoneCluster
from ozone_tpu.utils.checksum import ChecksumType
from ozone_tpu.utils.tracing import Tracer

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from benchmarks.harness import reference  # noqa: E402

K, P, CELL, BPC = 6, 3, 4096, 1024
OPTS = CoderOptions(K, P, "rs", cell_size=CELL)
SPEC = FusedSpec(OPTS, ChecksumType.CRC32C, bytes_per_checksum=BPC)
STRIPES_PER_KEY = 5
KEY_BYTES = STRIPES_PER_KEY * K * CELL


@pytest.fixture
def mesh4():
    ex = MeshExecutor(mesh=make_mesh(4), depth=2)
    yield ex
    ex.close()


# ------------------------------------ (a) the jitted program, every unit
@pytest.mark.parametrize("lost", range(K + P))
def test_jitted_sharded_decode_through_the_executor_is_the_references(
        mesh4, monkeypatch, lost):
    """Not the host twin: the SPMD program `sharded_decode_apply` on 4
    devices, fed by the executor's lane (padded to its width), for a
    submission that is no multiple of 4 stripes and one that is."""
    monkeypatch.setenv("OZONE_TPU_FUSED_BACKEND", "jax")
    rng = np.random.default_rng([27, lost])
    data = rng.integers(0, 256, (13, K, CELL), dtype=np.uint8)
    units = np.concatenate([data, reference.encode(K, P, data)], axis=1)
    # the survivors the reader would take: the first k of the other 8
    # for a lost parity unit, the last k for a lost data unit
    others = [u for u in range(K + P) if u != lost]
    valid = others[:K] if lost >= K else others[-K:]
    key = codec_service.decode_key(SPEC, valid, [lost])
    before = mesh_executor.METRICS.snapshot()
    futs = [mesh4.submit(key, units[a:b][:, valid], width=2)
            for a, b in ((0, 5), (5, 13))]
    for (a, b), fut in zip(((0, 5), (5, 13)), futs):
        rec, crcs = fut.result(timeout=300)
        want = reference.recover(K, P, valid, [lost], units[a:b][:, valid])
        assert np.array_equal(np.asarray(rec), want)
        assert np.array_equal(np.asarray(rec)[:, 0], units[a:b, lost])
        want_crcs = reference.crc32c_slices(want.reshape(-1), BPC)
        assert np.array_equal(
            np.asarray(crcs, dtype=np.uint32).reshape(-1), want_crcs)
    prog = mesh4._programs[key]
    assert not prog.host_twin and prog.compile_count() >= 1
    assert prog.jitted[0].__wrapped__.__name__ == "sharded_decode_apply"
    after = mesh_executor.METRICS.snapshot()
    dispatches = after["dispatches"] - before.get("dispatches", 0)
    # every dispatch's output lay on all 4 devices of the mesh
    assert after["output_shards"] == 4
    assert (after["output_shards_dispatched"]
            - before.get("output_shards_dispatched", 0)) == 4 * dispatches


# ------------------------------------------- a small cluster of rs-6-3
def _write_keys(cluster, n: int, seed: int) -> dict[str, np.ndarray]:
    bucket = cluster.client().create_volume("v").create_bucket(
        "b", replication=f"rs-{K}-{P}-{CELL}")
    rng = np.random.default_rng(seed)
    payloads = {}
    for i in range(n):
        payloads[f"k{i}"] = rng.integers(0, 256, KEY_BYTES, dtype=np.uint8)
        bucket.write_key(f"k{i}", payloads[f"k{i}"])
    cluster.heartbeat_all()  # container reports -> SCM replica maps
    return payloads


@pytest.fixture
def cluster(tmp_path):
    c = MiniOzoneCluster(tmp_path, num_datanodes=K + P + 2,
                         container_size=KEY_BYTES,
                         stale_after_s=1000.0, dead_after_s=2000.0)
    yield c
    c.close()


# --------------------------------- (b) one plan, from either SCM view
def test_plan_from_the_rpc_listing_is_the_plan_from_the_scm(cluster, mesh4):
    from ozone_tpu.net.rpc import RpcServer
    from ozone_tpu.net.scm_service import GrpcScmClient, ScmGrpcService

    _write_keys(cluster, 8, seed=3)
    held: dict[str, int] = {}
    for c in cluster.scm.containers.containers():
        for dn_id in c.replicas:
            held[dn_id] = held.get(dn_id, 0) + 1
    victim = max(sorted(held), key=held.get)
    # one container is worse off than its peers, and one node is out of
    # service: the order and the targets both have something to get wrong
    weakest = next(c for c in cluster.scm.containers.containers()
                   if victim in c.replicas)
    other = next(d for d in sorted(weakest.replicas) if d != victim)
    cluster.datanode(other).delete_container(weakest.id, force=True)
    del weakest.replicas[other]
    spare = next(d.id for d in cluster.datanodes if d.id not in held
                 or held[d.id] == min(held.values()))
    cluster.scm.decommission(spare)
    cluster.stop_datanode(victim)

    server = RpcServer()
    ScmGrpcService(cluster.scm, server)
    server.start()
    rpc = GrpcScmClient(server.address)
    try:
        assert rpc.list_nodes() == cluster.scm.list_nodes()
        local = ReconstructionStorm(
            cluster.scm, cluster.clients, executor=mesh4).plan(victim)
        remote = ReconstructionStorm(
            rpc, cluster.clients, executor=mesh4).plan(victim)
    finally:
        rpc.close()
        server.stop()
    assert len(local) == held[victim] >= 2
    assert remote == local
    assert local[0].container_id == weakest.id
    for cmd in local:
        assert victim not in cmd.targets.values()
        assert spare not in cmd.targets.values()
        assert not set(cmd.targets.values()) & set(cmd.sources.values())
        assert sorted(cmd.targets) == sorted(
            set(range(1, K + P + 1)) - set(cmd.sources))


# ------------------- (c) ten streams, nine patterns, through the mesh
def test_ten_streams_over_nine_patterns_rebuild_every_replica(
        cluster, mesh4):
    payloads = _write_keys(cluster, 18, seed=4)
    containers = [c for c in cluster.scm.containers.containers()
                  if len(c.replicas) == K + P]
    assert len(containers) >= 10, len(containers)
    storm = ReconstructionStorm(cluster.scm, cluster.clients,
                                executor=mesh4, bytes_per_checksum=BPC,
                                max_parallel_containers=10)
    assert storm.max_parallel_containers == 10
    # container -> the key written into it, for the reference
    om = cluster.client().om
    key_of = {}
    for name in payloads:
        for g in om.key_block_groups(om.lookup_key("v", "b", name)):
            key_of[g.container_id] = (name, g)

    # two rounds: every container loses one unit, then another; the lost
    # unit rotates over all 9, so 9 erasure patterns meet in the lanes
    jobs = []
    for rnd in range(2):
        for j, c in enumerate(containers):
            by_unit = {r.replica_index - 1: dn
                       for dn, r in c.replicas.items()}
            unit = (rnd * 4 + j) % (K + P)
            jobs.append((rnd, c.id, unit, by_unit))
    assert {u for _r, _c, u, _n in jobs} == set(range(K + P))

    def repair(job):
        _rnd, cid, unit, by_unit = job
        cluster.datanode(by_unit[unit]).delete_container(cid, force=True)
        storm.repair_container(ReconstructionCommand(
            cid, OPTS,
            sources={u + 1: dn for u, dn in by_unit.items() if u != unit},
            targets={unit + 1: by_unit[unit]}))

    before = mesh_executor.METRICS.snapshot()
    single0 = codec_service.METRICS.counter("stripes_dispatched").value
    t0 = time.monotonic()
    for rnd in range(2):  # no two repairs of one container in flight
        with ThreadPoolExecutor(
                max_workers=storm.max_parallel_containers) as pool:
            list(pool.map(repair, [j for j in jobs if j[0] == rnd]))
    mesh4.quiesce()
    after = mesh_executor.METRICS.snapshot()

    # every decode stripe went through the mesh, none past it
    assert codec_service.METRICS.counter(
        "stripes_dispatched").value == single0
    assert (after["stripes_dispatched"] - before["stripes_dispatched"]
            == len(jobs) * STRIPES_PER_KEY)
    assert after["dispatches"] > before["dispatches"]

    # byte-exact against the reference, read off the targets themselves
    for _rnd, cid, unit, by_unit in jobs:
        name, g = key_of[cid]
        data = payloads[name].reshape(-1, K, CELL)
        want = np.concatenate(
            [data, reference.encode(K, P, data)], axis=1)[:, unit]
        dn = cluster.datanode(by_unit[unit])
        blk = dn.get_block(g.block_id)
        assert blk.block_group_length == g.length
        got = np.concatenate([
            np.asarray(dn.read_chunk(g.block_id, info, verify=True),
                       dtype=np.uint8).reshape(-1)
            for info in sorted(blk.chunks, key=lambda i: i.offset)])
        assert np.array_equal(got, want.reshape(-1)), (cid, unit)
        stored = np.concatenate([
            np.array([int.from_bytes(s, "big")
                      for s in info.checksum.checksums], dtype=np.uint32)
            for info in sorted(blk.chunks, key=lambda i: i.offset)])
        assert np.array_equal(
            stored, reference.crc32c_slices(want.reshape(-1), BPC))

    # each repair's stage record sums to its root, mesh stages in it
    records = Tracer.instance().recorder.operations(
        "repair:container", t0, float("inf"))
    assert len(records) == len(jobs)
    seen = set()
    for r in records:
        assert abs(sum(r["stages"].values()) - r["durationUs"]) \
            <= len(r["stages"])
        mesh_stages = {s for s in r["stages"] if s.startswith("mesh:")}
        assert mesh_stages, r["stages"]
        assert not any(s.startswith("codec:") for s in r["stages"])
        seen |= mesh_stages
    assert seen == {"mesh:queue_wait", "mesh:device_dispatch"}
