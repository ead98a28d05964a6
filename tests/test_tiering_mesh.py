"""The tiering sweep on a mesh: `TieringExecutor.transition_keys` over
replicated keys lands on the mesh executor's encode lane through the
door, packs windows of that lane's width across keys, lays every key out
as a PUT would, and what it leaves on the datanodes is what the plain
reference (`benchmarks/harness/reference.py`) says, unit for unit and
CRC for CRC; the same with the OM behind RPC, a user's overwrite raced
in; and the sweep's spans and counters."""

import numpy as np
import pytest

from benchmarks.harness import storecheck
from ozone_tpu.codec import service as codec_service
from ozone_tpu.lifecycle.executor import METRICS, TieringExecutor
from ozone_tpu.parallel import mesh_executor
from ozone_tpu.parallel.sharded import make_mesh
from ozone_tpu.testing.minicluster import (
    MiniOzoneCluster,
    MiniOzoneHACluster,
)
from ozone_tpu.utils.tracing import Tracer

K, P, CELL = 6, 3, 4096
STRIPE = K * CELL
EC = f"rs-{K}-{P}-{CELL}"
SCHEME = {"k": K, "p": P, "cell": CELL, "bpc": CELL}
#: 8 stripes a block group
BLOCK = 8 * CELL


@pytest.fixture
def mesh4(monkeypatch):
    """The process-wide executor the door asks for, over 4 of the
    tests' host devices; two stripes a device, so a lane's dispatch (the
    packer's window) is 8 stripes."""
    monkeypatch.setenv("OZONE_TPU_TIER_BATCH", "2")
    codec_service.reset_for_tests()
    mesh_executor.reset_for_tests()
    ex = mesh_executor.MeshExecutor(mesh=make_mesh(4), depth=2)
    monkeypatch.setattr(mesh_executor, "_executor", ex)
    yield ex
    mesh_executor.reset_for_tests()
    codec_service.reset_for_tests()


@pytest.fixture
def cluster(tmp_path):
    c = MiniOzoneCluster(tmp_path, num_datanodes=9, block_size=BLOCK,
                         container_size=4 * 1024 * 1024,
                         stale_after_s=1000.0, dead_after_s=2000.0)
    yield c
    c.close()


def _counters() -> dict:
    out = {f"lifecycle/{n}": c.value for n, c in METRICS._counters.items()}
    for prefix, reg in (("mesh", mesh_executor.METRICS),
                        ("codec.service", codec_service.METRICS)):
        out.update({f"{prefix}/{n}": c.value
                    for n, c in reg._counters.items()})
    return out


def _delta(after: dict, before: dict, name: str) -> float:
    return after.get(name, 0) - before.get(name, 0)


def _write(bucket, sizes: dict, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    datas = {}
    for name, stripes in sizes.items():
        datas[name] = rng.integers(0, 256, stripes * STRIPE, dtype=np.uint8)
        bucket.write_key(name, datas[name])
    return datas


def _stored_as_the_reference_says(om, clients, name: str,
                                  payload: np.ndarray) -> storecheck.Tally:
    """Every unit of every block group of the key, read straight off
    its datanode, against the reference's encode of the payload."""
    tally = storecheck.Tally()
    info = om.lookup_key("v", "b", name)
    assert info["replication"] == EC
    at = 0
    for g in om.key_block_groups(info):
        units = storecheck.expected_units(SCHEME, payload[at:at + g.length])
        at += g.length
        for u, dn_id in enumerate(g.pipeline.nodes):
            storecheck.check_unit(clients.get(dn_id), g.block_id, g.length,
                                  units[:, u], SCHEME, tally,
                                  f"{name} unit {u}")
    assert at == payload.size
    return storecheck.finish(tally, SCHEME)


def test_the_source_is_one_range_over_the_blocks_it_was_written_in(
        monkeypatch):
    """Source blocks end where no stripe does (16 MiB blocks, 6 MiB
    stripes): a window's read takes each block's part and copies none."""
    from types import SimpleNamespace

    from ozone_tpu.client import replicated
    from ozone_tpu.lifecycle.executor import _Source

    data = np.arange(1000, dtype=np.uint32).astype(np.uint8)
    cuts = [0, 300, 600, 1000]

    class Reader:
        def __init__(self, group, clients):
            self.group = group

        def read(self, offset, length):
            return data[self.group.at + offset:
                        self.group.at + offset + length]

    monkeypatch.setattr(replicated, "ReplicatedKeyReader", Reader)
    src = _Source([SimpleNamespace(at=a, length=b - a)
                   for a, b in zip(cuts, cuts[1:])], clients=None)
    assert src.length == 1000
    for lo, n in ((0, 1000), (250, 100), (300, 300), (590, 20), (999, 1)):
        parts = src.read(lo, n)
        assert [off for off, _ in parts] == \
            [max(lo, a) for a, b in zip(cuts, cuts[1:])
             if a < lo + n and b > lo]
        assert np.array_equal(np.concatenate([d for _, d in parts]),
                              data[lo:lo + n])


# ------------------------------------------- (a) and (c): on the mesh
@pytest.mark.parametrize("backend", ["host_twin", "jax"])
def test_a_sweep_lands_on_the_mesh_lane_and_stores_the_references_units(
        cluster, mesh4, monkeypatch, backend):
    """Keys of 2, 4 and 12 stripes: 18 stripes in windows of 8, so the
    12-stripe key rides two dispatches and the last window goes out with
    a zero-padded tail; `jax` is the jitted SPMD program
    `sharded_fused_encode` on the 4 devices, not the host twin."""
    if backend == "jax":
        monkeypatch.setenv("OZONE_TPU_FUSED_BACKEND", "jax")
    oz = cluster.client()
    oz.create_volume("v").create_bucket("b", replication="RATIS/THREE")
    b = oz.get_volume("v").get_bucket("b")
    datas = _write(b, {"cold-a": 2, "cold-b": 4, "cold-c": 12}, seed=33)

    ex = TieringExecutor(cluster.om, cluster.clients)
    before = _counters()
    t0 = Tracer.instance().recorder.operations("tier:key")
    stats = ex.transition_keys([("v", "b", n, EC) for n in datas])
    after = _counters()

    assert stats == {"transitioned": 3, "conflicts": 0, "failed": 0,
                     "skipped": 0, "bytes": 18 * STRIPE, "dispatches": 3}
    # the window is the lane's width, learned from the door
    assert ex.last_window == mesh4.dispatch_width(2) == 8
    assert _delta(after, before, "mesh/stripes_dispatched") == 18
    assert _delta(after, before, "mesh/slots_dispatched") == 24
    assert _delta(after, before, "mesh/dispatches") == 3
    assert _delta(after, before, "codec.service/stripes_dispatched") == 0
    assert mesh4.stats()["programs_host_twin"] == (backend == "host_twin")
    assert _delta(after, before, "lifecycle/stripes_packed") == 18
    assert _delta(after, before, "lifecycle/pad_stripes") == 6
    assert _delta(after, before, "lifecycle/windows_submitted") == 3
    assert _delta(after, before, "lifecycle/keys_split") == 1  # cold-c

    for name, want in datas.items():
        assert np.array_equal(b.read_key(name), want)
        tally = _stored_as_the_reference_says(
            cluster.om, cluster.clients, name, want)
        assert (tally.records_wrong, tally.stored_bytes_differ,
                tally.stored_crcs_differ, tally.first_error) == (0, 0, 0, "")
        groups = cluster.om.key_block_groups(
            cluster.om.lookup_key("v", "b", name))
        # a PUT's geometry: 8 stripes a group, whatever the source's
        assert [g.length for g in groups] == \
            [min(8 * STRIPE, want.size - at)
             for at in range(0, want.size, 8 * STRIPE)]
        assert tally.units_compared == len(groups) * (K + P)

    # one stage record a key; its stages partition its duration, and the
    # window's mesh spans are in the trace of EVERY key that rode it
    ops = Tracer.instance().recorder.operations("tier:key")[len(t0):]
    assert len(ops) == 3
    for op in ops:
        assert sum(op["stages"].values()) == pytest.approx(
            op["durationUs"], abs=len(op["stages"]))
        assert {"tier:key", "tier:read", "tier:pack", "tier:write",
                "tier:finalize", "mesh:queue_wait",
                "mesh:device_dispatch"} <= set(op["stages"]), op["stages"]
    for name in ("read", "pack", "write", "finalize"):
        assert METRICS.histogram(f"{name}_seconds").count >= 3


def test_one_chip_keeps_the_services_window(cluster, monkeypatch):
    """Where the door keeps the sweep on the codec service (one
    device), the window is what that lane compiles at."""
    monkeypatch.setenv("OZONE_TPU_TIER_BATCH", "2")
    monkeypatch.setattr(mesh_executor, "maybe_executor", lambda: None)
    codec_service.reset_for_tests()
    oz = cluster.client()
    oz.create_volume("v").create_bucket("b", replication="RATIS/THREE")
    b = oz.get_volume("v").get_bucket("b")
    datas = _write(b, {"cold-a": 3, "cold-b": 2}, seed=34)
    ex = TieringExecutor(cluster.om, cluster.clients)
    before = _counters()
    stats = ex.transition_keys([("v", "b", n, EC) for n in datas])
    after = _counters()
    assert stats["transitioned"] == 2 and stats["dispatches"] == 3
    assert ex.last_window == 2
    assert _delta(after, before, "codec.service/stripes_dispatched") == 5
    assert _delta(after, before, "mesh/stripes_dispatched") == 0
    for name, want in datas.items():
        assert np.array_equal(b.read_key(name), want)
    codec_service.reset_for_tests()


def test_a_partial_last_stripe_is_the_keys_only_one(cluster, mesh4):
    """A key of 9 1/2 stripes over two block groups: the first group is
    8 whole stripes, the second 1 1/2, as ECKeyWriter lays it out."""
    oz = cluster.client()
    oz.create_volume("v").create_bucket("b", replication="RATIS/THREE")
    b = oz.get_volume("v").get_bucket("b")
    want = np.random.default_rng(35).integers(
        0, 256, 9 * STRIPE + STRIPE // 2, dtype=np.uint8)
    b.write_key("cold-x", want)
    ex = TieringExecutor(cluster.om, cluster.clients)
    assert ex.transition_keys([("v", "b", "cold-x", EC)])["transitioned"] == 1
    info = cluster.om.lookup_key("v", "b", "cold-x")
    assert info["replication"] == EC
    assert [g.length for g in cluster.om.key_block_groups(info)] == \
        [8 * STRIPE, STRIPE + STRIPE // 2]
    assert np.array_equal(b.read_key("cold-x"), want)
    # a fresh PUT of the same bytes lays its groups out the same
    b.write_key("put-x", want, replication=EC)
    assert [g.length for g in cluster.om.key_block_groups(
        cluster.om.lookup_key("v", "b", "put-x"))] == \
        [8 * STRIPE, STRIPE + STRIPE // 2]


def test_a_container_closed_under_a_group_is_taken_again(cluster, mesh4):
    """The SCM closes an EC container once its blocks are all allocated:
    a key whose writes then meet INVALID_CONTAINER_STATE is converted
    again inside the same call, and is no failure."""
    oz = cluster.client()
    oz.create_volume("v").create_bucket("b", replication="RATIS/THREE")
    b = oz.get_volume("v").get_bucket("b")
    datas = _write(b, {"cold-a": 2, "cold-b": 3}, seed=36)
    ex = TieringExecutor(cluster.om, cluster.clients)
    closed = []
    real = ex._open_group

    def open_then_close_once(ks, gs):
        real(ks, gs)
        if not closed:
            closed.append(gs.ng.container_id)
            for dn_id in gs.ng.pipeline.nodes:
                cluster.clients.get(dn_id).close_container(
                    gs.ng.container_id)
            cluster.scm.containers.finalize_container(gs.ng.container_id)

    ex._open_group = open_then_close_once
    before = _counters()
    stats = ex.transition_keys([("v", "b", n, EC) for n in datas])
    after = _counters()
    assert stats["transitioned"] == 2 and stats["failed"] == 0
    assert _delta(after, before, "lifecycle/closed_container_retries") >= 1
    for name, want in datas.items():
        assert np.array_equal(b.read_key(name), want)
        groups = cluster.om.key_block_groups(
            cluster.om.lookup_key("v", "b", name))
        assert closed[0] not in [g.container_id for g in groups]
        assert _stored_as_the_reference_says(
            cluster.om, cluster.clients, name, want).first_error == ""


# --------------------------------- (b): the OM behind RPC, and a race
def test_over_rpc_a_raced_overwrite_wins_and_a_deleted_key_is_skipped(
        tmp_path, mesh4):
    """`TieringExecutor(GrpcOmClient, clients)`, as the benchmark's cell
    builds it: the OM's answers come back as the wire's errors."""
    ec = "rs-3-2-4096"
    ha = MiniOzoneHACluster(tmp_path, num_meta=1, num_datanodes=5,
                            block_size=BLOCK)
    try:
        oz = ha.client()
        oz.create_volume("v").create_bucket("b", replication="RATIS/THREE")
        b = oz.get_volume("v").get_bucket("b")
        rng = np.random.default_rng(37)
        datas = {f"cold-{i}": rng.integers(0, 256, n * 3 * CELL,
                                           dtype=np.uint8)
                 for i, n in enumerate((2, 4, 12))}
        for name, d in datas.items():
            b.write_key(name, d)
        b.write_key("gone", datas["cold-0"])
        newer = np.full(5 * CELL, 7, np.uint8)
        ex = TieringExecutor(oz.om, oz.clients)

        def user_overwrite(ks) -> None:
            if ks.key == "cold-1":
                b.write_key(ks.key, newer)

        ex.pre_commit_hook = user_overwrite
        oz.om.delete_key("v", "b", "gone")
        stats = ex.transition_keys(
            [("v", "b", n, ec) for n in (*datas, "gone")])
        assert stats["conflicts"] == 1 and stats["transitioned"] == 2
        assert stats["failed"] == 0 and stats["skipped"] == 1
        # the user's bytes won, replicated as the user wrote them
        info = oz.om.lookup_key("v", "b", "cold-1")
        assert info["replication"].startswith("RATIS")
        assert np.array_equal(b.read_key("cold-1"), newer)
        scheme = {"k": 3, "p": 2, "cell": CELL, "bpc": CELL}
        for name in ("cold-0", "cold-2"):
            info = oz.om.lookup_key("v", "b", name)
            assert info["replication"] == ec
            assert np.array_equal(b.read_key(name), datas[name])
            tally, at = storecheck.Tally(), 0
            for g in oz.om.key_block_groups(info):
                units = storecheck.expected_units(
                    scheme, datas[name][at:at + g.length])
                at += g.length
                for u, dn_id in enumerate(g.pipeline.nodes):
                    storecheck.check_unit(
                        oz.clients.get(dn_id), g.block_id, g.length,
                        units[:, u], scheme, tally, f"{name} unit {u}")
            storecheck.finish(tally, scheme)
            assert tally.first_error == "" and tally.units_compared >= 5
        # the roots ended as what happened to each key
        outcomes = {s.tags.get("key"): s.tags.get("outcome")
                    for s in Tracer.instance().traces()
                    if s.name == "tier:key"}
        assert outcomes["cold-1"] == "conflict"
        assert outcomes["cold-0"] == outcomes["cold-2"] == "transitioned"
        # the OM's RPCs are stages of the key they served
        op = [o for o in Tracer.instance().recorder.operations("tier:key")
              if "client:/ozone.tpu.OmService/CommitKey" in o["stages"]][-1]
        assert {"client:/ozone.tpu.OmService/OpenKey",
                "client:/ozone.tpu.OmService/AllocateBlock"} \
            <= set(op["stages"])
    finally:
        ha.shutdown()
