"""Lifecycle subsystem tests: policy model, S3 lifecycle API, the
term-fenced sweeper (kill-9 / exactly-once regression), the batched
tiering executor, and the conflict fence."""

import urllib.error
import urllib.request

import numpy as np
import pytest

from ozone_tpu.lifecycle.policy import (
    ACTION_EXPIRE,
    ACTION_TRANSITION,
    LifecycleError,
    LifecycleRule,
    rules_from_s3_xml,
    rules_to_s3_xml,
)
from ozone_tpu.lifecycle.service import LifecycleService
from ozone_tpu.om import requests as rq
from ozone_tpu.storage.ids import BlockID, StorageError
from ozone_tpu.testing.minicluster import MiniOzoneCluster

EC = "rs-3-2-4096"


@pytest.fixture
def cluster(tmp_path):
    c = MiniOzoneCluster(
        tmp_path, num_datanodes=6, block_size=8 * 4096,
        container_size=4 * 1024 * 1024,
        stale_after_s=1000.0, dead_after_s=2000.0,
    )
    yield c
    c.close()


# ---------------------------------------------------------------- policy
def test_rule_validation():
    LifecycleRule("r", prefix="a/", age_days=3,
                  action=ACTION_TRANSITION, target=EC).validate()
    LifecycleRule("r", age_days=0, action=ACTION_EXPIRE).validate()
    with pytest.raises(LifecycleError):
        LifecycleRule("", action=ACTION_EXPIRE).validate()
    with pytest.raises(LifecycleError):
        LifecycleRule("r", action="SHRED").validate()
    with pytest.raises(LifecycleError):
        LifecycleRule("r", age_days=-1, action=ACTION_EXPIRE).validate()
    with pytest.raises(LifecycleError):
        # transition target must be an EC scheme
        LifecycleRule("r", action=ACTION_TRANSITION,
                      target="RATIS/THREE").validate()


def test_s3_xml_roundtrip_and_mapping():
    body = b"""<?xml version="1.0"?>
    <LifecycleConfiguration xmlns="http://s3.amazonaws.com/doc/2006-03-01/">
      <Rule>
        <ID>warm</ID>
        <Filter><Prefix>logs/</Prefix></Filter>
        <Status>Enabled</Status>
        <Transition><Days>30</Days>
          <StorageClass>STANDARD_IA</StorageClass></Transition>
        <Expiration><Days>90</Days></Expiration>
      </Rule>
      <Rule>
        <ID>pinned</ID>
        <Prefix>cold/</Prefix>
        <Status>Disabled</Status>
        <Transition><Days>1</Days>
          <StorageClass>rs-3-2-4096</StorageClass></Transition>
      </Rule>
    </LifecycleConfiguration>"""
    rules = rules_from_s3_xml(body, default_target="rs-6-3-1024k")
    # combined rule splits into transition + expiration
    assert [r["action"] for r in rules] == [
        ACTION_TRANSITION, ACTION_EXPIRE, ACTION_TRANSITION]
    # warm AWS storage class maps to the cluster default EC scheme; a
    # literal scheme passes through
    assert rules[0]["target"] == "rs-6-3-1024k"
    assert rules[2]["target"] == "rs-3-2-4096"
    assert rules[0]["prefix"] == "logs/" and rules[1]["age_days"] == 90
    assert rules[2]["enabled"] is False
    # render -> parse is stable
    again = rules_from_s3_xml(rules_to_s3_xml(rules),
                              default_target="rs-6-3-1024k")
    assert again == rules

    with pytest.raises(LifecycleError):
        rules_from_s3_xml(b"<LifecycleConfiguration/>")
    with pytest.raises(LifecycleError):
        rules_from_s3_xml(b"not xml at all")
    with pytest.raises(LifecycleError):  # Date schedules unsupported
        rules_from_s3_xml(
            b"<LifecycleConfiguration><Rule><ID>x</ID>"
            b"<Transition><Date>2026-01-01</Date></Transition>"
            b"</Rule></LifecycleConfiguration>")


def test_rules_persist_replicated_in_bucket_metadata(cluster):
    om = cluster.om
    om.submit(rq.CreateVolume("v"))
    om.create_bucket("v", "b", replication="RATIS/THREE")
    rules = [{"id": "r0", "prefix": "p/", "age_days": 2,
              "action": ACTION_TRANSITION, "target": EC}]
    om.set_bucket_lifecycle("v", "b", rules)
    got = om.get_bucket_lifecycle("v", "b")
    assert got[0]["prefix"] == "p/" and got[0]["target"] == EC
    # rules ride the bucket row -> they replicate + survive like any
    # bucket property
    assert om.bucket_info("v", "b")["lifecycle"] == got
    with pytest.raises(rq.OMError):
        om.set_bucket_lifecycle("v", "b", [{"id": "bad",
                                            "action": "SHRED"}])
    om.delete_bucket_lifecycle("v", "b")
    assert om.get_bucket_lifecycle("v", "b") == []
    # FSO buckets reject rules outright: the sweeper's flat prefix scan
    # can't see an id-keyed tree, and accepting the PUT would configure
    # a silent no-op the operator thinks is enforced
    om.create_bucket("v", "fso", replication="RATIS/THREE",
                     layout="FILE_SYSTEM_OPTIMIZED")
    with pytest.raises(rq.OMError) as ei:
        om.set_bucket_lifecycle("v", "fso", rules)
    assert ei.value.code == rq.INVALID_REQUEST


# ------------------------------------------------------- sweeper datapath
def _write_keys(cluster, bucket, names, size=30_000, seed=0):
    b = cluster.client().get_volume("v").get_bucket(bucket)
    rng = np.random.default_rng(seed)
    out = {}
    for name in names:
        d = rng.integers(0, 256, size, dtype=np.uint8)
        b.write_key(name, d)
        out[name] = d
    return b, out


def test_sweep_transitions_expires_and_reclaims(cluster):
    oz = cluster.client()
    oz.create_volume("v").create_bucket("b", replication="RATIS/THREE")
    b, datas = _write_keys(cluster, "b",
                           [f"cold-{i}" for i in range(4)])
    hot = np.arange(9000, dtype=np.uint64).astype(np.uint8)
    b.write_key("hot-x", hot)
    b.write_key("ttl-1", hot)
    cluster.om.set_bucket_lifecycle("v", "b", [
        {"id": "warm", "prefix": "cold-", "age_days": 0,
         "action": ACTION_TRANSITION, "target": EC},
        {"id": "ttl", "prefix": "ttl-", "age_days": 0,
         "action": ACTION_EXPIRE},
    ])
    # the old replicated blocks we expect reclaimed
    old = cluster.om.key_block_groups(
        cluster.om.lookup_key("v", "b", "cold-0"))
    svc = LifecycleService(cluster.om, clients=cluster.clients)
    stats = svc.run_once()
    assert stats["complete"] and stats["transitioned"] == 4
    assert stats["expired"] == 1 and stats["failed"] == 0
    for name, want in datas.items():
        info = cluster.om.lookup_key("v", "b", name)
        assert info["replication"] == EC
        assert np.array_equal(b.read_key(name), want)
    # untouched keys keep their replication; the expired key is gone
    assert cluster.om.lookup_key(
        "v", "b", "hot-x")["replication"].startswith("RATIS")
    with pytest.raises(rq.OMError):
        cluster.om.lookup_key("v", "b", "ttl-1")
    # old replicated blocks retire through scm/block_deletion.py — the
    # sweep queued them (post-commit only), heartbeats deliver deletes
    assert cluster.scm.deleted_blocks.pending_count() > 0
    cluster.tick(rounds=2)
    assert cluster.scm.deleted_blocks.pending_count() == 0
    g = old[0]
    bid = BlockID(g.container_id, g.local_id)
    for dn_id in g.pipeline.nodes:
        with pytest.raises(StorageError):
            cluster.clients.get(dn_id).get_block(bid)
    # a second sweep finds nothing to do (idempotent)
    stats2 = svc.run_once()
    assert stats2["transitioned"] == 0 and stats2["expired"] == 0


def test_many_keys_share_device_dispatches(cluster, monkeypatch):
    """The tentpole's batching claim: a sweep over many small keys must
    pack MANY keys per DeviceBatchPipeline submission — dispatches ~
    total_stripes / window, never one-plus per key."""
    # one stripe a device: on the tests' 8 host devices the sweep joins
    # the mesh lane, whose dispatch (the packer's window) is 8 stripes
    monkeypatch.setenv("OZONE_TPU_TIER_BATCH", "1")
    oz = cluster.client()
    oz.create_volume("v").create_bucket("b", replication="RATIS/THREE")
    # 24576 bytes = exactly 2 rs-3-2-4096 stripes per key
    b, datas = _write_keys(cluster, "b",
                           [f"cold-{i}" for i in range(10)], size=24576)
    cluster.om.set_bucket_lifecycle("v", "b", [
        {"id": "warm", "prefix": "cold-", "age_days": 0,
         "action": ACTION_TRANSITION, "target": EC}])
    svc = LifecycleService(cluster.om, clients=cluster.clients)
    stats = svc.run_once()
    assert stats["transitioned"] == 10
    # 10 keys x 2 stripes = 20 stripes / window 8 -> 3 dispatches
    assert stats["dispatches"] == 3, stats
    for name, want in datas.items():
        assert np.array_equal(b.read_key(name), want)


def test_transition_conflict_fence_preserves_user_write(cluster):
    """A user overwrite racing the transition must win: the fenced
    commit loses deterministically, its EC blocks ride the deletion
    chain, and the user's bytes stay authoritative."""
    oz = cluster.client()
    oz.create_volume("v").create_bucket("b", replication="RATIS/THREE")
    b, _ = _write_keys(cluster, "b", ["cold-0"])
    newer = np.full(5000, 7, np.uint8)
    cluster.om.set_bucket_lifecycle("v", "b", [
        {"id": "warm", "prefix": "cold-", "age_days": 0,
         "action": ACTION_TRANSITION, "target": EC}])
    svc = LifecycleService(cluster.om, clients=cluster.clients)

    def overwrite(ks):
        b.write_key(ks.key, newer)

    svc.executor().pre_commit_hook = overwrite
    stats = svc.run_once()
    assert stats["conflicts"] == 1 and stats["transitioned"] == 0
    info = cluster.om.lookup_key("v", "b", "cold-0")
    assert info["replication"].startswith("RATIS")  # user version won
    assert np.array_equal(b.read_key("cold-0"), newer)
    # the abandoned EC version was routed into the purge chain; the
    # post-sweep purge pass already handed its blocks to the SCM
    # deletion log (the old replicated version stayed LIVE, so these
    # pending deletes can only be the fenced EC blocks)
    discarded = [v for _, v in cluster.om.store.iterate("deleted_keys")
                 if v.get("replication") == EC]
    assert discarded or cluster.scm.deleted_blocks.pending_count() > 0, \
        "fenced EC version must enter the deletion chain"
    cluster.tick(rounds=2)
    assert cluster.scm.deleted_blocks.pending_count() == 0


def test_kill9_term_fence_exactly_once(cluster):
    """The acceptance regression: kill -9 of the lifecycle leader
    mid-sweep neither loses nor double-applies a transition, and the
    deposed leader's late checkpoints are refused by the term fence."""
    oz = cluster.client()
    oz.create_volume("v").create_bucket("b", replication="RATIS/THREE")
    b, datas = _write_keys(cluster, "b",
                           [f"cold-{i}" for i in range(6)])
    cluster.om.set_bucket_lifecycle("v", "b", [
        {"id": "warm", "prefix": "cold-", "age_days": 0,
         "action": ACTION_TRANSITION, "target": EC}])
    # term-1 leader sweeps PART of the namespace, then is kill-9'd (its
    # in-memory state is simply abandoned — exactly what -9 leaves)
    old_leader = LifecycleService(cluster.om, clients=cluster.clients,
                                  term_fn=lambda: 1, page=2)
    stats1 = old_leader.run_once(max_keys=2)
    assert 0 < stats1["transitioned"] <= 2 and not stats1["complete"]
    assert cluster.om.lifecycle_status()["in_progress"]

    # the new leader (higher ring term) fences, resumes from the
    # replicated cursor, and finishes the sweep
    new_leader = LifecycleService(cluster.om, clients=cluster.clients,
                                  term_fn=lambda: 2, page=2)
    stats2 = new_leader.run_once()
    assert stats2["complete"]
    assert stats1["transitioned"] + stats2["transitioned"] == 6
    for name, want in datas.items():
        info = cluster.om.lookup_key("v", "b", name)
        assert info["replication"] == EC, name
        assert np.array_equal(b.read_key(name), want), name

    # the deposed leader wakes up and tries to keep sweeping: its very
    # first checkpoint is refused (LIFECYCLE_FENCED) and it applies
    # NOTHING — no transition double-applied, no cursor regression
    stats3 = old_leader.run_once()
    assert stats3.get("fenced") is True
    assert stats3["transitioned"] == 0
    with pytest.raises(rq.OMError) as ei:
        cluster.om.submit(rq.LifecycleCheckpoint(
            term=1, cursor={"bucket": "/v/b", "after": ""}))
    assert ei.value.code == rq.LIFECYCLE_FENCED
    # and the stored state still belongs to term 2, sweep complete
    st = cluster.om.lifecycle_status()
    assert st["term"] == 2 and not st["in_progress"]


def test_expire_fence_spares_concurrent_overwrite(cluster):
    """TTL expiry is fenced on the SCANNED version: a user overwrite
    racing the sweep must win, exactly like the transition fence."""
    oz = cluster.client()
    oz.create_volume("v").create_bucket("b", replication="RATIS/THREE")
    b, _ = _write_keys(cluster, "b", ["ttl-x"])
    stale_oid = cluster.om.lookup_key("v", "b", "ttl-x")["object_id"]
    fresh = np.full(4000, 9, np.uint8)
    b.write_key("ttl-x", fresh)  # user overwrite after the "scan"
    with pytest.raises(rq.OMError) as ei:
        cluster.om.submit(rq.DeleteKey("v", "b", "ttl-x",
                                       expect_object_id=stale_oid))
    assert ei.value.code == rq.KEY_MODIFIED
    assert np.array_equal(b.read_key("ttl-x"), fresh)  # data survived
    # the fresh version's own id still deletes (normal expiry)
    oid = cluster.om.lookup_key("v", "b", "ttl-x")["object_id"]
    cluster.om.submit(rq.DeleteKey("v", "b", "ttl-x",
                                   expect_object_id=oid))
    with pytest.raises(rq.OMError):
        cluster.om.lookup_key("v", "b", "ttl-x")


def test_sweep_deadline_bounds_work_and_resumes(cluster):
    oz = cluster.client()
    oz.create_volume("v").create_bucket("b", replication="RATIS/THREE")
    b, datas = _write_keys(cluster, "b",
                           [f"cold-{i}" for i in range(4)])
    cluster.om.set_bucket_lifecycle("v", "b", [
        {"id": "warm", "prefix": "cold-", "age_days": 0,
         "action": ACTION_TRANSITION, "target": EC}])
    tight = LifecycleService(cluster.om, clients=cluster.clients,
                             sweep_deadline_s=1e-6)
    stats = tight.run_once()
    assert stats.get("deadline_exceeded") is True
    assert stats["transitioned"] == 0
    # a later sweep with a sane budget finishes the job
    svc = LifecycleService(cluster.om, clients=cluster.clients)
    stats2 = svc.run_once()
    assert stats2["complete"] and stats2["transitioned"] == 4


def test_follower_never_sweeps():
    class _Om:  # the service must bail before touching anything
        def __getattr__(self, name):  # pragma: no cover
            raise AssertionError("follower touched OM state")

    svc = LifecycleService(_Om(), leader_fn=lambda: False)
    assert svc.run_once() == {"skipped": "not_leader"}


# ------------------------------------------------------------- S3 surface
def _http(method, url, data=None, headers=None):
    req = urllib.request.Request(url, data=data, method=method,
                                 headers=headers or {})
    with urllib.request.urlopen(req, timeout=30) as r:
        return r.status, r.read()


def test_s3_lifecycle_api_end_to_end(cluster):
    """Acceptance: keys written replicated under an age rule are
    transitioned to EC by the background sweeper and read back
    byte-exact THROUGH the S3 gateway; the lifecycle configuration
    round-trips over the S3 XML API."""
    from ozone_tpu.gateway.s3 import S3Gateway

    gw = S3Gateway(cluster.client(), replication="RATIS/THREE")
    gw.start()
    try:
        base = f"http://{gw.address}"
        _http("PUT", f"{base}/tierb")
        body = (b'<LifecycleConfiguration>'
                b'<Rule><ID>warm</ID><Filter><Prefix>cold/</Prefix>'
                b'</Filter><Status>Enabled</Status>'
                b'<Transition><Days>0</Days>'
                b'<StorageClass>rs-3-2-4096</StorageClass></Transition>'
                b'</Rule>'
                b'<Rule><ID>ttl</ID><Filter><Prefix>ttl/</Prefix>'
                b'</Filter><Status>Enabled</Status>'
                b'<Expiration><Days>0</Days></Expiration></Rule>'
                b'</LifecycleConfiguration>')
        status, _ = _http("PUT", f"{base}/tierb?lifecycle", data=body)
        assert status == 200
        # GET round-trips the stored rules as XML
        status, got = _http("GET", f"{base}/tierb?lifecycle")
        assert status == 200
        rt = rules_from_s3_xml(got)
        assert {r["id"] for r in rt} == {"warm", "ttl"}
        assert rt[0]["target"] == "rs-3-2-4096"

        rng = np.random.default_rng(3)
        payloads = {f"cold/{i}": rng.integers(
            0, 256, 40_000, dtype=np.uint8).tobytes() for i in range(3)}
        for k, v in payloads.items():
            _http("PUT", f"{base}/tierb/{k}", data=v)
        _http("PUT", f"{base}/tierb/ttl/x", data=b"doomed")
        _http("PUT", f"{base}/tierb/keep/x", data=b"hot stays")

        svc = LifecycleService(cluster.om, clients=cluster.clients)
        stats = svc.run_once()
        assert stats["transitioned"] == 3 and stats["expired"] == 1

        for k, v in payloads.items():
            status, got = _http("GET", f"{base}/tierb/{k}")
            assert status == 200 and got == v, k
            info = cluster.om.lookup_key("s3v", "tierb", k)
            assert info["replication"] == EC
        status, got = _http("GET", f"{base}/tierb/keep/x")
        assert got == b"hot stays"
        with pytest.raises(urllib.error.HTTPError) as ei:
            _http("GET", f"{base}/tierb/ttl/x")
        assert ei.value.code == 404
        # a ranged GET through the gateway decodes only covering cells
        status, part = _http("GET", f"{base}/tierb/cold/0",
                             headers={"Range": "bytes=100-199"})
        assert status == 206
        assert part == payloads["cold/0"][100:200]

        # DELETE clears; GET then answers NoSuchLifecycleConfiguration
        status, _ = _http("DELETE", f"{base}/tierb?lifecycle")
        assert status == 204
        with pytest.raises(urllib.error.HTTPError) as ei:
            _http("GET", f"{base}/tierb?lifecycle")
        assert ei.value.code == 404
        # malformed XML answers 400, not a 500
        with pytest.raises(urllib.error.HTTPError) as ei:
            _http("PUT", f"{base}/tierb?lifecycle", data=b"<junk")
        assert ei.value.code == 400
    finally:
        gw.stop()


def test_recon_lifecycle_endpoint(cluster):
    import json

    from ozone_tpu.recon.recon import ReconServer

    cluster.om.submit(rq.CreateVolume("v"))
    cluster.om.create_bucket("v", "b", replication="RATIS/THREE")
    cluster.om.set_bucket_lifecycle("v", "b", [
        {"id": "warm", "prefix": "", "age_days": 1,
         "action": ACTION_TRANSITION, "target": EC}])
    recon = ReconServer(cluster.om, cluster.scm)
    recon.start()
    try:
        out = json.loads(urllib.request.urlopen(
            f"http://{recon.address}/api/lifecycle", timeout=10).read())
        assert out["buckets"][0]["rules"][0]["id"] == "warm"
        assert "metrics" in out
        # the codec-service panel rides the same server (batch fill /
        # queue depth for the device's continuous batching)
        cx = json.loads(urllib.request.urlopen(
            f"http://{recon.address}/api/codec", timeout=10).read())
        if cx.get("started") is False:
            # monitoring GET must not spawn the dispatcher itself
            assert set(cx) == {"started"}
        else:
            for want in ("fill_ratio", "ops_per_dispatch",
                         "queue_depth", "linger_ms", "weights"):
                assert want in cx, want
        # the mesh-executor panel rides the same server (multi-chip
        # dispatch/coalescing accounting); the GET must not spawn the
        # executor either
        mx = json.loads(urllib.request.urlopen(
            f"http://{recon.address}/api/mesh", timeout=10).read())
        if mx.get("started") is False:
            assert set(mx) == {"started"}
        else:
            for want in ("fill_ratio", "ops_per_dispatch", "devices",
                         "mesh_depth", "programs", "max_inflight"):
                assert want in mx, want
        page = urllib.request.urlopen(
            f"http://{recon.address}/", timeout=10).read().decode()
        assert "Lifecycle tiering" in page and "/api/lifecycle" in page
        assert "Codec service" in page and "/api/codec" in page
        assert "Mesh executor" in page and "/api/mesh" in page
    finally:
        recon.stop()
