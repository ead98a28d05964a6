"""Geo-DR subsystem tests: the rule model + S3 ?replication XML codec,
the term-fenced WAL-tailing shipper over a two-MiniOzoneCluster pair
(convergence, scheme conversion with a CodecService bulk dispatch,
kill-9 replay idempotence, LWW conflicts, fencing), the S3 gateway
verbs, the Recon endpoint, and the freon geo churn workload."""

import urllib.error
import urllib.request
import xml.etree.ElementTree as ET

import numpy as np
import pytest

from ozone_tpu.om import requests as rq
from ozone_tpu.replication_geo import shipper as geo
from ozone_tpu.replication_geo.rules import (
    GeoReplicationError,
    ReplicationRule,
    rules_from_s3_xml,
    rules_to_s3_xml,
)
from ozone_tpu.replication_geo.shipper import (
    GEO_META_OID,
    ReplicationShipper,
)
from ozone_tpu.testing.minicluster import MiniOzoneCluster

EC = "rs-3-2-4096"


# ---------------------------------------------------------------- rules
def test_rule_validation():
    ReplicationRule("r", endpoint="127.0.0.1:9860").validate()
    ReplicationRule("r", endpoint="ep", scheme=EC).validate()
    ReplicationRule("r", endpoint="ep", scheme="RATIS/THREE").validate()
    with pytest.raises(GeoReplicationError):
        ReplicationRule("", endpoint="ep").validate()
    with pytest.raises(GeoReplicationError):
        ReplicationRule("r").validate()  # no endpoint
    with pytest.raises(GeoReplicationError):
        ReplicationRule("r", endpoint="ep", scheme="junk").validate()
    with pytest.raises(GeoReplicationError):
        from ozone_tpu.replication_geo.rules import validate_rules

        validate_rules([ReplicationRule("r", endpoint="ep").to_json(),
                        ReplicationRule("r", endpoint="ep").to_json()])


def test_s3_xml_roundtrip_and_endpoint_forms():
    body = b"""<?xml version="1.0"?>
    <ReplicationConfiguration
        xmlns="http://s3.amazonaws.com/doc/2006-03-01/">
      <Role></Role>
      <Rule>
        <ID>mirror</ID>
        <Priority>2</Priority>
        <Status>Enabled</Status>
        <Filter><Prefix>logs/</Prefix></Filter>
        <Destination>
          <Bucket>arn:aws:s3:10.0.0.2:9860::mirror-bucket</Bucket>
          <StorageClass>STANDARD_IA</StorageClass>
        </Destination>
      </Rule>
      <Rule>
        <ID>explicit</ID>
        <Priority>1</Priority>
        <Status>Disabled</Status>
        <Prefix>tmp/</Prefix>
        <Destination>
          <Endpoint>10.0.0.3:9860</Endpoint>
          <Bucket>other</Bucket>
          <StorageClass>rs-3-2-4096</StorageClass>
        </Destination>
      </Rule>
      <Rule>
        <ID>renamed</ID>
        <Priority>3</Priority>
        <Destination>
          <Bucket>arn:aws:s3:10.0.0.4:9860::drvol/drbucket</Bucket>
        </Destination>
      </Rule>
    </ReplicationConfiguration>"""
    rules = rules_from_s3_xml(body, default_target="rs-6-3-1024k")
    # Priority orders: "explicit" (1) before "mirror" (2)
    assert [r["id"] for r in rules] == ["explicit", "mirror", "renamed"]
    assert rules[0]["endpoint"] == "10.0.0.3:9860"
    assert rules[0]["bucket"] == "other"
    assert rules[0]["scheme"] == EC  # literal scheme passes through
    assert rules[0]["enabled"] is False
    assert rules[1]["endpoint"] == "10.0.0.2:9860"
    assert rules[1]["bucket"] == "mirror-bucket"
    assert rules[1]["scheme"] == "rs-6-3-1024k"  # warm class mapped
    assert rules[1]["prefix"] == "logs/"
    # the ARN resource slot carries a destination volume rename
    assert rules[2]["volume"] == "drvol"
    assert rules[2]["bucket"] == "drbucket"
    assert rules[2]["scheme"] == ""  # absent: keep the source scheme
    # GET body re-parses to the same rules (stable round trip — a
    # CLI-set volume rename survives GET + re-PUT)
    assert rules_from_s3_xml(rules_to_s3_xml(rules)) == rules


def test_s3_xml_rejects():
    with pytest.raises(GeoReplicationError):
        rules_from_s3_xml(b"<junk")
    with pytest.raises(GeoReplicationError):
        rules_from_s3_xml(b"<ReplicationConfiguration/>")
    with pytest.raises(GeoReplicationError):  # rule without Destination
        rules_from_s3_xml(
            b"<ReplicationConfiguration><Rule><ID>x</ID></Rule>"
            b"</ReplicationConfiguration>")
    with pytest.raises(GeoReplicationError):  # ARN without endpoint
        rules_from_s3_xml(
            b"<ReplicationConfiguration><Rule><ID>x</ID><Destination>"
            b"<Bucket>arn:aws:s3:::plain</Bucket></Destination></Rule>"
            b"</ReplicationConfiguration>")


# ------------------------------------------------------------- clusters
def _mini(tmp_path, name):
    return MiniOzoneCluster(
        tmp_path / name, num_datanodes=6, block_size=8 * 4096,
        container_size=4 * 1024 * 1024,
        stale_after_s=1000.0, dead_after_s=2000.0,
    )


@pytest.fixture
def pair(tmp_path, request):
    """A (source, destination) MiniOzoneCluster pair; the destination
    is registered in-process under a per-test endpoint name."""
    src = _mini(tmp_path, "src")
    dst = _mini(tmp_path, "dst")
    endpoint = f"dst-{request.node.name}"
    geo.register_inprocess(endpoint, dst.client)
    yield src, dst, endpoint
    geo.unregister_inprocess(endpoint)
    src.close()
    dst.close()


def _payload(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n,
                                                dtype=np.uint8)


def _set_rule(src, endpoint, volume="v", bucket="b", **kw):
    src.om.set_bucket_geo_replication(volume, bucket, [{
        "id": kw.pop("id", "r1"), "endpoint": endpoint, **kw}])


# --------------------------------------------------------- convergence
def test_two_cluster_convergence_puts_overwrites_deletes(pair):
    """The end-to-end proof: puts, overwrites and deletes on the source
    converge byte-exact at the destination, the scheme-converting
    bucket re-encodes through the shared CodecService at bulk QoS, and
    the lag gauge returns to 0."""
    from ozone_tpu.utils.metrics import get_registry

    src, dst, endpoint = pair
    sc = src.client()
    sc.create_volume("v")
    # bucket 1: same-scheme replication (replicated -> replicated)
    src.om.create_bucket("v", "b", "RATIS/THREE")
    # bucket 2: scheme-converting (replicated source -> EC destination)
    src.om.create_bucket("v", "ec", "RATIS/THREE")
    _set_rule(src, endpoint, bucket="b")
    src.om.set_bucket_geo_replication("v", "ec", [{
        "id": "conv", "endpoint": endpoint, "scheme": EC}])
    vb = sc.get_volume("v").get_bucket("b")
    ve = sc.get_volume("v").get_bucket("ec")
    data = {f"k{i}": _payload(20_000 + i, seed=i) for i in range(6)}
    creg = get_registry("codec.service")
    bulk_before = (creg.histogram("queue_wait_bulk_seconds").count
                   if creg is not None else 0)
    for name, d in data.items():
        vb.write_key(name, d)
        ve.write_key(name, d)
    stats = src.om.run_geo_once()
    assert stats["complete"] and stats["failed"] == 0
    assert stats["keys_shipped"] >= len(data) * 2
    # churn AFTER the first ship: overwrite k0/k3, delete k1 — the
    # delta cycle must supersede the shipped replicas and retire k1
    data["k0"] = _payload(9_000, seed=100)
    data["k3"] = _payload(31_000, seed=101)
    for name in ("k0", "k3"):
        vb.write_key(name, data[name])
        ve.write_key(name, data[name])
    vb.delete_key("k1")
    ve.delete_key("k1")
    del data["k1"]
    stats = src.om.run_geo_once()
    assert stats["complete"] and stats["failed"] == 0
    assert stats["keys_shipped"] >= 4
    assert stats["deletes_shipped"] == 2

    dc = dst.client()
    for bname in ("b", "ec"):
        db = dc.get_volume("v").get_bucket(bname)
        for name, d in data.items():
            info = dst.om.lookup_key("v", bname, name)
            assert np.array_equal(db.read_key_info(info), d), \
                (bname, name)
            assert info["metadata"][GEO_META_OID] == \
                src.om.lookup_key("v", bname, name)["object_id"]
        with pytest.raises(rq.OMError):
            dst.om.lookup_key("v", bname, "k1")
    # the converting bucket landed EC at the destination
    assert str(dst.om.lookup_key("v", "ec", "k0")["replication"]) == EC
    assert str(dst.om.lookup_key("v", "b", "k0")
               ["replication"]).startswith("RATIS")
    # scheme conversion rode the shared codec service at bulk QoS
    creg = get_registry("codec.service")
    assert creg.histogram("queue_wait_bulk_seconds").count > bulk_before
    # shipped, nothing pending: the lag gauge is back to 0
    lag = src.om.geo_status()["lag"]
    assert lag["entries"] == 0 and lag["seconds"] == 0.0
    reg = get_registry("replication")
    assert reg.gauge("lag_entries").value == 0


def test_bootstrap_ships_preexisting_keys(pair):
    """Keys written BEFORE the rule was installed ship on the first
    cycle (the bucket reconcile), not only new WAL traffic."""
    src, dst, endpoint = pair
    sc = src.client()
    sc.create_volume("v")
    src.om.create_bucket("v", "b", "RATIS/THREE")
    b = sc.get_volume("v").get_bucket("b")
    d = _payload(12_345, seed=7)
    b.write_key("old-key", d)
    _set_rule(src, endpoint)  # rule installed AFTER the write
    stats = src.om.run_geo_once()
    assert stats["bootstrapped"] == 1
    got = dst.client().get_volume("v").get_bucket("b").read_key("old-key")
    assert np.array_equal(got, d)
    # a second cycle re-bootstraps nothing and ships nothing
    stats2 = src.om.run_geo_once()
    assert stats2["bootstrapped"] == 0 and stats2["keys_shipped"] == 0


def test_prefix_filter_and_rename_routing(pair):
    src, dst, endpoint = pair
    sc = src.client()
    sc.create_volume("v")
    src.om.create_bucket("v", "b", "RATIS/THREE")
    src.om.set_bucket_geo_replication("v", "b", [{
        "id": "r1", "endpoint": endpoint, "prefix": "ship/",
        "bucket": "mirror", "volume": "dr"}])
    b = sc.get_volume("v").get_bucket("b")
    b.write_key("ship/yes", _payload(5000, seed=1))
    b.write_key("keep/no", _payload(5000, seed=2))
    stats = src.om.run_geo_once()
    assert stats["keys_shipped"] == 1
    # routed to the rule's destination volume/bucket rename
    info = dst.om.lookup_key("dr", "mirror", "ship/yes")
    assert info["size"] == 5000
    with pytest.raises(rq.OMError):
        dst.om.lookup_key("dr", "mirror", "keep/no")


# ------------------------------------------------- idempotence / crash
def test_replay_idempotent_after_crash_before_checkpoint(pair):
    """Satellite: kill -9 of the shipper mid-page (replayed but NOT
    checkpointed) must converge byte-exact on re-run with no
    duplicate-key or resurrect-after-delete anomalies."""
    src, dst, endpoint = pair
    sc = src.client()
    sc.create_volume("v")
    src.om.create_bucket("v", "b", "RATIS/THREE")
    _set_rule(src, endpoint)
    b = sc.get_volume("v").get_bucket("b")
    d = _payload(22_222, seed=3)
    b.write_key("crashy", d)
    b.write_key("doomed", _payload(4_000, seed=4))
    src.om.run_geo_once()
    b.delete_key("doomed")
    b.write_key("crashy", d)  # overwrite: a fresh version to ship

    class _Die(RuntimeError):
        pass

    s1 = ReplicationShipper(src.om, clients=src.clients)
    orig = s1._checkpoint

    def crashing_checkpoint(term, cursor, **kw):
        if not kw.get("fence"):
            raise _Die("kill -9 before the cursor committed")
        return orig(term, cursor, **kw)

    s1._checkpoint = crashing_checkpoint
    with pytest.raises(_Die):
        s1.run_once()
    # the page REPLAYED (data at dest) but the cursor did not move
    dst_info = dst.om.lookup_key("v", "b", "crashy")
    cursor_before = (src.om.store.get("system", "geo_state")
                     or {}).get("cursor")
    # a fresh shipper (the restarted leader) re-applies the same page:
    # the geo-src-oid marker makes it a no-op, deletes don't resurrect
    s2 = ReplicationShipper(src.om, clients=src.clients)
    stats = s2.run_once()
    assert stats["complete"] and stats["failed"] == 0
    assert stats["keys_shipped"] == 0  # nothing re-written
    assert stats["in_sync"] >= 1
    after = dst.om.lookup_key("v", "b", "crashy")
    assert after["object_id"] == dst_info["object_id"]  # no new version
    got = dst.client().get_volume("v").get_bucket("b").read_key("crashy")
    assert np.array_equal(got, d)
    with pytest.raises(rq.OMError):
        dst.om.lookup_key("v", "b", "doomed")  # stayed deleted
    cursor_after = (src.om.store.get("system", "geo_state")
                    or {}).get("cursor")
    assert cursor_after != cursor_before  # the re-run checkpointed


def test_term_fencing_rejects_deposed_shipper(pair):
    """A shipper fenced at an older term loses deterministically: its
    checkpoints are refused on every replica (GEO_FENCED), so a deposed
    leader can never regress the WAL cursor."""
    src, dst, endpoint = pair
    sc = src.client()
    sc.create_volume("v")
    src.om.create_bucket("v", "b", "RATIS/THREE")
    _set_rule(src, endpoint)
    old = ReplicationShipper(src.om, clients=src.clients,
                             term_fn=lambda: 1)
    assert old.run_once()["complete"]
    new = ReplicationShipper(src.om, clients=src.clients,
                             term_fn=lambda: 2)
    assert new.run_once()["complete"]
    # the deposed term-1 shipper now fences out: its cursor checkpoint
    # is refused on every replica, so the fenced state keeps term 2
    sc.get_volume("v").get_bucket("b").write_key(
        "late", _payload(1000, seed=5))
    stats = old.run_once()
    assert stats.get("fenced") is True
    state = src.om.store.get("system", "geo_state")
    assert int(state["term"]) == 2  # never regressed to the deposed term
    # the deposed instance may have REPLAYED the page before its
    # checkpoint was refused (at-least-once); what fencing guarantees
    # is convergence without a duplicate version: the current-term
    # shipper re-covers the un-checkpointed page as a no-op
    stats = new.run_once()
    assert stats["complete"] and stats["failed"] == 0
    first = dst.om.lookup_key("v", "b", "late")
    assert new.run_once()["keys_shipped"] == 0  # stable: no re-ship
    assert dst.om.lookup_key("v", "b", "late")["object_id"] == \
        first["object_id"]
    got = dst.client().get_volume("v").get_bucket("b").read_key("late")
    assert np.array_equal(got, _payload(1000, seed=5))


# --------------------------------------------------------- LWW conflicts
def test_destination_overwrite_beats_stale_replay(pair):
    """Last-writer-wins: a destination-side overwrite NEWER than the
    source commit survives the replay (counted as a conflict), and a
    destination-local key is never deleted by a source tombstone."""
    src, dst, endpoint = pair
    sc, dc = src.client(), dst.client()
    sc.create_volume("v")
    src.om.create_bucket("v", "b", "RATIS/THREE")
    _set_rule(src, endpoint)
    b = sc.get_volume("v").get_bucket("b")
    b.write_key("contested", _payload(6000, seed=10))
    # destination user overwrites AFTER the source commit (newer mtime)
    dc.create_volume("v")
    dst.om.create_bucket("v", "b", "RATIS/THREE")
    newer = _payload(7000, seed=11)
    dc.get_volume("v").get_bucket("b").write_key("contested", newer)
    stats = src.om.run_geo_once()
    assert stats["conflicts"] >= 1
    got = dc.get_volume("v").get_bucket("b").read_key("contested")
    assert np.array_equal(got, newer)  # destination version survived
    # tombstone replay must not delete a destination-local key
    b.write_key("local-at-dest", _payload(100, seed=12))
    local = _payload(200, seed=13)
    src.om.run_geo_once()
    # destination user overwrites the replica -> row loses its marker
    dc.get_volume("v").get_bucket("b").write_key("local-at-dest", local)
    b.delete_key("local-at-dest")
    stats = src.om.run_geo_once()
    assert stats["conflicts"] >= 1
    got = dc.get_volume("v").get_bucket("b").read_key("local-at-dest")
    assert np.array_equal(got, local)  # not resurrected, not deleted


def test_source_overwrite_beats_stale_destination_replica(pair):
    """The other LWW direction: when the source key moves again, the
    replay supersedes the destination replica (fenced on the observed
    destination version)."""
    src, dst, endpoint = pair
    sc = src.client()
    sc.create_volume("v")
    src.om.create_bucket("v", "b", "RATIS/THREE")
    _set_rule(src, endpoint)
    b = sc.get_volume("v").get_bucket("b")
    b.write_key("k", _payload(1000, seed=20))
    src.om.run_geo_once()
    v2 = _payload(2000, seed=21)
    b.write_key("k", v2)
    stats = src.om.run_geo_once()
    assert stats["keys_shipped"] == 1
    got = dst.client().get_volume("v").get_bucket("b").read_key("k")
    assert np.array_equal(got, v2)


# ----------------------------------------------------- journal gap path
def test_journal_gap_reconciles_and_retires_stale_replicas(pair):
    """When the WAL journal rolled past the cursor, the shipper falls
    back to a full reconcile: missing keys ship, and destination
    replicas whose source key vanished (delete lost with the journal)
    are retired by marker."""
    src, dst, endpoint = pair
    sc = src.client()
    sc.create_volume("v")
    src.om.create_bucket("v", "b", "RATIS/THREE")
    _set_rule(src, endpoint)
    b = sc.get_volume("v").get_bucket("b")
    b.write_key("stays", _payload(3000, seed=30))
    b.write_key("goes", _payload(3000, seed=31))
    src.om.run_geo_once()
    b.delete_key("goes")
    d2 = _payload(4000, seed=32)
    b.write_key("fresh", d2)
    # simulate journal retention rolling past the cursor
    with src.om.store._lock:
        src.om.store._updates.clear()
        src.om.store._txid += 10
    stats = src.om.run_geo_once()
    assert stats.get("journal_gap") is True
    dc = dst.client()
    got = dc.get_volume("v").get_bucket("b").read_key("fresh")
    assert np.array_equal(got, d2)
    with pytest.raises(rq.OMError):
        dst.om.lookup_key("v", "b", "goes")  # stale replica retired
    assert dst.om.lookup_key("v", "b", "stays")["size"] == 3000


def test_fan_in_reconcile_never_retires_other_sources(pair):
    """Two source buckets fanning into ONE shared destination bucket:
    a journal-gap reconcile of one source must not retire replicas the
    other source shipped (the geo-src marker scopes retirement), and a
    tombstone from one source never deletes the other's key of the
    same name."""
    src, dst, endpoint = pair
    sc = src.client()
    sc.create_volume("v")
    src.om.create_bucket("v", "b1", "RATIS/THREE")
    src.om.create_bucket("v", "b2", "RATIS/THREE")
    for bname in ("b1", "b2"):
        src.om.set_bucket_geo_replication("v", bname, [{
            "id": "fan", "endpoint": endpoint, "bucket": "shared"}])
    d1 = _payload(3000, seed=50)
    d2 = _payload(3000, seed=51)
    sc.get_volume("v").get_bucket("b1").write_key("from-b1", d1)
    sc.get_volume("v").get_bucket("b2").write_key("from-b2", d2)
    src.om.run_geo_once()
    assert dst.om.lookup_key("v", "shared", "from-b1")["size"] == 3000
    assert dst.om.lookup_key("v", "shared", "from-b2")["size"] == 3000
    # journal gap -> full reconcile of BOTH buckets; b1's sweep of the
    # shared destination must leave b2's replica alone (and vice versa)
    with src.om.store._lock:
        src.om.store._updates.clear()
        src.om.store._txid += 10
    stats = src.om.run_geo_once()
    assert stats.get("journal_gap") is True
    assert stats["deletes_shipped"] == 0
    db = dst.client().get_volume("v").get_bucket("shared")
    assert np.array_equal(db.read_key("from-b1"), d1)
    assert np.array_equal(db.read_key("from-b2"), d2)
    # cross-source tombstone: b1 deletes a name b2 also ships — b2's
    # replica of ITS key must survive b1's tombstone replay
    sc.get_volume("v").get_bucket("b2").write_key("contest", d2)
    src.om.run_geo_once()
    sc.get_volume("v").get_bucket("b1").write_key("contest", d1)
    src.om.run_geo_once()  # b1's version landed last (LWW by ship order)
    sc.get_volume("v").get_bucket("b1").delete_key("contest")
    stats = src.om.run_geo_once()
    # the shared row now belongs to whichever source shipped last; a
    # b1 tombstone may retire only a b1-shipped row — never b2's data
    try:
        row = dst.om.lookup_key("v", "shared", "contest")
        meta = row.get("metadata") or {}
        assert meta.get("geo-src") == "/v/b2"
    except rq.OMError:
        # deleted: legal only if b1's version was the one on the row
        assert stats["deletes_shipped"] >= 1


# --------------------------------------------------------------- guards
def test_fso_bucket_rejected(pair):
    src, _dst, endpoint = pair
    src.client().create_volume("v")
    src.om.create_bucket("v", "fso", "RATIS/THREE",
                         layout="FILE_SYSTEM_OPTIMIZED")
    with pytest.raises(rq.OMError) as ei:
        _set_rule(src, endpoint, bucket="fso")
    assert ei.value.code == rq.INVALID_REQUEST


def test_failed_destination_stalls_cursor_not_silently_skips(pair):
    """A key that cannot reach its destination aborts the cycle WITHOUT
    checkpointing its page: at-least-once, never silently-dropped."""
    src, _dst, endpoint = pair
    sc = src.client()
    sc.create_volume("v")
    src.om.create_bucket("v", "b", "RATIS/THREE")
    src.om.set_bucket_geo_replication("v", "b", [{
        "id": "r1", "endpoint": "nowhere-unregistered-endpoint:1"}])
    b = sc.get_volume("v").get_bucket("b")
    b.write_key("k", _payload(100, seed=40))
    s = ReplicationShipper(src.om, clients=src.clients)
    # the unreachable endpoint raises out of run_once (gRPC dial of a
    # bogus address) — and the cursor/bootstrap set did not advance
    with pytest.raises(Exception):
        s.run_once()
    state = src.om.store.get("system", "geo_state") or {}
    assert not state.get("bootstrapped")
    reg_ok = src.om.set_bucket_geo_replication(  # now point it right
        "v", "b", [{"id": "r1", "endpoint": endpoint}])
    assert reg_ok["geo_replication"][0]["endpoint"] == endpoint


# ------------------------------------------------------------ gateways
def test_s3_gateway_replication_verbs(tmp_path, request):
    from ozone_tpu.gateway.s3 import S3Gateway

    src = _mini(tmp_path, "src")
    endpoint = f"dst-{request.node.name}"
    gw = S3Gateway(src.client(), replication="RATIS/THREE")
    gw.start()
    base = f"http://{gw.address}"

    def req(method, path, data=None):
        return urllib.request.urlopen(urllib.request.Request(
            base + path, data=data, method=method))

    try:
        assert req("PUT", "/geo-b").status == 200
        # no configuration yet -> the AWS 404 code
        with pytest.raises(urllib.error.HTTPError) as ei:
            req("GET", "/geo-b?replication")
        assert ei.value.code == 404
        assert b"ReplicationConfigurationNotFoundError" in ei.value.read()
        body = (
            '<ReplicationConfiguration>'
            '<Role></Role><Rule><ID>dr</ID><Status>Enabled</Status>'
            '<Filter><Prefix>logs/</Prefix></Filter>'
            f'<Destination><Bucket>arn:aws:s3:{endpoint}::mirror'
            '</Bucket><StorageClass>GLACIER</StorageClass>'
            '</Destination></Rule></ReplicationConfiguration>'
        ).encode()
        assert req("PUT", "/geo-b?replication", data=body).status == 200
        tree = ET.fromstring(req("GET", "/geo-b?replication").read())
        ids = [e.text for e in tree.iter() if e.tag.endswith("ID")]
        assert ids == ["dr"]
        arns = [e.text for e in tree.iter()
                if e.tag.endswith("Bucket")]
        assert arns == [f"arn:aws:s3:{endpoint}::mirror"]
        # warm class mapped to an EC scheme
        scs = [e.text for e in tree.iter()
               if e.tag.endswith("StorageClass")]
        assert scs and scs[0].startswith("rs-")
        # malformed XML -> 400 MalformedXML
        with pytest.raises(urllib.error.HTTPError) as ei:
            req("PUT", "/geo-b?replication", data=b"<junk")
        assert ei.value.code == 400
        # DELETE clears; GET 404s again
        assert req("DELETE", "/geo-b?replication").status == 204
        with pytest.raises(urllib.error.HTTPError) as ei:
            req("GET", "/geo-b?replication")
        assert ei.value.code == 404
        # FSO bucket: the deterministic rejection is a CLIENT error
        # (400 InvalidRequest), never a retryable 500
        src.om.create_bucket("s3v", "fsob", "RATIS/THREE",
                             layout="FILE_SYSTEM_OPTIMIZED")
        with pytest.raises(urllib.error.HTTPError) as ei:
            req("PUT", "/fsob?replication", data=body)
        assert ei.value.code == 400
        assert b"InvalidRequest" in ei.value.read()
    finally:
        gw.stop()
        src.close()


def test_recon_replication_endpoint(pair):
    import json

    from ozone_tpu.recon.recon import ReconServer

    src, _dst, endpoint = pair
    src.client().create_volume("v")
    src.om.create_bucket("v", "b", "RATIS/THREE")
    _set_rule(src, endpoint, prefix="logs/")
    recon = ReconServer(src.om, src.scm)
    recon.start()
    try:
        out = json.loads(urllib.request.urlopen(
            f"http://{recon.address}/api/replication", timeout=10)
            .read())
        assert out["buckets"][0]["rules"][0]["endpoint"] == endpoint
        assert "lag" in out and "entries" in out["lag"]
        assert "metrics" in out
        page = urllib.request.urlopen(
            f"http://{recon.address}/", timeout=10).read().decode()
        assert "Geo replication" in page and "/api/replication" in page
    finally:
        recon.stop()


# ----------------------------------------------------------- freon geo
def test_freon_geo_churn_converges(pair):
    """The acceptance churn: write/overwrite/delete under a rule, one
    ship cycle, byte-exact convergence verified THROUGH the destination
    and the lag gauge back at 0."""
    from ozone_tpu.tools import freon

    src, dst, endpoint = pair
    rep = freon.geo(src.client(), endpoint, n_keys=12, size=6_000,
                    threads=2, dest_client=dst.client())
    s = rep.summary()
    assert s["failures"] == 0
    assert s["verify_failures"] == 0
    assert s["shipped"] >= 1 and s["deletes_shipped"] >= 1
    assert s["lag_entries"] == 0
