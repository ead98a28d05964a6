"""The S3 gateway's account of a request: each request is one operation
root `s3:<kind>` whose stages are the gateway's own (`s3:auth`,
`s3:recv`, `s3:etag`, `s3:send`) and the client's operation under it;
the registry `gateway` counts requests, bytes, secret fetches and
admission rejects; the EC writer counts the zero cells that fill a key's
partial stripe (`client.ops` `partial_stripes`, `pad_cells`, and the tag
`pad_cells` of `ec:flush`)."""

import time

import numpy as np
import pytest

from benchmarks.harness import s3_clients
from ozone_tpu import admission
from ozone_tpu.gateway.s3 import S3_VOLUME, S3Gateway, _request_kind
from ozone_tpu.testing.minicluster import MiniOzoneCluster
from ozone_tpu.utils.metrics import registry
from ozone_tpu.utils.tracing import Tracer

EC = "rs-6-3-4096"
K, CELL = 6, 4096
BUCKET = "spans"
ACCESS = "spans-user"
#: object sizes -> zero data cells of their partial stripe
SIZES = {
    "one_stripe_and_4_cells": (K * CELL + 4 * CELL, 2),
    "one_stripe_and_a_short_cell": (K * CELL + 2 * CELL + 1000, 3),
    "a_short_stripe": (CELL + 7, 4),
}


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    c = MiniOzoneCluster(tmp_path_factory.mktemp("s3spans"), num_datanodes=9,
                         stale_after_s=1000.0, dead_after_s=2000.0)
    yield c
    c.close()


@pytest.fixture(scope="module")
def gateway(cluster):
    client = cluster.client()
    gw = S3Gateway(client, replication=EC, require_auth=True)
    client.om.create_bucket(S3_VOLUME, BUCKET, EC)
    secret = client.om.get_s3_secret(ACCESS, create=True)
    gw.start()
    yield gw, secret
    gw.stop()


@pytest.fixture
def conn(gateway):
    gw, secret = gateway
    c = s3_clients.Connection(gw.address, BUCKET,
                              s3_clients.Signer(ACCESS, secret, gw.address))
    yield c
    c.close()


@pytest.fixture
def tracer():
    Tracer._instance = None
    yield Tracer.instance()
    Tracer._instance = None


def _payload(n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8)


def _counters(name: str) -> dict:
    reg = registry(name)
    out = {k: c.value for k, c in list(reg._counters.items())}
    out.update({f"{k}.count": h.count
                for k, h in list(reg._histograms.items())})
    return out


def _delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def _settled(read, done, timeout: float = 10.0):
    """read() once done(it) holds: a root ends, and its request is
    booked, on the gateway's thread just AFTER the reply is written."""
    deadline = time.monotonic() + timeout
    got = read()
    while not done(got) and time.monotonic() < deadline:
        time.sleep(0.01)
        got = read()
    return got


def _requests(before: dict, n: int) -> dict:
    return _settled(lambda: _delta(_counters("gateway"), before),
                    lambda d: sum(v for k, v in d.items()
                                  if k.startswith("requests_")) >= n)


@pytest.mark.parametrize("method,path,kind", [
    ("GET", "b/k", "get"), ("PUT", "b/k/deeper", "put"),
    ("HEAD", "b/k", "head"), ("DELETE", "b/k", "delete"),
    ("GET", "", "other"), ("PUT", "b", "other"), ("POST", "b/k", "other"),
    ("PUT", "b/k?uploadId=1", "other"), ("GET", "b/k?acl", "other"),
    ("DELETE", "b/k?tagging", "other"), ("POST", "b/k?uploads", "other"),
])
def test_a_request_on_an_objects_bytes_is_of_its_methods_kind(
        method, path, kind):
    from urllib.parse import parse_qs

    path, _, query = path.partition("?")
    parts = [p for p in path.split("/") if p]
    assert _request_kind(method, parts,
                         parse_qs(query, keep_blank_values=True)) == kind


def test_each_request_is_one_root_and_the_clients_operation_its_child(
        conn, tracer):
    data = _payload(K * CELL + 4 * CELL)
    t0 = time.monotonic()
    assert conn.request("PUT", "one", data)[0] == 200
    status, _h, body = conn.request("GET", "one")
    assert status == 200 and body == data.tobytes()
    assert conn.request("HEAD", "one")[0] == 200
    assert conn.request("DELETE", "one")[0] == 204
    assert conn.request("GET", "one")[0] == 404
    recs = _settled(lambda: tracer.recorder.operations("", t0),
                    lambda r: len(r) >= 5)
    assert [r["root"] for r in recs] == [
        "s3:put", "s3:get", "s3:head", "s3:delete", "s3:get"]
    put, get, head, delete, missing = (r["stages"] for r in recs)
    assert {"s3:put", "s3:auth", "s3:recv", "s3:etag", "s3:send",
            "client:put", "client:write"} <= set(put)
    assert {"s3:get", "s3:auth", "s3:send", "client:get"} <= set(get)
    assert "s3:recv" not in get and "s3:etag" not in get
    assert {"s3:head", "s3:auth", "s3:send"} <= set(head)
    assert {"s3:delete", "s3:auth", "s3:send"} <= set(delete)
    assert {"s3:get", "s3:auth", "s3:send"} <= set(missing)
    for r in recs:
        # the stages partition the root: they sum to its duration
        assert abs(sum(r["stages"].values()) - r["durationUs"]) \
            <= len(r["stages"])
    # the client's operations are children of the request's root
    spans = tracer.traces()
    roots = {s.trace_id: s for s in spans if s.name.startswith("s3:")
             and not s.parent_id}
    for name in ("client:put", "client:get"):
        (child,) = [s for s in spans if s.name == name]
        assert child.parent_id and child.trace_id in roots
    for name in ("s3:auth", "s3:recv", "s3:etag", "s3:send"):
        assert all(s.parent_id for s in spans if s.name == name), name


@pytest.mark.parametrize("size_name", sorted(SIZES))
def test_the_gateway_registry_counts_what_was_sent(conn, size_name):
    size, _pad = SIZES[size_name]
    before = _counters("gateway")
    names = [f"{size_name}-{i}" for i in range(3)]
    for i, name in enumerate(names):
        assert conn.request("PUT", name, _payload(size, i))[0] == 200
    for name in names[:2]:
        assert conn.request("GET", name)[0] == 200
    assert conn.request("HEAD", names[0])[0] == 200
    assert conn.request("DELETE", names[2])[0] == 204
    got = _requests(before, 7)
    assert got == {
        "requests_put": 3, "requests_get": 2, "requests_head": 1,
        "requests_delete": 1, "request_seconds_put.count": 3,
        "request_seconds_get.count": 2, "request_seconds_head.count": 1,
        "request_seconds_delete.count": 1,
        "bytes_in": 3 * size, "bytes_out": 2 * size,
        "secret_fetches": 7}


def test_a_request_the_gateway_hop_refuses_is_counted(conn, monkeypatch):
    monkeypatch.setenv("OZONE_TPU_ADMIT_OPS_GATEWAY", "1")
    monkeypatch.setenv("OZONE_TPU_ADMIT_BURST_S", "1")
    admission.reset_for_tests()
    try:
        before = _counters("gateway")
        statuses = [conn.request("HEAD", "nothing-here")[0]
                    for _ in range(3)]
        got = _requests(before, 3)
    finally:
        monkeypatch.undo()
        admission.reset_for_tests()
    assert statuses.count(503) == got["admission_rejects"] >= 1
    assert 404 in statuses


@pytest.mark.parametrize("size_name", sorted(SIZES))
def test_pad_cells_count_the_zero_cells_of_a_keys_partial_stripe(
        conn, tracer, size_name):
    size, pad = SIZES[size_name]
    before = _counters("client.ops")
    for i in range(2):
        assert conn.request("PUT", f"pad-{size_name}-{i}",
                            _payload(size, i))[0] == 200
    # a key of whole stripes pads nothing
    assert conn.request("PUT", f"whole-{size_name}",
                        _payload(2 * K * CELL))[0] == 200
    got = _delta(_counters("client.ops"), before)
    assert got.get("partial_stripes") == 2
    assert got.get("pad_cells") == 2 * pad
    flushes = [s for s in tracer.traces() if s.name == "ec:flush"]
    assert sorted(s.tags["pad_cells"] for s in flushes) == [0, pad, pad]


def test_a_head_that_fails_sends_no_body_and_the_connection_stays_in_step(
        conn):
    """A reply to HEAD carries its error's length and no body: on a
    keep-alive connection the next reply is read whole."""
    data = _payload(K * CELL + CELL + 1)
    assert conn.request("PUT", "in-step", data)[0] == 200
    for _ in range(2):
        status, headers, body = conn.request("HEAD", "not-there")
        assert status == 404 and body == b""
        assert int(headers["content-length"]) > 0
    status, _h, body = conn.request("GET", "in-step")
    assert status == 200 and body == data.tobytes()


def test_concurrent_requests_are_each_booked_once(gateway):
    """More clients than cores, the interpreter switching often: no
    request's count or bytes is lost between the handler threads."""
    import os
    import sys
    import threading

    gw, secret = gateway
    data = _payload(K * CELL + 1)
    first = s3_clients.Connection(gw.address, BUCKET, s3_clients.Signer(
        ACCESS, secret, gw.address))
    assert first.request("PUT", "shared", data)[0] == 200
    first.close()
    n_threads, per = 2 * (os.cpu_count() or 4), 5
    before = _counters("gateway")
    errors: list = []

    def client() -> None:
        c = s3_clients.Connection(gw.address, BUCKET, s3_clients.Signer(
            ACCESS, secret, gw.address))
        try:
            for _ in range(per):
                if c.request("GET", "shared")[0] != 200:
                    errors.append("status")
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)
        finally:
            c.close()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=client) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads) and not errors
    got = _requests(before, n_threads * per)
    assert got["requests_get"] == got["request_seconds_get.count"] \
        == n_threads * per
    assert got["bytes_out"] == n_threads * per * data.size
