"""How many OM calls one S3 request makes. The gateway asks the OM only
what the operation's own call does not already check: a GET, ranged GET
or HEAD is the secret and one LookupKey, a DELETE the secret and one
DeleteKey, a PUT the secret, the bucket's small-object settings and the
key's open, block and commit. No request asks for the volume, and no
request asks for the bucket just to learn that it exists."""

import http.client
import threading

import numpy as np
import pytest

from benchmarks.harness import s3_clients
from ozone_tpu.gateway.s3 import S3_VOLUME, S3Gateway
from ozone_tpu.testing.minicluster import MiniOzoneCluster

EC = "rs-6-3-4096"
K, CELL = 6, 4096
BUCKET = "trips"
ACCESS = "trips-user"
#: one full stripe and four cells of the next
SIZE = K * CELL + 4 * CELL

SECRET = ["get_s3_secret"]
#: request kind -> the OM calls it makes, in order
CALLS = {
    "get": SECRET + ["lookup_key"],
    "ranged_get": SECRET + ["lookup_key"],
    "head": SECRET + ["lookup_key"],
    "put": SECRET + ["bucket_info", "open_key", "allocate_block",
                     "commit_key"],
    "delete": SECRET + ["delete_key"],
}


#: client-side helpers that build objects from a reply already held and
#: send nothing, to a remote OM (`net/om_service.py`) as to this one
LOCAL = {"key_block_groups"}


class RecordingOM:
    """The OM as the gateway's client sees it, with the name of every
    call that would be a round trip recorded, in order."""

    def __init__(self, om):
        self._om = om
        self._lock = threading.Lock()
        self.calls: list[str] = []

    def __getattr__(self, name):
        attr = getattr(self._om, name)
        if not callable(attr) or name in LOCAL:
            return attr

        def call(*args, **kwargs):
            with self._lock:
                self.calls.append(name)
            return attr(*args, **kwargs)

        return call


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    c = MiniOzoneCluster(tmp_path_factory.mktemp("s3trips"),
                         num_datanodes=9, stale_after_s=1000.0,
                         dead_after_s=2000.0)
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
def recorded(gateway):
    gw, secret = gateway
    real = gw.client.om
    rec = RecordingOM(real)
    signer = s3_clients.Signer(ACCESS, secret, gw.address)
    conn = s3_clients.Connection(gw.address, BUCKET, signer)
    gw.client.om = rec
    try:
        yield gw, signer, conn, rec
    finally:
        gw.client.om = real
        conn.close()


def _ranged_get(gw, signer, name: str, lo: int, hi: int):
    path = f"/{BUCKET}/{name}"
    headers = signer.headers("GET", path)
    headers["Range"] = f"bytes={lo}-{hi}"
    host, port = gw.address.rsplit(":", 1)
    c = http.client.HTTPConnection(host, int(port), timeout=60)
    try:
        c.request("GET", path, headers=headers)
        resp = c.getresponse()
        return resp.status, resp.read()
    finally:
        c.close()


@pytest.mark.parametrize("kind", sorted(CALLS))
def test_each_request_asks_the_om_only_what_its_own_call_does_not(
        recorded, kind):
    gw, signer, conn, rec = recorded
    data = np.random.default_rng(len(kind)).integers(
        0, 256, SIZE, dtype=np.uint8)
    name = f"obj-{kind}"
    # set-up: the object, and the principal's volume looked up (the
    # gateway keeps that mapping for a minute), before the count
    assert conn.request("PUT", name, data)[0] == 200
    rec.calls.clear()
    if kind == "get":
        status, _h, body = conn.request("GET", name)
        assert status == 200 and body == data.tobytes()
    elif kind == "ranged_get":
        lo, hi = CELL - 5, K * CELL + 9
        status, body = _ranged_get(gw, signer, name, lo, hi)
        assert status == 206 and body == data[lo:hi + 1].tobytes()
    elif kind == "head":
        status, headers, _b = conn.request("HEAD", name)
        assert status == 200 and int(headers["content-length"]) == SIZE
    elif kind == "put":
        status = conn.request("PUT", name + "-again", data)[0]
        assert status == 200
    else:
        assert conn.request("DELETE", name)[0] == 204
    calls = list(rec.calls)
    if kind == "delete":
        assert conn.request("GET", name)[0] == 404
    assert calls == CALLS[kind]
    assert "volume_info" not in calls
