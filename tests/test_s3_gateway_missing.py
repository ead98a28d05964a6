"""What an S3 request answers when its bucket, key or tenant volume is
missing, on the paths where the gateway leaves the existence check to
the operation's own OM call: a missing bucket is 404 NoSuchBucket on
every object verb, a multi-delete, the multipart calls and either end of
a copy; a missing key is 404 NoSuchKey on GET and 204 on DELETE; a
tenant whose volume is gone answers 404 NoSuchBucket, not 500. A bucket
that opts into small objects after the gateway started stores its next
small PUT inline: the gateway keeps nothing about a bucket between
requests."""

import http.client
import time
import xml.etree.ElementTree as ET

import pytest

from ozone_tpu.gateway.s3 import S3_VOLUME, S3Gateway
from ozone_tpu.gateway.s3_auth import sign_request
from ozone_tpu.testing.minicluster import MiniOzoneCluster

EC = "rs-3-2-4096"
BUCKET = "here"
ACCESS = "missing-user"
TENANT = "ghostco"


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    c = MiniOzoneCluster(tmp_path_factory.mktemp("s3missing"),
                         num_datanodes=5, stale_after_s=1000.0,
                         dead_after_s=2000.0)
    yield c
    c.close()


@pytest.fixture(scope="module")
def gateway(cluster):
    client = cluster.client()
    gw = S3Gateway(client, replication=EC, require_auth=True)
    om = client.om
    om.create_bucket(S3_VOLUME, BUCKET, EC)
    secret = om.get_s3_secret(ACCESS, create=True)
    gw.start()
    # a tenant whose volume is deleted after its user was granted
    om.create_tenant(TENANT)
    grant = om.tenant_assign_user(TENANT, "ghost")
    om.delete_volume(TENANT)
    yield gw, (ACCESS, secret), (grant["access_id"], grant["secret"])
    gw.stop()


def _request(gw, creds, method: str, path: str, body: bytes = b"",
             headers: dict | None = None):
    """(status, error code or "", body) of one SigV4-signed request."""
    url = f"http://{gw.address}{path}"
    hdrs = {"host": gw.address,
            "x-amz-date": time.strftime("%Y%m%dT%H%M%SZ", time.gmtime()),
            **(headers or {})}
    hdrs = sign_request(*creds, method, url, hdrs, body)
    host, port = gw.address.rsplit(":", 1)
    c = http.client.HTTPConnection(host, int(port), timeout=60)
    try:
        c.request(method, path, body=body or None, headers=hdrs)
        resp = c.getresponse()
        data = resp.read()
    finally:
        c.close()
    code = (ET.fromstring(data).findtext("Code")
            if data and resp.status >= 400 else "")
    return resp.status, code or "", data


def _put(gw, creds, path: str, body: bytes = b"payload") -> None:
    assert _request(gw, creds, "PUT", path, body)[0] == 200


_DELETE_XML = (b"<Delete><Object><Key>a</Key></Object>"
               b"<Object><Key>b</Key></Object></Delete>")

#: case -> (method, path, body, extra headers); "nosuch" is no bucket
MISSING_BUCKET = {
    "get": ("GET", "/nosuch/k", b"", None),
    "head": ("HEAD", "/nosuch/k", b"", None),
    "put": ("PUT", "/nosuch/k", b"payload", None),
    "delete": ("DELETE", "/nosuch/k", b"", None),
    "multi_delete": ("POST", "/nosuch?delete", _DELETE_XML, None),
    "multipart_initiate": ("POST", "/nosuch/k?uploads", b"", None),
    "multipart_part": ("PUT", "/nosuch/k?partNumber=1&uploadId=u1",
                       b"part", None),
    "multipart_abort": ("DELETE", "/nosuch/k?uploadId=u1", b"", None),
    "copy_from_missing_bucket": (
        "PUT", f"/{BUCKET}/copy-dst", b"",
        {"x-amz-copy-source": "/nosuch/k"}),
    "copy_to_missing_bucket": (
        "PUT", "/nosuch/copy-dst", b"",
        {"x-amz-copy-source": f"/{BUCKET}/copy-src"}),
}


@pytest.mark.parametrize("case", sorted(MISSING_BUCKET))
def test_a_missing_bucket_is_no_such_bucket(gateway, case):
    gw, creds, _tenant = gateway
    method, path, body, headers = MISSING_BUCKET[case]
    if case == "copy_to_missing_bucket":
        _put(gw, creds, f"/{BUCKET}/copy-src")
    status, code, _data = _request(gw, creds, method, path, body, headers)
    assert status == 404
    # a reply to HEAD carries no body, so no code either
    assert code == ("" if method == "HEAD" else "NoSuchBucket")


@pytest.mark.parametrize("method,status,code", [
    ("GET", 404, "NoSuchKey"),
    ("DELETE", 204, ""),
])
def test_a_missing_key_in_a_bucket_that_exists(gateway, method, status,
                                                code):
    gw, creds, _tenant = gateway
    assert _request(gw, creds, method, f"/{BUCKET}/never-put")[:2] == (
        status, code)


@pytest.mark.parametrize("method,path", [
    ("GET", "/tb/k"), ("HEAD", "/tb/k"), ("PUT", "/tb/k"),
    ("DELETE", "/tb/k"), ("PUT", "/tb"),
])
def test_a_tenant_whose_volume_is_gone_has_no_such_bucket(gateway, method,
                                                          path):
    gw, _creds, tenant = gateway
    body = b"payload" if path == "/tb/k" and method == "PUT" else b""
    status, code, _data = _request(gw, tenant, method, path, body)
    assert status == 404
    assert code == ("" if method == "HEAD" else "NoSuchBucket")


def test_a_bucket_that_opts_into_small_objects_is_seen_at_once(gateway):
    gw, creds, _tenant = gateway
    om = gw.client.om
    om.create_bucket(S3_VOLUME, "late", EC)
    _put(gw, creds, "/late/before", b"x" * 100)
    assert om.lookup_key(S3_VOLUME, "late", "before").get("inline") is None
    om.set_bucket_smallobj(S3_VOLUME, "late")
    _put(gw, creds, "/late/after", b"y" * 100)
    info = om.lookup_key(S3_VOLUME, "late", "after")
    assert info.get("inline") is not None and info["size"] == 100
    assert _request(gw, creds, "GET", "/late/after") == (
        200, "", b"y" * 100)
