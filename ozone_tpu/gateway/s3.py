"""S3-compatible REST gateway.

Mirror of the reference's s3gateway (hadoop-ozone/s3gateway: stateless
JAX-RS endpoints — ObjectEndpoint.java:147 put:217/get:395 with range
reads and multipart upload, BucketEndpoint list/multi-delete, Gateway.java
main): a stateless HTTP translator in front of the object store client.
Buckets live in the designated "s3v" volume like the reference's S3
volume mapping. Multipart uploads store parts as hidden keys and stitch
them on complete (the reference tracks parts in OM's multipartInfo table).

Auth (_authenticate, enforced when require_auth=True): full AWS SigV4
verification against the OM's s3-secret table — header-auth and
presigned-URL query-auth, including aws-chunked payload signatures
(STREAMING-AWS4-HMAC-SHA256-PAYLOAD chunk-by-chunk) — the role the
reference's AWSSignatureProcessor + OM S3 secret validation play.
Anonymous access is allowed only where a public bucket ACL grants it
(see _authorize_anonymous: GET/HEAD under a bucket ACL exposing READ,
never mutations) or when require_auth=False (in-framework/test mode,
where requests without credentials run as the gateway identity). The
wire protocol (paths, query verbs, XML bodies, ETags) follows S3.
"""

from __future__ import annotations

import hashlib
import json
import logging
import math
import threading
import time
import xml.etree.ElementTree as ET
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional
from urllib.parse import parse_qs, quote as _url_quote, unquote, urlparse

import numpy as np

from ozone_tpu import admission
from ozone_tpu.client.ozone_client import OzoneBucket, OzoneClient
from ozone_tpu.gateway.s3_auth import (
    STREAMING,
    AuthError,
    decode_aws_chunked,
    parse_authorization,
    parse_query_auth,
    verify_presigned,
    verify_request,
)
from ozone_tpu.om.requests import OMError
from ozone_tpu.storage.ids import StorageError
from ozone_tpu.utils.metrics import registry
from ozone_tpu.utils.tracing import Tracer

# a local OzoneManager raises OMError; a remote OM (GrpcOmClient) re-raises
# the same codes as StorageError — the gateway maps both identically
_OM_ERRORS = (OMError, StorageError)

log = logging.getLogger(__name__)

S3_VOLUME = "s3v"
_NS = "http://s3.amazonaws.com/doc/2006-03-01/"

#: the gateway's registry: `requests_<kind>` and a histogram
#: `request_seconds_<kind>` for each kind of request (`_request_kind`),
#: `bytes_in` / `bytes_out` (request and reply bodies), `secret_fetches`
#: (the OM's secret asked for, once a signed request) and
#: `admission_rejects` (requests the gateway hop refused as SlowDown)
METRICS = registry("gateway")
_KINDS = ("get", "put", "head", "delete", "other")
# made here, not at a first use that two handler threads could race to
for _name in ("bytes_in", "bytes_out", "secret_fetches", "admission_rejects",
              *(f"requests_{k}" for k in _KINDS)):
    METRICS.counter(_name)
for _kind in _KINDS:
    METRICS.histogram(f"request_seconds_{_kind}")

#: query verbs that make a request on an object something other than a
#: plain GET / PUT / HEAD / DELETE of its bytes
_SUBRESOURCES = frozenset({"uploads", "uploadId", "acl", "tagging"})


def _request_kind(method: str, parts: list, q: dict) -> str:
    """`get`, `put`, `head` or `delete` for a request on an object's
    bytes; `other` for everything else (bucket and service requests,
    multipart, ACLs, tags). Each request is one operation root
    `s3:<kind>`."""
    if (len(parts) >= 2 and method in ("GET", "PUT", "HEAD", "DELETE")
            and not _SUBRESOURCES.intersection(q)):
        return method.lower()
    return "other"


def _xml(root: ET.Element) -> bytes:
    return b'<?xml version="1.0" encoding="UTF-8"?>' + ET.tostring(root)


def _iso_now() -> str:
    import time

    return _iso_ts(time.time())


def _iso_ts(ts: float) -> str:
    import datetime

    return datetime.datetime.fromtimestamp(
        ts, datetime.timezone.utc
    ).strftime("%Y-%m-%dT%H:%M:%S.000Z")


def _opaque_token(key: str) -> str:
    """V2 continuation tokens are SERVER-issued opaque strings (AWS
    contract; SDKs never decode them). Ours wrap the resume key, which
    may contain XML-hostile bytes — base64url with a version prefix and
    a CRC32 tag keeps the response well-formed for ANY key and makes the
    token self-validating (a raw key that happens to look like one can't
    be misdecoded)."""
    import base64
    import zlib

    raw = key.encode()
    tag = zlib.crc32(raw).to_bytes(4, "big")
    return "t2:" + base64.urlsafe_b64encode(tag + raw).decode()


def _parse_token(token: str) -> str:
    import base64
    import zlib

    if token.startswith("t2:"):
        # current format: CRC32 tag + key
        try:
            blob = base64.urlsafe_b64decode(token[3:])
            if (len(blob) >= 4
                    and zlib.crc32(blob[4:]).to_bytes(4, "big") == blob[:4]):
                return blob[4:].decode()
        except Exception:  # noqa: BLE001 - malformed: treat as raw
            pass
    elif token.startswith("t1:"):
        # legacy in-flight tokens: the t1 prefix shipped in TWO shapes
        # (tag-less, then CRC-tagged in place — the in-place change is
        # why the current format is t2). Try the tagged shape first
        # (what the immediately-previous release emitted), then the
        # original tag-less decode; an upgraded gateway mis-parsing an
        # old token would silently resume a listing from a wrong key.
        try:
            blob = base64.urlsafe_b64decode(token[3:])
            if (len(blob) >= 4
                    and zlib.crc32(blob[4:]).to_bytes(4, "big") == blob[:4]):
                return blob[4:].decode()
            return blob.decode()
        except Exception:  # noqa: BLE001 - malformed: treat as raw
            pass
    return token  # raw keys from older clients / start-after reuse


def _esc_fn(q: dict):
    """?encoding-type=url handling shared by every listing verb: returns
    (enc_url, esc) where esc URL-encodes key-derived response strings so
    XML-hostile bytes survive the round trip."""
    enc_url = q.get("encoding-type", [""])[0] == "url"
    return enc_url, ((lambda s: _url_quote(s, safe="/")) if enc_url
                     else (lambda s: s))


def _etag(body: bytes) -> str:
    """An object's ETag, the MD5 of its bytes (stage `s3:etag`)."""
    with Tracer.instance().span("s3:etag", bytes=len(body)):
        return hashlib.md5(body).hexdigest()


def _err(code: str, message: str, status: int) -> tuple[int, bytes]:
    e = ET.Element("Error")
    ET.SubElement(e, "Code").text = code
    ET.SubElement(e, "Message").text = message
    return status, _xml(e)


class S3Gateway:
    def __init__(self, client: OzoneClient, host: str = "127.0.0.1",
                 port: int = 0, replication: str = "rs-6-3-1024k",
                 require_auth: bool = False,
                 max_clock_skew_s: float = 900.0,
                 domain: Optional[str] = None):
        self.client = client
        self.replication = replication
        #: virtual-host-style addressing (VirtualHostStyleFilter.java):
        #: requests whose Host is <bucket>.<domain> route to that bucket
        #: with the path holding only the key. None = path-style only.
        self.domain = domain
        # require_auth=True enforces SigV4 on every request (anonymous
        # access still allowed per public bucket ACL grants); False
        # accepts unsigned requests but validates presented signatures
        self.require_auth = require_auth
        # signed-request freshness window (AWS: 15 min); 0 disables
        self.max_clock_skew_s = max_clock_skew_s
        # layout-feature view (refreshed from the OM on a short TTL):
        # gates gateway-side feature paths like aws-chunked uploads
        self._upgrade_cache: Optional[dict] = None
        self._upgrade_cache_t = 0.0
        self.upgrade_cache_ttl_s = 5.0
        try:
            client.om.create_volume(S3_VOLUME)
        except _OM_ERRORS:
            pass
        # per-request bucket namespace: the default s3v volume, or the
        # authenticated principal's tenant volume (reference
        # OMMultiTenantManager: accessId -> tenant -> tenant volume).
        # ThreadingHTTPServer handles each request on its own thread, so a
        # thread-local carries it without plumbing through every handler.
        self._request_ctx = threading.local()
        # accessId -> (volume, expiry): tenant assignment is admin-rare,
        # so a short TTL cache keeps the hot path at one OM round trip
        # (the secret fetch) instead of two
        self._tenant_cache: dict = {}
        self._tenant_cache_ttl_s = 60.0
        gateway = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                log.debug("s3: " + fmt, *args)

            def _reply(self, status: int, body=b"",
                       headers: Optional[dict] = None):
                # stage `s3:send`: the reply written, an object's array
                # made bytes included
                with Tracer.instance().span("s3:send"):
                    if isinstance(body, np.ndarray):
                        body = body.tobytes()
                    self.send_response(status)
                    for k, v in (headers or {}).items():
                        self.send_header(k, v)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    # a reply to HEAD has no body (RFC 9110 9.3.2), an
                    # error's neither: on a keep-alive connection the
                    # client would read it as the next reply's start
                    if body and self.command != "HEAD":
                        self.wfile.write(body)
                        METRICS.counter("bytes_out").inc(len(body))

            def _body(self) -> bytes:
                # memoized: read once so both signature verification and
                # the operation handler can consume it
                if not hasattr(self, "_cached_body"):
                    n = int(self.headers.get("Content-Length", 0))
                    if n:
                        # stage `s3:recv`: the body off the socket
                        with Tracer.instance().span("s3:recv", bytes=n):
                            self._cached_body = self.rfile.read(n)
                        METRICS.counter("bytes_in").inc(n)
                    else:
                        self._cached_body = b""
                return self._cached_body

            def _dispatch(self, method: str):
                # the handler instance persists across requests on a
                # keep-alive connection — drop the previous request's
                # memoized body or it would be served again
                self.__dict__.pop("_cached_body", None)
                gateway._route(self, method)

            def do_GET(self):
                self._dispatch("GET")

            def do_PUT(self):
                self._dispatch("PUT")

            def do_POST(self):
                self._dispatch("POST")

            def do_DELETE(self):
                self._dispatch("DELETE")

            def do_HEAD(self):
                self._dispatch("HEAD")

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self.port = self._httpd.server_port
        self.host = host
        self._thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------- lifecycle
    @property
    def address(self) -> str:
        return f"{self.host}:{self.port}"

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="s3-gateway", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()

    # ------------------------------------------------------------- routing
    def _authenticate(self, h, method: str) -> Optional[str]:
        """SigV4 validation (reference: s3gateway AuthorizationFilter +
        AWSSignatureProcessor, secret from OM's s3SecretTable). Returns
        the authenticated access id, or None for anonymous requests.
        Handles all three SigV4 carriages: the Authorization header,
        query parameters (presigned URLs), and aws-chunked streaming
        payloads (per-chunk signatures chained from the seed)."""
        u = urlparse(h.path)
        header = h.headers.get("Authorization")
        if not header:
            # real parameter check, not a substring test: an anonymous
            # request whose query merely CONTAINS the text (e.g. a key
            # prefix filter) must not be misrouted into presigned auth
            if "X-Amz-Signature" in parse_qs(u.query):
                return self._authenticate_presigned(h, method, u)
            if str(h.headers.get("x-amz-content-sha256", "")) == STREAMING:
                # anonymous aws-chunked has no seed signature to verify
                # a chunk chain against; storing the body verbatim would
                # persist the chunk framing as object data
                raise AuthError("InvalidRequest",
                                "aws-chunked streaming requires SigV4")
            return None
        auth = parse_authorization(header)
        METRICS.counter("secret_fetches").inc()
        secret = self.client.om.get_s3_secret(auth.access_id, create=False)
        if secret is None:
            raise AuthError("InvalidAccessKeyId", auth.access_id)
        verify_request(
            secret, method, u.path, u.query, dict(h.headers), h._body(),
            auth, max_skew_s=self.max_clock_skew_s or None,
        )
        if str(h.headers.get("x-amz-content-sha256", "")) == STREAMING:
            if not self._feature_allowed("S3_CHUNKED_UPLOAD"):
                # layout-gated gateway feature (RequestFeatureValidator
                # pattern applied at the S3 admission point): refuse
                # until the cluster finalizes
                raise AuthError(
                    "NotImplemented",
                    "aws-chunked uploads need layout feature "
                    "S3_CHUNKED_UPLOAD; cluster is not finalized")
            # chunked-signature streaming PUT (ObjectEndpointStreaming):
            # verify the chunk chain and hand the DECODED payload to the
            # object op; declared decoded length must match
            amz_date = str(h.headers.get("x-amz-date", ""))
            decoded = decode_aws_chunked(
                h._body(), secret, auth, amz_date, auth.signature)
            declared = h.headers.get("x-amz-decoded-content-length")
            if declared is not None:
                try:
                    expect = int(declared)
                except ValueError:
                    raise AuthError(  # 4xx, not an InternalError 500
                        "InvalidArgument",
                        f"bad x-amz-decoded-content-length: {declared!r}")
                if expect != len(decoded):
                    raise AuthError("IncompleteBody",
                                    f"decoded {len(decoded)} != {declared}")
            h._cached_body = decoded
        return auth.access_id

    def _feature_allowed(self, name: str) -> bool:
        """Is a layout-gated feature finalized cluster-wide? Served from
        the OM's UpgradeStatus with a short cache. Fails OPEN on a
        status-fetch error: an unreachable OM will fail the actual
        upload anyway, and gating only matters while the (reachable)
        cluster is mid-upgrade."""
        import time as _time

        now = _time.monotonic()
        if (self._upgrade_cache is None
                or now - self._upgrade_cache_t > self.upgrade_cache_ttl_s):
            try:
                self._upgrade_cache = self.client.om.upgrade_status()
                self._upgrade_cache_t = now
            except Exception:  # noqa: BLE001
                return True
        feats = {f["name"]: f.get("allowed", True)
                 for f in self._upgrade_cache.get("features", [])}
        return bool(feats.get(name, True))

    def _authenticate_presigned(self, h, method: str, u) -> str:
        if str(h.headers.get("x-amz-content-sha256", "")) == STREAMING:
            # presigned URLs sign UNSIGNED-PAYLOAD; there is no seed
            # signature to chain chunk signatures from, and storing the
            # body verbatim would persist the chunk framing
            raise AuthError("InvalidRequest",
                            "aws-chunked streaming cannot be presigned")
        parsed = parse_query_auth(u.query)
        auth = parsed[0]
        METRICS.counter("secret_fetches").inc()
        secret = self.client.om.get_s3_secret(auth.access_id, create=False)
        if secret is None:
            raise AuthError("InvalidAccessKeyId", auth.access_id)
        # hand over the REAL request headers: X-Amz-SignedHeaders picks
        # which ones enter the canonical request, and SDKs may sign more
        # than just host (e.g. host;x-amz-content-sha256)
        headers = {k.lower(): v for k, v in h.headers.items()}
        headers.setdefault("host", "")
        return verify_presigned(
            secret, method, u.path, u.query, headers,
            parsed=parsed, max_skew_s=self.max_clock_skew_s or None,
        )

    def _public_grants(self, bucket: str) -> set:
        try:
            acl = self.client.om.get_bucket_acl(self._vol, bucket)
        except _OM_ERRORS:
            return set()
        return {
            g.get("permission")
            for g in acl
            if g.get("grantee") == "*"
        }

    def _anonymous_allowed(self, method: str, bucket: str) -> bool:
        grants = self._public_grants(bucket)
        if "FULL_CONTROL" in grants:
            return True
        if method in ("GET", "HEAD"):
            return "READ" in grants
        return "WRITE" in grants

    @property
    def _vol(self) -> str:
        return getattr(self._request_ctx, "volume", S3_VOLUME)

    def _volume_for(self, access_id: str) -> str:
        import time as _time

        now = _time.monotonic()
        hit = self._tenant_cache.get(access_id)
        if hit is not None and hit[1] > now:
            return hit[0]
        tenant = self.client.om.tenant_for_access_id(access_id)
        vol = tenant["volume"] if tenant is not None else S3_VOLUME
        self._tenant_cache[access_id] = (vol, now + self._tenant_cache_ttl_s)
        return vol

    def _vhost_bucket(self, h) -> Optional[str]:
        """Bucket from virtual-host-style addressing: Host =
        <bucket>.<domain> (VirtualHostStyleFilter.java semantics; the
        port is ignored, an exact-domain Host stays path-style)."""
        if self.domain is None:
            return None
        host = (h.headers.get("Host") or "").split(":")[0]
        suffix = "." + self.domain
        if host.endswith(suffix) and len(host) > len(suffix):
            return host[: -len(suffix)]
        return None

    def _route(self, h, method: str) -> None:
        """One request as one operation root `s3:<kind>`, from its
        request line to its reply written: the client's own operation
        (`client:get`, `client:put`) and the gateway's stages
        (`s3:auth`, `s3:recv`, `s3:etag`, `s3:send`) are its children."""
        u = urlparse(h.path)
        q = parse_qs(u.query, keep_blank_values=True)
        parts = [unquote(p) for p in u.path.strip("/").split("/") if p]
        vbucket = self._vhost_bucket(h)
        if vbucket is not None:
            parts = [vbucket] + parts
        kind = _request_kind(method, parts, q)
        tracer = Tracer.instance()
        root = tracer.begin_operation(f"s3:{kind}", method=method)
        t0 = time.perf_counter()
        try:
            with tracer.activate(tracer.context(root)):
                self._serve(h, method, u, q, parts)
        finally:
            tracer.end_operation(root)
            METRICS.counter(f"requests_{kind}").inc()
            METRICS.histogram(f"request_seconds_{kind}").observe(
                time.perf_counter() - t0, root.trace_id)

    def _serve(self, h, method: str, u, q: dict, parts: list) -> None:
        try:
            # stage `s3:auth`: the signature checked against the secret
            # the OM holds (its RPC is a child) and the principal's volume
            with Tracer.instance().span("s3:auth"):
                principal = self._authenticate(h, method)
                self._request_ctx.volume = (
                    self._volume_for(principal) if principal is not None
                    else S3_VOLUME
                )
            if principal is None and self.require_auth:
                # anonymous: gated by the bucket's public ACL grants
                # (READ for reads, WRITE for mutations)
                if not (parts and self._anonymous_allowed(method, parts[0])):
                    h._reply(*_err("AccessDenied", "anonymous access", 403))
                    return
            # admission: the tenant key is the RESOLVED volume, so every
            # access id of one tenant shares the same buckets (and the
            # untenanted world shares "s3v"). Looked up per request, not
            # cached on self, so reset_for_tests() re-reads knobs live.
            tenant = self._vol
            ctl = admission.controller("gateway")
            with admission.tenant_context(tenant):
                # charge BEFORE reading the body: rejecting by the
                # declared Content-Length is what makes a rejection
                # cheaper than the work it sheds
                nbytes = (int(h.headers.get("Content-Length") or 0)
                          if method in ("PUT", "POST") else 0)
                ctl.charge(tenant, nbytes,
                           priority=admission.ambient_qos())
                with ctl.admit(method):
                    if not parts:
                        self._list_buckets(h)
                        return
                    bucket, key = parts[0], "/".join(parts[1:])
                    if not key:
                        self._bucket_op(h, method, bucket, q)
                    else:
                        self._object_op(h, method, bucket, key, q)
        except AuthError as e:
            status = (400 if "Malformed" in e.code or e.code in
                      ("InvalidRequest", "InvalidArgument",
                       "IncompleteBody",
                       "AuthorizationQueryParametersError")
                      else 501 if e.code == "NotImplemented" else 403)
            h._reply(*_err(e.code, str(e), status))
        except _OM_ERRORS as e:
            code = {
                "KEY_NOT_FOUND": ("NoSuchKey", 404),
                "BUCKET_NOT_FOUND": ("NoSuchBucket", 404),
                # a tenant whose volume is gone has no buckets at all
                "VOLUME_NOT_FOUND": ("NoSuchBucket", 404),
                "BUCKET_ALREADY_EXISTS": ("BucketAlreadyExists", 409),
                "BUCKET_NOT_EMPTY": ("BucketNotEmpty", 409),
                "NO_SUCH_MULTIPART_UPLOAD": ("NoSuchUpload", 404),
                "INVALID_PART": ("InvalidPart", 400),
                "QUOTA_EXCEEDED": ("QuotaExceeded", 403),
                # deterministic rule rejections (e.g. lifecycle or geo
                # replication on an FSO bucket) are client errors: a
                # 500 would make SDKs retry a request that can never
                # succeed
                "INVALID_REQUEST": ("InvalidRequest", 400),
                # admission pushback (queue bound, tenant bucket, SLO
                # shed) maps to the S3 throttling vocabulary — 503
                # SlowDown — so stock SDK retry policies back off
                # instead of treating overload as a hard failure
                "SERVER_BUSY": ("SlowDown", 503),
            }.get(e.code, ("InternalError", 500))
            headers = None
            if e.code == "SERVER_BUSY":
                METRICS.counter("admission_rejects").inc()
                # Retry-After is integer seconds (RFC 9110); round UP so
                # the client never comes back before the hinted instant
                hint = admission.retry_after_hint(str(e)) or 1.0
                headers = {"Retry-After": str(max(1, math.ceil(hint)))}
            status, body = _err(code[0], str(e), code[1])
            h._reply(status, body, headers)
        except Exception as e:  # noqa: BLE001
            log.exception("s3 %s %s failed", method, h.path)
            h._reply(*_err("InternalError", str(e), 500))

    # ------------------------------------------------------------- buckets
    def _list_buckets(self, h) -> None:
        root = ET.Element("ListAllMyBucketsResult", xmlns=_NS)
        buckets = ET.SubElement(root, "Buckets")
        for b in self.client.om.list_buckets(self._vol):
            be = ET.SubElement(buckets, "Bucket")
            ET.SubElement(be, "Name").text = b["name"]
            ET.SubElement(be, "CreationDate").text = str(b.get("created", ""))
        h._reply(200, _xml(root), {"Content-Type": "application/xml"})

    _CANNED_ACLS = {
        "private": [],
        "public-read": [{"grantee": "*", "permission": "READ"}],
        "public-read-write": [
            {"grantee": "*", "permission": "READ"},
            {"grantee": "*", "permission": "WRITE"},
        ],
    }

    def _bucket_acl_op(self, h, method: str, bucket: str) -> None:
        """?acl subresource (reference BucketEndpoint get/put ACL: S3
        grants map onto bucket ACLs)."""
        om = self.client.om
        if method == "GET":
            acl = om.get_bucket_acl(self._vol, bucket)
            root = ET.Element("AccessControlPolicy", xmlns=_NS)
            owner = ET.SubElement(root, "Owner")
            ET.SubElement(owner, "ID").text = "owner"
            grants = ET.SubElement(root, "AccessControlList")
            for g in acl or [{"grantee": "owner",
                              "permission": "FULL_CONTROL"}]:
                ge = ET.SubElement(grants, "Grant")
                gr = ET.SubElement(ge, "Grantee")
                ET.SubElement(gr, "ID").text = g["grantee"]
                ET.SubElement(ge, "Permission").text = g["permission"]
            h._reply(200, _xml(root), {"Content-Type": "application/xml"})
        elif method == "PUT":
            canned = h.headers.get("x-amz-acl")
            if canned is not None:
                if canned not in self._CANNED_ACLS:
                    h._reply(*_err("InvalidArgument", canned, 400))
                    return
                acl = self._CANNED_ACLS[canned]
            else:
                try:
                    acl = self._parse_acl_body(h._body())
                except (ET.ParseError, KeyError) as e:
                    h._reply(*_err("MalformedACLError", str(e), 400))
                    return
            om.set_bucket_acl(self._vol, bucket, acl)
            h._reply(200)
        else:
            h._reply(*_err("MethodNotAllowed", method, 405))

    @staticmethod
    def _parse_acl_body(body: bytes) -> list[dict]:
        acl = []
        if not body:
            return acl
        for ge in ET.fromstring(body).iter():
            if ge.tag.rpartition("}")[2] != "Grant":
                continue
            fields = {c.tag.rpartition("}")[2]: c for c in ge}
            grantee = fields.get("Grantee")
            gid = ""
            if grantee is not None:
                for c in grantee:
                    if c.tag.rpartition("}")[2] in ("ID", "URI"):
                        gid = (c.text or "").rpartition("/")[2]
            if gid in ("AllUsers",):
                gid = "*"
            acl.append({
                "grantee": gid,
                "permission": (fields["Permission"].text or "").strip(),
            })
        return acl

    def _bucket_op(self, h, method: str, bucket: str, q) -> None:
        om = self.client.om
        if "acl" in q:
            self._bucket_acl_op(h, method, bucket)
            return
        if "tagging" in q:
            # bucket tagging is not supported (object tagging is);
            # answer the AWS way instead of falling through to a
            # ListBucketResult that get-bucket-tagging would misparse
            om.bucket_info(self._vol, bucket)  # 404 on missing bucket
            if method == "GET":
                h._reply(*_err("NoSuchTagSet",
                               "no tag set on this bucket", 404))
            else:
                h._body()
                h._reply(*_err("NotImplemented",
                               "bucket tagging is not supported", 501))
            return
        if method == "GET" and "location" in q:
            # SDK handshake endpoints (boto3 probes these): one region
            om.bucket_info(self._vol, bucket)  # 404 on missing bucket
            root = ET.Element("LocationConstraint", xmlns=_NS)
            root.text = "us-east-1"
            h._reply(200, _xml(root), {"Content-Type": "application/xml"})
            return
        if method == "PUT" and "versioning" in q:
            om.bucket_info(self._vol, bucket)  # NoSuchBucket -> 404
            # not wired to object versions; failing loudly beats the
            # silent 200 the create-bucket branch would return
            h._reply(*_err("NotImplemented",
                           "bucket versioning is not supported", 501))
            return
        if method == "GET" and "versioning" in q:
            info = om.bucket_info(self._vol, bucket)
            root = ET.Element("VersioningConfiguration", xmlns=_NS)
            if info.get("versioning"):
                ET.SubElement(root, "Status").text = "Enabled"
            h._reply(200, _xml(root), {"Content-Type": "application/xml"})
            return
        if method == "GET" and "uploads" in q:
            self._list_uploads(h, bucket, q)
            return
        if "lifecycle" in q:
            # Put/Get/DeleteBucketLifecycleConfiguration, backed by the
            # OM's replicated bucket metadata + the lifecycle sweeper
            # (lifecycle/policy.py) — a deliberate extension beyond
            # Apache Ozone 1.5, which answers 501 here
            self._bucket_lifecycle_op(h, method, bucket)
            return
        if "replication" in q:
            # Put/Get/DeleteBucketReplication, backed by the OM's
            # replicated bucket metadata + the geo-DR shipper
            # (replication_geo/) — a deliberate extension beyond
            # Apache Ozone 1.5, which answers 501 here
            self._bucket_replication_op(h, method, bucket)
            return
        # subresources the store does not implement answer the AWS way
        # (501 NotImplemented, like the reference's unsupported-feature
        # responses) instead of falling through to bucket create/list —
        # a silent 200 would make `aws s3api put-bucket-policy`
        # look like it took effect
        for sub in ("policy", "website", "cors",
                    "encryption", "accelerate",
                    "requestPayment", "logging", "notification",
                    "inventory", "analytics", "metrics", "intelligent-tiering",
                    "ownershipControls", "publicAccessBlock"):
            if sub in q:
                if method in ("PUT", "POST", "DELETE"):
                    # drain BEFORE any raising call, or an early 404
                    # leaves body bytes on a keep-alive socket
                    h._body()
                om.bucket_info(self._vol, bucket)  # NoSuchBucket -> 404
                h._reply(*_err(
                    "NotImplemented",
                    f"bucket {sub} is not supported", 501))
                return
        if method == "PUT":
            try:
                om.create_bucket(self._vol, bucket, self.replication)
            except OMError as e:
                # S3 returns success when the same owner re-creates a bucket
                if e.code != "BUCKET_ALREADY_EXISTS":
                    raise
            h._reply(200, headers={"Location": f"/{bucket}"})
        elif method == "DELETE":
            om.delete_bucket(self._vol, bucket)
            h._reply(204)
        elif method in ("GET",):
            self._list_objects(h, bucket, q)
        elif method == "POST" and "delete" in q:
            self._multi_delete(h, bucket)
        elif method == "HEAD":
            om.bucket_info(self._vol, bucket)
            h._reply(200)
        else:
            h._reply(*_err("MethodNotAllowed", method, 405))

    def _default_ec_target(self) -> str:
        """Warm storage classes map to this gateway's scheme when it IS
        an RS scheme; a replicated-default gateway tiers to the
        cluster-default EC layout. Shared by the ?lifecycle and
        ?replication subresources so their StorageClass mapping cannot
        drift."""
        from ozone_tpu.scm.pipeline import (
            ReplicationConfig,
            ReplicationType,
        )

        try:
            conf = ReplicationConfig.parse(self.replication)
            return (self.replication
                    if conf.type is ReplicationType.EC
                    and conf.ec.codec == "rs" else "rs-6-3-1024k")
        except ValueError:
            return "rs-6-3-1024k"

    def _bucket_lifecycle_op(self, h, method: str, bucket: str) -> None:
        """?lifecycle subresource: PUT parses the AWS
        LifecycleConfiguration XML into the internal rule model (warm
        storage classes map to this gateway's EC scheme), GET renders
        the stored rules back, DELETE clears them. Rules persist in OM
        bucket metadata; the background sweeper enforces them."""
        from ozone_tpu.lifecycle.policy import (
            LifecycleError,
            rules_from_s3_xml,
            rules_to_s3_xml,
        )

        default = self._default_ec_target()
        om = self.client.om
        if method in ("PUT", "POST", "DELETE"):
            body = h._body()  # drain before any raising call
        if method == "PUT":
            try:
                rules = rules_from_s3_xml(body, default_target=default)
            except LifecycleError as e:
                h._reply(*_err("MalformedXML", str(e), 400))
                return
            om.set_bucket_lifecycle(self._vol, bucket, rules)
            h._reply(200)
        elif method == "GET":
            rules = om.get_bucket_lifecycle(self._vol, bucket)
            if not rules:
                om.bucket_info(self._vol, bucket)  # NoSuchBucket -> 404
                h._reply(*_err(
                    "NoSuchLifecycleConfiguration",
                    "The lifecycle configuration does not exist", 404))
                return
            h._reply(200, rules_to_s3_xml(rules),
                     {"Content-Type": "application/xml"})
        elif method == "DELETE":
            om.delete_bucket_lifecycle(self._vol, bucket)
            h._reply(204)
        else:
            h._reply(*_err("MethodNotAllowed", method, 405))

    def _bucket_replication_op(self, h, method: str, bucket: str) -> None:
        """?replication subresource: PUT parses the AWS
        ReplicationConfiguration XML into the internal rule model (the
        ARN's region slot — or an explicit <Endpoint> — names the
        destination cluster; warm storage classes map to this gateway's
        EC scheme), GET renders the stored rules back, DELETE clears
        them. Rules persist in OM bucket metadata; the background
        ReplicationShipper enforces them."""
        from ozone_tpu.replication_geo.rules import (
            GeoReplicationError,
            rules_from_s3_xml,
            rules_to_s3_xml,
        )

        default = self._default_ec_target()
        om = self.client.om
        if method in ("PUT", "POST", "DELETE"):
            body = h._body()  # drain before any raising call
        if method == "PUT":
            try:
                rules = rules_from_s3_xml(body, default_target=default)
            except GeoReplicationError as e:
                h._reply(*_err("MalformedXML", str(e), 400))
                return
            om.set_bucket_geo_replication(self._vol, bucket, rules)
            h._reply(200)
        elif method == "GET":
            rules = om.get_bucket_geo_replication(self._vol, bucket)
            if not rules:
                om.bucket_info(self._vol, bucket)  # NoSuchBucket -> 404
                h._reply(*_err(
                    "ReplicationConfigurationNotFoundError",
                    "The replication configuration was not found", 404))
                return
            h._reply(200, rules_to_s3_xml(rules),
                     {"Content-Type": "application/xml"})
        elif method == "DELETE":
            om.delete_bucket_geo_replication(self._vol, bucket)
            h._reply(204)
        else:
            h._reply(*_err("MethodNotAllowed", method, 405))

    def _list_uploads(self, h, bucket: str, q) -> None:
        """GET /bucket?uploads — ListMultipartUploads (BucketEndpoint
        ?uploads listing, BucketEndpoint.java:325): every in-progress
        upload in (key, uploadId) order, with prefix filtering,
        delimiter -> CommonPrefixes grouping, key-marker /
        upload-id-marker resume, and max-uploads truncation."""
        om = self.client.om
        om.bucket_info(self._vol, bucket)  # NoSuchBucket -> 404
        prefix = q.get("prefix", [""])[0]
        delim = q.get("delimiter", [""])[0]
        try:
            max_uploads = int(q.get("max-uploads", ["1000"])[0])
        except ValueError:
            max_uploads = -1
        if not 1 <= max_uploads <= 1000:
            # AWS bounds MaxUploads to 1-1000; clamping 0 to "truncated
            # with empty markers" would spin paginating clients forever
            h._reply(*_err("InvalidArgument", "max-uploads must be in "
                           "1..1000", 400))
            return
        key_marker = q.get("key-marker", [""])[0]
        id_marker = q.get("upload-id-marker", [""])[0]
        # the OM scan bounds by STORE key (/vol/bucket/<key>/<uploadId>)
        # — a superset when the prefix crosses the key/uploadId
        # boundary (key "a" matches prefix "a/"); re-check the key name
        entries = [
            m for m in om.list_multipart_uploads(self._vol, bucket, prefix)
            if m["name"].startswith(prefix)
        ]
        # AWS ordering: ascending key, then ascending uploadId
        entries.sort(key=lambda m: (m["name"], m["upload_id"]))
        uploads: list[dict] = []
        common: list[str] = []
        truncated = False
        for m in entries:
            name, uid = m["name"], m["upload_id"]
            if key_marker:
                # resume AFTER the marker pair: without an
                # upload-id-marker the whole marker key is consumed;
                # with one, later uploads of that key still list
                if name < key_marker or (
                        name == key_marker
                        and (not id_marker or uid <= id_marker)):
                    continue
            if delim:
                rest = name[len(prefix):]
                cut = rest.find(delim)
                if cut >= 0:
                    cp = prefix + rest[: cut + len(delim)]
                    # a key-marker equal to (or past) a served group's
                    # prefix consumes the group, like V1 NextMarker
                    if key_marker and cp <= key_marker:
                        continue
                    if common and common[-1] == cp:
                        continue
                    if len(uploads) + len(common) >= max_uploads:
                        truncated = True
                        break
                    common.append(cp)
                    continue
            if len(uploads) + len(common) >= max_uploads:
                truncated = True
                break
            uploads.append(m)
        # ?encoding-type=url: same contract as ListObjects — keys,
        # prefixes and key markers answer URL-encoded
        enc_url, esc = _esc_fn(q)
        root = ET.Element("ListMultipartUploadsResult", xmlns=_NS)
        ET.SubElement(root, "Bucket").text = bucket
        ET.SubElement(root, "KeyMarker").text = esc(key_marker)
        ET.SubElement(root, "UploadIdMarker").text = id_marker
        if enc_url:
            ET.SubElement(root, "EncodingType").text = "url"
        if truncated:
            # next markers name the last entity served; a CommonPrefix
            # resumes key-only (uploads inside it were never listed)
            last_key = uploads[-1]["name"] if uploads else ""
            last_cp = common[-1] if common else ""
            if last_cp > last_key:
                ET.SubElement(root, "NextKeyMarker").text = esc(last_cp)
                ET.SubElement(root, "NextUploadIdMarker").text = ""
            else:
                ET.SubElement(root, "NextKeyMarker").text = esc(last_key)
                ET.SubElement(root, "NextUploadIdMarker").text = (
                    uploads[-1]["upload_id"])
        ET.SubElement(root, "Prefix").text = esc(prefix)
        if delim:
            ET.SubElement(root, "Delimiter").text = esc(delim)
        ET.SubElement(root, "MaxUploads").text = str(max_uploads)
        ET.SubElement(root, "IsTruncated").text = (
            "true" if truncated else "false")
        for m in uploads:
            u = ET.SubElement(root, "Upload")
            ET.SubElement(u, "Key").text = esc(m["name"])
            ET.SubElement(u, "UploadId").text = m["upload_id"]
            owner = ET.SubElement(u, "Owner")
            ET.SubElement(owner, "ID").text = "ozone"
            init = ET.SubElement(u, "Initiator")
            ET.SubElement(init, "ID").text = "ozone"
            ET.SubElement(u, "StorageClass").text = "STANDARD"
            ET.SubElement(u, "Initiated").text = _iso_ts(
                m.get("created", 0.0))
        for cp in common:
            e = ET.SubElement(root, "CommonPrefixes")
            ET.SubElement(e, "Prefix").text = esc(cp)
        h._reply(200, _xml(root), {"Content-Type": "application/xml"})

    def _list_objects(self, h, bucket: str, q) -> None:
        """ListObjects V2 AND V1 over one paging engine: prefix,
        delimiter -> CommonPrefixes grouping, max-keys truncation.
        V2 (?list-type=2) resumes via NextContinuationToken /
        start-after; V1 (no list-type — older SDKs) resumes via
        ?marker and reports Marker/NextMarker instead of
        KeyCount/ContinuationToken (BucketEndpoint list semantics)."""
        om = self.client.om
        v1 = q.get("list-type", [""])[0] != "2"
        prefix = q.get("prefix", [""])[0]
        delim = q.get("delimiter", [""])[0]
        try:
            max_keys = max(0, int(q.get("max-keys", ["1000"])[0]))
        except ValueError:
            h._reply(*_err("InvalidArgument", "bad max-keys", 400))
            return
        marker = q.get("marker", [""])[0]
        # both resume cursors emit entities in key order, so the
        # group-already-served check below treats them identically
        token = (marker if v1
                 else _parse_token(
                     q.get("continuation-token", [""])[0]))
        after = token or q.get("start-after", [""])[0]
        contents: list[dict] = []
        common: list[str] = []
        truncated = False
        next_token = ""
        # both layouts page server-side now (OBS: bounded store scan;
        # FSO: pruned path-order tree walk) — fetch windows until the
        # entity budget fills or the listing runs dry, so a large
        # rolled-up group is skipped inside THIS request, not bounced
        # back to the client
        window = (max_keys + 1) if max_keys else 0
        cursor = after
        while max_keys:  # AWS: MaxKeys=0 returns empty, not truncated
            keys = om.list_keys(self._vol, bucket, prefix,
                                start_after=cursor,
                                limit=window or None)
            for k in keys:
                name = k["name"]
                if delim:
                    rest = name[len(prefix):]
                    cut = rest.find(delim)
                    if cut >= 0:  # group under the rolled-up prefix
                        cp = prefix + rest[: cut + len(delim)]
                        # V2 continuation tokens are SERVER-issued and
                        # emit entities in key order, so cp <= token
                        # means the group was served on a prior page.
                        # V1 markers are client-arbitrary (like raw
                        # start-after): only a marker EQUAL to the
                        # prefix consumes the group (AWS NextMarker
                        # semantics); a marker inside the group must
                        # still emit its CommonPrefix.
                        if token and (cp == token
                                      or (not v1 and cp <= token)):
                            continue
                        if common and common[-1] == cp:
                            continue
                        if len(contents) + len(common) >= max_keys:
                            truncated = True
                            break
                        common.append(cp)
                        continue
                if len(contents) + len(common) >= max_keys:
                    truncated = True
                    break
                contents.append(k)
            if truncated or not window or len(keys) < window:
                break
            cursor = keys[-1]["name"]
        if truncated:
            next_token = (contents[-1]["name"] if contents else "")
            last_cp = common[-1] if common else ""
            next_token = max(next_token, last_cp)
        # ?encoding-type=url (boto3 sends it by default): key-derived
        # strings in the RESPONSE are URL-encoded, so keys containing
        # XML-hostile characters (newlines, control bytes) survive the
        # round trip; the EncodingType element tells the SDK to decode
        enc_url, esc = _esc_fn(q)
        root = ET.Element("ListBucketResult", xmlns=_NS)
        ET.SubElement(root, "Name").text = bucket
        ET.SubElement(root, "Prefix").text = esc(prefix)
        if enc_url:
            ET.SubElement(root, "EncodingType").text = "url"
        if delim:
            ET.SubElement(root, "Delimiter").text = esc(delim)
        if v1:
            ET.SubElement(root, "Marker").text = esc(marker)
        else:
            ET.SubElement(root, "KeyCount").text = str(
                len(contents) + len(common))
        ET.SubElement(root, "MaxKeys").text = str(max_keys)
        ET.SubElement(root, "IsTruncated").text = (
            "true" if truncated else "false")
        if truncated and next_token:
            # V1 NextMarker is a KEY (encoding-type applies); the V2
            # token is opaque and safe for any key bytes
            ET.SubElement(root,
                          "NextMarker" if v1
                          else "NextContinuationToken").text = \
                esc(next_token) if v1 else _opaque_token(next_token)
        for k in contents:
            c = ET.SubElement(root, "Contents")
            ET.SubElement(c, "Key").text = esc(k["name"])
            ET.SubElement(c, "Size").text = str(k["size"])
            ET.SubElement(c, "LastModified").text = str(k.get("modified", ""))
        for cp in common:
            e = ET.SubElement(root, "CommonPrefixes")
            ET.SubElement(e, "Prefix").text = esc(cp)
        h._reply(200, _xml(root), {"Content-Type": "application/xml"})

    def _multi_delete(self, h, bucket: str) -> None:
        """POST /bucket?delete (BucketEndpoint multi-delete): per-key
        success/error entries, quiet-mode suppression of successes."""
        try:
            tree = ET.fromstring(h._body())
        except ET.ParseError as e:
            h._reply(*_err("MalformedXML", str(e), 400))
            return
        quiet = (tree.findtext("{*}Quiet") or
                 tree.findtext("Quiet") or "").lower() == "true"
        names = [
            el.findtext("{*}Key") or el.findtext("Key") or ""
            for el in list(tree.iter("{%s}Object" % _NS)) +
            list(tree.iter("Object"))
        ]
        om = self.client.om
        # the per-name deletes below would list a missing bucket as one
        # error per key in a 200: ask once, so it answers NoSuchBucket
        om.bucket_info(self._vol, bucket)
        root = ET.Element("DeleteResult", xmlns=_NS)
        for name in names:
            if not name:
                continue
            try:
                om.delete_key(self._vol, bucket, name)
                if not quiet:
                    d = ET.SubElement(root, "Deleted")
                    ET.SubElement(d, "Key").text = name
            except _OM_ERRORS as e:
                # S3 treats deleting a missing key as success
                if e.code == "KEY_NOT_FOUND":
                    if not quiet:
                        d = ET.SubElement(root, "Deleted")
                        ET.SubElement(d, "Key").text = name
                else:
                    er = ET.SubElement(root, "Error")
                    ET.SubElement(er, "Key").text = name
                    ET.SubElement(er, "Code").text = e.code
                    ET.SubElement(er, "Message").text = str(e)
        h._reply(200, _xml(root), {"Content-Type": "application/xml"})

    # ------------------------------------------------------------- objects
    def _bucket_handle(self, bucket: str) -> OzoneBucket:
        """A handle that asks the OM nothing: the operation's own OM
        call (LookupKey, DeleteKey, the PUT's small-object BucketInfo,
        the multipart calls) answers BUCKET_NOT_FOUND for a missing
        bucket, so a VolumeInfo + BucketInfo first would only ask it
        again. Nothing is kept across requests."""
        return OzoneBucket(self.client, self._vol, bucket)

    def _object_op(self, h, method: str, bucket: str, key: str, q) -> None:
        if method == "POST" and "uploads" in q:
            self._mpu_initiate(h, bucket, key)
        elif method == "PUT" and "uploadId" in q:
            self._mpu_part(h, bucket, key, q)
        elif method == "POST" and "uploadId" in q:
            self._mpu_complete(h, bucket, key, q)
        elif method == "DELETE" and "uploadId" in q:
            self._mpu_abort(h, bucket, key, q)
        elif method == "GET" and "uploadId" in q:
            self._mpu_list_parts(h, bucket, key, q)
        elif "acl" in q:
            self._object_acl(h, method, bucket, key)
        elif "tagging" in q:
            self._object_tagging(h, method, bucket, key)
        elif method == "PUT":
            self._put_object(h, bucket, key)
        elif method == "GET":
            self._get_object(h, bucket, key)
        elif method == "HEAD":
            self._head_object(h, bucket, key)
        elif method == "DELETE":
            try:
                self.client.om.delete_key(self._vol, bucket, key)
            except _OM_ERRORS as e:
                # S3 answers 204 for a missing key too (as _multi_delete
                # does), so a retried DELETE is no error
                if e.code != "KEY_NOT_FOUND":
                    raise
            h._reply(204)
        else:
            h._reply(*_err("MethodNotAllowed", method, 405))

    def _object_acl(self, h, method: str, bucket: str,
                    key: str) -> None:
        """Object ?acl sub-resource. Like the reference, per-object
        grants don't exist — GET renders the effective policy (owner
        FULL_CONTROL + the bucket's public grants); PUT answers
        NotImplemented instead of silently accepting grants that could
        never be enforced."""
        if method == "GET":
            self.client.om.lookup_key(self._vol, bucket, key)  # 404s
            root = ET.Element("AccessControlPolicy", xmlns=_NS)
            owner = ET.SubElement(root, "Owner")
            ET.SubElement(owner, "ID").text = "ozone"
            acl = ET.SubElement(root, "AccessControlList")

            def grant(grantee, perm):
                g = ET.SubElement(acl, "Grant")
                ge = ET.SubElement(g, "Grantee")
                xsi = "{http://www.w3.org/2001/XMLSchema-instance}type"
                if grantee == "*":
                    # the AWS Group shape: clients detect public access
                    # by the AllUsers URI, not an ID
                    ge.set(xsi, "Group")
                    ET.SubElement(ge, "URI").text = (
                        "http://acs.amazonaws.com/groups/global/"
                        "AllUsers")
                else:
                    ge.set(xsi, "CanonicalUser")
                    ET.SubElement(ge, "ID").text = grantee
                ET.SubElement(g, "Permission").text = perm

            grant("ozone", "FULL_CONTROL")
            for p in sorted(self._public_grants(bucket)):
                grant("*", p)
            h._reply(200, _xml(root),
                     {"Content-Type": "application/xml"})
        elif method == "PUT":
            h._body()
            h._reply(*_err("NotImplemented",
                           "object ACLs are bucket-derived", 501))
        else:
            h._reply(*_err("MethodNotAllowed", method, 405))

    @staticmethod
    def _validate_tags(tags: dict) -> Optional[str]:
        """AWS tag restrictions: <=10 tags per object, key <=128 chars,
        value <=256, no duplicate keys (dict dedupes already)."""
        if len(tags) > 10:
            return "object tags cannot exceed 10"
        for k, v in tags.items():
            if not k or len(k) > 128:
                return f"invalid tag key {k!r}"
            if len(v) > 256:
                return f"tag value too long for {k!r}"
        return None

    def _object_tagging(self, h, method: str, bucket: str,
                        key: str) -> None:
        """?tagging sub-resource (ObjectEndpoint PUT/GET/DELETE tagging;
        S3 PutObjectTagging family). Tags live on the key row's attrs,
        replicated like every other key mutation."""
        om = self.client.om
        if method == "PUT":
            try:
                # bytes straight in: ET honors XML encoding decls, and
                # a bad .decode() here would 500 instead of 400
                root = ET.fromstring(h._body())
                tags = {
                    t.findtext(f"{{{_NS}}}Key", t.findtext("Key", "")):
                    t.findtext(f"{{{_NS}}}Value", t.findtext("Value", ""))
                    for ts in (root.findall(f"{{{_NS}}}TagSet")
                               or root.findall("TagSet"))
                    for t in (ts.findall(f"{{{_NS}}}Tag")
                              or ts.findall("Tag"))
                }
            except ET.ParseError as e:
                h._reply(*_err("MalformedXML", str(e), 400))
                return
            bad = self._validate_tags(tags)
            if bad:
                h._reply(*_err("InvalidTag", bad, 400))
                return
            om.set_key_attrs(self._vol, bucket, key, {"tags": tags})
            h._reply(200)
        elif method == "GET":
            info = om.lookup_key(self._vol, bucket, key)
            tags = (info.get("attrs") or {}).get("tags", {})
            root = ET.Element("Tagging", xmlns=_NS)
            ts = ET.SubElement(root, "TagSet")
            for k, v in sorted(tags.items()):
                t = ET.SubElement(ts, "Tag")
                ET.SubElement(t, "Key").text = k
                ET.SubElement(t, "Value").text = v
            h._reply(200, _xml(root),
                     {"Content-Type": "application/xml"})
        elif method == "DELETE":
            om.set_key_attrs(self._vol, bucket, key, {"tags": None})
            h._reply(204)
        else:
            h._reply(*_err("MethodNotAllowed", method, 405))

    def _parse_copy_source(self, h) -> Optional[tuple[str, str]]:
        """x-amz-copy-source: '/bucket/key' or 'bucket/key' (URL-encoded).
        Returns (bucket, key) or None when the header is absent."""
        from urllib.parse import unquote

        src = h.headers.get("x-amz-copy-source")
        if not src:
            return None
        src = unquote(src).lstrip("/")
        b, _, k = src.partition("/")
        if not b or not k:
            raise ValueError(src)
        return b, k

    def _put_object(self, h, bucket: str, key: str) -> None:
        try:
            src = self._parse_copy_source(h)
        except ValueError as e:
            h._reply(*_err("InvalidArgument",
                           f"bad x-amz-copy-source: {e}", 400))
            return
        if src is not None:  # CopyObject (ObjectEndpoint.put copyHeader)
            h._body()  # drain any (ignored) request body
            src_info = self.client.om.lookup_key(self._vol, src[0], src[1])
            data = self._bucket_handle(src[0]).read_key_info(
                src_info).tobytes()
            # metadata directive: COPY (default) carries the source
            # object's user metadata; REPLACE takes this request's
            if (h.headers.get("x-amz-metadata-directive", "COPY")
                    .upper() == "REPLACE"):
                meta = self._user_metadata(h)
            else:
                meta = src_info.get("metadata") or {}
            # tagging directive: COPY (default) carries the source's
            # tags; REPLACE takes this request's x-amz-tagging header
            if (h.headers.get("x-amz-tagging-directive", "COPY")
                    .upper() == "REPLACE"):
                tags = {k: v[0] for k, v in parse_qs(
                    h.headers.get("x-amz-tagging", ""),
                    keep_blank_values=True).items()}
            else:
                tags = (src_info.get("attrs") or {}).get("tags", {})
            self._bucket_handle(bucket).write_key(
                key, np.frombuffer(data, np.uint8), metadata=meta
            )
            if tags:
                self.client.om.set_key_attrs(self._vol, bucket, key,
                                             {"tags": tags})
            etag = _etag(data)
            root = ET.Element("CopyObjectResult", xmlns=_NS)
            ET.SubElement(root, "ETag").text = f'"{etag}"'
            ET.SubElement(root, "LastModified").text = _iso_now()
            h._reply(200, _xml(root), {"Content-Type": "application/xml"})
            return
        tags = None
        hdr = h.headers.get("x-amz-tagging")
        if hdr:
            # query-string-encoded tags on the PUT itself
            tags = {k: v[0] for k, v in parse_qs(
                hdr, keep_blank_values=True).items()}
            bad = self._validate_tags(tags)
            if bad:
                h._body()  # drain, or keep-alive desyncs on early 400
                h._reply(*_err("InvalidTag", bad, 400))
                return
        body = h._body()
        self._bucket_handle(bucket).write_key(
            key, np.frombuffer(body, np.uint8),
            metadata=self._user_metadata(h),
        )
        if tags:
            self.client.om.set_key_attrs(self._vol, bucket, key,
                                         {"tags": tags})
        etag = _etag(body)
        h._reply(200, headers={"ETag": f'"{etag}"'})

    @staticmethod
    def _user_metadata(h) -> dict:
        """x-amz-meta-* request headers -> user metadata map (stored on
        the key like the reference's custom-metadata support)."""
        out = {}
        for name, value in h.headers.items():
            low = name.lower()
            if low.startswith("x-amz-meta-"):
                out[low[len("x-amz-meta-"):]] = value
        return out

    @staticmethod
    def _meta_headers_from(info: dict) -> dict:
        return {
            f"x-amz-meta-{k}": str(v)
            for k, v in (info.get("metadata") or {}).items()
        }

    def _get_object(self, h, bucket: str, key: str) -> None:
        # one lookup serves the bucket's existence, the metadata
        # headers AND the block list
        info = self.client.om.lookup_key(self._vol, bucket, key)
        bh = self._bucket_handle(bucket)
        meta = self._meta_headers_from(info)
        size = int(info["size"])
        rng = h.headers.get("Range")
        ranged = False
        lo = hi = 0
        if rng and rng.startswith("bytes="):
            lo_s, _, hi_s = rng[6:].partition("-")
            if not lo_s:  # suffix form bytes=-N: the LAST N bytes
                n = int(hi_s)
                lo = max(0, size - n)
                hi = size - 1
                ranged = True
            else:
                lo = int(lo_s)
                if hi_s and int(hi_s) < lo:
                    # client-sent inverted range-spec: RFC 9110
                    # §14.1.1 says the Range header is invalid and
                    # MUST be ignored (full 200 body), matching real
                    # S3 — not a 416
                    ranged = False
                else:
                    hi = int(hi_s) if hi_s else size - 1
                    ranged = True
            if ranged and lo >= size:
                # unsatisfiable range: 416 with the star form, never a
                # 206 whose Content-Range would carry hi < lo (S3 /
                # RFC 9110 §14.4 semantics)
                status, body = _err(
                    "InvalidRange",
                    "The requested range is not satisfiable", 416)
                h._reply(status, body,
                         {"Content-Range": f"bytes */{size}"})
                return
        if ranged:
            # ranged GET reads ONLY the covering cells/chunks (round-4
            # positioned reads), not the whole key
            hi = min(hi, size - 1)
            part = bh.read_key_info_range(info, lo, hi - lo + 1)
            h._reply(
                206,
                part,
                {
                    "Content-Type": "application/octet-stream",
                    "Content-Range": f"bytes {lo}-{hi}/{size}",
                    **meta,
                },
            )
        else:
            h._reply(200, bh.read_key_info(info),
                     {"Content-Type": "application/octet-stream", **meta})

    def _head_object(self, h, bucket: str, key: str) -> None:
        """HEAD must report the real object size in Content-Length with no
        body (S3 semantics; SDKs size objects this way before ranged
        GETs), so the reply is hand-rolled instead of using _reply."""
        info = self.client.om.lookup_key(self._vol, bucket, key)
        with Tracer.instance().span("s3:send"):
            h.send_response(200)
            h.send_header("Content-Type", "application/octet-stream")
            h.send_header("Content-Length", str(info["size"]))
            for k, v in (info.get("metadata") or {}).items():
                h.send_header(f"x-amz-meta-{k}", str(v))
            h.end_headers()

    # ------------------------------------------------------------- multipart
    # Backed by the OM multipart table (om/multipart.py), the reference's
    # design: the gateway is stateless, upload state survives restarts,
    # and parts stream through the normal EC/replicated datapath.
    def _mpu_initiate(self, h, bucket: str, key: str) -> None:
        mpu = self._bucket_handle(bucket).initiate_multipart_upload(
            key, metadata=self._user_metadata(h))
        root = ET.Element("InitiateMultipartUploadResult", xmlns=_NS)
        ET.SubElement(root, "Bucket").text = bucket
        ET.SubElement(root, "Key").text = key
        ET.SubElement(root, "UploadId").text = mpu.upload_id
        h._reply(200, _xml(root), {"Content-Type": "application/xml"})

    def _mpu_handle(self, h, bucket: str, key: str, q):
        # no existence pre-check: the underlying OM call raises
        # BUCKET_NOT_FOUND or NO_SUCH_MULTIPART_UPLOAD itself (mapped to
        # 404 in _serve), avoiding extra round trips per part
        from ozone_tpu.client.ozone_client import MultipartUpload

        upload_id = q["uploadId"][0]
        return MultipartUpload(self._bucket_handle(bucket), key, upload_id)

    def _mpu_part(self, h, bucket: str, key: str, q) -> None:
        mpu = self._mpu_handle(h, bucket, key, q)
        if mpu is None:
            return
        part_no = int(q.get("partNumber", ["1"])[0])
        try:
            src = self._parse_copy_source(h)
        except ValueError as e:
            h._reply(*_err("InvalidArgument",
                           f"bad x-amz-copy-source: {e}", 400))
            return
        if src is not None:  # UploadPartCopy (ObjectEndpoint copy-part)
            h._body()
            data = self._bucket_handle(src[0]).read_key(src[1]).tobytes()
            rng = h.headers.get("x-amz-copy-source-range")
            if rng:
                # AWS requires the full bytes=<lo>-<hi> form here (no
                # open-ended or suffix ranges) and rejects bounds that
                # fall outside the source object
                lo_s, dash, hi_s = rng.removeprefix("bytes=").partition("-")
                if (not rng.startswith("bytes=") or not dash
                        or not lo_s.isdigit() or not hi_s.isdigit()):
                    h._reply(*_err(
                        "InvalidArgument",
                        f"bad x-amz-copy-source-range: {rng}", 400))
                    return
                lo, hi = int(lo_s), int(hi_s)
                if lo > hi or hi >= len(data):
                    h._reply(*_err(
                        "InvalidRange",
                        f"range {lo}-{hi} outside source of "
                        f"{len(data)} bytes", 416))
                    return
                data = data[lo : hi + 1]
            etag = mpu.write_part(part_no, np.frombuffer(data, np.uint8))
            root = ET.Element("CopyPartResult", xmlns=_NS)
            ET.SubElement(root, "ETag").text = f'"{etag}"'
            ET.SubElement(root, "LastModified").text = _iso_now()
            h._reply(200, _xml(root), {"Content-Type": "application/xml"})
            return
        body = h._body()
        etag = mpu.write_part(part_no, np.frombuffer(body, np.uint8))
        h._reply(200, headers={"ETag": f'"{etag}"'})

    def _mpu_complete(self, h, bucket: str, key: str, q) -> None:
        mpu = self._mpu_handle(h, bucket, key, q)
        if mpu is None:
            return
        # parts may be listed in the XML body; default to all uploaded
        parts = None
        body = h._body()
        if body:
            listed = []
            for pe in ET.fromstring(body):
                if pe.tag.rpartition("}")[2] != "Part":
                    continue
                fields = {c.tag.rpartition("}")[2]: (c.text or "") for c in pe}
                listed.append({
                    "part_number": int(fields["PartNumber"]),
                    "etag": fields.get("ETag", "").strip('"'),
                })
            parts = listed or None
        if parts is None:
            parts = [
                {"part_number": p["part_number"], "etag": p["etag"]}
                for p in mpu.list_parts()
            ]
        info = mpu.complete(parts)
        root = ET.Element("CompleteMultipartUploadResult", xmlns=_NS)
        ET.SubElement(root, "Bucket").text = bucket
        ET.SubElement(root, "Key").text = key
        ET.SubElement(root, "ETag").text = f'"{info["etag"]}"'
        h._reply(200, _xml(root), {"Content-Type": "application/xml"})

    def _mpu_abort(self, h, bucket: str, key: str, q) -> None:
        mpu = self._mpu_handle(h, bucket, key, q)
        if mpu is None:
            return
        mpu.abort()
        h._reply(204)

    def _mpu_list_parts(self, h, bucket: str, key: str, q) -> None:
        mpu = self._mpu_handle(h, bucket, key, q)
        if mpu is None:
            return
        root = ET.Element("ListPartsResult", xmlns=_NS)
        ET.SubElement(root, "Bucket").text = bucket
        ET.SubElement(root, "Key").text = key
        ET.SubElement(root, "UploadId").text = mpu.upload_id
        for p in mpu.list_parts():
            pe = ET.SubElement(root, "Part")
            ET.SubElement(pe, "PartNumber").text = str(p["part_number"])
            ET.SubElement(pe, "ETag").text = f'"{p["etag"]}"'
            ET.SubElement(pe, "Size").text = str(p["size"])
        h._reply(200, _xml(root), {"Content-Type": "application/xml"})
