"""S3 clients for the S3 cells, in a worker process of their own: each
client keeps one keep-alive HTTP connection to the gateway, signs every
request (AWS SigV4 in the `Authorization` header, `UNSIGNED-PAYLOAD`),
waits for its reply and sends the next (a closed loop, MinIO warp's
shape), and holds every reply to its own `BucketModel`: a client owns
its names, so the model is exact under any concurrency.

    python -m benchmarks.harness.s3_clients      (from the checkout root)

It speaks JSON lines. In on stdin: the plan (`plan()`), then, once it
has answered `{"ready": ...}` on stdout, `{"t1": <monotonic>}`: from
then until t1 every client draws shuffled blocks of the plan's mix and
runs them; an operation in flight at t1 is finished. Out on stdout, last:
`{"ops", "models", "gets_differ", "heads_wrong"}`. It exits where stdin
closes before the start. Imports no JAX and nothing of the program: the
signer is written here from the SigV4 specification.

Set-up, before "ready": each client PUTs its `preload` objects, then
GETs and HEADs one of them, all held to the model (not in the op log).
"""

from __future__ import annotations

import hashlib
import hmac
import http.client
import json
import sys
import threading
import time
from urllib.parse import quote

import numpy as np

from benchmarks.harness.bucket_model import BucketModel
from benchmarks.harness.context import PayloadPool

UNSIGNED = "UNSIGNED-PAYLOAD"
REGION = "us-east-1"
#: the stream of a run's seed that a client's payloads come from (the
#: client's number is the third word); its draws of names and blocks
#: come from the next
PAYLOAD_STREAM = 41
DRAW_STREAM = 42


def object_name(client: int, j: int) -> str:
    """The name of client `client`'s j-th object (its j-th PUT)."""
    return f"c{client:02d}-o{j:05d}"


def parse_name(name: str) -> tuple[int, int]:
    c, o = name.split("-")
    return int(c[1:]), int(o[1:])


def payload_pool(seed: int, client: int, object_bytes: int) -> PayloadPool:
    """Client `client`'s payloads: object j's bytes are `payload(j)`."""
    return PayloadPool(np.random.default_rng([seed, PAYLOAD_STREAM,
                                              client]), object_bytes)


class Signer:
    """AWS SigV4 for header auth with an unsigned payload: the canonical
    request over method, path, the signed headers `host`,
    `x-amz-content-sha256` and `x-amz-date`, and `UNSIGNED-PAYLOAD`."""

    def __init__(self, access_id: str, secret: str, host: str):
        self.access_id = access_id
        self.secret = secret
        self.host = host
        self._keys: dict[str, bytes] = {}

    def _key(self, date: str) -> bytes:
        key = self._keys.get(date)
        if key is None:
            key = ("AWS4" + self.secret).encode()
            for part in (date, REGION, "s3", "aws4_request"):
                key = hmac.new(key, part.encode(), hashlib.sha256).digest()
            self._keys[date] = key
        return key

    def headers(self, method: str, path: str) -> dict:
        amz_date = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
        date = amz_date[:8]
        hdrs = {"host": self.host, "x-amz-content-sha256": UNSIGNED,
                "x-amz-date": amz_date}
        signed = ";".join(sorted(hdrs))
        canonical = "\n".join([
            method, quote(path, safe="/-_.~"), "",
            "".join(f"{k}:{hdrs[k]}\n" for k in sorted(hdrs)), signed,
            UNSIGNED])
        scope = f"{date}/{REGION}/s3/aws4_request"
        to_sign = "\n".join([
            "AWS4-HMAC-SHA256", amz_date, scope,
            hashlib.sha256(canonical.encode()).hexdigest()])
        sig = hmac.new(self._key(date), to_sign.encode(),
                       hashlib.sha256).hexdigest()
        hdrs["Authorization"] = (
            f"AWS4-HMAC-SHA256 Credential={self.access_id}/{scope}, "
            f"SignedHeaders={signed}, Signature={sig}")
        return hdrs


class Connection:
    """One keep-alive HTTP/1.1 connection, signed requests on it.
    `replied_at` is when the last reply was read whole (monotonic)."""

    def __init__(self, endpoint: str, bucket: str, signer: Signer):
        self.endpoint = endpoint
        self.bucket = bucket
        self.signer = signer
        self._conn = None
        self.replied_at = 0.0

    def request(self, method: str, name: str, body=None
                ) -> tuple[int, dict, bytes]:
        """(status, headers lower-cased, body) of one request; a
        connection the gateway dropped is opened again for the next."""
        path = f"/{self.bucket}/{name}"
        headers = self.signer.headers(method, path)
        if body is not None:
            body = memoryview(body).cast("B")  # sent as it lies, no copy
        headers["Content-Length"] = str(0 if body is None else body.nbytes)
        if self._conn is None:
            host, port = self.endpoint.rsplit(":", 1)
            self._conn = http.client.HTTPConnection(host, int(port),
                                                    timeout=120)
        try:
            self._conn.request(method, path, body=body, headers=headers)
            resp = self._conn.getresponse()
            data = resp.read()
            self.replied_at = time.monotonic()
        except Exception:
            self._conn.close()
            self._conn = None
            raise
        return resp.status, {k.lower(): v for k, v in resp.getheaders()}, \
            data

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()


def plan(endpoint: str, bucket: str, access_id: str, secret: str,
         seed: int, clients: list[int], object_bytes: int, preload: int,
         mix: dict) -> dict:
    """The first line a worker reads."""
    return {"endpoint": endpoint, "bucket": bucket, "access_id": access_id,
            "secret": secret, "seed": seed, "clients": clients,
            "object_bytes": object_bytes, "preload": preload, "mix": mix}


class Client:
    """One S3 client: its connection, payloads, draws and model."""

    def __init__(self, p: dict, number: int):
        self.number = number
        self.conn = Connection(p["endpoint"], p["bucket"], Signer(
            p["access_id"], p["secret"], p["endpoint"]))
        self.pool = payload_pool(p["seed"], number, p["object_bytes"])
        self.rng = np.random.default_rng([p["seed"], DRAW_STREAM, number])
        self.block = [kind for kind, n in p["mix"].items()
                      for _ in range(int(n))]
        self.model = BucketModel()
        self.next_object = 0
        self.ops: list[list] = []
        self.gets_differ = 0
        self.heads_wrong = 0

    # one request of each kind, held to the model; -> bytes it moved
    def put(self) -> tuple[str, int]:
        j = self.next_object
        self.next_object += 1
        name = object_name(self.number, j)
        data = self.pool.payload(j)
        status, _h, body = self.conn.request("PUT", name, data)
        if status != 200:
            raise RuntimeError(f"PUT {name}: {status} {body[:200]!r}")
        self.model.put(name, data)
        return name, data.size

    def _pick(self) -> str:
        live = self.model.live()
        if not live:
            raise RuntimeError(f"client {self.number} holds no object")
        return live[int(self.rng.integers(len(live)))]

    def get(self, name: str = "") -> tuple[str, int]:
        name = name or self._pick()
        want = self.model.get(name)
        status, _h, body = self.conn.request("GET", name)
        if status != want.status or not np.array_equal(
                np.frombuffer(body, dtype=np.uint8), want.body):
            self.gets_differ += 1
            raise RuntimeError(f"GET {name}: {status}, {len(body)} bytes "
                               f"differ from the model's {want.status}, "
                               f"{want.size}")
        return name, len(body)

    def head(self, name: str = "") -> tuple[str, int]:
        name = name or self._pick()
        want = self.model.head(name)
        status, headers, _b = self.conn.request("HEAD", name)
        if status != want.status \
                or int(headers.get("content-length", -1)) != want.size:
            self.heads_wrong += 1
            raise RuntimeError(f"HEAD {name}: {status}, Content-Length "
                               f"{headers.get('content-length')}, the "
                               f"model's {want.status}, {want.size}")
        return name, 0

    def delete(self) -> tuple[str, int]:
        name = self._pick()
        status, _h, body = self.conn.request("DELETE", name)
        if status != 204:
            raise RuntimeError(f"DELETE {name}: {status} {body[:200]!r}")
        self.model.delete(name)
        return name, 0

    def set_up(self, preload: int) -> None:
        for _ in range(preload):
            self.put()
        first = object_name(self.number, 0)
        self.get(first)
        self.head(first)

    def run(self, t1: float) -> None:
        """Shuffled blocks of the mix until t1, every operation logged:
        [kind, start, end, bytes, ok, error, client, name]. A done
        operation ends when its reply is read: the check against the
        model is the load generator's work, not the system's."""
        while True:
            for kind in self.rng.permutation(self.block):
                start = time.monotonic()
                if start >= t1:
                    return
                try:
                    name, nbytes = getattr(self, str(kind))()
                    self.ops.append([str(kind), start, self.conn.replied_at,
                                     nbytes, True, "", self.number, name])
                except Exception as e:  # noqa: BLE001 - a failed op counts
                    self.ops.append([str(kind), start, time.monotonic(), 0,
                                     False, repr(e)[:300], self.number, ""])


def _all(clients: list[Client], fn) -> None:
    errors: list[BaseException] = []

    def one(c: Client) -> None:
        try:
            fn(c)
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)

    threads = [threading.Thread(target=one, args=(c,),
                                name=f"s3-client-{c.number}")
               for c in clients]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


def _say(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def main() -> int:
    line = sys.stdin.readline()
    if not line:
        return 1
    p = json.loads(line)
    clients = [Client(p, n) for n in p["clients"]]
    t0 = time.monotonic()
    _all(clients, lambda c: c.set_up(p["preload"]))
    _say({"ready": True, "set_up_s": time.monotonic() - t0})
    line = sys.stdin.readline()
    if not line:
        return 1  # the run ended before its window
    t1 = json.loads(line)["t1"]
    _all(clients, lambda c: c.run(t1))
    for c in clients:
        c.conn.close()
    _say({"ops": [op for c in clients for op in c.ops],
          "models": {str(c.number): {"live": c.model.live(),
                                     "deleted": c.model.deleted()}
                     for c in clients},
          "gets_differ": sum(c.gets_differ for c in clients),
          "heads_wrong": sum(c.heads_wrong for c in clients)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
