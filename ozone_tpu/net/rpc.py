"""Generic gRPC plumbing: byte-level services without codegen.

Role analog of the reference's gRPC/Netty datapath transport
(XceiverServerGrpc.java:76 / GrpcXceiverService.java:42 on the server,
XceiverClientGrpc on the client). Services register python callables per
method name; requests/responses are raw bytes in the net/wire.py format.
Errors cross the wire as grpc ABORTED with a JSON {code, message} detail
and are re-raised as StorageError on the client.
"""

from __future__ import annotations

import contextlib
import json
import logging
import threading
from concurrent import futures
from typing import Callable, Optional

import grpc

from ozone_tpu.storage.ids import StorageError

log = logging.getLogger(__name__)

#: pem -> serial parse cache for revocation checks (bounded)
_SERIAL_CACHE: dict = {}

Method = Callable[[bytes], bytes]


#: stream-unary handler: consumes an iterator of request frames, returns
#: one response (the streaming-write commit ack shape)
StreamMethod = Callable[..., bytes]


class _GenericHandler(grpc.GenericRpcHandler):
    def __init__(self, methods: dict[str, Method],
                 stream_methods: Optional[dict[str, StreamMethod]] = None,
                 server_stream_methods: Optional[dict[str, Method]] = None,
                 server: Optional["RpcServer"] = None,
                 admission=None):
        self._methods = methods
        self._stream_methods = stream_methods or {}
        #: unary request -> iterator of byte frames (the replication
        #: download shape: large payloads never buffer in one message)
        self._server_stream_methods = server_stream_methods or {}
        #: owning server: read at call time for its live crl_provider
        self._server = server
        #: AdmissionController bounding this service's in-flight work:
        #: past the bound, new calls are answered SERVER_BUSY instead
        #: of queuing invisibly in the executor's backlog
        self._admission = admission

    @contextlib.contextmanager
    def _admit(self, method_name: str):
        ctl = self._admission
        if ctl is None:
            yield
            return
        with ctl.admit(method_name.rpartition("/")[2]):
            yield

    def _check_revoked(self, context) -> None:
        """Certificate revocation (the CRL the reference distributes
        from the SCM CA): a peer presenting a revoked-but-unexpired
        cert is refused at the application layer — the TLS handshake
        itself cannot consult a live CRL. Aborts UNAUTHENTICATED."""
        srv = self._server
        provider = getattr(srv, "crl_provider", None) if srv else None
        if provider is None:
            return
        crl = provider()
        if not crl:
            return
        pems = dict(context.auth_context()).get("x509_pem_cert") or []
        if not pems:
            return
        pem = bytes(pems[0])
        serial = _SERIAL_CACHE.get(pem)
        if serial is None:
            from cryptography import x509 as _x509

            serial = _x509.load_pem_x509_certificate(pem).serial_number
            if len(_SERIAL_CACHE) > 256:
                _SERIAL_CACHE.clear()
            _SERIAL_CACHE[pem] = serial
        if serial in crl:
            context.abort(
                grpc.StatusCode.UNAUTHENTICATED,
                json.dumps({"code": "CERTIFICATE_REVOKED",
                            "message": f"serial {serial} is revoked"}))

    def _guard(self, fn, method_name):
        def wrapped(request, context: grpc.ServicerContext) -> bytes:
            # before the try: context.abort raises to terminate, and
            # the generic except below must not re-wrap it as INTERNAL
            self._check_revoked(context)
            from ozone_tpu.utils.tracing import Tracer

            remote_ctx = dict(context.invocation_metadata()).get("x-trace-id")
            try:
                with self._admit(method_name), Tracer.instance().span(
                    f"server:{method_name}",
                    child_of=remote_ctx or None,
                ) as sp:
                    out = fn(request)
                if sp.thread:
                    # a costed trace: how long this daemon had the call
                    # (the caller's span subtracts it from what it saw:
                    # the wire, gRPC's threads, its own turns at the
                    # interpreter) and what the handler's thread spent
                    context.set_trailing_metadata((
                        ("x-server-us", str(int(sp.duration * 1e6))),
                        ("x-server-cpu-us", str(int(sp.cpu * 1e6)))))
                return out
            except StorageError as e:
                context.abort(
                    grpc.StatusCode.ABORTED,
                    json.dumps({"code": e.code, "message": e.msg}),
                )
            except Exception as e:  # noqa: BLE001 - surface as INTERNAL
                log.exception("rpc %s failed", method_name)
                context.abort(
                    grpc.StatusCode.INTERNAL,
                    json.dumps({"code": "IO_EXCEPTION", "message": str(e)}),
                )

        return wrapped

    def _guard_stream(self, fn, method_name):
        """Guard for server-streaming handlers: exceptions fire during
        ITERATION of the response generator, so the try must wrap the
        yield loop, not just the call."""
        def wrapped(request, context: grpc.ServicerContext):
            self._check_revoked(context)
            from ozone_tpu.utils.tracing import Tracer

            remote_ctx = dict(context.invocation_metadata()).get(
                "x-trace-id")
            try:
                with self._admit(method_name), Tracer.instance().span(
                    f"server:{method_name}",
                    child_of=remote_ctx or None,
                ):
                    yield from fn(request)
            except StorageError as e:
                context.abort(
                    grpc.StatusCode.ABORTED,
                    json.dumps({"code": e.code, "message": e.msg}),
                )
            except Exception as e:  # noqa: BLE001 - surface as INTERNAL
                log.exception("rpc %s failed", method_name)
                context.abort(
                    grpc.StatusCode.INTERNAL,
                    json.dumps({"code": "IO_EXCEPTION", "message": str(e)}),
                )

        return wrapped

    def service(self, handler_call_details):
        name = handler_call_details.method
        fn = self._methods.get(name)
        if fn is not None:
            return grpc.unary_unary_rpc_method_handler(self._guard(fn, name))
        sfn = self._stream_methods.get(name)
        if sfn is not None:
            return grpc.stream_unary_rpc_method_handler(self._guard(sfn, name))
        ssfn = self._server_stream_methods.get(name)
        if ssfn is not None:
            return grpc.unary_stream_rpc_method_handler(
                self._guard_stream(ssfn, name))
        return None


class RpcServer:
    """One grpc.Server hosting any number of named services.

    Pass `tls` (utils/ca.py TlsMaterial) to serve over TLS with client
    certificates required (the reference's SecurityConfig-driven
    grpc.tls.enabled mode on XceiverServerGrpc/ReplicationServer);
    `mutual=False` downgrades to server-auth-only TLS."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 max_workers: int = 16, tls=None, mutual: bool = True):
        self._server = grpc.server(
            futures.ThreadPoolExecutor(max_workers=max_workers),
            options=[
                ("grpc.max_send_message_length", 128 * 1024 * 1024),
                ("grpc.max_receive_message_length", 128 * 1024 * 1024),
                # no SO_REUSEPORT: a restarted daemon re-binding its port
                # must either own it exclusively or fail — with reuseport
                # the kernel load-balances new connections onto the old
                # shutting-down server's socket, which accepts TCP but
                # never answers the HTTP/2 handshake
                ("grpc.so_reuseport", 0),
            ],
        )
        if tls is not None:
            self.port = self._server.add_secure_port(
                f"{host}:{port}", tls.server_credentials(mutual=mutual))
        else:
            self.port = self._server.add_insecure_port(f"{host}:{port}")
        self.host = host
        self.tls_enabled = tls is not None
        #: callable() -> set of revoked cert serials (CRL); None = no
        #: revocation checking. Read per-request so updates apply live.
        self.crl_provider = None

    @property
    def address(self) -> str:
        return f"{self.host}:{self.port}"

    def add_service(self, service_name: str, methods: dict[str, Method],
                    stream_methods: Optional[dict[str, StreamMethod]] = None,
                    server_stream_methods: Optional[dict] = None,
                    admission=None) -> None:
        full = {
            f"/{service_name}/{name}": fn for name, fn in methods.items()
        }
        sfull = {
            f"/{service_name}/{name}": fn
            for name, fn in (stream_methods or {}).items()
        }
        ssfull = {
            f"/{service_name}/{name}": fn
            for name, fn in (server_stream_methods or {}).items()
        }
        self._server.add_generic_rpc_handlers(
            (_GenericHandler(full, sfull, ssfull, server=self,
                             admission=admission),))

    def start(self) -> None:
        self._server.start()

    def stop(self, grace: Optional[float] = 0.5) -> None:
        # wait for full termination so the port is actually released
        # before a successor binds it
        self._server.stop(grace).wait(timeout=(grace or 0) + 5)


def _call_traced(fn, key: str, address: str, request,
                 timeout: Optional[float]) -> bytes:
    """`fn(request)` under its `client:<key>` span, the context sent
    along. In a costed trace the span is tagged with the daemon's own
    account of the call (`_GenericHandler._guard`'s trailing metadata):
    `server_us`, how long the daemon had it, and `server_cpu_us`, what
    its handler thread spent; a server that sends none leaves no tag."""
    from ozone_tpu.utils.tracing import Tracer

    tracer = Tracer.instance()
    with tracer.span(f"client:{key}", address=address) as sp:
        ctx = tracer.inject()
        metadata = (("x-trace-id", ctx),) if ctx else None
        if not sp.thread:
            return fn(request, timeout=timeout, metadata=metadata)
        out, call = fn.with_call(request, timeout=timeout,
                                 metadata=metadata)
        for k, v in call.trailing_metadata() or ():
            if k == "x-server-us":
                sp.tags["server_us"] = int(v)
            elif k == "x-server-cpu-us":
                sp.tags["server_cpu_us"] = int(v)
        return out


class RpcChannel:
    """Client side: method callables with raw-bytes serialization.

    `tls` (TlsMaterial) switches to a secure channel presenting this
    role's client certificate; `server_name` overrides SNI/authority when
    dialing by IP (certs carry role names + localhost SANs). `owner` tags
    the channel for scoped partition injection (net/partition.py)."""

    def __init__(self, address: str, tls=None,
                 server_name: Optional[str] = None,
                 owner: Optional[str] = None,
                 traced: bool = True):
        self.address = address
        self.owner = owner
        #: False for infrastructure channels (the span exporter) whose
        #: own RPCs must not generate spans — self-tracing feedback
        self.traced = traced
        options = [
            ("grpc.max_send_message_length", 128 * 1024 * 1024),
            ("grpc.max_receive_message_length", 128 * 1024 * 1024),
            # bounded reconnect backoff: a channel dialed BEFORE its
            # server binds (launcher supervisors, HA rings booting,
            # fail-fast polls against a jax-importing daemon) must not
            # back off past the caller's whole readiness window — the
            # grpc default doubles toward 120 s, which made "poll until
            # the subprocess answers" loops miss servers that had been
            # up for a minute (the acceptance launcher flake)
            ("grpc.initial_reconnect_backoff_ms", 250),
            ("grpc.min_reconnect_backoff_ms", 250),
            ("grpc.max_reconnect_backoff_ms", 2000),
        ]
        if tls is not None:
            # daemons dial by IP:port while certs carry role + localhost
            # SANs; authentication is CA membership (mutual TLS), so the
            # default authority override targets the shared localhost SAN
            options.append((
                "grpc.ssl_target_name_override", server_name or "localhost"))
            self._channel = grpc.secure_channel(
                address, tls.channel_credentials(), options=options)
        else:
            self._channel = grpc.insecure_channel(address, options=options)
        self._calls: dict[str, Callable] = {}
        #: True once ANY call on this channel succeeded; a channel that
        #: never connected is the wedge-prone kind FailoverChannels
        #: .invalidate drops, while a once-healthy channel rides grpc's
        #: own reconnection through transient failures
        self.ever_connected = False

    def _map_rpc_error(self, key: str, e: grpc.RpcError):
        detail = e.details() or ""
        try:
            d = json.loads(detail)
            # a JSON-detail error was PRODUCED BY THE SERVER: the
            # connection works (a follower answering OM_NOT_LEADER
            # forever must not look "never connected" to
            # FailoverChannels.invalidate)
            self.ever_connected = True
            return StorageError(d.get("code", "IO_EXCEPTION"),
                                d.get("message", detail))
        except (ValueError, KeyError):
            # no JSON detail -> the server never produced an answer.
            # Transport-level failures get their own code so failover
            # clients can tell "replica unreachable: rotate" apart from
            # "server raised: surface it" (retrying a handler bug across
            # every replica would mask the real error)
            code = ("UNAVAILABLE"
                    if e.code() in (grpc.StatusCode.UNAVAILABLE,
                                    grpc.StatusCode.DEADLINE_EXCEEDED,
                                    grpc.StatusCode.CANCELLED)
                    else "IO_EXCEPTION")
            return StorageError(code,
                                f"rpc {key} to {self.address}: "
                                f"{e.code()}: {detail}")

    def _check_partition(self, key: str,
                         timeout: Optional[float] = None) -> None:
        from ozone_tpu.net import partition

        # one consult covers address partitions AND verb-level rules
        # (the byteman-analog method-boundary injection)
        drop, d = partition.consult(self.address, key, self.owner)
        if drop:
            raise StorageError(
                "UNAVAILABLE",
                f"rpc {key} to {self.address}: injected network partition",
            )
        if d > 0:
            import time as _time

            # injected link latency (slow-network drill) honors the
            # caller's deadline: latency past the timeout behaves like a
            # real slow link — block until the deadline, then fail
            if timeout is not None and d >= timeout:
                _time.sleep(timeout)  # ozlint: allow[deadline-propagation] -- injected chaos latency must block like a real slow link; bounded by the caller's timeout
                raise StorageError(
                    "UNAVAILABLE",
                    f"rpc {key} to {self.address}: injected latency "
                    f"{d}s exceeded deadline {timeout}s",
                )
            _time.sleep(d)  # ozlint: allow[deadline-propagation] -- injected chaos latency, not a retry sleep (partition.py delay rule)

    def call_streaming(self, service: str, method: str, frames,
                       timeout: Optional[float] = 120.0) -> bytes:
        """Client-streaming call: send an iterator of byte frames, get one
        response (the zero-round-trip-per-chunk write path)."""
        key = f"/{service}/{method}"
        self._check_partition(key, timeout)
        fn = self._calls.get(key)
        if fn is None:
            fn = self._channel.stream_unary(key)
            self._calls[key] = fn
        try:
            return _call_traced(fn, key, self.address, iter(frames),
                                timeout)
        except grpc.RpcError as e:
            raise self._map_rpc_error(key, e) from e

    def call_server_stream(self, service: str, method: str,
                           request: bytes,
                           timeout: Optional[float] = 300.0):
        """Server-streaming call: one request, an iterator of byte
        frames back (large downloads never buffer in one message)."""
        from ozone_tpu.utils.tracing import Tracer

        key = f"/{service}/{method}"
        self._check_partition(key, timeout)
        fn = self._calls.get(key)
        if fn is None:
            fn = self._channel.unary_stream(key)
            self._calls[key] = fn
        tracer = Tracer.instance()
        try:
            with tracer.span(f"client:{key}", address=self.address):
                ctx = tracer.inject()
                metadata = (("x-trace-id", ctx),) if ctx else None
                yield from fn(request, timeout=timeout,
                              metadata=metadata)
        except grpc.RpcError as e:
            raise self._map_rpc_error(key, e) from e

    def call(self, service: str, method: str, request: bytes,
             timeout: Optional[float] = 30.0) -> bytes:
        key = f"/{service}/{method}"
        self._check_partition(key, timeout)
        fn = self._calls.get(key)
        if fn is None:
            fn = self._channel.unary_unary(key)
            self._calls[key] = fn
        try:
            if not self.traced:
                out = fn(request, timeout=timeout)
            else:
                out = _call_traced(fn, key, self.address, request, timeout)
            self.ever_connected = True
            return out
        except grpc.RpcError as e:
            raise self._map_rpc_error(key, e) from e

    def close(self) -> None:
        self._channel.close()


class FailoverChannels:
    """Address-list channel pool for HA failover clients (the
    OMFailoverProxyProvider / SCMBlockLocationFailoverProxyProvider
    plumbing): comma-list parsing, a thread-safe lazily-built channel
    cache, and a sticky index that follows leader hints or rotates on
    unreachable replicas. Shared by GrpcOmClient and GrpcScmClient so
    the failover behavior cannot drift between them."""

    def __init__(self, address: str, tls=None):
        self.addresses = [a.strip() for a in address.split(",")
                          if a.strip()]
        if not self.addresses:
            raise ValueError("empty address list")
        self._tls = tls
        self._chs: dict[str, RpcChannel] = {}
        #: channels to replicas retired by reconcile(); closed with the
        #: pool (an immediate close could race an in-flight RPC)
        self._retired: list[RpcChannel] = []
        #: cert-rotation watermark (RotatingTls.version): cached
        #: channels minted under a retired identity are dropped so the
        #: next call reconnects with the renewed cert
        self._tls_ver = getattr(tls, "version", None)
        self._idx = 0
        self._lock = threading.Lock()

    @property
    def current(self) -> str:
        with self._lock:
            return self.addresses[self._idx]

    def channel(self, addr: Optional[str] = None) -> tuple[str, RpcChannel]:
        with self._lock:
            ver = getattr(self._tls, "version", None)
            if ver != self._tls_ver:
                # the cert rotated: retire every cached channel (parked,
                # not closed — an in-flight RPC may still be using one)
                self._retired.extend(self._chs.values())
                self._chs.clear()
                self._tls_ver = ver
            a = addr if addr is not None else self.addresses[self._idx]
            ch = self._chs.get(a)
            if ch is None:
                ch = self._chs[a] = RpcChannel(a, tls=self._tls)
            return a, ch

    def rotate(self) -> None:
        with self._lock:
            self._idx = (self._idx + 1) % len(self.addresses)

    def invalidate(self, addr: str) -> None:
        """Drop AND close the cached channel for an UNREACHABLE
        replica: a channel dialed before its server ever bound can
        wedge in permanent TRANSIENT_FAILURE (fail-fast calls starving
        the subchannel's reconnect — observed against daemons whose jax
        import delays the bind by tens of seconds); recreating it on
        the next attempt reconnects instantly, which is what makes
        poll-until-up supervisor loops converge. Closing (not parking)
        is safe here: the channel is unreachable, so a concurrent
        in-flight RPC on it can only be waiting to fail — the close
        surfaces that as a clean rotate-and-retry, and parking one
        channel per poll tick would leak sockets for the whole wait.

        Only NEVER-connected channels are dropped: a once-healthy
        channel hitting a transient failure (a partition, a restart)
        recovers through grpc's own reconnection, and recreating it per
        failed call would churn sockets for the whole outage."""
        with self._lock:
            ch = self._chs.get(addr)
            if ch is None or ch.ever_connected:
                return
            del self._chs[addr]
        try:
            ch.close()
        except Exception:  # ozlint: allow[error-swallowing] -- best-effort channel teardown
            pass

    def reconcile(self, ring: list) -> None:
        """Adopt a server-shipped membership as the address list (online
        ring growth AND retirement: the server ships the full current
        ring on heartbeat responses, so clients both learn added
        replicas and stop dialing removed ones). The sticky index stays
        on the replica currently in use when it survives the change."""
        ring = [a.strip() for a in ring if a and a.strip()]
        if not ring:
            return
        with self._lock:
            if set(ring) == set(self.addresses):
                return
            cur = self.addresses[self._idx]
            # in place: callers alias this list (GrpcScmClient.addresses)
            self.addresses[:] = dict.fromkeys(ring)
            self._idx = (self.addresses.index(cur)
                         if cur in self.addresses else 0)
            # drop retired channels from the cache but DON'T close them
            # here: a concurrent caller may be mid-RPC on one, and a
            # forced close would surface a spurious error instead of a
            # clean rotate-and-retry. Ring changes are rare, so parking
            # them until close() is bounded in practice.
            self._retired.extend(self._chs.pop(a) for a in list(self._chs)
                                 if a not in self.addresses)

    def follow_hint(self, addr: Optional[str]) -> None:
        """Pin to a hinted leader address; a hint that is unknown or
        points back at the current replica rotates instead (a deposed
        leader advertising itself must not pin clients forever)."""
        with self._lock:
            if addr and addr in self.addresses:
                i = self.addresses.index(addr)
                if i != self._idx:
                    self._idx = i
                    return
            self._idx = (self._idx + 1) % len(self.addresses)

    def close(self) -> None:
        with self._lock:
            chans = list(self._chs.values()) + self._retired
            self._chs.clear()
            self._retired = []
        for ch in chans:
            ch.close()
