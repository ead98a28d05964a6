"""An RPC's client span says how long the daemon had it: the server
guard sends its own span's duration and CPU as trailing metadata, the
traced channel tags its `client:/<service>/<method>` span with them, and
the operation's stage record sums them by method."""

import time
from concurrent import futures

import grpc
import pytest

from ozone_tpu.net.rpc import RpcChannel, RpcServer
from ozone_tpu.utils.tracing import Tracer

SERVICE = "ozone.tpu.TestService"


@pytest.fixture
def tracer():
    Tracer._instance = None
    yield Tracer.instance()
    Tracer._instance = None


def _slow(req: bytes) -> bytes:
    time.sleep(0.010)
    return req[::-1]


def _slow_stream(frames, *_a) -> bytes:
    time.sleep(0.010)
    return b"".join(frames)


@pytest.fixture
def served():
    srv = RpcServer(port=0)
    srv.add_service(SERVICE, {"Echo": _slow},
                    stream_methods={"Put": _slow_stream})
    srv.start()
    yield srv.address
    srv.stop(grace=0)


@pytest.fixture
def old_server():
    """A server of before this change: the same wire, no trailing
    metadata."""
    class Handler(grpc.GenericRpcHandler):
        def service(self, details):
            return grpc.unary_unary_rpc_method_handler(
                lambda req, ctx: req[::-1])

    srv = grpc.server(futures.ThreadPoolExecutor(max_workers=2))
    srv.add_generic_rpc_handlers((Handler(),))
    port = srv.add_insecure_port("127.0.0.1:0")
    srv.start()
    yield f"127.0.0.1:{port}"
    srv.stop(0)


def _client_spans(tracer):
    return [s for s in tracer.traces() if s.name.startswith("client:/")]


@pytest.mark.parametrize("kind", ["unary", "client_stream"])
def test_the_client_span_carries_the_daemons_time(tracer, served, kind):
    ch = RpcChannel(served)
    try:
        with tracer.operation("client:get"):
            if kind == "unary":
                assert ch.call(SERVICE, "Echo", b"abc") == b"cba"
            else:
                assert ch.call_streaming(SERVICE, "Put",
                                         [b"ab", b"c"]) == b"abc"
    finally:
        ch.close()
    (span,) = _client_spans(tracer)
    assert 10_000 <= span.tags["server_us"] <= span.duration * 1e6
    assert 0 <= span.tags["server_cpu_us"] < 10_000  # it slept
    # the server's own span, in this process here, is the same interval
    (server,) = [s for s in tracer.traces() if s.name.startswith("server:")]
    assert span.tags["server_us"] == int(server.duration * 1e6)
    assert server.parent_id == span.span_id
    # and the operation's record sums it by method
    (rec,) = tracer.recorder.operations("client:get")
    method = "Echo" if kind == "unary" else "Put"
    assert rec["rpc"] == {f"/{SERVICE}/{method}": [
        1, int(round(span.duration * 1e6)), span.tags["server_us"]]}


def test_an_untraced_channel_leaves_no_span_and_no_error(tracer, served):
    ch = RpcChannel(served, traced=False)
    try:
        with tracer.operation("client:get"):
            assert ch.call(SERVICE, "Echo", b"abc") == b"cba"
    finally:
        ch.close()
    assert _client_spans(tracer) == []
    (rec,) = tracer.recorder.operations("client:get")
    assert rec["rpc"] == {}


def test_an_old_server_gives_a_span_with_no_tag_and_no_error(
        tracer, old_server):
    ch = RpcChannel(old_server)
    try:
        with tracer.operation("client:get"):
            assert ch.call(SERVICE, "Echo", b"abc") == b"cba"
    finally:
        ch.close()
    (span,) = _client_spans(tracer)
    assert "server_us" not in span.tags and "server_cpu_us" not in span.tags
    (rec,) = tracer.recorder.operations("client:get")
    assert rec["rpc"] == {f"/{SERVICE}/Echo": [
        1, int(round(span.duration * 1e6)), 0]}


def test_a_failed_call_still_maps_its_error(tracer, served):
    from ozone_tpu.storage.ids import StorageError

    ch = RpcChannel(served)
    try:
        with pytest.raises(StorageError):
            ch.call(SERVICE, "NoSuchMethod", b"")
    finally:
        ch.close()
    (span,) = _client_spans(tracer)
    assert "server_us" not in span.tags


def test_an_operation_that_is_not_costed_asks_the_daemon_for_nothing(
        tracer, served):
    """The daemon's account rides the calls of a costed trace alone
    (the second operation of a name within COST_INTERVAL_S is not one):
    every other call is made as it was, with no trailing metadata."""
    ch = RpcChannel(served)
    try:
        for _ in range(2):
            with tracer.operation("client:get"):
                assert ch.call(SERVICE, "Echo", b"abc") == b"cba"
    finally:
        ch.close()
    first, second = _client_spans(tracer)
    assert first.thread and first.tags["server_us"] >= 10_000
    assert "server_cpu_us" in first.tags
    assert not second.thread
    assert "server_us" not in second.tags
    assert "server_cpu_us" not in second.tags
    a, b = tracer.recorder.operations("client:get")
    assert "cost" in a and "rpc" in a
    assert "cost" not in b and "rpc" not in b
