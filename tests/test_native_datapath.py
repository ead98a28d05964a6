"""Native C++ datapath: protocol, parity with the gRPC verbs, checksum
enforcement, fences, tokens, and fallback behavior.

The sidecar (native/datapath.cpp + storage/fast_datapath.py) must be
semantically indistinguishable from the gRPC bulk verbs — same
file-per-block layout, same fence/token/layout gates, same
CHECKSUM_MISMATCH + unhealthy-container behavior — while moving the
per-chunk work out of the interpreter (reference analog:
GrpcXceiverService.java:42 native-epoll transport + ChunkUtils.java
mapped IO)."""

from __future__ import annotations

import numpy as np
import pytest

from ozone_tpu.net.dn_service import DatanodeGrpcService
from ozone_tpu.net.rpc import RpcServer
from ozone_tpu.client.native_dn import NativeDatanodeClient
from ozone_tpu.storage.datanode import Datanode
from ozone_tpu.storage.fast_datapath import DatapathSidecar, load_lib
from ozone_tpu.storage.ids import (
    BlockData,
    BlockID,
    ChunkInfo,
    StorageError,
)
from ozone_tpu.utils.checksum import Checksum, ChecksumType

pytestmark = pytest.mark.skipif(load_lib() is None,
                                reason="no native toolchain")


@pytest.fixture()
def cluster(tmp_path):
    """One datanode served by gRPC + the native sidecar, like the
    daemon wires them (minus SCM)."""
    dn = Datanode(tmp_path / "dn", dn_id="dn0")
    dn.create_container(1)
    server = RpcServer()
    sidecar = DatapathSidecar(dn)
    port = sidecar.start()
    assert port is not None
    DatanodeGrpcService(dn, server,
                        datapath_port=sidecar.advertise)
    server.start()
    client = NativeDatanodeClient("dn0", server.address)
    yield dn, client, sidecar
    client.close()
    sidecar.stop()
    server.stop()
    dn.close()


def _payload(seed: int, n: int = 256 * 1024) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8)


def test_native_write_read_roundtrip(cluster):
    dn, client, _ = cluster
    assert client._native_port() is not None
    data = _payload(1)
    cs = Checksum(ChecksumType.CRC32C, 16 * 1024).compute(data)
    bid = BlockID(1, 1)
    infos = [ChunkInfo(f"c{j}", j * data.size, data.size, cs)
             for j in range(3)]
    client.write_chunks_commit(
        bid, [(i, data) for i in infos],
        commit=BlockData(bid, infos), sync=True)
    # committed through the Python control plane
    bd = dn.get_block(bid)
    assert [c.name for c in bd.chunks] == ["c0", "c1", "c2"]
    # read back through the native path, with CRC verification
    out = client.read_chunks(bid, infos, verify=True)
    assert len(out) == 3
    for arr in out:
        np.testing.assert_array_equal(arr, data)
    # single-chunk verbs ride the same path
    one = client.read_chunk(bid, infos[1], verify=True)
    np.testing.assert_array_equal(one, data)
    assert dn.metrics.counter("batched_write_streams").value >= 1
    assert dn.metrics.counter("batched_read_streams").value >= 1


def test_native_matches_grpc_bytes(cluster, tmp_path):
    """Bytes written natively and via gRPC land identically (same
    layout, same offsets), and either transport reads the other's."""
    dn, client, _ = cluster
    data = _payload(2, 64 * 1024)
    cs = Checksum(ChecksumType.CRC32C, 16 * 1024).compute(data)
    b_native = BlockID(1, 10)
    b_grpc = BlockID(1, 11)
    info = ChunkInfo("c0", 0, data.size, cs)
    client.write_chunk(b_native, info, data)
    # force the gRPC path for the twin write
    super(NativeDatanodeClient, client).write_chunk(b_grpc, info, data)
    f_native = dn.get_container(1).chunks.block_path(b_native)
    f_grpc = dn.get_container(1).chunks.block_path(b_grpc)
    assert f_native.read_bytes() == f_grpc.read_bytes()
    # cross-transport read
    got = super(NativeDatanodeClient, client).read_chunk(
        b_native, info, verify=True)
    np.testing.assert_array_equal(got, data)


def test_native_read_checksum_mismatch_marks_unhealthy(cluster):
    dn, client, _ = cluster
    data = _payload(3, 32 * 1024)
    cs = Checksum(ChecksumType.CRC32C, 16 * 1024).compute(data)
    bid = BlockID(1, 20)
    info = ChunkInfo("c0", 0, data.size, cs)
    client.write_chunk(bid, info, data)
    # corrupt on disk behind the store's back
    path = dn.get_container(1).chunks.block_path(bid)
    raw = bytearray(path.read_bytes())
    raw[100] ^= 0xFF
    path.write_bytes(bytes(raw))
    with pytest.raises(StorageError) as ei:
        client.read_chunk(bid, info, verify=True)
    assert ei.value.code == "CHECKSUM_MISMATCH"
    assert dn.get_container(1).state.value == "UNHEALTHY"
    assert dn.metrics.counter("checksum_failures").value == 1
    # verify=False still serves the bytes (scrub decides health)


def test_native_write_fence(cluster):
    """The single-writer fence holds across the native path: a second
    writer streaming into an owned block is refused before any byte
    lands (BLOCK_WRITE_CONFLICT, same as the gRPC verbs)."""
    dn, client, _ = cluster
    data = _payload(4, 16 * 1024)
    cs = Checksum(ChecksumType.CRC32C, 16 * 1024).compute(data)
    bid = BlockID(1, 30)
    info = ChunkInfo("c0", 0, data.size, cs)
    client.write_chunks_commit(bid, [(info, data)], writer="w1")
    with pytest.raises(StorageError) as ei:
        client.write_chunks_commit(bid, [(info, data)], writer="w2")
    assert ei.value.code == "BLOCK_WRITE_CONFLICT"
    assert dn.metrics.counter("write_fence_violations").value == 1


def test_native_commit_id_mismatch_refused(cluster):
    dn, client, _ = cluster
    data = _payload(5, 4096)
    cs = Checksum(ChecksumType.CRC32C, 16 * 1024).compute(data)
    bid = BlockID(1, 40)
    info = ChunkInfo("c0", 0, data.size, cs)
    with pytest.raises(StorageError) as ei:
        client.write_chunks_commit(
            bid, [(info, data)],
            commit=BlockData(BlockID(1, 41), [info]))
    assert ei.value.code == "INVALID_ARGUMENT"


def test_native_missing_container(cluster):
    _, client, _ = cluster
    data = _payload(6, 4096)
    info = ChunkInfo("c0", 0, data.size,
                     Checksum(ChecksumType.CRC32C).compute(data))
    with pytest.raises(StorageError) as ei:
        client.write_chunks_commit(BlockID(999, 1), [(info, data)])
    assert ei.value.code == "CONTAINER_NOT_FOUND"
    # the connection survives an early refusal (drain-to-END protocol)
    bid = BlockID(1, 50)
    client.write_chunks_commit(bid, [(info, data)],
                               commit=BlockData(bid, [info]))


def test_fallback_when_no_sidecar(tmp_path):
    """A server without a native listener serves everything over gRPC
    through the same client."""
    dn = Datanode(tmp_path / "dn", dn_id="dn0")
    dn.create_container(1)
    server = RpcServer()
    DatanodeGrpcService(dn, server)  # no datapath_port provider
    server.start()
    client = NativeDatanodeClient("dn0", server.address)
    try:
        assert client._native_port() is None
        data = _payload(7, 8192)
        cs = Checksum(ChecksumType.CRC32C, 16 * 1024).compute(data)
        bid = BlockID(1, 1)
        info = ChunkInfo("c0", 0, data.size, cs)
        client.write_chunks_commit(bid, [(info, data)],
                                   commit=BlockData(bid, [info]))
        got = client.read_chunk(bid, info, verify=True)
        np.testing.assert_array_equal(got, data)
    finally:
        client.close()
        server.stop()
        dn.close()


def test_native_block_tokens_enforced(tmp_path):
    """Token enforcement holds on the native path: no token -> refused,
    OM-granted token -> served (BlockTokenVerifier parity)."""
    from ozone_tpu.client.dn_client import TokenStore
    from ozone_tpu.utils.security import (
        AccessMode,
        BlockTokenIssuer,
        BlockTokenVerifier,
        SecretKeyManager,
    )

    secrets = SecretKeyManager()
    verifier = BlockTokenVerifier(secrets, enabled=True)
    issuer = BlockTokenIssuer(secrets)
    dn = Datanode(tmp_path / "dn", dn_id="dn0")
    dn.create_container(1)
    server = RpcServer()
    sidecar = DatapathSidecar(dn, verifier=verifier)
    assert sidecar.start() is not None
    DatanodeGrpcService(dn, server, verifier=verifier,
                        datapath_port=sidecar.advertise)
    server.start()
    data = _payload(8, 4096)
    cs = Checksum(ChecksumType.CRC32C, 16 * 1024).compute(data)
    bid = BlockID(1, 1)
    info = ChunkInfo("c0", 0, data.size, cs)

    bare = NativeDatanodeClient("dn0", server.address)
    tokens = TokenStore()
    tokens.put_block_token(
        bid, issuer.issue(bid, [AccessMode.READ, AccessMode.WRITE],
                          owner="u"))
    authed = NativeDatanodeClient("dn0", server.address, tokens=tokens)
    try:
        with pytest.raises(StorageError) as ei:
            bare.write_chunks_commit(bid, [(info, data)])
        assert ei.value.code == "BLOCK_TOKEN_VERIFICATION_FAILED"
        authed.write_chunks_commit(bid, [(info, data)],
                                   commit=BlockData(bid, [info]))
        got = authed.read_chunk(bid, info, verify=True)
        np.testing.assert_array_equal(got, data)
    finally:
        bare.close()
        authed.close()
        sidecar.stop()
        server.stop()
        dn.close()


def test_native_partition_rules_apply(cluster):
    """Chaos rules keyed on the gRPC address cover the native path."""
    from ozone_tpu.net import partition

    dn, client, _ = cluster
    data = _payload(9, 4096)
    info = ChunkInfo("c0", 0, data.size,
                     Checksum(ChecksumType.CRC32C).compute(data))
    partition.block(client.address)
    try:
        with pytest.raises(StorageError) as ei:
            client.write_chunks_commit(BlockID(1, 60), [(info, data)])
        assert ei.value.code == "UNAVAILABLE"
    finally:
        partition.clear()
    bid = BlockID(1, 60)
    client.write_chunks_commit(bid, [(info, data)],
                               commit=BlockData(bid, [info]))


def test_a_timeout_the_spent_budget_caused_is_the_budgets_not_the_peers():
    """The socket's IO timeout is the operation's remaining budget where
    that is shorter: when it fires with the budget spent the caller sees
    DEADLINE_EXCEEDED (a sweep's window closing), not UNAVAILABLE, which
    callers book against the datanode (a failed key, an excluded node)."""
    import time

    from ozone_tpu.client import resilience
    from ozone_tpu.client.native_dn import NativeDatanodeClient
    from ozone_tpu.storage.ids import StorageError

    c = NativeDatanodeClient.__new__(NativeDatanodeClient)
    c.address = "127.0.0.1:1"
    # no budget, or a live one: the peer's fault, as before
    assert c._io_fault(TimeoutError("timed out")).code == "UNAVAILABLE"
    with resilience.start("op", seconds=60.0):
        assert c._io_fault(TimeoutError("timed out")).code == "UNAVAILABLE"
    with resilience.start("op", seconds=0.001):
        time.sleep(0.005)
        with pytest.raises(StorageError) as e:
            c._io_fault(TimeoutError("timed out"))
        assert e.value.code == resilience.DEADLINE_EXCEEDED
        # a refused or reset connection stays the peer's, budget or not
        assert c._io_fault(ConnectionError("reset")).code == "UNAVAILABLE"
