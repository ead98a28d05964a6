"""Zero-copy datapath invariants.

Three contracts, all enforced through the process-wide copy-accounting
registry in codec/hostmem.py:

1. <= 1 host copy per chunk per direction on the native PUT and GET
   paths (steady state is 0: payloads travel as views over pooled
   buffers from socket to consumer).
2. Byte-exactness survives pooled-buffer reuse — a recycled slab must
   never leak a previous request's bytes — including under a chaos
   overlay of injected partitions mid-soak.
3. Leases go back to the pool: after errors mid-stream, and after a
   1k-GET soak the pool's high-water mark stays at its steady-state
   plateau (no leak, no unbounded growth).
"""

from __future__ import annotations

import gc
import threading
import time

import numpy as np
import pytest

from ozone_tpu.client.native_dn import NativeDatanodeClient
from ozone_tpu.codec import hostmem
from ozone_tpu.net import partition
from ozone_tpu.net.dn_service import DatanodeGrpcService
from ozone_tpu.net.rpc import RpcServer
from ozone_tpu.storage.datanode import Datanode
from ozone_tpu.storage.fast_datapath import (
    DatapathSidecar,
    load_lib,
    native_pool_stats,
)
from ozone_tpu.storage.ids import (
    BlockData,
    BlockID,
    ChunkInfo,
    StorageError,
)
from ozone_tpu.utils.checksum import Checksum, ChecksumType

needs_native = pytest.mark.skipif(load_lib() is None,
                                  reason="no native toolchain")


# ------------------------------------------------------------- fixtures
@pytest.fixture()
def cluster(tmp_path):
    dn = Datanode(tmp_path / "dn", dn_id="dn0")
    dn.create_container(1)
    server = RpcServer()
    sidecar = DatapathSidecar(dn)
    assert sidecar.start() is not None
    DatanodeGrpcService(dn, server, datapath_port=sidecar.advertise)
    server.start()
    client = NativeDatanodeClient("dn0", server.address)
    yield dn, client
    client.close()
    sidecar.stop()
    server.stop()
    dn.close()


def _chunks(seed: int, n_chunks: int, size: int):
    rng = np.random.default_rng(seed)
    cs = Checksum(ChecksumType.CRC32C, 16 * 1024)
    infos, datas = [], []
    for j in range(n_chunks):
        d = rng.integers(0, 256, size, dtype=np.uint8)
        infos.append(ChunkInfo(f"c{j}", j * size, size, cs.compute(d)))
        datas.append(d)
    return infos, datas


class _CopyMeter:
    """Delta view of the datapath registry across a with-block."""

    def __enter__(self):
        self._c0 = hostmem._COPIES.value
        self._b0 = hostmem._BYTES_COPIED.value
        self._m0 = hostmem._BYTES_MOVED.value
        return self

    def __exit__(self, *exc):
        self.copies = hostmem._COPIES.value - self._c0
        self.bytes_copied = hostmem._BYTES_COPIED.value - self._b0
        self.bytes_moved = hostmem._BYTES_MOVED.value - self._m0


def _drain_leases():
    """Drop lingering array views so their weakref finalizers return
    the backing leases to the pool, once no EC reader thread that an
    earlier test of this process orphaned (a straggler still asleep in
    its read) is left to give its survivor batch back meanwhile."""
    t_end = time.monotonic() + 30
    while any(t.name.startswith("ec-read") for t in threading.enumerate()) \
            and time.monotonic() < t_end:
        time.sleep(0.05)
    gc.collect()


# ------------------------------------------- copies-per-chunk (the bar)
@needs_native
def test_put_host_copies_per_chunk_at_most_one(cluster):
    dn, client = cluster
    n_chunks, size = 8, 256 * 1024
    infos, datas = _chunks(1, n_chunks, size)
    bid = BlockID(1, 1)
    with _CopyMeter() as m:
        client.write_chunks_commit(bid, list(zip(infos, datas)),
                                   commit=BlockData(bid, infos),
                                   sync=True)
    assert m.copies <= n_chunks, \
        f"{m.copies} host copies for {n_chunks} chunks on PUT"
    # the payload crossed the wire without materializing
    assert m.bytes_moved >= n_chunks * size
    assert m.bytes_copied <= n_chunks * size


@needs_native
def test_get_host_copies_per_chunk_at_most_one(cluster):
    dn, client = cluster
    n_chunks, size = 8, 256 * 1024
    infos, datas = _chunks(2, n_chunks, size)
    bid = BlockID(1, 2)
    client.write_chunks_commit(bid, list(zip(infos, datas)),
                               commit=BlockData(bid, infos))
    with _CopyMeter() as m:
        out = client.read_chunks(bid, infos, verify=True)
    assert m.copies <= n_chunks, \
        f"{m.copies} host copies for {n_chunks} chunks on GET"
    assert m.bytes_moved >= n_chunks * size
    for got, want in zip(out, datas):
        np.testing.assert_array_equal(got, want)


# ------------------------------------------ byte-exactness under reuse
@needs_native
def test_pooled_reuse_byte_exact_under_chaos(cluster):
    """Soak PUT/GET through the recycled pool slabs with a chaos
    overlay (injected partitions + delays mid-loop): a reused buffer
    must never leak a previous request's bytes, and every recovered
    request reads back byte-exact."""
    dn, client = cluster
    rng = np.random.default_rng(3)
    cs = Checksum(ChecksumType.CRC32C, 16 * 1024)
    _drain_leases()
    base = hostmem.pool().stats()
    try:
        for i in range(40):
            # odd sizes: exercise every size class + short final reads
            n = int(rng.integers(1, 96)) * 1024 + int(rng.integers(0, 17))
            data = rng.integers(0, 256, n, dtype=np.uint8)
            info = ChunkInfo("c0", 0, n, cs.compute(data))
            bid = BlockID(1, 100 + i)
            if i % 9 == 4:
                # blackhole: the request fails loudly, leases go home
                partition.block(client.address)
                with pytest.raises(StorageError):
                    client.write_chunks_commit(bid, [(info, data)])
                partition.clear()
            elif i % 9 == 7:
                partition.delay(client.address, 0.02)
            client.write_chunks_commit(bid, [(info, data)],
                                       commit=BlockData(bid, [info]))
            got = client.read_chunks(bid, [info], verify=True)[0]
            np.testing.assert_array_equal(got, data)
            del got
    finally:
        partition.clear()
    _drain_leases()
    assert hostmem.pool().stats()["leased_count"] == base["leased_count"]


# --------------------------------------------------- lease return paths
@needs_native
def test_midstream_error_returns_leases_to_pool(cluster):
    """A CHECKSUM_MISMATCH halfway through a batched read aborts the
    stream; the recv slab (and every per-chunk view handed out before
    the fault) must land back in the pool."""
    dn, client = cluster
    n_chunks, size = 4, 64 * 1024
    infos, datas = _chunks(4, n_chunks, size)
    bid = BlockID(1, 200)
    client.write_chunks_commit(bid, list(zip(infos, datas)),
                               commit=BlockData(bid, infos))
    # corrupt chunk 2 on disk behind the store's back
    path = dn.get_container(1).chunks.block_path(bid)
    raw = bytearray(path.read_bytes())
    raw[2 * size + 17] ^= 0xFF
    path.write_bytes(bytes(raw))
    _drain_leases()
    base = hostmem.pool().stats()["leased_count"]
    with pytest.raises(StorageError) as ei:
        client.read_chunks(bid, infos, verify=True)
    assert ei.value.code == "CHECKSUM_MISMATCH"
    _drain_leases()
    assert hostmem.pool().stats()["leased_count"] == base


@needs_native
def test_pool_high_water_stable_after_1k_gets(cluster):
    """The leak test: 1k GETs through the pooled GET path must not grow
    the pool's high-water mark past its steady-state plateau, and every
    lease must be back on the free lists at the end."""
    dn, client = cluster
    size = 64 * 1024
    infos, datas = _chunks(5, 1, size)
    bid = BlockID(1, 300)
    client.write_chunks_commit(bid, list(zip(infos, datas)),
                               commit=BlockData(bid, infos))
    for _ in range(20):  # warmup: reach the steady-state plateau
        client.read_chunks(bid, infos, verify=True)
    _drain_leases()
    plateau = hostmem.pool().stats()
    for _ in range(1000):
        out = client.read_chunks(bid, infos, verify=True)
        del out
    _drain_leases()
    end = hostmem.pool().stats()
    assert end["high_water_bytes"] == plateau["high_water_bytes"], \
        "pool high-water grew during the soak: leases are leaking"
    assert end["leased_count"] == plateau["leased_count"]
    np.testing.assert_array_equal(
        client.read_chunks(bid, infos, verify=True)[0], datas[0])


@needs_native
def test_native_arena_capsule_roundtrip():
    """The C++ arena's capsule API: lease/retain/release bookkeeping
    shows up in dp_pool_stat and buffers recycle."""
    lib = load_lib()
    s0 = native_pool_stats()
    buf = lib.dp_buf_lease(100 * 1024)
    assert buf
    assert lib.dp_buf_cap(buf) >= 100 * 1024
    assert lib.dp_buf_data(buf)
    s1 = native_pool_stats()
    assert s1["leased_bytes"] > s0["leased_bytes"]
    lib.dp_buf_retain(buf)
    lib.dp_buf_release(buf)
    s2 = native_pool_stats()
    assert s2["leased_bytes"] == s1["leased_bytes"]  # still 1 ref
    lib.dp_buf_release(buf)
    s3 = native_pool_stats()
    assert s3["leased_bytes"] == s0["leased_bytes"]
    assert s3["high_water_bytes"] >= s1["leased_bytes"] - s0["leased_bytes"]


# ------------------------------------------------- hostmem unit surface
def test_pool_size_classes_and_reuse():
    p = hostmem.HostBufferPool(max_retained=1 << 20, max_class=1 << 18,
                               min_class=4096)
    a = p.lease(5000)
    assert a.cap == 8192  # next power-of-two class
    mm = a._mm
    a.release()
    b = p.lease(6000)
    assert b._mm is mm, "freed buffer of the same class must be reused"
    b.release()
    assert p.stats()["leased_count"] == 0
    big = p.lease((1 << 18) + 1)  # above max_class: transient
    big.release()
    assert p.stats()["free_bytes"] <= 1 << 20
    p.trim()
    assert p.stats()["free_bytes"] == 0


def test_lease_refcount_pins_arrays():
    p = hostmem.HostBufferPool(max_retained=1 << 20)
    lease = p.lease(4096)
    lease.view[:4] = b"abcd"
    arr = lease.array(length=4)
    lease.release()  # creator ref gone; the array still pins it
    assert p.stats()["leased_count"] == 1
    assert bytes(arr.tobytes()) == b"abcd"
    del arr
    gc.collect()
    assert p.stats()["leased_count"] == 0
    with pytest.raises(RuntimeError):
        lease.release()


def test_as_array_zero_copy_and_counted_fallback():
    c0 = hostmem._COPIES.value
    raw = bytearray(b"\x01\x02\x03\x04")
    v = hostmem.as_array(raw)
    assert hostmem._COPIES.value == c0  # no copy for flat buffers
    raw[0] = 9
    assert v[0] == 9, "as_array must alias the source buffer"
    arr = np.arange(16, dtype=np.uint8).reshape(4, 4)[:, ::2]
    flat = hostmem.as_array(arr)  # non-contiguous: one counted copy
    assert hostmem._COPIES.value == c0 + 1
    assert flat.size == arr.size


def test_copy_ratio_gauge_tracks_registry():
    hostmem.count_move(1000)
    moved = hostmem._BYTES_MOVED.value
    copied = hostmem._BYTES_COPIED.value
    assert abs(hostmem._RATIO.value - copied / moved) < 1e-9


def test_to_device_round_trips_payload():
    jax = pytest.importorskip("jax")
    data = np.arange(8192, dtype=np.uint8)
    on_dev = hostmem.to_device(data)
    np.testing.assert_array_equal(np.asarray(on_dev), data)
    assert isinstance(on_dev, jax.Array)
