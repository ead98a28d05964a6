"""Batched decode pipeline + persistent decode-plan cache tests.

The read/repair twin of the writer's batched device pipeline: degraded
reads and offline reconstruction must issue ONE device dispatch per
stripe batch (not per stripe), and erasure-pattern churn must never
recompile the decode executable — the plan cache swaps the tiny device
matrix under one jitted program per shape.
"""

import itertools

import numpy as np
import pytest

from tests.test_ec_pipeline import CELL, OPTS, MiniEC, _write_key
from ozone_tpu.codec.api import CoderOptions
from ozone_tpu.codec.pipeline import (
    DeviceBatchPipeline,
    batched,
    decode_batch_size,
)
from ozone_tpu.storage.ids import StorageError


@pytest.fixture
def cluster(tmp_path):
    c = MiniEC(tmp_path, n_dn=8)
    yield c
    c.close()


# ------------------------------------------------------------- plan cache
def test_pattern_churn_never_recompiles(monkeypatch):
    """Every 2-erasure pattern of RS(6,3) decodes through the SAME
    compiled program: the per-pattern work is a small device matrix from
    the plan cache, not a fresh jit (the compile-count probe that
    catches a per-pattern cache recompiling mid-read under churn)."""
    monkeypatch.setenv("OZONE_TPU_FUSED_BACKEND", "jax")
    from ozone_tpu.codec import fused
    from ozone_tpu.utils.checksum import Checksum, ChecksumType

    cell, bpc = 2048, 512
    opts = CoderOptions(6, 3, "rs", cell_size=cell)
    spec = fused.FusedSpec(opts, ChecksumType.CRC32C, bpc)
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, (3, 6, cell), dtype=np.uint8)
    parity, _ = (np.asarray(x) for x in fused.make_fused_encoder(spec)(data))
    units = np.concatenate([data, parity], axis=1)

    host = Checksum(ChecksumType.CRC32C, bpc)
    before = fused.decode_jit_cache_size()
    patterns = list(itertools.combinations(range(9), 2))
    for erased in patterns:
        valid = [u for u in range(9) if u not in erased][:6]
        fn = fused.make_fused_decoder(spec, valid, list(erased))
        rec, crcs = (np.asarray(x) for x in fn(units[:, valid]))
        assert np.array_equal(rec, units[:, list(erased)]), erased
        # device CRCs of the recovered cells match the host checksummer
        got = tuple(int(v).to_bytes(4, "big") for v in crcs[0, 0].tolist())
        assert got == host.compute(units[0, erased[0]]).checksums, erased
    grew = fused.decode_jit_cache_size() - before
    assert grew <= 1, (
        f"{grew} compiles across {len(patterns)} erasure patterns — the "
        "decode-plan cache must reuse ONE executable per shape")


def test_sharded_pattern_churn_never_recompiles(monkeypatch):
    """Same property for the sharded-DP decode: one SPMD executable per
    (mesh, shape) serves every erasure pattern."""
    monkeypatch.setenv("OZONE_TPU_FUSED_BACKEND", "jax")
    from ozone_tpu.codec import fused
    from ozone_tpu.parallel import sharded
    from ozone_tpu.utils.checksum import ChecksumType

    cell = 1024
    opts = CoderOptions(6, 3, "rs", cell_size=cell)
    spec = fused.FusedSpec(opts, ChecksumType.CRC32C, 512)
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, (4, 6, cell), dtype=np.uint8)
    parity, _ = (np.asarray(x) for x in fused.make_fused_encoder(spec)(data))
    units = np.concatenate([data, parity], axis=1)

    mesh = sharded.make_mesh(4)
    sharded._sharded_decode_apply_cached.cache_clear()
    for erased in itertools.combinations(range(9), 2):
        valid = [u for u in range(9) if u not in erased][:6]
        fn = sharded.make_sharded_decoder(spec, valid, list(erased), mesh)
        rec, _ = (np.asarray(x) for x in fn(units[:, valid]))
        assert np.array_equal(rec, units[:, list(erased)]), erased
    info = sharded._sharded_decode_apply_cached.cache_info()
    assert info.currsize == 1, info


def test_ring_pattern_churn_never_recompiles(monkeypatch):
    """And for the survivor-sharded ppermute ring (use_ring clusters):
    one ring executable per (mesh, shape) serves every erasure pattern —
    OPERATIONS.md promises operators no recompile stalls on degraded
    clusters regardless of the decode topology."""
    monkeypatch.setenv("OZONE_TPU_FUSED_BACKEND", "jax")
    from ozone_tpu.codec import fused
    from ozone_tpu.parallel import sharded
    from ozone_tpu.utils.checksum import ChecksumType

    cell = 1024
    opts = CoderOptions(6, 3, "rs", cell_size=cell)
    spec = fused.FusedSpec(opts, ChecksumType.CRC32C, 512)
    rng = np.random.default_rng(2)
    data = rng.integers(0, 256, (4, 6, cell), dtype=np.uint8)
    parity, _ = (np.asarray(x) for x in fused.make_fused_encoder(spec)(data))
    units = np.concatenate([data, parity], axis=1)

    mesh = sharded.make_mesh(4)
    sharded._ring_apply_cached.cache_clear()
    for erased in itertools.combinations(range(9), 2):
        valid = [u for u in range(9) if u not in erased][:6]
        fn = sharded.make_ring_decoder(spec, valid, list(erased), mesh)
        rec, _ = (np.asarray(x) for x in fn(units[:, valid]))
        assert np.array_equal(rec, units[:, list(erased)]), erased
    info = sharded._ring_apply_cached.cache_info()
    assert info.currsize == 1, info


def test_decode_batch_size_knob(monkeypatch):
    monkeypatch.delenv("OZONE_TPU_DECODE_BATCH", raising=False)
    assert decode_batch_size() == 8
    monkeypatch.setenv("OZONE_TPU_DECODE_BATCH", "3")
    assert decode_batch_size() == 3
    monkeypatch.setenv("OZONE_TPU_DECODE_BATCH", "0")
    assert decode_batch_size() == 1  # floor: at least one stripe
    monkeypatch.setenv("OZONE_TPU_DECODE_BATCH", "junk")
    assert decode_batch_size() == 8


# --------------------------------------------------------------- pipeline
def test_device_batch_pipeline_order_and_depth():
    """submit(N) returns batch N-1's results; exactly one batch stays in
    flight; drain flushes the tail — and every input goes through fn
    exactly once, in order."""
    seen = []

    def fn(batch):
        seen.append(batch.copy())
        return batch + 1, batch * 2

    pipe = DeviceBatchPipeline(fn)
    batches = [np.full((2, 2), i, dtype=np.int64) for i in range(5)]
    got = []
    for i, b in enumerate(batches):
        out = pipe.submit(b, ctx=i)
        if i == 0:
            assert out is None  # depth-1: nothing to hand back yet
        if out is not None:
            got.append(out)
    out = pipe.drain()
    assert out is not None
    got.append(out)
    assert pipe.drain() is None
    assert [ctx for ctx, _ in got] == list(range(5))
    for i, (_ctx, (plus, times)) in enumerate(got):
        assert np.array_equal(plus, batches[i] + 1)
        assert np.array_equal(times, batches[i] * 2)
    assert len(seen) == 5


def test_batched_slices():
    assert [list(b) for b in batched(list(range(7)), 3)] == [
        [0, 1, 2], [3, 4, 5], [6]]
    assert list(batched([], 3)) == []


# ---------------------------------------------------------- degraded read
def _kill_unit(cluster, group, u):
    dn = next(d for d in cluster.dns if d.id == group.pipeline.nodes[u])
    try:
        dn.delete_block(group.block_id)
    except StorageError:
        pass


def test_degraded_read_one_dispatch_per_stripe_batch(cluster, monkeypatch):
    """A degraded whole-group read decodes through the batched pipeline:
    one device dispatch per stripe batch — NOT per stripe — and the
    bytes are exact."""
    import ozone_tpu.client.ec_reader as ec_reader_mod

    monkeypatch.setenv("OZONE_TPU_DECODE_BATCH", "2")
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, 11 * CELL + 13, dtype=np.uint8)
    groups = _write_key(cluster, data)

    calls: list[int] = []
    real = ec_reader_mod.make_fused_decoder

    def spy(spec, valid, erased):
        fn = real(spec, valid, erased)

        def wrapped(batch):
            calls.append(int(np.asarray(batch).shape[0]))
            return fn(batch)

        return wrapped

    monkeypatch.setattr(ec_reader_mod, "make_fused_decoder", spy)
    total_stripes = 0
    expected_dispatches = 0
    parts = []
    for g in groups:
        _kill_unit(cluster, g, 1)  # lose data unit 1 in every group
        r = cluster.reader(g)
        total_stripes += r.num_stripes
        expected_dispatches += -(-r.num_stripes // 2)
        parts.append(r.read_all())
    got = np.concatenate(parts)
    assert np.array_equal(got, data)
    assert calls, "degraded read never reached the device decoder"
    assert sum(calls) == total_stripes
    assert max(calls) <= 2  # the configured batch depth
    # one dispatch per BATCH, not per stripe
    assert len(calls) == expected_dispatches
    assert len(calls) < total_stripes


def test_recover_cells_iter_streams_batches(cluster, monkeypatch):
    """recover_cells_iter yields (stripe_batch, (rec, crcs)) in stripe
    order with the configured granularity, and matches the one-shot
    recover_cells_with_crcs output."""
    monkeypatch.setenv("OZONE_TPU_DECODE_BATCH", "2")
    rng = np.random.default_rng(12)
    data = rng.integers(0, 256, 12 * CELL, dtype=np.uint8)  # 4 stripes
    g = _write_key(cluster, data)[0]
    _kill_unit(cluster, g, 0)

    r = cluster.reader(g)
    yielded = list(r.recover_cells_iter([0]))
    assert [sb for sb, _ in yielded] == [[0, 1], [2, 3]]
    rec = np.concatenate([out[0] for _, out in yielded])
    r2 = cluster.reader(g)
    cells, crcs = r2.recover_cells_with_crcs([0])
    assert np.array_equal(rec, cells)
    assert crcs.shape[0] == r2.num_stripes
    # recovered unit-0 cells are the original data column
    for s in range(4):
        start = s * 3 * CELL
        assert np.array_equal(cells[s, 0], data[start:start + CELL])


def test_recover_cells_iter_restarts_on_midstream_failure(
        cluster, monkeypatch):
    """A survivor dying AFTER batches were already yielded restarts the
    recovery with the unit excluded and re-yields every batch — and the
    streaming reconstruction consumer, which already wrote the first
    batch's chunks, overwrites idempotently and still commits a
    byte-exact replica."""
    import ozone_tpu.client.ec_reader as er
    import ozone_tpu.storage.reconstruction as recon_mod
    from ozone_tpu.storage.reconstruction import (
        ECReconstructionCoordinator,
        ReconstructionCommand,
    )

    # batch depth 1: the depth-1 pipeline yields batch [0] at submit of
    # stripe 1, so the fault at stripe 2 fires AFTER batch 0's chunks
    # were already streamed to the target — the restart must overwrite
    monkeypatch.setenv("OZONE_TPU_DECODE_BATCH", "1")
    rng = np.random.default_rng(14)
    data = rng.integers(0, 256, 12 * CELL, dtype=np.uint8)  # 4 stripes
    g = _write_key(cluster, data)[0]
    lost = 1
    dn_lost = next(d for d in cluster.dns if d.id == g.pipeline.nodes[lost])
    dn_lost.delete_container(g.container_id, force=True)

    real = er.ECBlockGroupReader._read_cell_checked
    state = {"fired": False, "streamed_before_failure": 0}
    real_stream = recon_mod.write_unit_stream

    def counting_stream(*a, **kw):
        if not state["fired"]:
            state["streamed_before_failure"] += 1
        return real_stream(*a, **kw)

    monkeypatch.setattr(recon_mod, "write_unit_stream", counting_stream)

    def flaky(self, u, s):
        if not state["fired"] and u == 0 and s >= 2:
            state["fired"] = True
            raise er._UnitReadError(u, ConnectionError("injected"))
        return real(self, u, s)

    monkeypatch.setattr(er.ECBlockGroupReader, "_read_cell_checked", flaky)

    sources = {
        u + 1: g.pipeline.nodes[u]
        for u in range(OPTS.all_units) if u != lost
    }
    cmd = ReconstructionCommand(
        g.container_id, OPTS, sources, {lost + 1: "dn7"})
    coord = ECReconstructionCoordinator(
        cluster.clients, bytes_per_checksum=1024)
    coord.reconstruct_container_group(cmd)
    assert state["fired"], "the injected mid-stream failure never fired"
    assert state["streamed_before_failure"] > 0, (
        "failure fired before any batch streamed — the restart-after-"
        "partial-write path was not exercised")

    dn7 = next(d for d in cluster.dns if d.id == "dn7")
    blk = dn7.get_block(g.block_id)
    for info in blk.chunks:
        dn7.read_chunk(g.block_id, info, verify=True)
    g.pipeline.nodes[lost] = "dn7"
    got = cluster.reader(g).read_all()
    assert np.array_equal(got, data[: g.length])


# ---------------------------------------------------------- reconstruction
def test_reconstruction_batched_byte_exact(cluster, monkeypatch):
    """Offline repair through the batched pipeline: byte-exact rebuilt
    replica, device CRCs intact, commit covers every streamed batch."""
    from ozone_tpu.storage.reconstruction import (
        ECReconstructionCoordinator,
        ReconstructionCommand,
    )

    monkeypatch.setenv("OZONE_TPU_DECODE_BATCH", "2")
    rng = np.random.default_rng(13)
    data = rng.integers(0, 256, 10 * CELL + 77, dtype=np.uint8)
    groups = _write_key(cluster, data)
    g = groups[0]
    lost = 2
    dn_lost = next(d for d in cluster.dns if d.id == g.pipeline.nodes[lost])
    dn_lost.delete_container(g.container_id, force=True)

    sources = {
        u + 1: g.pipeline.nodes[u]
        for u in range(OPTS.all_units) if u != lost
    }
    cmd = ReconstructionCommand(
        g.container_id, OPTS, sources, {lost + 1: "dn7"})
    coord = ECReconstructionCoordinator(
        cluster.clients, bytes_per_checksum=1024)
    coord.reconstruct_container_group(cmd)

    dn7 = next(d for d in cluster.dns if d.id == "dn7")
    blk = dn7.get_block(g.block_id)
    assert blk.block_group_length == g.length
    # the commit record covers every batch's streamed chunks, in order
    assert [i.offset for i in blk.chunks] == sorted(
        i.offset for i in blk.chunks)
    for info in blk.chunks:  # device CRCs verify on read
        dn7.read_chunk(g.block_id, info, verify=True)
    # full key still readable using the rebuilt replica only
    g.pipeline.nodes[lost] = "dn7"
    got = cluster.reader(g).read_all()
    assert np.array_equal(got, data[: g.length])
