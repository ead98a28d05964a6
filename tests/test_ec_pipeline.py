"""EC write/read pipeline tests over in-process datanodes.

Strategy mirrors the reference's TestECKeyOutputStream +
TestECContainerRecovery: write keys of awkward sizes, re-read, kill units,
assert degraded reads and targeted recovery are byte-exact, and verify the
rollback-to-new-group path on write failure.
"""

import itertools

import numpy as np
import pytest

from ozone_tpu.client.dn_client import DatanodeClientFactory, LocalDatanodeClient
from ozone_tpu.client.ec_reader import (
    ECBlockGroupReader,
    InsufficientLocationsError,
)
from ozone_tpu.client.ec_writer import BlockGroup, ECKeyWriter, block_lengths
from ozone_tpu.codec.api import CoderOptions
from ozone_tpu.scm.pipeline import Pipeline, ReplicationConfig
from ozone_tpu.storage.datanode import Datanode
from ozone_tpu.storage.ids import StorageError

CELL = 4096  # small cells keep tests fast
OPTS = CoderOptions(3, 2, "rs", cell_size=CELL)


class MiniEC:
    """Tiny in-process cluster: n datanodes + naive group allocator."""

    def __init__(self, tmp_path, n_dn=6, opts=OPTS):
        self.opts = opts
        self.dns = [Datanode(tmp_path / f"dn{i}", dn_id=f"dn{i}") for i in range(n_dn)]
        self.clients = DatanodeClientFactory()
        for dn in self.dns:
            self.clients.register_local(dn)
        self._cid = itertools.count(1)
        self._lid = itertools.count(1)
        self.allocated: list[BlockGroup] = []

    def allocate(self, excluded: list[str]) -> BlockGroup:
        nodes = [d.id for d in self.dns if d.id not in excluded][
            : self.opts.all_units
        ]
        if len(nodes) < self.opts.all_units:
            raise RuntimeError("not enough nodes")
        g = BlockGroup(
            container_id=next(self._cid),
            local_id=next(self._lid),
            pipeline=Pipeline(ReplicationConfig.from_ec(self.opts), nodes),
        )
        self.allocated.append(g)
        return g

    def writer(self, **kw) -> ECKeyWriter:
        kw.setdefault("block_size", 4 * CELL)  # 4 stripes per group
        kw.setdefault("bytes_per_checksum", 1024)
        kw.setdefault("stripe_batch", 3)
        return ECKeyWriter(self.opts, self.allocate, self.clients, **kw)

    def reader(self, g: BlockGroup, **kw) -> ECBlockGroupReader:
        kw.setdefault("bytes_per_checksum", 1024)
        return ECBlockGroupReader(g, self.opts, self.clients, **kw)

    def close(self):
        for d in self.dns:
            d.close()


@pytest.fixture
def cluster(tmp_path):
    c = MiniEC(tmp_path)
    yield c
    c.close()


def _write_key(cluster, data: np.ndarray, **kw) -> list[BlockGroup]:
    w = cluster.writer(**kw)
    # write in uneven pieces to exercise buffering
    pos = 0
    rng = np.random.default_rng(123)
    while pos < data.size:
        n = min(int(rng.integers(1, 3 * CELL)), data.size - pos)
        w.write(data[pos : pos + n])
        pos += n
    groups = w.close()
    assert w.bytes_written == data.size
    assert sum(g.length for g in groups) == data.size
    return groups


def _read_key(cluster, groups, **kw) -> np.ndarray:
    parts = [cluster.reader(g, **kw).read_all() for g in groups]
    return np.concatenate(parts) if parts else np.zeros(0, np.uint8)


@pytest.mark.parametrize(
    "size",
    [
        1,  # sub-cell
        CELL,  # exactly one cell
        CELL + 17,  # partial second cell
        3 * CELL,  # exactly one stripe
        3 * CELL + 1,  # stripe + 1 byte
        7 * CELL + 99,  # partial stripe in second stripe row
        12 * CELL,  # exactly one full group (4 stripes)
        25 * CELL + 5,  # multiple groups, partial tail
    ],
)
def test_write_read_roundtrip(cluster, size):
    rng = np.random.default_rng(size)
    data = rng.integers(0, 256, size, dtype=np.uint8)
    groups = _write_key(cluster, data)
    got = _read_key(cluster, groups)
    assert np.array_equal(got, data)


def test_block_lengths_math():
    # group_length=7*CELL+99 over k=3: block0 = 3*CELL, block1 = 2*CELL+99...
    k = 3
    L = 7 * CELL + 99
    bl = block_lengths(L, k, CELL)
    assert sum(bl) == L
    # stripe layout: s0: c0,c1,c2 | s1: c3,c4,c5 | s2: c6, partial(99), 0
    assert bl[0] == 3 * CELL
    assert bl[1] == 2 * CELL + 99
    assert bl[2] == 2 * CELL


def test_degraded_read_single_and_double_loss(cluster):
    rng = np.random.default_rng(42)
    # kill exactly n_kill distinct units per group (p=2 tolerable)
    for n_kill in (1, 2):
        data = rng.integers(0, 256, 10 * CELL + 7, dtype=np.uint8)
        groups = _write_key(cluster, data)
        for g in groups:
            for u in rng.choice(5, size=n_kill, replace=False).tolist():
                dn_id = g.pipeline.nodes[u]
                dn = next(d for d in cluster.dns if d.id == dn_id)
                try:
                    dn.delete_block(g.block_id)
                except StorageError:
                    pass
        got = _read_key(cluster, groups)
        assert np.array_equal(got, data), f"n_kill={n_kill}"


def test_ranged_reads_match_slices(cluster):
    """Cell-granular positioned reads (round 4): every awkward range
    equals the slice of a full read, on healthy AND degraded groups
    (where only the covering stripes may be reconstructed)."""
    rng = np.random.default_rng(31)
    data = rng.integers(0, 256, 9 * CELL + 123, dtype=np.uint8)
    groups = _write_key(cluster, data)
    g = groups[0]
    cases = [(0, 1), (CELL - 1, 2), (0, g.length), (g.length - 1, 1),
             (CELL // 2, 3 * CELL), (2 * CELL + 7, CELL + 100),
             (g.length, 0)]
    for off, ln in cases:
        got = cluster.reader(g).read(off, ln)
        assert np.array_equal(got, data[off:off + ln]), (off, ln)
    with pytest.raises(ValueError):
        cluster.reader(g).read(0, g.length + 1)
    with pytest.raises(ValueError):
        cluster.reader(g).read(-1, 1)
    # randomized sweep: any (offset, length) equals the slice
    for _ in range(20):
        off = int(rng.integers(0, g.length))
        ln = int(rng.integers(0, g.length - off + 1))
        got = cluster.reader(g).read(off, ln)
        assert np.array_equal(got, data[off:off + ln]), (off, ln)
    # degrade: drop one data unit and one parity unit
    for u in (1, 4):
        dn = next(d for d in cluster.dns if d.id == g.pipeline.nodes[u])
        dn.delete_block(g.block_id)
    for off, ln in cases:
        got = cluster.reader(g).read(off, ln)
        assert np.array_equal(got, data[off:off + ln]), \
            f"degraded range ({off},{ln})"
    for _ in range(20):
        off = int(rng.integers(0, g.length))
        ln = int(rng.integers(0, g.length - off + 1))
        got = cluster.reader(g).read(off, ln)
        assert np.array_equal(got, data[off:off + ln]), \
            f"degraded random range ({off},{ln})"


def test_replicated_ranged_read(cluster):
    from ozone_tpu.client.replicated import (
        ReplicatedKeyReader,
        ReplicatedKeyWriter,
    )

    def allocate(excluded, ec=()):
        g = cluster.allocate(excluded)
        g.pipeline.nodes = g.pipeline.nodes[:3]
        return g

    w = ReplicatedKeyWriter(allocate, cluster.clients,
                            block_size=16 * CELL, chunk_size=CELL)
    rng = np.random.default_rng(37)
    data = rng.integers(0, 256, 5 * CELL + 19, dtype=np.uint8)
    w.write(data)
    (g,) = w.close()
    for off, ln in [(0, 1), (CELL - 1, 2), (0, g.length),
                    (g.length - 1, 1), (2 * CELL + 5, 2 * CELL),
                    (g.length, 0)]:
        got = ReplicatedKeyReader(g, cluster.clients).read(off, ln)
        assert np.array_equal(got, data[off:off + ln]), (off, ln)
    with pytest.raises(ValueError):
        ReplicatedKeyReader(g, cluster.clients).read(1, g.length)


def test_ranged_read_off_missing_unit_needs_no_recovery(cluster):
    """A ranged read that never touches the missing unit must not pay a
    reconstruction: the recovery entry points are forbidden for the
    duration."""
    rng = np.random.default_rng(41)
    data = rng.integers(0, 256, 3 * CELL, dtype=np.uint8)  # one stripe
    groups = _write_key(cluster, data)
    g = groups[0]
    dn = next(d for d in cluster.dns if d.id == g.pipeline.nodes[2])
    dn.delete_block(g.block_id)  # data unit 2 gone
    r = cluster.reader(g)

    def boom(*a, **kw):
        raise AssertionError("range off the missing unit must not "
                             "trigger recovery")
    r.recover_cells = r.recover_cells_iter = boom
    # bytes [0, 2*CELL) live on units 0 and 1 only
    got = r.read(CELL // 2, CELL)
    assert np.array_equal(got, data[CELL // 2 : CELL // 2 + CELL])
    # and a range ON the missing unit still reconstructs (fresh reader)
    got = cluster.reader(g).read(2 * CELL + 5, 100)
    assert np.array_equal(got, data[2 * CELL + 5 : 2 * CELL + 105])


def test_short_replica_fails_over_not_zero_fill(cluster):
    """A replica missing its tail chunk must fail over to the next
    replica, never serve zero-filled bytes (stale-replica safety)."""
    from ozone_tpu.client.replicated import (
        ReplicatedKeyReader,
        ReplicatedKeyWriter,
    )
    from ozone_tpu.storage.ids import BlockData

    def allocate(excluded, ec=()):
        g = cluster.allocate(excluded)
        g.pipeline.nodes = g.pipeline.nodes[:3]
        return g

    w = ReplicatedKeyWriter(allocate, cluster.clients,
                            block_size=8 * CELL, chunk_size=CELL)
    rng = np.random.default_rng(43)
    data = rng.integers(0, 256, 3 * CELL, dtype=np.uint8)
    w.write(data)
    (g,) = w.close()
    # truncate the FIRST replica's record to 2 chunks (a datanode that
    # died before the last commit; re-written record, chunk file stays)
    dn0 = next(d for d in cluster.dns if d.id == g.pipeline.nodes[0])
    bd = dn0.get_block(g.block_id)
    dn0.put_block(BlockData(g.block_id, bd.chunks[:2]))
    # whole and tail ranged reads must come from a healthy replica
    got = ReplicatedKeyReader(g, cluster.clients).read_all()
    assert np.array_equal(got, data)
    got = ReplicatedKeyReader(g, cluster.clients).read(2 * CELL + 1, 100)
    assert np.array_equal(got, data[2 * CELL + 1 : 2 * CELL + 101])


def test_too_many_losses_raises(cluster):
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, 4 * CELL, dtype=np.uint8)
    groups = _write_key(cluster, data)
    g = groups[0]
    for u in range(3):  # kill 3 of 5 units: only 2 remain < k=3
        dn = next(d for d in cluster.dns if d.id == g.pipeline.nodes[u])
        dn.delete_block(g.block_id)
    with pytest.raises(InsufficientLocationsError):
        cluster.reader(g).recover_cells([0, 1, 2])


def test_recover_cells_targeted(cluster):
    rng = np.random.default_rng(2)
    data = rng.integers(0, 256, 6 * CELL, dtype=np.uint8)  # 2 full stripes
    groups = _write_key(cluster, data)
    g = groups[0]
    # recover data unit 1 and parity unit 4 without killing anything
    rec = cluster.reader(g).recover_cells([1, 4])
    assert rec.shape == (2, 2, CELL)
    # expected data cells of unit 1: stripe s covers data[s*3*C + 1*C : +C]
    for s in range(2):
        expect = data[s * 3 * CELL + CELL : s * 3 * CELL + 2 * CELL]
        assert np.array_equal(rec[s, 0], expect)
    # parity unit must equal freshly encoded parity
    from ozone_tpu.codec import create_encoder

    stripes = data.reshape(2, 3, CELL)
    parity = create_encoder(OPTS, "numpy").encode(stripes)
    assert np.array_equal(rec[:, 1, :], parity[:, 1, :])


class FlakyClient(LocalDatanodeClient):
    """Fails the first `n_failures` write_chunk calls."""

    def __init__(self, dn, n_failures=1):
        super().__init__(dn)
        self.n_failures = n_failures

    def write_chunk(self, block_id, info, data, sync=False, writer=None):
        if self.n_failures > 0:
            self.n_failures -= 1
            raise StorageError("IO_EXCEPTION", "injected failure")
        return super().write_chunk(block_id, info, data, sync, writer=writer)


def test_write_failure_rolls_to_new_group(cluster):
    # make dn0 fail once: the first stripe write fails, the writer must
    # exclude dn0, allocate a new group, and replay
    cluster.clients._local["dn0"] = FlakyClient(cluster.dns[0], n_failures=1)
    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, 5 * CELL, dtype=np.uint8)
    groups = _write_key(cluster, data)
    assert all("dn0" not in g.pipeline.nodes for g in groups[0:1]) or len(
        cluster.allocated
    ) > len(groups)
    got = _read_key(cluster, groups)
    assert np.array_equal(got, data)


def test_checksums_stored_and_verified(cluster):
    rng = np.random.default_rng(4)
    data = rng.integers(0, 256, 3 * CELL, dtype=np.uint8)
    groups = _write_key(cluster, data)
    g = groups[0]
    dn = next(d for d in cluster.dns if d.id == g.pipeline.nodes[0])
    bd = dn.get_block(g.block_id)
    assert bd.chunks[0].checksum.checksums  # device CRCs persisted
    assert bd.block_group_length == data.size
    # corrupt unit 0 on disk; verified read must fall back to reconstruction
    path = dn.get_container(g.container_id).chunks.block_path(g.block_id)
    raw = bytearray(path.read_bytes())
    raw[10] ^= 0xFF
    path.write_bytes(bytes(raw))
    got = cluster.reader(g).read_all()
    assert np.array_equal(got, data)


class FlakyPutBlockClient(LocalDatanodeClient):
    """Fails put_block call number `fail_call` (0-based; chunks always
    succeed), so a chosen stripe's commit phase fails mid-flight."""

    def __init__(self, dn, fail_call=1):
        super().__init__(dn)
        self.fail_call = fail_call
        self.calls = 0

    def put_block(self, block, sync=False, writer=None):
        me = self.calls
        self.calls += 1
        if me == self.fail_call:
            raise StorageError("IO_EXCEPTION", "injected putBlock failure")
        return super().put_block(block, sync, writer=writer)


def _assert_no_inflated_survivors(cluster, groups):
    """Every datanode holding a finalized group must agree on its
    committed length (datanode metadata is what offline reconstruction
    trusts — no unit may report bytes the client never acked)."""
    first = cluster.allocated[0]
    if first.length and first is not groups[-1]:
        for u, dn_id in enumerate(first.pipeline.nodes):
            dn = next(d for d in cluster.dns if d.id == dn_id)
            try:
                bd = dn.get_block(first.block_id)
            except StorageError:
                continue  # failed node holds no commit: fine
            assert bd.block_group_length == first.length, \
                f"unit {u} on {dn_id} reports inflated group length " \
                f"{bd.block_group_length} != {first.length}"


def test_putblock_failure_rolls_back_survivor_commits(cluster):
    """Per-stripe path: a putBlock failure mid-stripe must not leave
    OTHER datanodes committed at the inflated group length — the
    concurrently dispatched putBlocks roll back to the pre-stripe
    watermark."""
    cluster.clients._local["dn0"] = FlakyPutBlockClient(
        cluster.dns[0], fail_call=1)  # stripe 0 commits; stripe 1 fails
    rng = np.random.default_rng(13)
    # two stripes: stripe 0 commits, stripe 1's putBlock fails on dn0
    # and replays into a fresh group after rollover
    data = rng.integers(0, 256, 2 * 3 * CELL, dtype=np.uint8)
    groups = _write_key(cluster, data, batched_rpc=False)
    got = _read_key(cluster, groups)
    assert np.array_equal(got, data)
    _assert_no_inflated_survivors(cluster, groups)


def test_batched_run_commit_failure_rolls_back_survivors(cluster):
    """Batched-RPC path: the run's piggybacked commit fails on one
    unit while the other units' streams committed the run-end record —
    survivors must roll back to the pre-run watermark and the run
    replays into a fresh group."""
    cluster.clients._local["dn0"] = FlakyPutBlockClient(
        cluster.dns[0], fail_call=0)  # the run's only commit fails
    rng = np.random.default_rng(17)
    data = rng.integers(0, 256, 2 * 3 * CELL, dtype=np.uint8)
    groups = _write_key(cluster, data)
    got = _read_key(cluster, groups)
    assert np.array_equal(got, data)
    _assert_no_inflated_survivors(cluster, groups)


class _NoStreamClient(LocalDatanodeClient):
    """A member without the WriteChunksCommit verb (pre-finalize layout
    / older server): refuses the batch, serves the per-chunk verbs."""

    calls = 0

    def write_chunks_commit(self, block_id, chunks, commit=None,
                            sync=False, writer=None):
        _NoStreamClient.calls += 1
        raise StorageError("NOT_SUPPORTED_OPERATION_PRIOR_FINALIZATION",
                           "WriteChunksCommit needs layout feature")


class _FlakyCombinedClient(LocalDatanodeClient):
    """Fails combined chunk+commit call number `fail_call` (0-based)."""

    def __init__(self, dn, fail_call=1):
        super().__init__(dn)
        self.fail_call = fail_call
        self.calls = 0

    def write_chunks_commit(self, block_id, chunks, commit=None,
                            sync=False, writer=None):
        me = self.calls
        self.calls += 1
        if me == self.fail_call:
            raise StorageError("IO_EXCEPTION", "injected combined failure")
        return super().write_chunks_commit(block_id, chunks, commit,
                                           sync, writer)


def test_replicated_combined_partial_failure_rolls_back_survivors(cluster):
    """A member failing the combined chunk+commit call must not leave
    the OTHER members committed with the unacked chunk (the split path
    never commits until every member took the data; replicas must not
    disagree on committed length)."""
    from ozone_tpu.client.replicated import (
        ReplicatedKeyReader,
        ReplicatedKeyWriter,
    )

    cluster.clients._local["dn2"] = _FlakyCombinedClient(
        cluster.dns[2], fail_call=1)  # chunk 0 lands; chunk 1 fails

    def allocate(excluded, ec=()):
        g = cluster.allocate(excluded)
        g.pipeline.nodes = g.pipeline.nodes[:3]
        return g

    w = ReplicatedKeyWriter(allocate, cluster.clients,
                            block_size=8 * CELL, chunk_size=CELL)
    rng = np.random.default_rng(29)
    data = rng.integers(0, 256, 2 * CELL, dtype=np.uint8)
    w.write(data)
    groups = w.close()
    got = np.concatenate(
        [ReplicatedKeyReader(g, cluster.clients).read_all()
         for g in groups])
    assert np.array_equal(got, data)
    # the first group finalized at chunk 0 only; the survivors that
    # took chunk 1's combined call must have rolled back to one chunk
    first = cluster.allocated[0]
    assert first.length == CELL
    for dn_id in first.pipeline.nodes[:2]:
        dn = next(d for d in cluster.dns if d.id == dn_id)
        bd = dn.get_block(first.block_id)
        assert len(bd.chunks) == 1, \
            f"{dn_id} kept the unacked chunk after rollback"


def test_replicated_writer_combined_commit_downgrade(cluster):
    """The replicated writer's combined chunk+commit fan-out downgrades
    to split phases when a member lacks the verb, with byte-exact data
    and no member excluded."""
    from ozone_tpu.client.replicated import (
        ReplicatedKeyReader,
        ReplicatedKeyWriter,
    )

    _NoStreamClient.calls = 0
    cluster.clients._local["dn1"] = _NoStreamClient(cluster.dns[1])

    def allocate(excluded, ec=()):
        g = cluster.allocate(excluded)
        g.pipeline.nodes = g.pipeline.nodes[:3]  # THREE-replica pipeline
        return g

    w = ReplicatedKeyWriter(allocate, cluster.clients,
                            block_size=8 * CELL, chunk_size=CELL)
    rng = np.random.default_rng(23)
    data = rng.integers(0, 256, 5 * CELL + 11, dtype=np.uint8)
    w.write(data)
    groups = w.close()
    assert w._combined_commit is False
    assert _NoStreamClient.calls == 1  # probed once, never again
    assert w._excluded == []
    assert sum(g.length for g in groups) == data.size
    got = np.concatenate(
        [ReplicatedKeyReader(g, cluster.clients).read_all()
         for g in groups])
    assert np.array_equal(got, data)


def test_mixed_version_member_falls_back_to_per_stripe(cluster):
    """One pipeline member refusing the batched verb downgrades the
    writer to per-stripe RPCs for the rest of the write (the
    allDataNodesSupportPiggybacking downgrade) — with a clean rollback,
    no reallocation, and byte-exact data."""
    _NoStreamClient.calls = 0
    cluster.clients._local["dn1"] = _NoStreamClient(cluster.dns[1])
    rng = np.random.default_rng(19)
    data = rng.integers(0, 256, 6 * 3 * CELL + 7, dtype=np.uint8)
    w = cluster.writer()
    w.write(data)
    groups = w.close()
    assert w._stream_writes is False
    assert _NoStreamClient.calls == 1  # probed once, never again
    assert sum(g.length for g in groups) == data.size
    got = _read_key(cluster, groups)
    assert np.array_equal(got, data)
    # the downgrade is not a node failure: nobody was excluded
    assert w._excluded == []
