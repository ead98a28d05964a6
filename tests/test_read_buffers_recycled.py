"""An EC read's two buffers are leases of the host buffer pool.

The key's one buffer (the user's answer) and each survivor batch a
recovery decodes from come out of `codec/hostmem.py`'s pool and go back
to it when their last array dies. A recycled buffer holds ANOTHER key's
bytes, so held here: with every pool buffer poisoned at its lease every
read and repair is byte- and CRC-exact; a warm pool serves the next
reads without mapping a byte; a buffer a user still sees, or a reader
thread still writes, is never handed out again.
"""

import gc
import logging
import threading
import time

import numpy as np
import pytest

from ozone_tpu.client import resilience
from ozone_tpu.client.ec_reader import OPS
from ozone_tpu.codec import hostmem
from ozone_tpu.codec.api import CoderOptions
from ozone_tpu.net import partition
from ozone_tpu.storage.ids import StorageError
from ozone_tpu.utils.checksum import Checksum, ChecksumType
from ozone_tpu.utils.tracing import Tracer
from tests.test_ec_pipeline import CELL
from tests.test_ec_read_once import BPC, RANGES, RS32, _lose, _one_group
from tests.test_resilience import _SlowClient

POISON = 0xA5
SCHEMES = {"rs-6-3": (6, 3), "rs-10-4": (10, 4)}


@pytest.fixture
def poisoned(monkeypatch):
    """Every lease of the pool, recycled or fresh, is handed out full of
    0xA5 up to its class's last byte: a byte the read did not write
    shows in the answer."""
    lease = hostmem.HostBufferPool.lease

    def poisoned_lease(self, n):
        got = lease(self, n)
        np.frombuffer(got._mm, dtype=np.uint8).fill(POISON)
        return got

    monkeypatch.setattr(hostmem.HostBufferPool, "lease", poisoned_lease)


@pytest.fixture
def leases(monkeypatch):
    """(bytes, address) of every array `lease_array` hands out, in
    order; no reference kept."""
    seen: list[tuple[int, int]] = []
    lease_array = hostmem.HostBufferPool.lease_array

    def recording(self, n):
        arr, fresh = lease_array(self, n)
        seen.append((n, arr.ctypes.data))
        return arr, fresh

    monkeypatch.setattr(hostmem.HostBufferPool, "lease_array", recording)
    return seen


def _leased() -> int:
    gc.collect()
    return hostmem.pool().stats()["leased_count"]


def _base() -> int:
    """The pool's leases once no reader thread an EARLIER test orphaned
    (a straggler still asleep in its read) is left to give one back."""
    t_end = time.monotonic() + 30
    while any(t.name.startswith("ec-read") for t in threading.enumerate()):
        assert time.monotonic() < t_end, "reader threads never ended"
        time.sleep(0.05)
    return _leased()


def _settled(base: int, timeout_s: float = 10.0) -> bool:
    """True once the pool's leases are back at `base` (an orphaned
    reader thread lets go of its batch when it is done)."""
    t_end = time.monotonic() + timeout_s
    while _leased() != base:
        if time.monotonic() > t_end:
            return False
        time.sleep(0.02)
    return True


# ----------------------------------------------------- poison: whole keys
@pytest.mark.parametrize("lost", [(), (1,), (0, 3)],
                         ids=["healthy", "1lost", "2lost"])
@pytest.mark.parametrize("scheme", list(SCHEMES))
def test_whole_key_is_exact_out_of_poisoned_buffers(tmp_path, poisoned,
                                                    scheme, lost):
    """11 stripes and a short twelfth: two decode batches, a short
    cell and an empty one, each written over poison."""
    k, p = SCHEMES[scheme]
    opts = CoderOptions(k, p, "rs", cell_size=CELL)
    cluster, g, data = _one_group(tmp_path, opts, 11, tail=CELL + 17,
                                  seed=k)
    try:
        _lose(cluster, g, lost)
        for _ in range(2):  # the second read's buffers are recycled
            got = cluster.reader(g).read_all()
            assert np.array_equal(got, data)
            del got
    finally:
        cluster.close()


# ------------------------------------------------ poison: the seven shapes
@pytest.mark.parametrize("degraded", [False, True],
                         ids=["healthy", "degraded"])
@pytest.mark.parametrize("name", list(RANGES))
def test_ranged_read_is_exact_out_of_poisoned_buffers(tmp_path, poisoned,
                                                      name, degraded):
    tail, lost, offset, length, _n_rec = RANGES[name]
    cluster, g, data = _one_group(tmp_path, RS32, 3, tail=tail, seed=7)
    try:
        if length is None:
            length = data.size - offset
        if degraded:
            _lose(cluster, g, lost)
        for _ in range(2):
            got = cluster.reader(g).read(offset, length)
            assert np.array_equal(got, data[offset:offset + length])
            del got
    finally:
        cluster.close()


# ------------------------------------------------------ poison: the repair
def _assert_repair_exact(opts, data, stripes, targets, yielded) -> None:
    """Every recovered data cell against the key (zero-padded where the
    key ends inside or before it), every cell's device CRCs against the
    host's of the same bytes."""
    k = opts.data_units
    padded = np.zeros(stripes * k * CELL, dtype=np.uint8)
    padded[:data.size] = data
    truth = padded.reshape(stripes, k, CELL)
    host = Checksum(ChecksumType.CRC32C, BPC)
    seen = []
    for sb, (rec, crcs) in yielded:
        seen.extend(sb)
        assert rec.shape == (len(sb), len(targets), CELL)
        for bi, s in enumerate(sb):
            for ti, u in enumerate(targets):
                if u < k:
                    assert np.array_equal(rec[bi, ti], truth[s, u]), (s, u)
                want = tuple(int(v).to_bytes(4, "big")
                             for v in crcs[bi, ti].tolist())
                assert want == host.compute(rec[bi, ti]).checksums, (s, u)
    assert seen == list(range(stripes))


@pytest.mark.parametrize("targets", [[1], [0, 3]], ids=["1lost", "2lost"])
@pytest.mark.parametrize("scheme", list(SCHEMES))
def test_repair_stream_is_exact_out_of_poisoned_buffers(tmp_path, poisoned,
                                                        scheme, targets):
    """`recover_cells_iter(targets)` as `storage/reconstruction.py`
    calls it: no callback, every stripe, two decode batches."""
    k, p = SCHEMES[scheme]
    opts = CoderOptions(k, p, "rs", cell_size=CELL)
    cluster, g, data = _one_group(tmp_path, opts, 11, seed=20 + k)
    try:
        _lose(cluster, g, targets)
        for _ in range(2):
            _assert_repair_exact(opts, data, 11, targets, list(
                cluster.reader(g).recover_cells_iter(targets)))
    finally:
        cluster.close()


@pytest.mark.parametrize("targets", [[0], [1], [2], [1, 4]],
                         ids=["full_cell", "short_cell", "empty_cell",
                              "short_and_parity"])
def test_repair_of_a_short_last_stripe_out_of_poisoned_buffers(
        tmp_path, poisoned, targets):
    """The last stripe holds one whole cell, 17 bytes of the second and
    nothing of the third: the survivors' short and absent cells must
    come zero-padded into a batch that held poison."""
    cluster, g, data = _one_group(tmp_path, RS32, 3, tail=CELL + 17,
                                  seed=31)
    try:
        _lose(cluster, g, targets)
        for _ in range(2):
            _assert_repair_exact(RS32, data, 4, targets, list(
                cluster.reader(g).recover_cells_iter(targets)))
    finally:
        cluster.close()


# ----------------------------------------------- poison: through the client
@pytest.mark.parametrize("degraded", [False, True],
                         ids=["healthy", "degraded"])
def test_key_of_several_groups_through_the_client(tmp_path, poisoned,
                                                  degraded):
    """`OzoneBucket._read_groups_range`: the groups write into slices
    of one leased buffer; a range the groups do not cover raises and
    leaks no lease."""
    from ozone_tpu.testing.minicluster import MiniOzoneCluster

    row = 3 * CELL
    c = MiniOzoneCluster(tmp_path, num_datanodes=6, block_size=4 * CELL,
                         container_size=1024 * 1024,
                         stale_after_s=1000.0, dead_after_s=2000.0)
    try:
        oz = c.client()
        oz.clients.health = resilience.HealthRegistry(hedge_floor_s=30.0)
        b = oz.create_volume("v").create_bucket(
            "b", replication=f"rs-3-2-{CELL}")
        data = np.random.default_rng(41).integers(
            0, 256, 9 * row + CELL + 17, dtype=np.uint8)
        b.write_key("k", data)
        info = oz.om.lookup_key("v", "b", "k")
        assert len(info["block_groups"]) == 3
        if degraded:
            g = c.om.key_block_groups(info)[1]
            c.datanode(g.pipeline.nodes[0]).delete_block(g.block_id)
        for _ in range(2):
            got = b.read_key_info(info)
            assert np.array_equal(got, data)
            del got
            # over the seam of two groups, and the short tail
            got = b.read_key_info_range(info, 4 * row - 100, row)
            assert np.array_equal(got, data[4 * row - 100:5 * row - 100])
            got = b.read_key_info_range(info, 8 * row + 5, row + CELL + 12)
            assert np.array_equal(got, data[8 * row + 5:])
            del got
        # the record lost its last group: the range is not covered
        short = dict(info, block_groups=info["block_groups"][:2])
        base = _base()
        with pytest.raises(StorageError) as ei:
            b.read_key_info(short)
        assert ei.value.code == "IO_EXCEPTION"
        del ei
        assert _leased() == base
    finally:
        c.close()


# ------------------------------------------------- a warm pool maps nothing
class _Rendezvous:
    """Holds the first `parties` calls of `read_chunks` on one datanode
    until all have arrived: every reader is then inside its fan-out at
    once, its key buffer and its survivor batch leased."""

    def __init__(self, inner, parties: int):
        self._inner = inner
        self.dn_id = inner.dn_id
        self._barrier = threading.Barrier(parties)
        self._left = parties
        self._lock = threading.Lock()

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def read_chunks(self, block_id, infos, verify=False):
        with self._lock:
            wait, self._left = self._left > 0, self._left - 1
        if wait:
            self._barrier.wait(timeout=60)
        return self._inner.read_chunks(block_id, infos, verify)


@pytest.mark.parametrize("lost", [(), (0,), (0, 2)],
                         ids=["healthy", "1lost", "2lost"])
def test_after_a_warm_get_eight_readers_lease_no_fresh_byte(tmp_path, lost):
    """The pool at its defaults, eight readers at once: after each has
    done one GET (all eight in flight together), five more rounds of the
    same shape recycle every buffer: `pool_fresh_bytes` does not move
    and every lease counts as recycled."""
    readers, rounds = 8, 5
    opts = CoderOptions(6, 3, "rs", cell_size=CELL)
    # 8 stripes: one whole-width decode batch a GET, launched from the
    # reader's own rows (no staging buffer of the codec service's)
    cluster, g, data = _one_group(tmp_path, opts, 8, seed=51)
    fresh = hostmem.METRICS.counter("pool_fresh_bytes")
    n_leases = hostmem.METRICS.counter("pool_leases")
    recycled = hostmem.METRICS.counter("pool_leases_recycled")
    try:
        _lose(cluster, g, lost)
        cluster.reader(g).read_all()  # compile the decode shape
        dn_id = g.pipeline.nodes[1]  # a data unit every plan reads
        cluster.clients._local[dn_id] = _Rendezvous(
            cluster.clients.get(dn_id), readers)
        between = threading.Barrier(readers)
        marks: list[tuple[int, int, int, float]] = []
        errors: list[BaseException] = []

        def reader_thread(i: int) -> None:
            try:
                for r in range(1 + rounds):
                    got = cluster.reader(g).read_all()
                    assert np.array_equal(got, data)
                    del got
                    # every reader has let go before the next round
                    if between.wait(timeout=60) == 0:
                        marks.append((fresh.value, n_leases.value,
                                      recycled.value, time.monotonic()))
                    between.wait(timeout=60)
            except BaseException as e:  # noqa: BLE001 - reported below
                errors.append(e)
                between.abort()

        threads = [threading.Thread(target=reader_thread, args=(i,))
                   for i in range(readers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors
        assert not any(t.is_alive() for t in threads)
        warm, last = marks[0], marks[-1]
        assert last[0] - warm[0] == 0, "a warm pool mapped fresh memory"
        assert last[1] - warm[1] == last[2] - warm[2] > 0
        spans = [s for s in Tracer.instance().spans
                 if s.name == "ec:read" and s.mono >= warm[3]]
        assert len(spans) == readers * rounds
        assert {s.tags["fresh_bytes"] for s in spans} == {0}
    finally:
        cluster.close()


# ------------------------------------------- what a user holds stays theirs
def test_a_users_answer_is_not_leased_again_while_any_view_lives(tmp_path):
    cluster, g, data = _one_group(tmp_path, RS32, 3, seed=61)
    pool = hostmem.pool()
    try:
        _lose(cluster, g, (0,))
        base = _base()
        got = cluster.reader(g).read_all()
        addr = got.ctypes.data
        view = got[CELL + 3:2 * CELL]
        del got
        assert _leased() == base + 1, "the view pins the key's buffer"
        # whatever the pool hands out now is other memory: scribbling
        # over it leaves the user's bytes alone
        others = [pool.lease_array(data.size)[0] for _ in range(4)]
        for o in others:
            assert o.ctypes.data != addr
            o.fill(POISON)
        assert np.array_equal(view, data[CELL + 3:2 * CELL])
        del others, o
        del view
        assert _leased() == base
        # given back last, handed out first: the same pages, recycled
        again, fresh = pool.lease_array(data.size)
        assert again.ctypes.data == addr and not fresh
    finally:
        cluster.close()


# -------------------------------------- an abandoned batch and its writers
@pytest.mark.parametrize("transport", ["copied", "in_place"])
def test_a_hedged_stragglers_batch_waits_for_its_late_writer(
        tmp_path, leases, transport):
    """A survivor straggles, the recovery hedges to a spare and the read
    returns; the straggler's reader thread is still to write its cells
    into the abandoned batch: by copies out of its late answer, or, over
    the native datapath, by a late receive straight into the batch's
    rows. Until it has, those pages are nobody else's."""
    straggle_s = 1.5
    if transport == "in_place":
        from ozone_tpu.storage.fast_datapath import load_lib
        from tests.test_read_in_place import _group

        if load_lib() is None:
            pytest.skip("no native toolchain")
        cluster, g, data = _group(tmp_path, RS32, "native", stripes=3,
                                  seed=71)
    else:
        cluster, g, data = _one_group(tmp_path, RS32, 3, seed=71)
    pool = hostmem.pool()
    in_place = OPS.counter("survivor_cells_in_place")
    try:
        _lose(cluster, g, (0,))
        cluster.reader(g).read_all()  # compile the decode shapes first
        base = _base()
        victim = g.pipeline.nodes[1]
        if transport == "in_place":
            partition.add_rule(
                dst=cluster.clients.remote_address(victim),
                verb="ReadChunks", delay_s=straggle_s)
        else:
            cluster.clients._local[victim] = _SlowClient(
                cluster.clients.get(victim), straggle_s)
        cluster.clients.health = resilience.HealthRegistry()
        del leases[:]
        t0 = time.monotonic()
        r = cluster.reader(g)
        got = r.read_all()
        assert time.monotonic() - t0 < straggle_s
        assert 1 in r._failed
        assert np.array_equal(got, data)
        # the key's buffer, the abandoned batch, the retry's batch
        (_, a_out), (n_batch, a_abandoned), (_, a_retry) = leases
        assert len({a_out, a_abandoned, a_retry}) == 3
        assert _leased() == base + 2, "the answer and the abandoned batch"
        received = in_place.value
        mine = [pool.lease_array(n_batch)[0] for _ in range(4)]
        for m in mine:
            assert m.ctypes.data != a_abandoned
            m.fill(POISON)
        # the straggler writes, late, and lets go
        assert _settled(base + 1 + len(mine)), "the batch never came back"
        assert time.monotonic() - t0 >= straggle_s
        if transport == "in_place":
            # its three cells landed in the abandoned batch's own rows
            assert in_place.value - received == 3
        assert np.array_equal(got, data)
        assert all((m == POISON).all() for m in mine)
        del mine, m, got
        assert _leased() == base
    finally:
        partition.clear()
        cluster.close()


class _DiesMidFill:
    """Serves its first cell and fails every later one."""

    def __init__(self, inner):
        self._inner = inner
        self.dn_id = inner.dn_id

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def read_chunk(self, block_id, info, verify=False):
        if info.offset > 0:
            raise StorageError("UNAVAILABLE", "injected fault")
        return self._inner.read_chunk(block_id, info, verify)

    def read_chunks(self, block_id, infos, verify=False):
        return [self.read_chunk(block_id, i, verify) for i in infos]


def test_a_batch_abandoned_by_a_dying_unit_waits_for_a_slow_one(
        tmp_path, leases, monkeypatch):
    """One survivor dies mid-fill while another is still reading: the
    error abandons the batch at the hedge delay, the retry's batch is
    leased while the slow reader thread has yet to write into the
    abandoned one, and is other memory."""
    slow_s = 0.8
    # pytest keeps every log record to the test's end, and the warning
    # of the unit's failure holds the error, its traceback and so the
    # dead reader thread's frames, the batch among them
    monkeypatch.setattr(logging.getLogger("ozone_tpu.client.ec_reader"),
                        "disabled", True)
    cluster, g, data = _one_group(tmp_path, RS32, 3, seed=81)
    try:
        _lose(cluster, g, (0,))
        cluster.reader(g).read_all()  # compile the decode shapes first
        base = _base()
        nodes = g.pipeline.nodes
        cluster.clients._local[nodes[3]] = _DiesMidFill(
            cluster.clients.get(nodes[3]))
        slow = cluster.clients._local[nodes[2]] = _SlowClient(
            cluster.clients.get(nodes[2]), slow_s)
        cluster.clients.health = resilience.HealthRegistry()
        del leases[:]
        r = cluster.reader(g)
        got = r.read_all()
        assert 3 in r._failed and 2 not in r._failed
        assert slow.read_calls >= 2, "the slow unit was read by both plans"
        assert np.array_equal(got, data)
        (_, a_out), (_, a_abandoned), (_, a_retry) = leases
        assert len({a_out, a_abandoned, a_retry}) == 3
        del got
        assert _settled(base), "the abandoned batch never came back"
    finally:
        cluster.close()
