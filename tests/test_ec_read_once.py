"""A degraded GET reads each surviving cell once and assembles the key once.

The recovery's survivor batches hold the live data cells it decoded from:
a read copies them to its output from there and fetches only what the
recovery did not read, and a key's groups write into slices of one
buffer. Held here over in-process datanodes with a client that counts
what is asked of it.
"""

import time

import numpy as np
import pytest

from ozone_tpu.client import resilience
from ozone_tpu.client.ec_reader import OPS
from ozone_tpu.codec import hostmem
from ozone_tpu.codec.api import CoderOptions
from ozone_tpu.storage.ids import StorageError
from ozone_tpu.utils.checksum import Checksum, ChecksumType
from ozone_tpu.utils.tracing import Tracer
from tests.test_ec_pipeline import CELL, MiniEC, _write_key
from tests.test_resilience import _SlowClient

BPC = 1024  # MiniEC's bytes per checksum


class _CountingClient:
    """Passes every verb through and logs each chunk a read asks for as
    (datanode, offset in the unit's block, length)."""

    def __init__(self, inner, asked: list):
        self._inner = inner
        self.dn_id = inner.dn_id
        self.asked = asked

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def read_chunk(self, block_id, info, verify=False):
        self.asked.append((self.dn_id, info.offset, info.length))
        return self._inner.read_chunk(block_id, info, verify)

    def read_chunks(self, block_id, infos, verify=False):
        self.asked.extend((self.dn_id, i.offset, i.length) for i in infos)
        return self._inner.read_chunks(block_id, infos, verify)


def _count_reads(cluster) -> list:
    asked: list = []
    for dn_id, c in list(cluster.clients._local.items()):
        cluster.clients._local[dn_id] = _CountingClient(c, asked)
    return asked


def _lose(cluster, g, units) -> None:
    for u in units:
        dn = next(d for d in cluster.dns if d.id == g.pipeline.nodes[u])
        dn.delete_block(g.block_id)


def _ops() -> dict:
    return {n: OPS.counter(n).value
            for n in ("get_cells_reused", "get_cells_fetched",
                      "get_wire_bytes")}


def _last_read_span():
    return [s for s in Tracer.instance().spans if s.name == "ec:read"][-1]


def _one_group(tmp_path, opts, stripes, tail=0, seed=0):
    """One block group of `stripes` whole stripes and `tail` bytes more."""
    cluster = MiniEC(tmp_path, n_dn=opts.all_units + 1, opts=opts)
    # counts of what is asked hold only while no hedge fires: a floor
    # no loaded test host reaches (the straggler test sets its own)
    cluster.clients.health = resilience.HealthRegistry(hedge_floor_s=30.0)
    data = np.random.default_rng(seed).integers(
        0, 256, stripes * opts.data_units * CELL + tail, dtype=np.uint8)
    (g,) = _write_key(cluster, data, block_size=(stripes + 1) * CELL)
    return cluster, g, data


# ------------------------------------------------------- each cell once
@pytest.mark.parametrize("lost", [(1,), (0, 3)], ids=["1lost", "2lost"])
@pytest.mark.parametrize("scheme", [(6, 3), (10, 4)],
                         ids=["rs-6-3", "rs-10-4"])
def test_whole_key_asks_each_surviving_cell_once(tmp_path, scheme, lost):
    """The cell's traffic: a whole-key GET with data units lost. Every
    datanode is asked for each of its cells at most once, and what is
    asked for is what the answer needs: k cells a stripe."""
    k, p = scheme
    opts = CoderOptions(k, p, "rs", cell_size=CELL)
    stripes = 11  # two decode batches (8 + 3)
    cluster, g, data = _one_group(tmp_path, opts, stripes, seed=k)
    try:
        _lose(cluster, g, lost)
        asked = _count_reads(cluster)
        before = _ops()
        got = cluster.reader(g).read_all()
        assert np.array_equal(got, data)
        assert len(set(asked)) == len(asked), "a cell was asked for twice"
        wire = sum(n for _, _, n in asked)
        assert wire <= 1.05 * data.size, (wire, data.size)
        delta = {n: v - before[n] for n, v in _ops().items()}
        live = k - len(lost)
        assert delta == {"get_cells_reused": live * stripes,
                         "get_cells_fetched": len(asked),
                         "get_wire_bytes": wire}
        tags = _last_read_span().tags
        assert tags["cells_reused"] == live * stripes
        assert tags["cells_fetched"] == k * stripes
    finally:
        cluster.close()


# ------------------------------------------------ byte-exact, every shape
RS32 = CoderOptions(3, 2, "rs", cell_size=CELL)
ROW = 3 * CELL

#: name -> (tail bytes after 3 whole stripes, lost units, offset, length
#: (None: to the end), stripes that need recovery)
RANGES = {
    "off_the_missing_cell": (0, (1,), 10, CELL - 20, 0),
    "ends_inside_missing_cell": (0, (1,), 100, CELL + 200, 1),
    "starts_inside_missing_cell": (0, (1,), ROW + CELL + 7, ROW + 100, 2),
    "short_last_stripe_whole_key": (CELL + 17, (0,), 0, None, 4),
    "short_last_stripe_lost_short_cell": (CELL + 17, (1,), 0, None, 4),
    "short_last_stripe_lost_empty_cell": (CELL + 17, (2,), 0, None, 3),
    "parity_lost_too": (0, (2, 3), 5, 2 * ROW, 3),
}


@pytest.mark.parametrize("name", list(RANGES))
def test_ranged_degraded_read_exact_and_once(tmp_path, name):
    tail, lost, offset, length, n_rec = RANGES[name]
    cluster, g, data = _one_group(tmp_path, RS32, 3, tail=tail, seed=7)
    try:
        if length is None:
            length = data.size - offset
        _lose(cluster, g, lost)
        asked = _count_reads(cluster)
        r = cluster.reader(g)
        if n_rec == 0:
            def boom(*a, **kw):
                raise AssertionError("range off the missing unit must "
                                     "not trigger recovery")
            r.recover_cells_iter = boom
        got = r.read(offset, length)
        assert np.array_equal(got, data[offset:offset + length])
        assert len(set(asked)) == len(asked), "a cell was asked for twice"
        # only the covering stripes moved: at most k cells of each
        s0, s1 = offset // ROW, (offset + length - 1) // ROW
        assert len(asked) <= 3 * (s1 - s0 + 1)
        assert all(s0 * CELL <= off <= s1 * CELL for _, off, _ in asked)
        tags = _last_read_span().tags
        assert (tags["cells_reused"] > 0) == (n_rec > 0), tags
    finally:
        cluster.close()


def test_read_into_callers_buffer(tmp_path):
    """`out=` is written in place and returned; a buffer of the wrong
    size, type or a read-only one is refused before any read."""
    cluster, g, data = _one_group(tmp_path, RS32, 3, seed=3)
    try:
        _lose(cluster, g, (0,))
        key = np.full(data.size + 10, 0xEE, dtype=np.uint8)
        dst = key[5:5 + 2 * ROW]
        ret = cluster.reader(g).read(CELL, 2 * ROW, out=dst)
        assert ret is dst
        assert np.array_equal(dst, data[CELL:CELL + 2 * ROW])
        assert (key[:5] == 0xEE).all() and (key[5 + 2 * ROW:] == 0xEE).all()
        frozen = np.zeros(ROW, np.uint8)
        frozen.flags.writeable = False
        for bad in (np.zeros(ROW - 1, np.uint8), np.zeros(ROW, np.int8),
                    np.zeros((1, ROW), np.uint8), frozen):
            with pytest.raises(ValueError):
                cluster.reader(g).read(0, ROW, out=bad)
    finally:
        cluster.close()


def test_lrc_key_reuses_the_local_groups_cells(tmp_path):
    """An LRC local repair reads the lost unit's group alone: its data
    cells are reused, the other group's are fetched, none twice."""
    opts = CoderOptions(12, 4, "lrc", cell_size=CELL, local_groups=2)
    cluster, g, data = _one_group(tmp_path, opts, 3, tail=777, seed=13)
    try:
        _lose(cluster, g, (4,))
        asked = _count_reads(cluster)
        got = cluster.reader(g).read_all()
        assert np.array_equal(got, data)
        assert len(set(asked)) == len(asked), "a cell was asked for twice"
        tags = _last_read_span().tags
        # group 0 is units 0-5: five live data cells in each of the 3
        # whole stripes; the short fourth stripe has no byte of unit 4,
        # is not recovered, and its one cell with data is fetched
        assert tags["cells_reused"] == 5 * 3
        assert tags["cells_fetched"] == len(asked)
    finally:
        cluster.close()


# ------------------------------------------------------- retries, hedges
class _LiarThenDead:
    """A survivor that serves WRONG bytes for its first cells and fails
    every read past `dead_from`: what an unverified read copied out of
    an abandoned attempt must not outlive the replan."""

    def __init__(self, inner, dead_from: int):
        self._inner = inner
        self.dn_id = inner.dn_id
        self.dead_from = dead_from

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def read_chunk(self, block_id, info, verify=False):
        if info.offset >= self.dead_from:
            raise StorageError("UNAVAILABLE", "injected fault")
        return np.asarray(
            self._inner.read_chunk(block_id, info, verify)) ^ 0x5A

    def read_chunks(self, block_id, infos, verify=False):
        return [self.read_chunk(block_id, i, verify) for i in infos]


@pytest.mark.parametrize("liar", [1, 3], ids=["data_unit", "parity_unit"])
def test_survivor_failing_mid_recovery_leaves_nothing_behind(tmp_path, liar):
    """The first decode batch reads the liar's cells (and for a data
    unit copies them to the output); the second batch finds it dead. The
    replan excludes it, and the result is exact: nothing the abandoned
    attempt wrote stays."""
    cluster, g, data = _one_group(tmp_path, RS32, 11, seed=5)
    try:
        _lose(cluster, g, (0,))
        dn_id = g.pipeline.nodes[liar]
        cluster.clients._local[dn_id] = _LiarThenDead(
            cluster.clients.get(dn_id), dead_from=8 * CELL)
        r = cluster.reader(g, verify=False)
        got = r.read_all()
        assert liar in r._failed
        assert np.array_equal(got, data)
    finally:
        cluster.close()


def test_straggling_survivor_is_hedged_and_the_read_exact(tmp_path):
    """A survivor data unit that straggles inside the recovery is
    dropped for the spare parity unit; the retry reconstructs it with
    the lost unit and the read is exact, long before the straggler
    answers."""
    straggle_s = 2.5
    cluster, g, data = _one_group(tmp_path, RS32, 3, seed=9)
    try:
        _lose(cluster, g, (0,))
        cluster.reader(g).read_all()  # compile the decode shapes first
        victim = g.pipeline.nodes[1]
        cluster.clients._local[victim] = _SlowClient(
            cluster.clients.get(victim), straggle_s)
        cluster.clients.health = resilience.HealthRegistry()
        replans0 = resilience.METRICS.counter("straggler_replans").value
        t0 = time.monotonic()
        r = cluster.reader(g)
        got = r.read_all()
        assert time.monotonic() - t0 < straggle_s
        assert np.array_equal(got, data)
        assert 1 in r._failed
        assert resilience.METRICS.counter(
            "straggler_replans").value > replans0
    finally:
        cluster.close()


# ----------------------------------------------------------- repair path
@pytest.mark.parametrize("targets", [[1], [0, 4]],
                         ids=["data_unit", "data_and_parity"])
def test_recover_cells_iter_yields_what_repair_writes(tmp_path, targets):
    """The repair path's stream is what it was: per decode batch the
    lost units' cells and their device CRCs, in stripe order, with
    nothing booked under the GET's counters and no callback needed."""
    cluster, g, data = _one_group(tmp_path, RS32, 11, seed=21)
    try:
        _lose(cluster, g, targets)
        before = _ops()
        yielded = list(cluster.reader(g).recover_cells_iter(targets))
        assert _ops() == before
        assert [list(sb) for sb, _ in yielded] == \
            [list(range(8)), [8, 9, 10]]
        # the truth: data cells from the key, parity from an intact copy
        truth = data.reshape(11, 3, CELL)
        host = Checksum(ChecksumType.CRC32C, BPC)
        for sb, (rec, crcs) in yielded:
            assert rec.shape == (len(sb), len(targets), CELL)
            assert crcs.shape == (len(sb), len(targets), CELL // BPC)
            for bi, s in enumerate(sb):
                for ti, u in enumerate(targets):
                    if u < 3:
                        assert np.array_equal(rec[bi, ti], truth[s, u])
                    want = tuple(int(v).to_bytes(4, "big")
                                 for v in crcs[bi, ti].tolist())
                    assert want == host.compute(rec[bi, ti]).checksums
        # and the one-shot form assembles the same arrays
        cells, crcs = cluster.reader(g).recover_cells_with_crcs(targets)
        assert np.array_equal(
            cells, np.concatenate([r for _, (r, _) in yielded]))
        assert np.array_equal(
            crcs, np.concatenate([c for _, (_, c) in yielded]))
    finally:
        cluster.close()


def test_on_survivors_sees_every_batch_before_its_results(tmp_path):
    cluster, g, data = _one_group(tmp_path, RS32, 11, seed=22)
    try:
        _lose(cluster, g, (1,))
        order = []

        def seen(sb, valid, batch):
            assert valid == [0, 2, 3]
            assert batch.shape == (len(sb), 3, CELL)
            truth = data.reshape(11, 3, CELL)
            assert np.array_equal(batch[:, 0], truth[list(sb), 0])
            order.append(("survivors", sb[0]))

        for sb, _ in cluster.reader(g).recover_cells_iter(
                [1], on_survivors=seen):
            order.append(("results", sb[0]))
        # depth-1 pipeline: batch 2's survivors are read (and seen)
        # while batch 1 decodes
        assert order == [("survivors", 0), ("survivors", 8),
                         ("results", 0), ("results", 8)]
    finally:
        cluster.close()


# ------------------------------------------------ a key of several groups
@pytest.mark.parametrize("degraded", [False, True],
                         ids=["healthy", "degraded"])
def test_key_of_several_groups_is_assembled_once(tmp_path, degraded):
    """Through the client: the groups' readers write into slices of the
    key's one buffer. Every byte is copied into it once (the datapath's
    copy counters say so); a degraded group adds only the decode's own
    staging of its survivor batch."""
    from ozone_tpu.testing.minicluster import MiniOzoneCluster

    c = MiniOzoneCluster(tmp_path, num_datanodes=6, block_size=4 * CELL,
                         container_size=1024 * 1024,
                         stale_after_s=1000.0, dead_after_s=2000.0)
    try:
        oz = c.client()
        oz.clients.health = resilience.HealthRegistry(hedge_floor_s=30.0)
        b = oz.create_volume("v").create_bucket(
            "b", replication=f"rs-3-2-{CELL}")
        # 2 groups of 4 whole stripes and one of 2: whole cells, so no
        # short cell is padded (a counted copy of its own)
        data = np.random.default_rng(33).integers(
            0, 256, 10 * ROW, dtype=np.uint8)
        b.write_key("k", data)
        info = oz.om.lookup_key("v", "b", "k")
        assert len(info["block_groups"]) == 3
        staged = 0
        if degraded:
            # lose data unit 0 of the middle group
            g = c.om.key_block_groups(info)[1]
            c.datanode(g.pipeline.nodes[0]).delete_block(g.block_id)
            staged = g.length  # k survivor cells a stripe, all 4 stripes
        copied0 = hostmem._BYTES_COPIED.value
        user0 = OPS.counter("get_user_bytes").value
        got = b.read_key_info(info)
        assert np.array_equal(got, data)
        # one array: a lease of the host buffer pool, no view of parts
        assert not isinstance(got.base, np.ndarray)
        assert hostmem._BYTES_COPIED.value - copied0 == data.size + staged
        assert OPS.counter("get_user_bytes").value - user0 == data.size
        # a range over the seam of two groups
        got = b.read_key_info_range(info, 4 * ROW - 100, ROW)
        assert np.array_equal(got, data[4 * ROW - 100:5 * ROW - 100])
    finally:
        c.close()

