"""A reader learns which units are there in ONE round.

`available_units()` asks every unit's block record at once (the caller
and the workers of a pool the process keeps take units off one list)
and answers from `_block_meta` after; `_unit_block` stays the one place
that asks a node and reads its answer. Held here
over in-process datanodes behind clients that count, delay and fail
`get_block`, against the walk the round replaced (one unit after
another on the calling thread); every case runs under a time limit of
its own.
"""

import dataclasses
import itertools
import threading
import time

import numpy as np
import pytest

from ozone_tpu.client import resilience
from ozone_tpu.client.ec_reader import OPS, InsufficientLocationsError
from ozone_tpu.codec.api import CoderOptions
from ozone_tpu.storage.ids import StorageError
from ozone_tpu.storage.reconstruction import MISSING_NODE
from ozone_tpu.utils.tracing import Tracer
from tests.test_ec_pipeline import CELL
from tests.test_ec_read_once import _lose, _one_group

SCHEMES = {
    "rs-6-3": CoderOptions(6, 3, "rs", cell_size=CELL),
    "rs-10-4": CoderOptions(10, 4, "rs", cell_size=CELL),
    "lrc-12-2-2": CoderOptions(12, 4, "lrc", cell_size=CELL,
                               local_groups=2),
}
COUNTERS = ("block_record_rounds", "block_records_asked")
SLEEP = 0.020

pytestmark = pytest.mark.parametrize("scheme", list(SCHEMES))


@pytest.fixture(autouse=True)
def _fresh_tracer():
    """A process tracer of this case's own: the patterns below leave
    thousands of spans, and tests elsewhere index the tracer's ring."""
    Tracer._instance = None
    yield
    Tracer._instance = None


def _within(seconds: float, body) -> None:
    """The case's own time limit: `body` on a thread of its own, failed
    where it has not ended in time (no hang outlives its case)."""
    failure: list[BaseException] = []

    def run():
        try:
            body()
        except BaseException as e:  # noqa: BLE001 - re-raised below
            failure.append(e)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(seconds)
    assert not t.is_alive(), f"still running after {seconds} s"
    if failure:
        raise failure[0]


class _GetBlockClient:
    """Passes every verb through; `get_block` is logged by datanode and
    then handed to `on_get_block(dn_id)`, which may wait or raise."""

    def __init__(self, inner, calls: list, on_get_block=None):
        self._inner = inner
        self.dn_id = inner.dn_id
        self._calls = calls
        self._on = on_get_block

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def get_block(self, block_id):
        self._calls.append(self.dn_id)
        if self._on is not None:
            self._on(self.dn_id)
        return self._inner.get_block(block_id)


def _watch_get_block(cluster, on_get_block=None) -> list:
    """Every datanode's `get_block` calls, in one list of datanode ids."""
    calls: list = []
    for dn_id, c in list(cluster.clients._local.items()):
        cluster.clients._local[dn_id] = _GetBlockClient(
            c, calls, on_get_block)
    return calls


class _CountingHealth(resilience.HealthRegistry):
    """Logs the peer of every observation."""

    def __init__(self):
        super().__init__(hedge_floor_s=30.0)
        self.observed: list = []

    def observe(self, peer, fn, *a, **kw):
        self.observed.append(peer)
        return super().observe(peer, fn, *a, **kw)


def _reader(cluster, g):
    """A reader with a health registry of its own: what one reader's
    answers taught the breaker does not shape the next one's plan."""
    cluster.clients.health = _CountingHealth()
    return cluster.reader(g)


def _serial_walk(reader) -> None:
    """The walk the round replaced: every unit's record asked for in
    turn on this thread. After it the round has nobody left to ask."""
    for u in range(reader.k + reader.p):
        if u not in reader._failed:
            reader._unit_block(u)


def _ops() -> dict:
    return {n: OPS.counter(n).value for n in COUNTERS}


def _delta(before: dict) -> dict:
    return {n: v - before[n] for n, v in _ops().items()}


def _spans(name: str, since: float) -> list:
    """The finished spans called `name` that began after `since`."""
    return [s for s in list(Tracer.instance().spans)
            if s.name == name and s.mono >= since]


def _no_reader_threads(reader) -> bool:
    """The reader gave up its pool and the workers have gone home."""
    t_end = time.monotonic() + 10
    while any(t.name.startswith("ec-read") for t in threading.enumerate()) \
            and time.monotonic() < t_end:
        time.sleep(0.01)
    return reader._read_pool is None and not any(
        t.name.startswith("ec-read") for t in threading.enumerate())


# ---------------------------------------------------------- one wave, once
def test_round_costs_the_slowest_answer_and_asks_each_unit_once(
        tmp_path, scheme):
    """Every `get_block` sleeps 20 ms: a fresh reader's round ends in
    under three such sleeps where the walk takes one a unit, asks every
    unit exactly once, and what it learned no later question asks."""
    opts = SCHEMES[scheme]
    cluster, g, _data = _one_group(tmp_path, opts, 2, seed=1)
    calls = _watch_get_block(cluster, lambda dn: time.sleep(SLEEP))

    def body():
        took = []
        for _ in range(3):  # a loaded host may start a thread late once
            del calls[:]
            before, mark = _ops(), time.monotonic()
            reader = _reader(cluster, g)
            t0 = time.monotonic()
            avail = reader.available_units()
            took.append(time.monotonic() - t0)
            assert avail == list(range(opts.all_units))
            assert sorted(calls) == sorted(g.pipeline.nodes)
            assert _delta(before) == {
                "block_record_rounds": 1,
                "block_records_asked": opts.all_units}
            (sp,) = _spans("net:get_blocks", mark)
            assert (sp.tags["records_asked"],
                    sp.tags["records_present"]) == (opts.all_units,) * 2
            asked = _spans("net:get_block", mark)
            assert len(asked) == opts.all_units
            assert {s.parent_id for s in asked} == {sp.span_id}
            # a second call, a plan, a unit's own record: no RPC, no round
            assert reader.available_units() == avail
            assert reader._plan_read([0])[0]
            assert reader._unit_block(1) is not None
            assert len(calls) == opts.all_units, "a record was asked twice"
            assert _delta(before)["block_record_rounds"] == 1
            assert len(_spans("net:get_blocks", mark)) == 1
            if took[-1] < 3 * SLEEP:
                break
        assert min(took) < 3 * SLEEP, took
        assert min(took) >= SLEEP

    try:
        _within(60, body)
    finally:
        cluster.close()


def test_all_units_are_in_flight_together(tmp_path, scheme):
    """`get_block` waits on a barrier of as many parties as there are
    units: it passes only with every unit's record asked for at once
    (the caller's and a worker for every other unit)."""
    opts = SCHEMES[scheme]
    cluster, g, _data = _one_group(tmp_path, opts, 2, seed=2)
    barrier = threading.Barrier(opts.all_units)
    calls = _watch_get_block(cluster, lambda dn: barrier.wait(20))

    def body():
        reader = _reader(cluster, g)
        assert reader.available_units() == list(range(opts.all_units))
        assert len(calls) == opts.all_units
        assert not barrier.broken

    try:
        _within(60, body)
    finally:
        cluster.close()


def test_a_round_waits_for_no_worker_that_has_not_started(
        tmp_path, scheme, monkeypatch):
    """Every worker of the record pool is held elsewhere (other
    readers' rounds on a node that does not answer): the caller asks
    every unit itself, as the walk did, and waits for no hand-off."""
    from concurrent.futures import ThreadPoolExecutor

    from ozone_tpu.client import ec_reader

    opts = SCHEMES[scheme]
    cluster, g, _data = _one_group(tmp_path, opts, 2, seed=4)
    held = threading.Event()
    busy = ThreadPoolExecutor(max_workers=1)
    busy.submit(held.wait, 60)
    monkeypatch.setattr(ec_reader, "_record_pool", busy)
    askers: list = []
    calls = _watch_get_block(
        cluster, lambda dn: askers.append(threading.current_thread()))

    def body():
        me = threading.current_thread()
        before = _ops()
        reader = _reader(cluster, g)
        assert reader.available_units() == list(range(opts.all_units))
        assert len(calls) == opts.all_units and set(askers) == {me}
        assert _delta(before) == {"block_record_rounds": 1,
                                  "block_records_asked": opts.all_units}

    try:
        _within(30, body)
    finally:
        held.set()
        busy.shutdown()
        cluster.close()


# ------------------------------------------ the same answer, the same plan
#: how a unit comes to be absent: its node answers with an error, its
#: node is nobody (the repair coordinator's name for the lost unit), or
#: an earlier failure of this read put it in `_failed` (not asked)
MANNERS = ("storage_error", "missing_node", "failed")


@pytest.mark.parametrize("absent", [1, 2], ids=["1absent", "2absent"])
def test_same_answer_and_plan_as_the_serial_walk(tmp_path, scheme, absent):
    """Every pattern of `absent` units not there, each unit by one of
    the three manners in turn: the round's `available_units()`, and
    `_plan_read`'s read set and kind over it, are the walk's."""
    opts = SCHEMES[scheme]
    cluster, g, _data = _one_group(tmp_path, opts, 2, seed=3)
    refuses: set = set()

    def on_get_block(dn_id: str) -> None:
        if dn_id in refuses:
            raise StorageError("NO_SUCH_BLOCK", f"{dn_id} lost it")

    calls = _watch_get_block(cluster, on_get_block)

    def plan(reader, erased):
        try:
            return reader.available_units(), reader._plan_read(erased)
        except InsufficientLocationsError as e:
            return type(e)

    def body():
        patterns = list(itertools.combinations(range(opts.all_units),
                                               absent))
        for i, units in enumerate(patterns):
            manner = {u: MANNERS[(i + j) % 3] for j, u in enumerate(units)}
            nodes = list(g.pipeline.nodes)
            refuses.clear()
            for u, m in manner.items():
                if m == "storage_error":
                    refuses.add(nodes[u])
                elif m == "missing_node":
                    nodes[u] = MISSING_NODE
            view = dataclasses.replace(
                g, pipeline=dataclasses.replace(g.pipeline, nodes=nodes))
            failed = {u for u, m in manner.items() if m == "failed"}
            got = []
            for walk in (False, True):
                del calls[:]
                reader = _reader(cluster, view)
                reader._failed.update(failed)
                if walk:
                    _serial_walk(reader)
                got.append(plan(reader, list(units)))
                asked = [u for u in range(opts.all_units)
                         if manner.get(u) not in ("missing_node", "failed")]
                assert sorted(calls) == sorted(nodes[u] for u in asked), \
                    (units, manner, walk)
            assert got[0] == got[1], (units, manner, got)
            avail, (valid, kind) = got[0]
            assert avail == [u for u in range(opts.all_units)
                             if u not in units]
            assert set(valid) <= set(avail) and kind

    try:
        _within(240, body)
    finally:
        cluster.close()


# ------------------------------------------------------- a spent budget
def test_deadline_from_any_unit_propagates(tmp_path, scheme):
    """DEADLINE_EXCEEDED is the operation's verdict, not a unit's: the
    round raises it (the lowest unit's, once every answer is in) and no
    read takes it for "unit absent" (InsufficientLocationsError)."""
    opts = SCHEMES[scheme]
    cluster, g, _data = _one_group(tmp_path, opts, 2, seed=5)
    late = {g.pipeline.nodes[2]: "unit 2",
            g.pipeline.nodes[opts.all_units - 1]: "the last unit"}

    def spent(dn_id: str) -> None:
        if dn_id in late:
            raise StorageError(resilience.DEADLINE_EXCEEDED, late[dn_id])

    calls = _watch_get_block(cluster, spent)

    def body():
        reader = _reader(cluster, g)
        with pytest.raises(StorageError) as ei:
            reader.available_units()
        assert ei.value.code == resilience.DEADLINE_EXCEEDED
        assert ei.value.msg == "unit 2"
        assert len(calls) == opts.all_units  # the round went out whole
        assert set(reader._block_meta) == set(
            range(opts.all_units)) - {2, opts.all_units - 1}
        for entry in (lambda r: r.read_all(),
                      lambda r: r.recover_cells([0])):
            reader = _reader(cluster, g)
            with pytest.raises(StorageError) as ei:
                entry(reader)
            assert ei.value.code == resilience.DEADLINE_EXCEEDED
            assert _no_reader_threads(reader)

    try:
        _within(60, body)
    finally:
        cluster.close()


# ------------------------------------------------- one observation a node
def test_health_registry_sees_one_observation_a_node(tmp_path, scheme):
    """Every `get_block` of the round goes through `_health.observe`,
    once a node that can be dialed: an absent unit's node too, nobody
    for MISSING_NODE, and none again for what the cache answers."""
    opts = SCHEMES[scheme]
    cluster, g, _data = _one_group(tmp_path, opts, 2, seed=6)
    nodes = list(g.pipeline.nodes)
    _lose(cluster, g, (1,))  # its datanode answers NO_SUCH_BLOCK
    nodes[0] = MISSING_NODE
    view = dataclasses.replace(
        g, pipeline=dataclasses.replace(g.pipeline, nodes=nodes))
    calls = _watch_get_block(cluster)

    def body():
        reader = _reader(cluster, view)
        want = list(range(2, opts.all_units))
        assert reader.available_units() == want
        assert reader.available_units() == want
        assert reader._plan_read([0, 1])[0]
        assert sorted(reader._health.observed) == sorted(nodes[1:])
        assert sorted(calls) == sorted(nodes[1:])
        assert not reader._health.is_open(nodes[1])

    try:
        _within(60, body)
    finally:
        cluster.close()


# ----------------------------- counted, and the pool reaped, end to end
def test_degraded_read_and_repair_count_their_rounds(tmp_path, scheme):
    """A degraded `read()` and a repair's `recover_cells_iter()` each
    ask their records in one round, counted in `client.ops`, return the
    lost bytes, and leave no pool thread behind."""
    opts = SCHEMES[scheme]
    stripes, lost = 3, 1
    cluster, g, data = _one_group(tmp_path, opts, stripes, tail=333, seed=7)
    # the lost unit's cells, the short last stripe's zero-padded
    rows = np.zeros((stripes + 1) * opts.data_units * CELL, np.uint8)
    rows[:data.size] = data
    want = rows.reshape(stripes + 1, opts.data_units, CELL)[:, lost]
    nodes = list(g.pipeline.nodes)
    nodes[lost] = MISSING_NODE  # as the repair coordinator names it
    view = dataclasses.replace(
        g, pipeline=dataclasses.replace(g.pipeline, nodes=nodes))
    _lose(cluster, g, (lost,))
    calls = _watch_get_block(cluster)

    def body():
        before = _ops()
        reader = _reader(cluster, g)
        assert np.array_equal(reader.read_all(), data)
        assert _delta(before) == {"block_record_rounds": 1,
                                  "block_records_asked": opts.all_units}
        assert len(calls) == opts.all_units
        assert _no_reader_threads(reader)

        before, mark = _ops(), time.monotonic()
        reader = _reader(cluster, view)
        for sb, (rec, _crcs) in reader.recover_cells_iter([lost]):
            assert np.array_equal(rec[:, 0], want[list(sb)])
        # the lost unit's record is sought like any other (`_unit_block`
        # finds nobody to dial) and is not there
        assert _delta(before) == {"block_record_rounds": 1,
                                  "block_records_asked": opts.all_units}
        assert len(calls) == 2 * opts.all_units - 1
        (sp,) = _spans("net:get_blocks", mark)
        assert (sp.tags["records_asked"], sp.tags["records_present"]) == (
            opts.all_units, opts.all_units - 1)
        assert _no_reader_threads(reader)

    try:
        _within(120, body)
    finally:
        cluster.close()
