"""Shared codec service: cross-request continuous batching tests.

The tentpole contract (ROADMAP item 1): stripes from DIFFERENT in-flight
operations coalesce into one fused device dispatch; a lone stripe is
bounded by the linger knob; a near-expiry deadline forces a partial
batch instead of DEADLINE_EXCEEDED; weighted fair QoS keeps a bulk
sweep from starving interactive submissions; and every refactored
datapath falls back to its per-operation pipeline when the service is
disabled, byte-exact either way.
"""

import itertools
import threading
import time

import numpy as np
import pytest

from ozone_tpu.client.dn_client import DatanodeClientFactory
from ozone_tpu.client.ec_reader import ECBlockGroupReader
from ozone_tpu.client.ec_writer import BlockGroup, ECKeyWriter
from ozone_tpu.codec import service as cs
from ozone_tpu.codec.api import CoderOptions
from ozone_tpu.codec.fused import FusedSpec, make_fused_encoder
from ozone_tpu.scm.pipeline import Pipeline, ReplicationConfig
from ozone_tpu.storage.datanode import Datanode
from ozone_tpu.utils.checksum import ChecksumType

CELL = 4096
OPTS = CoderOptions(3, 2, "rs", cell_size=CELL)
SPEC = FusedSpec(OPTS, ChecksumType.CRC32C, 1024)


@pytest.fixture
def svc():
    cs.reset_for_tests()
    yield cs.get_service()
    cs.reset_for_tests()


@pytest.fixture
def fresh_service_env(monkeypatch):
    """Re-create the singleton AFTER knob monkeypatches apply."""
    def make(**env):
        for k, v in env.items():
            monkeypatch.setenv(k, v)
        cs.reset_for_tests()
        return cs.get_service()

    yield make
    cs.reset_for_tests()


class MiniEC:
    """Tiny in-process cluster (the test_ec_pipeline harness, local so
    this suite stands alone)."""

    def __init__(self, tmp_path, n_dn=6, opts=OPTS):
        self.opts = opts
        self.dns = [Datanode(tmp_path / f"dn{i}", dn_id=f"dn{i}")
                    for i in range(n_dn)]
        self.clients = DatanodeClientFactory()
        for dn in self.dns:
            self.clients.register_local(dn)
        self._cid = itertools.count(1)
        self._lid = itertools.count(1)

    def allocate(self, excluded):
        nodes = [d.id for d in self.dns
                 if d.id not in excluded][: self.opts.all_units]
        return BlockGroup(
            container_id=next(self._cid), local_id=next(self._lid),
            pipeline=Pipeline(ReplicationConfig.from_ec(self.opts),
                              nodes))

    def writer(self, **kw):
        kw.setdefault("block_size", 8 * CELL)
        kw.setdefault("bytes_per_checksum", 1024)
        kw.setdefault("stripe_batch", 4)
        return ECKeyWriter(self.opts, self.allocate, self.clients, **kw)

    def close(self):
        for d in self.dns:
            d.close()


@pytest.fixture
def cluster(tmp_path):
    c = MiniEC(tmp_path)
    yield c
    c.close()


def _rand(shape, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, shape, dtype=np.uint8)


# ------------------------------------------------------------ coalescing
def test_cross_request_stripes_share_one_dispatch(fresh_service_env):
    """Two distinct operations' stripes land in ONE fused dispatch, and
    each gets exactly its own slice of the batched outputs."""
    # the two submits fill the batch, so nothing waits for this linger;
    # the default 2 ms is a race between them on a loaded rig
    svc = fresh_service_env(OZONE_TPU_CODEC_LINGER_MS="500")
    fn = make_fused_encoder(SPEC)
    a, b = _rand((2, 3, CELL), 1), _rand((2, 3, CELL), 2)
    d0 = cs.METRICS.counter("dispatches").value
    x0 = cs.METRICS.counter("multi_op_dispatches").value
    f1 = svc.submit(cs.encode_key(SPEC), fn, a, width=4)
    f2 = svc.submit(cs.encode_key(SPEC), fn, b, width=4)
    p1, c1 = cs.wait_result(f1)
    p2, c2 = cs.wait_result(f2)
    ref_p, ref_c = (np.asarray(x) for x in fn(np.concatenate([a, b])))
    assert np.array_equal(np.concatenate([p1, p2]), ref_p)
    assert np.array_equal(np.concatenate([c1, c2]), ref_c)
    assert cs.METRICS.counter("dispatches").value - d0 == 1
    assert cs.METRICS.counter("multi_op_dispatches").value - x0 == 1


def test_large_submission_splits_across_constant_shape_batches(svc):
    """A submission wider than the lane batch splits into width-sized
    dispatches and reassembles in order — outputs byte-exact vs one
    direct call."""
    fn = make_fused_encoder(SPEC)
    data = _rand((11, 3, CELL), 3)
    d0 = cs.METRICS.counter("dispatches").value
    out_p, out_c = cs.wait_result(
        svc.submit(cs.encode_key(SPEC), fn, data, width=4))
    ref_p, ref_c = (np.asarray(x) for x in fn(data))
    assert np.array_equal(out_p, ref_p)
    assert np.array_equal(out_c, ref_c)
    assert cs.METRICS.counter("dispatches").value - d0 == 3  # 4+4+3pad


def test_mismatched_widths_never_pad_against_each_other(svc):
    """Lanes are keyed by (key, width): an 8-wide submitter and a
    2-wide submitter compile/batch separately."""
    fn = make_fused_encoder(SPEC)
    a = _rand((2, 3, CELL), 4)
    f1 = svc.submit(cs.encode_key(SPEC), fn, a, width=8)
    f2 = svc.submit(cs.encode_key(SPEC), fn, a, width=2)
    p1, _ = cs.wait_result(f1)
    p2, _ = cs.wait_result(f2)
    assert np.array_equal(p1, p2)


# ----------------------------------------------------- linger + deadline
def test_lone_stripe_completes_within_linger_plus_dispatch(
        fresh_service_env):
    """Acceptance: a lone 1-stripe submit into a wide lane completes
    within linger + one dispatch time, via the forced (linger) flush."""
    svc = fresh_service_env(OZONE_TPU_CODEC_LINGER_MS="40")
    fn = make_fused_encoder(SPEC)
    fn(_rand((1, 3, CELL)))  # absorb compile/first-touch cost
    ff0 = cs.METRICS.counter("forced_flushes").value
    t0 = time.monotonic()
    p, _ = cs.wait_result(
        svc.submit(cs.encode_key(SPEC), fn, _rand((1, 3, CELL), 5),
                   width=8, tail=True))
    dt = time.monotonic() - t0
    assert p.shape == (1, 2, CELL)
    # linger (40 ms) + generous dispatch allowance on a loaded CI rig
    assert dt < 0.04 + 1.0, f"lone stripe took {dt:.3f}s"
    assert dt >= 0.8 * 0.04, "linger path was skipped entirely"
    assert cs.METRICS.counter("forced_flushes").value == ff0 + 1
    assert cs.METRICS.gauge("batch_fill_pct").value < 100.0


def test_near_expiry_deadline_forces_partial_flush(fresh_service_env):
    """Acceptance: a submitter whose Deadline is about to expire gets a
    partial-batch dispatch instead of DEADLINE_EXCEEDED — even when the
    linger says to keep waiting for fill."""
    from ozone_tpu.client import resilience

    svc = fresh_service_env(OZONE_TPU_CODEC_LINGER_MS="5000")
    fn = make_fused_encoder(SPEC)
    fn(_rand((1, 3, CELL)))  # absorb compile cost outside the budget
    df0 = cs.METRICS.counter("deadline_flushes").value
    with resilience.start("near_expiry_put", seconds=0.25):
        t0 = time.monotonic()
        p, _ = cs.wait_result(
            svc.submit(cs.encode_key(SPEC), fn,
                       _rand((2, 3, CELL), 6), width=8))
        dt = time.monotonic() - t0
    assert p.shape == (2, 2, CELL)
    assert dt < 2.0, f"deadline flush never fired ({dt:.3f}s)"
    assert cs.METRICS.counter("deadline_flushes").value >= df0 + 1


# ---------------------------------------------------------------- QoS
def test_bulk_sweep_cannot_starve_interactive(fresh_service_env):
    """A saturating bulk sweep and an interactive submitter run
    concurrently: both make progress and the interactive P95 queue wait
    stays bounded while the sweep owns most of the device."""
    svc = fresh_service_env(OZONE_TPU_CODEC_LINGER_MS="1",
                            OZONE_TPU_CODEC_QOS="interactive=4,bulk=1")

    def slow_fn(batch):  # ~3 ms of fake device time per dispatch
        t_end = time.monotonic() + 0.003
        while time.monotonic() < t_end:
            pass
        return (batch.copy(),)

    def fast_fn(batch):
        return (batch.copy(),)

    stop = threading.Event()
    bulk_done = [0]

    def bulk():
        data = _rand((8, 3, CELL), 7)
        while not stop.is_set():
            cs.wait_result(svc.submit(("bulk-lane",), slow_fn, data,
                                      width=8, qos="bulk"))
            bulk_done[0] += 1

    threads = [threading.Thread(target=bulk) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        time.sleep(0.05)  # let the sweep saturate the dispatcher
        waits = []
        one = _rand((1, 3, CELL), 8)
        for _ in range(25):
            t0 = time.monotonic()
            (out,) = cs.wait_result(svc.submit(
                ("interactive-lane",), fast_fn, one, width=1,
                qos="interactive"))
            waits.append(time.monotonic() - t0)
            assert np.array_equal(out, one)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
    assert bulk_done[0] >= 3, "the bulk sweep made no progress"
    waits.sort()
    p95 = waits[int(0.95 * (len(waits) - 1))]
    # bounded: ~2 in-flight bulk dispatches (3 ms each) + scheduling
    # slack on a loaded CI rig — NOT the whole sweep's runtime
    assert p95 < 0.25, f"interactive P95 wait {p95:.3f}s — starved"


def test_starvation_guard_preempts_pathological_weights(
        fresh_service_env):
    """Even with weights pathologically inverted, the starvation guard
    serves an over-aged queue head (and counts the trip)."""
    svc = fresh_service_env(
        OZONE_TPU_CODEC_LINGER_MS="1",
        OZONE_TPU_CODEC_STARVE_MS="20",
        OZONE_TPU_CODEC_QOS="interactive=0.000001,bulk=1000")

    def slow_fn(batch):
        t_end = time.monotonic() + 0.002
        while time.monotonic() < t_end:
            pass
        return (batch.copy(),)

    one = _rand((1, 3, CELL), 10)
    # first interactive dispatch is FREE (vtime 0); it inflates the
    # class's virtual time so fairness alone would now park the class
    # behind the 1000x-weighted bulk queue for the whole backlog
    cs.wait_result(svc.submit(("interactive-lane",), slow_fn, one,
                              width=1, qos="interactive"))
    g0 = cs.METRICS.counter("starvation_guard_trips").value
    # a PRE-QUEUED bulk backlog keeps the bulk lane continuously
    # occupied (~160 ms of fake device time) — no submitter round-trips
    # to race, so the only way interactive gets served inside the
    # backlog window is the starvation guard
    data = _rand((4, 3, CELL), 9)
    bulk_futs = [svc.submit(("bulk-lane",), slow_fn, data, width=4,
                            qos="bulk") for _ in range(80)]
    t0 = time.monotonic()
    (out,) = cs.wait_result(svc.submit(
        ("interactive-lane",), slow_fn, one, width=1,
        qos="interactive"))
    dt = time.monotonic() - t0
    assert np.array_equal(out, one)
    assert cs.METRICS.counter("starvation_guard_trips").value > g0
    # served at ~starve_ms (20 ms), NOT after the whole 160 ms backlog
    assert dt < 0.12, f"guard served the interactive head at {dt:.3f}s"
    for f in bulk_futs:
        cs.wait_result(f)  # the sweep itself still completes


def test_idle_class_activation_floors_virtual_time(fresh_service_env):
    """SFQ activation floor: a class idle through a long burst of the
    other class joins at the system virtual clock — its stale LOW
    virtual time must not buy it a monopoly window (and the returning
    class must not be parked behind it for its past service)."""
    svc = fresh_service_env(OZONE_TPU_CODEC_LINGER_MS="1",
                            OZONE_TPU_CODEC_STARVE_MS="5000",
                            OZONE_TPU_CODEC_QOS="interactive=4,bulk=1")

    def slow_fn(batch):
        t_end = time.monotonic() + 0.002
        while time.monotonic() < t_end:
            pass
        return (batch.copy(),)

    one = _rand((1, 3, CELL), 11)
    # interactive-only phase: its virtual time climbs while bulk idles
    for _ in range(10):
        cs.wait_result(svc.submit(("interactive-lane",), slow_fn, one,
                                  width=1, qos="interactive"))
    # bulk becomes active with a ~100 ms backlog; without the floor its
    # vtime would be 0 << interactive's and fairness would serve ALL of
    # it before the next interactive submission (starve guard is far
    # away at 5 s, so only the floor can bound this)
    data = _rand((4, 3, CELL), 12)
    bulk_futs = [svc.submit(("bulk-lane",), slow_fn, data, width=4,
                            qos="bulk") for _ in range(50)]
    # judged when the service RESOLVES the future, in dispatches served
    # ahead of it: the waiter's own wake-up can trail by the whole
    # backlog, because `slow_fn` spins with the GIL and a dispatcher
    # with work queued never blocks (ROADMAP D10; half the backlog is
    # what the old 50 ms bound on the wake-up stood for)
    t0 = time.monotonic()
    seen = []
    fut = svc.submit(("interactive-lane",), slow_fn, one, width=1,
                     qos="interactive")
    fut.add_done_callback(lambda _f: seen.append(
        (sum(f.done() for f in bulk_futs), time.monotonic() - t0)))
    cs.wait_result(fut)
    ahead, dt = seen[0]
    assert ahead < len(bulk_futs) // 2, (
        f"interactive waited {dt:.3f}s, {ahead} dispatches, behind an "
        f"idle-activated bulk backlog — the WFQ activation floor is "
        f"broken")
    assert svc._vtime["bulk"] > 0.0  # joined at the clock, not at zero
    for f in bulk_futs:
        cs.wait_result(f)


# ------------------------------------------------------- datapath wiring
def test_concurrent_writers_coalesce_and_stay_byte_exact(
        cluster, fresh_service_env):
    """The end-to-end tentpole proof at test scale: concurrent small
    PUTs (each ONE stripe — far below the batch width) share fused
    dispatches across operations, and every key reads back byte-exact."""
    fresh_service_env(OZONE_TPU_CODEC_LINGER_MS="250")
    n_ops = 4
    datas = [_rand(3 * CELL, 20 + i) for i in range(n_ops)]
    groups: list = [None] * n_ops
    x0 = cs.METRICS.counter("multi_op_dispatches").value
    t0 = cs.METRICS.counter("tail_flushes").value
    barrier = threading.Barrier(n_ops)

    def put(i):
        barrier.wait()
        w = cluster.writer()
        w.write(datas[i])
        groups[i] = w.close()

    threads = [threading.Thread(target=put, args=(i,))
               for i in range(n_ops)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert all(g is not None for g in groups)
    # all four 1-stripe tails landed within the linger: at least one
    # dispatch carried stripes from MULTIPLE distinct operations
    assert cs.METRICS.counter("multi_op_dispatches").value > x0
    # the partial flushes rode the linger path and were counted
    assert cs.METRICS.counter("tail_flushes").value >= t0 + n_ops
    for i in range(n_ops):
        got = np.concatenate([
            ECBlockGroupReader(g, OPTS, cluster.clients,
                               bytes_per_checksum=1024).read_all()
            for g in groups[i]])
        assert np.array_equal(got, datas[i])


@pytest.mark.parametrize("size,lost", [
    (6 * CELL, 0),
    (7 * CELL + 11, 1),  # a partial last stripe, another unit lost
], ids=["whole_stripes", "partial_tail"])
def test_degraded_read_routes_through_service(cluster, svc, size, lost):
    """A degraded read decodes through the shared service (dispatch
    counters move) and stays byte-exact."""
    data = _rand(size, 30)
    w = cluster.writer()
    w.write(data)
    groups = w.close()
    d0 = cs.METRICS.counter("dispatches").value
    for g in groups:
        cluster.dns[[d.id for d in cluster.dns].index(
            g.pipeline.nodes[lost])].delete_container(
                g.container_id, force=True)
    got = np.concatenate([
        ECBlockGroupReader(g, OPTS, cluster.clients,
                           bytes_per_checksum=1024).read_all()
        for g in groups])
    assert np.array_equal(got, data)
    assert cs.METRICS.counter("dispatches").value > d0


def test_service_error_propagates_to_submitter(svc):
    """A fused fn failing mid-dispatch surfaces on the submitter's
    future, not as a dead dispatcher."""
    def broken(batch):
        raise RuntimeError("device fault")

    with pytest.raises(RuntimeError, match="device fault"):
        cs.wait_result(svc.submit(("broken-lane",), broken,
                                  _rand((1, 3, CELL), 32), width=1))
    # the dispatcher survived: a healthy lane still serves
    fn = make_fused_encoder(SPEC)
    p, _ = cs.wait_result(
        svc.submit(cs.encode_key(SPEC), fn, _rand((1, 3, CELL), 33),
                   width=1))
    assert p.shape == (1, 2, CELL)


def test_stats_snapshot_shape(svc):
    """The Recon /api/codec payload: fill ratio, ops/dispatch, queue
    depth and knob echo are always present."""
    fn = make_fused_encoder(SPEC)
    cs.wait_result(svc.submit(cs.encode_key(SPEC), fn,
                              _rand((2, 3, CELL), 34), width=2))
    out = svc.stats()
    for want in ("fill_ratio", "ops_per_dispatch", "queue_depth",
                 "lanes", "inflight", "linger_ms", "weights"):
        assert want in out, want
    assert 0.0 < out["fill_ratio"] <= 1.0


# ------------------------------------------------- staging at submit
# Rows are packed where they are produced: `submit` reserves rows of the
# lane's open staging batch, the submitter copies into them, and the
# dispatcher launches what is already packed (ISSUE 26).
STAGING = ("stripes_dispatched", "stripes_packed_at_submit",
           "stripes_borrowed", "staging_buffers_leased",
           "staging_buffers_reused", "dispatches", "forced_flushes",
           "deadline_flushes")


def _counts():
    out = {k: cs.METRICS.counter(k).value for k in STAGING}
    for h in ("submit_pack_seconds", "pack_seconds"):
        out[h + ".count"] = cs.METRICS.histogram(h).count
        out[h + ".sum"] = cs.METRICS.histogram(h).total
    return out


def _moved(before):
    after = _counts()
    return {k: after[k] - before[k] for k in after}


def _echo(batch):
    return (batch.copy(),)


def _in_thread(name, target):
    """Run `target` on a thread called `name`; (thread, outcome list)."""
    out = []

    def run():
        try:
            out.append(("ok", target()))
        except BaseException as e:  # noqa: BLE001 - handed to the test
            out.append(("raised", e))

    t = threading.Thread(target=run, name=name, daemon=True)
    t.start()
    return t, out


@pytest.fixture
def copy_hook(monkeypatch):
    """Script what happens between a part's copy and its commit, by the
    submitter's thread name: {"name": callable}."""
    hooks = {}
    real = cs.hostmem.count_copy

    def count_copy(nbytes, site=None, warn=True):
        real(nbytes, site=site, warn=warn)
        hook = hooks.get(threading.current_thread().name)
        if hook is not None:
            hook()

    monkeypatch.setattr(cs.hostmem, "count_copy", count_copy)
    return hooks


def test_concurrent_partial_submissions_keep_reservation_order(
        fresh_service_env):
    """(a) More submitter threads than cores, a shortened switch
    interval: every result byte-exact, each submission's rows in one
    unbroken run of the launched rows and each thread's submissions in
    the order it made them; staged + borrowed rows are all the rows."""
    import sys

    svc = fresh_service_env(OZONE_TPU_CODEC_LINGER_MS="1")
    width, threads, per_thread = 4, 16, 12
    launched, lock = [], threading.Lock()

    def fn(batch):
        with lock:
            launched.append(batch[:, 0, :3].copy())
        return (batch.copy(),)

    def rows(t, s, n):
        a = np.random.default_rng(1000 * t + s).integers(
            0, 256, (n, 2, 16), dtype=np.uint8)
        a[:, 0, 0], a[:, 0, 1], a[:, 0, 2] = t + 1, s, np.arange(n)
        return a

    def work(t):
        sizes = np.random.default_rng(t).integers(1, 2 * width + 2,
                                                  per_thread)
        for s, n in enumerate(sizes):
            data = rows(t, s, int(n))
            (out,) = svc.submit(("order",), fn, data,
                                width=width).result(timeout=60)
            assert np.array_equal(out, data), (t, s)
        return True

    before = _counts()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        runs = [_in_thread(f"w{t}", lambda t=t: work(t))
                for t in range(threads)]
        for th, _ in runs:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert all(not th.is_alive() for th, _ in runs)
    assert [o[0][0] for _, o in runs] == ["ok"] * threads, runs
    # a pad row shows zeros (a fresh buffer) or a row launched before
    seen, order = set(), []
    for batch in launched:
        for tag in map(tuple, batch.tolist()):
            if tag[0] and tag not in seen:
                seen.add(tag)
                order.append(tag)
    d = _moved(before)
    assert len(order) == d["stripes_dispatched"]
    assert d["stripes_packed_at_submit"] + d["stripes_borrowed"] \
        == d["stripes_dispatched"]
    assert d["stripes_packed_at_submit"] > 0 and d["stripes_borrowed"] > 0
    at = {tag: i for i, tag in enumerate(order)}
    for t in range(threads):
        mine = [tag for tag in order if tag[0] == t + 1]
        assert mine == sorted(mine), f"thread {t} out of order"
    for (t, s, r), i in at.items():
        if r:
            assert at[(t, s, r - 1)] == i - 1, "a submission was interleaved"


def test_staging_buffer_is_not_refilled_before_its_batch_completed(
        fresh_service_env):
    """(b) A `fn` that keeps its input and looks at it again when its
    outputs are pulled: the buffer never changed under it. After the
    warm-up nearly every batch is packed into a recycled buffer."""
    svc = fresh_service_env(OZONE_TPU_CODEC_LINGER_MS="1")
    changed = []

    class Held:
        def __init__(self, batch):
            self.batch, self.snap = batch, batch.copy()

        def __array__(self, dtype=None, copy=None):
            time.sleep(0.002)  # a pull that takes a while
            changed.append(not np.array_equal(self.batch, self.snap))
            return self.snap

    def fn(batch):
        return (Held(batch),)

    def work(t):
        for s in range(40):
            data = _rand((3, 2, 256), 100 * t + s)
            (out,) = svc.submit(("held",), fn, data,
                                width=4).result(timeout=60)
            assert np.array_equal(out, data)
        return True

    def round_of_work():
        runs = [_in_thread(f"h{t}", lambda t=t: work(t)) for t in range(3)]
        for th, _ in runs:
            th.join(timeout=120)
        assert [o[0][0] for _, o in runs] == ["ok"] * 3, runs

    round_of_work()  # warm-up: the free list fills
    before = _counts()
    round_of_work()
    d = _moved(before)
    assert d["dispatches"] >= 50
    assert d["staging_buffers_reused"] / d["staging_buffers_leased"] > 0.9
    assert changed and not any(changed)


def test_poisoned_pad_rows_change_no_riders_output(svc):
    """(c) Pad rows are not zeroed: they hold an earlier batch's bytes,
    and no rider's output shows it."""
    fn = make_fused_encoder(SPEC)
    saw = []

    def spy(batch):
        saw.append(batch.copy())
        return fn(batch)

    poison = np.full((3, 3, CELL), 0xFF, dtype=np.uint8)
    cs.wait_result(svc.submit(cs.encode_key(SPEC), spy, poison, width=4))
    before = _counts()
    lone = _rand((1, 3, CELL), 41)
    p, c = cs.wait_result(svc.submit(cs.encode_key(SPEC), spy, lone,
                                     width=4))
    assert _moved(before)["staging_buffers_reused"] == 1
    assert (saw[1][1:3] == 0xFF).all(), "the pad rows were not stale"
    ref_p, ref_c = (np.asarray(x) for x in fn(lone))
    assert np.array_equal(p, ref_p) and np.array_equal(c, ref_c)


def test_full_width_lone_submission_is_not_copied(svc):
    """(d) One submission covering whole widths, no partly reserved
    batch open: launched from the submitter's own rows."""
    given = []

    def fn(batch):
        given.append(batch)
        return (batch.copy(),)

    data = _rand((8, 3, 64), 42)
    before = _counts()
    (out,) = cs.wait_result(svc.submit(("whole",), fn, data, width=4))
    d = _moved(before)
    assert np.array_equal(out, data)
    assert all(np.shares_memory(b, data) for b in given) and len(given) == 2
    assert d["stripes_borrowed"] == d["stripes_dispatched"] == 8
    assert d["submit_pack_seconds.count"] == 0
    assert d["staging_buffers_leased"] == 0
    # rows that do not lie contiguous are staged like any partial batch
    (out,) = cs.wait_result(svc.submit(
        ("whole",), fn, _rand((4, 3, 128), 43)[:, :, ::2], width=4))
    assert _moved(before)["stripes_packed_at_submit"] == 4


@pytest.mark.parametrize("taken", [False, True],
                         ids=["batch_still_open", "batch_taken_for_launch"])
@pytest.mark.parametrize("alone", [False, True],
                         ids=["with_another_rider", "alone_in_its_batch"])
def test_a_submitter_failing_mid_fill_fails_alone(fresh_service_env,
                                                  copy_hook, alone, taken):
    """(e) Its rows become pad rows; the batch launches for its other
    rider, or not at all where it had none; the lane is left clean —
    whether the failure comes before the dispatcher took the batch (a
    long linger) or while it waits for the copy in `codec:pack`."""
    svc = fresh_service_env(
        OZONE_TPU_CODEC_LINGER_MS="30" if taken else "400")
    subs, fill = [], svc._fill

    def spy_fill(lane, sub, fills):
        subs.append(sub)
        fill(lane, sub, fills)

    svc._fill = spy_fill

    def boom():
        time.sleep(0.15 if taken else 0.03)
        raise MemoryError("fill failed")

    copy_hook["doomed"] = boom
    a = _rand((1, 3, 64), 51)
    before = _counts()
    th, out = _in_thread("doomed", lambda: svc.submit(
        ("e",), _echo, _rand((2, 3, 64), 53), width=4))
    time.sleep(0.01)  # the doomed rows are reserved, their copy "lands"
    fa = None if alone else svc.submit(("e",), _echo, a, width=4)
    th.join(timeout=30)
    assert out[0][0] == "raised" and isinstance(out[0][1], MemoryError)
    doomed = [s for s in subs if s.n == 2]
    assert isinstance(doomed[0].future.exception(timeout=0), MemoryError)
    if not alone:
        assert np.array_equal(fa.result(timeout=30)[0], a)
    if taken:
        # the dispatcher waited for the copy, inside codec:pack (booked
        # when it leaves the stage, which nothing here waits for)
        t_end = time.monotonic() + 10
        while _moved(before)["pack_seconds.sum"] < 0.05:
            assert time.monotonic() < t_end, _moved(before)
            time.sleep(0.005)
    d = _moved(before)
    assert (d["dispatches"], d["stripes_dispatched"]) == \
        ((0, 0) if alone else (1, 1))
    stats = svc.stats()
    assert stats["queue_depth"] == 0 and stats["lanes"] == 0
    # and the service still serves that lane
    (again,) = svc.submit(("e",), _echo, a, width=4).result(timeout=30)
    assert np.array_equal(again, a)


@pytest.mark.parametrize("reason", ["linger", "deadline"])
def test_flushes_fire_with_a_fill_in_progress(fresh_service_env,
                                              copy_hook, reason):
    """(f) The lane is taken for its reason while a copy is still
    landing; the launch waits for the copy, inside `codec:pack`."""
    from ozone_tpu.client import resilience

    svc = fresh_service_env(OZONE_TPU_CODEC_LINGER_MS=(
        "1" if reason == "linger" else "5000"))
    copy_hook["slow"] = lambda: time.sleep(0.15)
    data = _rand((2, 3, 64), 61)

    def submit():
        if reason == "linger":
            return svc.submit(("f",), _echo, data, width=4)
        with resilience.start("near_expiry", seconds=0.5):
            return svc.submit(("f",), _echo, data, width=4)

    before = _counts()
    th, out = _in_thread("slow", submit)
    th.join(timeout=30)
    assert out[0][0] == "ok", out
    (got,) = out[0][1].result(timeout=30)
    d = _moved(before)
    assert np.array_equal(got, data)
    assert d["dispatches"] == 1
    assert d["forced_flushes" if reason == "linger"
             else "deadline_flushes"] == 1
    assert d["pack_seconds.sum"] >= 0.1, "the launch did not wait in pack"
    assert d["submit_pack_seconds.sum"] >= 0.15


def test_close_with_reserved_rows_leaves_no_future_pending(
        fresh_service_env, copy_hook):
    """(g) Rows reserved, one copy still landing, nothing triggered yet:
    close() flushes or fails every future."""
    svc = fresh_service_env(OZONE_TPU_CODEC_LINGER_MS="5000")
    copy_hook["slow"] = lambda: time.sleep(0.3)
    a, b = _rand((1, 3, 64), 71), _rand((2, 3, 64), 72)
    fa = svc.submit(("g",), _echo, a, width=4)
    th, out = _in_thread("slow", lambda: svc.submit(("g",), _echo, b,
                                                    width=4))
    time.sleep(0.05)  # b's rows are reserved, its copy is landing
    assert not fa.done()
    svc.close()
    th.join(timeout=30)
    assert out[0][0] == "ok", out
    for fut, data in ((fa, a), (out[0][1], b)):
        assert fut.done()
        if fut.exception() is None:
            assert np.array_equal(fut.result()[0], data)
    with pytest.raises(RuntimeError, match="shut down"):
        svc.submit(("g",), _echo, a, width=4)


def test_a_lane_takes_one_row_shape(svc):
    """The lane's staging buffers have the shape its first submitter
    brought; another shape under the same key is refused at submit."""
    gate = threading.Event()

    def held(batch):
        gate.wait(timeout=30)
        return (batch.copy(),)

    plug = svc.submit(("plug",), held, _rand((1, 3, 64), 81), width=1)
    try:
        fut = svc.submit(("shape",), _echo, _rand((1, 3, 64), 82), width=4)
        with pytest.raises(ValueError, match="into a lane of"):
            svc.submit(("shape",), _echo, _rand((1, 3, 32), 83), width=4)
    finally:
        gate.set()
    plug.result(timeout=30)
    assert fut.result(timeout=30)[0].shape == (1, 3, 64)
