"""The mesh executor's two timelines, held to what
`test_codec_dispatcher_stages.py` holds the codec dispatcher to. Each of
its two threads books every stretch of its loop to leaf stages that
never nest, each a histogram of registry `mesh`, mirrored to the
profiler's own trace while a session is on: the dispatcher
(`mesh-executor`) `mesh:idle`, `mesh:pack`, `mesh:launch` and
`mesh:window_full` (ahead of the completer by the whole window); the
completer (`mesh-completer`) `mesh:completer_idle`, `mesh:d2h` and
`mesh:complete`. `mesh:queue_wait` and `mesh:device_dispatch` stay
spans of the submitting operation and land in its stage record."""

import threading
import time

import numpy as np
import pytest

from ozone_tpu.parallel import mesh_executor as me
from ozone_tpu.parallel.sharded import make_mesh
from ozone_tpu.utils.tracing import Stage, Tracer
from tests.test_codec_dispatcher_stages import (
    D2H_S,
    LAUNCH_S,
    _delta,
    _Lazy,
    _slow_fn,
)

KEY = ("encode", "scripted")
DISPATCHER = ("idle", "pack", "launch", "window_full")
COMPLETER = ("completer_idle", "d2h", "complete")


def _book():
    """(sum, count) of every stage histogram and of dispatch_seconds."""
    return {k: (me.METRICS.histogram(f"{k}_seconds").total,
                me.METRICS.histogram(f"{k}_seconds").count)
            for k in me.STAGES + ("dispatch",)}


@pytest.fixture(autouse=True)
def alone():
    """The process-wide executor an earlier test file may have left
    running books its own idle ticks into the same registry."""
    me.reset_for_tests()


@pytest.fixture
def timeline(monkeypatch):
    """Every stage either thread books, as (thread, stage, start, end)."""
    seen: list[tuple] = []

    class Recorded(Stage):
        __slots__ = ()

        def __exit__(self, *exc):
            super().__exit__(*exc)
            seen.append((threading.current_thread().name,
                         self.name.split(":", 1)[1], self._t0,
                         time.monotonic()))

    monkeypatch.setattr(me, "Stage", Recorded)
    return seen


def _of(timeline, thread: str, *stages: str) -> list[tuple]:
    return sorted((t0, t1) for th, st, t0, t1 in timeline
                  if th == thread and (not stages or st in stages))


@pytest.fixture
def mesh4(monkeypatch):
    # long enough that two halves submitted back to back meet in one
    # batch on a loaded machine; a full lane never waits for it
    monkeypatch.setenv("OZONE_TPU_MESH_LINGER_MS", "20")
    ex = me.MeshExecutor(mesh=make_mesh(4), depth=2)
    ex._programs[KEY] = me._MeshProgram(_slow_fn, (), True)
    yield ex
    ex.close()


@pytest.mark.parametrize("whole", [True, False],
                         ids=["one_submission_fast_path", "two_coalesced"])
def test_stage_histograms_follow_a_scripted_sequence(monkeypatch, timeline,
                                                     whole):
    monkeypatch.setenv("OZONE_TPU_MESH_LINGER_MS", "20")
    mesh = make_mesh(4)
    before = _book()
    t_start = time.monotonic()
    ex = me.MeshExecutor(mesh=mesh, depth=2)
    ex._programs[KEY] = me._MeshProgram(_slow_fn, (), True)
    try:
        # 1. nothing submitted: both threads are starved, and say so
        # while they still wait (a tick at a time)
        time.sleep(0.2)
        d = _delta(_book(), before)
        for k in ("idle", "completer_idle"):
            assert d[k][1] >= 2 and 0.1 <= d[k][0] <= 0.25, (k, d[k])
        assert d["pack"] == d["d2h"] == d["window_full"] == (0.0, 0)
        # 2. three dispatches of the lane's width (1 x 4 devices), one at
        # a time
        data = np.arange(4 * 3 * 64, dtype=np.uint8).reshape(4, 3, 64)
        for _ in range(3):
            if whole:
                futs = [ex.submit(KEY, data, width=1)]
            else:  # two operations' stripes staged into one batch
                futs = [ex.submit(KEY, data[:2], width=1),
                        ex.submit(KEY, data[2:], width=1)]
            outs = [f.result(timeout=10)[0] for f in futs]
            assert np.array_equal(np.concatenate(outs), data)
        ex.quiesce()
        time.sleep(0.2)  # and both starve again
        d = _delta(_book(), before)
        assert d["pack"][1] == d["launch"][1] == d["d2h"][1] == 3
        assert d["dispatch"][1] == d["complete"][1] == 3
        assert d["launch"][0] >= 3 * LAUNCH_S
        assert 3 * D2H_S <= d["d2h"][0] < d["launch"][0]
        assert 0 < d["pack"][0] < 3 * LAUNCH_S
        assert 0 < d["complete"][0] < 3 * LAUNCH_S
        # one batch at a time never fills the window
        assert d["window_full"] == (0.0, 0)
        # dispatch_seconds keeps its meaning, launch to host arrays, and
        # a batch is pulled as it lands: nothing holds it
        hold = d["dispatch"][0] - d["launch"][0] - d["d2h"][0]
        assert -1e-3 <= hold < 0.03
        # the operator's view (/api/mesh): both threads, since start
        took = ex.stats()["dispatcher_seconds"]
        assert set(took) == set(DISPATCHER + COMPLETER) | {"hold"}
        assert took["launch"] >= d["launch"][0] and took["hold"] >= 0.0
    finally:
        ex.close()  # joins both threads
    # 3. on each thread the stages never overlap, and leave little of
    # the time it ran out
    wall = time.monotonic() - t_start
    d = _delta(_book(), before)
    for thread, stages in (("mesh-executor", DISPATCHER),
                           ("mesh-completer", COMPLETER)):
        spans = _of(timeline, thread)
        assert {st for th, st, _, _ in timeline if th == thread} \
            <= set(stages)
        assert all(a[1] <= b[0] for a, b in zip(spans, spans[1:])), thread
        booked = sum(d[k][0] for k in stages)
        assert booked == pytest.approx(
            sum(t1 - t0 for t0, t1 in spans), abs=5e-3)
        gaps = sorted(((b[0] - a[1], a[1] - t_start)
                       for a, b in zip(spans, spans[1:])), reverse=True)[:3]
        assert 0.95 * wall <= booked <= wall, (thread, booked, wall, gaps)


def test_a_dispatcher_ahead_of_the_completer_waits_in_window_full(
        mesh4, timeline):
    """The dispatcher launches batch N+1 while N is being pulled, stops
    at `depth` launched and not yet taken (plus the one being pulled),
    and books that wait as `mesh:window_full`: it is not starved."""
    pull_s = 0.04

    class Slow(_Lazy):
        def __array__(self, dtype=None, copy=None):
            time.sleep(pull_s)
            return self.a

    key = ("encode", "slow-pull")
    mesh4._programs[key] = me._MeshProgram(
        lambda batch: (Slow(batch.copy()),), (), True)
    data = np.zeros((4, 3, 64), dtype=np.uint8)
    mesh4.submit(key, data, width=1).result(timeout=10)
    mesh4.quiesce()
    del timeline[:]
    before = _book()
    mesh4._max_inflight = 0
    unresolved = []
    t0 = time.monotonic()
    futs = [mesh4.submit(key, data, width=1) for _ in range(8)]
    while not futs[-1].done():
        unresolved.append(sum(not f.done() for f in futs)
                          - mesh4.stats()["queue_depth"] // 4)
        time.sleep(0.002)
    for f in futs:
        f.result(timeout=10)
    mesh4.quiesce()
    d = _delta(_book(), before)
    assert d["launch"][1] == d["d2h"][1] == 8
    # never more than depth + 1 packed and not yet resolved
    assert 0 < mesh4._max_inflight <= mesh4.depth + 1
    assert max(unresolved) <= mesh4.depth + 1
    launches = _of(timeline, "mesh-executor", "launch")
    pulls = _of(timeline, "mesh-completer", "d2h")
    assert any(l0 < p1 and p0 < l1
               for l0, l1 in launches for p0, p1 in pulls), \
        "no batch was launched while another was pulled"
    # 8 batches through a window of 3: the dispatcher waits out about
    # five pulls, as window_full; until its last launch it never idles
    assert d["window_full"][0] >= 4 * pull_s
    last_launch = launches[-1][0]
    idle = sum(min(t1, last_launch) - s0
               for s0, t1 in _of(timeline, "mesh-executor", "idle")
               if t0 <= s0 < last_launch)
    assert idle <= 0.01, (idle, d)
    # and the completer, always with a batch to take, never idles
    # before its last pull
    last_pull = pulls[-1][0]
    assert sum(min(t1, last_pull) - s0
               for s0, t1 in _of(timeline, "mesh-completer",
                                 "completer_idle")
               if pulls[0][0] <= s0 < last_pull) <= 0.01


def test_a_batch_is_let_go_of_inside_complete_with_the_lock_free(
        mesh4, timeline):
    """The riders' rows (tens of MiB each on the chip) are freed when
    the batch's record dies: on the completer, inside `mesh:complete`,
    and never while the executor's lock is held."""
    freed: list[tuple] = []

    class Rows(np.ndarray):
        def __array_finalize__(self, parent):
            # the submission itself, not the slices and copies of it
            self.mine = not isinstance(parent, Rows)

        def __del__(self):
            if self.mine:
                freed.append((threading.current_thread().name,
                              mesh4._lock.locked(), time.monotonic()))

    for rows in (4, 2):  # borrowed whole, then staged with a pad
        del freed[:], timeline[:]
        fut = mesh4.submit(
            KEY, np.zeros((rows, 3, 64), dtype=np.uint8).view(Rows),
            width=1)
        fut.result(timeout=10)
        mesh4.quiesce()
        (thread, locked, at), = freed
        assert thread == "mesh-completer" and not locked
        (t0, t1), = _of(timeline, "mesh-completer", "complete")
        assert t0 <= at <= t1


def test_a_failed_launch_still_books_its_stage(mesh4):
    def broken(batch):
        time.sleep(LAUNCH_S)
        raise RuntimeError("no such program")

    mesh4._programs[("encode", "broken")] = me._MeshProgram(broken, (), True)
    before = _book()
    fut = mesh4.submit(("encode", "broken"),
                       np.zeros((4, 3, 8), np.uint8), width=1)
    with pytest.raises(RuntimeError, match="no such program"):
        fut.result(timeout=10)
    d = _delta(_book(), before)
    assert d["launch"][1] == 1 and d["launch"][0] >= LAUNCH_S
    assert d["d2h"][1] == 0 and d["dispatch"][1] == 0
    # and the batch counts no more: the window is not a slot short
    assert mesh4.stats()["inflight"] == 0


def test_a_failed_pull_fails_its_batch_alone_and_returns_the_buffer(mesh4):
    """A pull that raises fails every rider of that batch and no other,
    and the staged batch goes back to the pool."""

    class Torn(_Lazy):
        def __array__(self, dtype=None, copy=None):
            if self.a[0, 0, 0] == 255:
                raise RuntimeError("link down")
            return self.a

    key = ("encode", "torn-pull")
    mesh4._programs[key] = me._MeshProgram(
        lambda batch: (Torn(batch.copy()),), (), True)
    good = np.full((2, 3, 64), 7, dtype=np.uint8)
    bad = np.full((2, 3, 64), 255, dtype=np.uint8)
    before = _book()
    # two riders a batch (staged, not borrowed): good, torn, good
    futs = [mesh4.submit(key, rows, width=1)
            for rows in (good, good, bad, bad, good, good)]
    for f in futs[2:4]:
        with pytest.raises(RuntimeError, match="link down"):
            f.result(timeout=10)
    for f in futs[:2] + futs[4:]:
        assert np.array_equal(f.result(timeout=10)[0], good)
    mesh4.quiesce()
    d = _delta(_book(), before)
    assert d["launch"][1] == d["d2h"][1] == 3
    assert d["complete"][1] == d["dispatch"][1] == 2
    stats = mesh4.stats()
    assert stats["inflight"] == 0 and stats["queue_depth"] == 0
    # every staged batch is back: the next three packs reuse, none
    # allocates
    free = sum(len(v) for v in mesh4._staging.values())
    assert 1 <= free <= mesh4.depth + 1
    reuses = me.METRICS.counter("staging_reuses").value
    for f in [mesh4.submit(key, good, width=1) for _ in range(2 * free)]:
        f.result(timeout=10)
    assert me.METRICS.counter("staging_reuses").value == reuses + free


@pytest.mark.parametrize("stuck", [False, True],
                         ids=["drains_what_was_launched",
                              "fails_what_a_stuck_pull_holds"])
def test_close_with_batches_at_the_completer_joins_both_threads(
        monkeypatch, stuck):
    """`close()` with batches queued at the completer: they still land
    where the pull returns; where it does not, `close()` comes back
    inside its timeout and every pending rider fails. Either way no
    future is left unresolved."""
    release = threading.Event()

    class Held(_Lazy):
        def __array__(self, dtype=None, copy=None):
            release.wait(timeout=20)
            return self.a

    monkeypatch.setattr(me, "CLOSE_TIMEOUT_S", 0.3 if stuck else 30.0)
    ex = me.MeshExecutor(mesh=make_mesh(4), depth=2)
    ex._programs[KEY] = me._MeshProgram(
        lambda batch: (Held(batch.copy()),), (), True)
    data = np.ones((4, 3, 64), dtype=np.uint8)
    try:
        # one being pulled, two launched behind it, two still in a lane
        futs = [ex.submit(KEY, data, width=1) for _ in range(5)]
        t_end = time.monotonic() + 10
        while ex.stats()["inflight"] < 3 and time.monotonic() < t_end:
            time.sleep(0.002)
        assert ex.stats()["inflight"] == 3
        if not stuck:
            release.set()
        t0 = time.monotonic()
        ex.close()
        took = time.monotonic() - t0
        assert all(f.done() for f in futs)
        if stuck:
            assert 0.25 <= took < 2.0
            for f in futs:
                with pytest.raises(RuntimeError, match="shut down|stopped"):
                    f.result(timeout=0)
        else:
            assert took < 5.0
            for f in futs:
                assert np.array_equal(f.result(timeout=0)[0], data)
        with pytest.raises(RuntimeError, match="shut down"):
            ex.submit(KEY, data, width=1)
    finally:
        release.set()
    # the held pull returns into futures that already failed: harmless,
    # and both threads end
    ex._thread.join(timeout=10)
    ex._completer.join(timeout=10)
    assert not ex._thread.is_alive() and not ex._completer.is_alive()
    assert ex.stats()["inflight"] == 0


def test_queue_wait_and_device_dispatch_land_in_the_stage_record(mesh4):
    """The two spans the dispatcher records on a submission's behalf are
    on the monotonic clock of the operation's own spans, so the root's
    stages still sum to its duration with `mesh:*` among them."""
    data = np.zeros((2, 3, 64), dtype=np.uint8)  # a partial batch: lingers
    t0 = time.monotonic()
    with Tracer.instance().operation("repair:container"):
        with Tracer.instance().span("repair:block"):
            mesh4.submit(KEY, data, width=1).result(timeout=10)
    (rec,) = Tracer.instance().recorder.operations(
        "repair:container", t0, float("inf"))
    assert abs(sum(rec["stages"].values()) - rec["durationUs"]) \
        <= len(rec["stages"])
    assert rec["stages"]["mesh:device_dispatch"] >= 1e6 * LAUNCH_S
    assert rec["stages"]["mesh:queue_wait"] >= 10_000  # the linger
    assert set(rec["stages"]) <= {"repair:container", "repair:block",
                                  "mesh:queue_wait", "mesh:device_dispatch"}


def test_stages_reach_the_profiler_trace_and_spans_do_not(mesh4, tmp_path):
    """In a profiling session the host plane holds each thread's leaf
    stages on that thread's line, on the device trace's own clock, and
    no other span of the program."""
    import glob

    import jax
    from jax.profiler import ProfileData

    data = np.zeros((4, 3, 64), dtype=np.uint8)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with Tracer.instance().operation("repair:container"):
            with Tracer.instance().span("repair:block"):
                mesh4.submit(KEY, data, width=1).result(timeout=10)
        time.sleep(0.12)  # two idle ticks inside the session
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
    by_line: dict[tuple, set[str]] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for n, line in enumerate(plane.lines):
                names = {e.name for e in line.events
                         if e.name.startswith(("mesh:", "repair:"))}
                if names:
                    by_line[(plane.name, n)] = names
    # two threads, each with its own stages; no span of the program
    assert sorted(by_line.values(), key=sorted) == [
        {"mesh:completer_idle", "mesh:d2h", "mesh:complete"},
        {"mesh:idle", "mesh:pack", "mesh:launch"}], by_line
