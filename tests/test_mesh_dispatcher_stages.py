"""The mesh dispatcher's timeline, held to what
`test_codec_dispatcher_stages.py` holds the codec dispatcher to: the one
`mesh-executor` thread books every stretch of its time to one of four
leaf stages that never nest — `mesh:idle` (nothing ready, nothing in
flight), `mesh:pack`, `mesh:launch`, `mesh:d2h` — each a histogram of
registry `mesh`, mirrored to the profiler's own trace while a session is
on; `mesh:queue_wait` and `mesh:device_dispatch` stay spans of the
submitting operation and land in its stage record."""

import time

import numpy as np
import pytest

from ozone_tpu.parallel import mesh_executor as me
from ozone_tpu.parallel.sharded import make_mesh
from ozone_tpu.utils.tracing import Tracer
from tests.test_codec_dispatcher_stages import (
    D2H_S,
    LAUNCH_S,
    STAGES,
    _delta,
    _slow_fn,
)

KEY = ("encode", "scripted")


def _book():
    """(sum, count) of every stage histogram and of dispatch_seconds."""
    return {k: (me.METRICS.histogram(f"{k}_seconds").total,
                me.METRICS.histogram(f"{k}_seconds").count)
            for k in STAGES + ("dispatch",)}


@pytest.fixture(autouse=True)
def alone():
    """The process-wide executor an earlier test file may have left
    running books its own idle ticks into the same registry."""
    me.reset_for_tests()


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
def test_stage_histograms_follow_a_scripted_sequence(monkeypatch, whole):
    monkeypatch.setenv("OZONE_TPU_MESH_LINGER_MS", "20")
    before = _book()
    t_start = time.monotonic()
    ex = me.MeshExecutor(mesh=make_mesh(4), depth=2)
    ex._programs[KEY] = me._MeshProgram(_slow_fn, (), True)
    try:
        # 1. nothing submitted: the dispatcher is starved, and says so
        # while it still waits (a tick at a time)
        time.sleep(0.2)
        idle_s, idle_n = _delta(_book(), before)["idle"]
        assert idle_n >= 2 and 0.1 <= idle_s <= 0.25
        assert _delta(_book(), before)["pack"] == (0.0, 0)
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
        d = _delta(_book(), before)
        assert d["pack"][1] == d["launch"][1] == d["d2h"][1] == 3
        assert d["dispatch"][1] == 3
        assert d["launch"][0] >= 3 * LAUNCH_S
        assert 3 * D2H_S <= d["d2h"][0] < d["launch"][0]
        assert 0 < d["pack"][0] < 3 * LAUNCH_S
        # dispatch_seconds keeps its meaning, launch to host arrays
        hold = d["dispatch"][0] - d["launch"][0] - d["d2h"][0]
        assert -1e-3 <= hold < 0.05
        # the operator's view (/api/mesh): the same split, since start
        took = ex.stats()["dispatcher_seconds"]
        assert set(took) == {"idle", "pack", "launch", "d2h", "hold"}
        assert took["launch"] >= d["launch"][0] and took["hold"] >= 0.0
    finally:
        ex.close()  # joins the dispatcher
    # 3. the stages never overlap: together they never exceed the wall
    # time the thread ran, and leave little of it out
    wall = time.monotonic() - t_start
    d = _delta(_book(), before)
    booked = sum(d[k][0] for k in STAGES)
    assert 0.8 * wall <= booked <= wall, (booked, wall, d)


def test_a_busy_dispatcher_books_no_idle_while_work_is_in_flight(mesh4):
    """Idle is only `_cond.wait` with no lane ready AND nothing in
    flight: a batch waiting to be pulled is completed, not idled on."""
    data = np.zeros((4, 3, 64), dtype=np.uint8)
    mesh4.submit(KEY, data, width=1).result(timeout=10)
    before = _book()
    t0 = time.monotonic()
    futs = [mesh4.submit(KEY, data, width=1) for _ in range(6)]
    for f in futs:
        f.result(timeout=10)
    wall = time.monotonic() - t0
    d = _delta(_book(), before)
    assert d["launch"][1] == 6
    busy = d["pack"][0] + d["launch"][0] + d["d2h"][0]
    assert busy >= 6 * (LAUNCH_S + D2H_S) - D2H_S
    assert d["idle"][0] <= max(0.0, wall - busy) + 0.01, (d, wall)


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
    """In a profiling session the host plane holds the four leaf stages
    on the dispatcher's thread, on the device trace's own clock, and no
    other span of the program."""
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
    by_line: dict[str, set[str]] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                names = {e.name for e in line.events}
                if any(n.startswith(("mesh:", "repair:"))
                       for n in names):
                    by_line[line.name] = names
    assert len(by_line) == 1, by_line  # one thread: the dispatcher's
    (names,) = by_line.values()
    ours = {n for n in names if n.startswith(("mesh:", "repair:"))}
    assert ours == {"mesh:idle", "mesh:pack", "mesh:launch", "mesh:d2h"}
