"""The codec dispatcher's timeline: the one dispatcher thread books every
stretch of its time to one of five leaf stages that never nest —
`codec:idle` (nothing ready, nothing in flight), `codec:pack`,
`codec:launch`, `codec:d2h`, `codec:complete` (after the pull until the
last rider's future is resolved) — each a histogram of `codec.service`,
and mirrors them to the profiler's own trace while a session is on."""

import subprocess
import sys
import time

import numpy as np
import pytest

from ozone_tpu.codec import service as cs
# the dispatcher's first pass imports this (and JAX with it): here, so
# that no test's timeline starts with a second of importing
from ozone_tpu.parallel import mesh_executor  # noqa: F401
from ozone_tpu.utils.tracing import Stage, Tracer

STAGES = ("idle", "pack", "launch", "d2h", "complete")
LAUNCH_S, D2H_S = 0.02, 0.01


class _Lazy:
    """A device array's stand-in: the pull to the host takes D2H_S."""

    def __init__(self, a: np.ndarray):
        self.a = a

    def __array__(self, dtype=None, copy=None):
        time.sleep(D2H_S)
        return self.a


def _slow_fn(batch: np.ndarray):
    time.sleep(LAUNCH_S)
    return (_Lazy(batch.copy()),)


def _book():
    """(sum, count) of every stage histogram and of dispatch_seconds."""
    return {k: (cs.METRICS.histogram(f"{k}_seconds").total,
                cs.METRICS.histogram(f"{k}_seconds").count)
            for k in STAGES + ("dispatch",)}


def _delta(after, before):
    return {k: (after[k][0] - before[k][0], after[k][1] - before[k][1])
            for k in after}


@pytest.fixture
def fresh(monkeypatch):
    monkeypatch.setenv("OZONE_TPU_CODEC_LINGER_MS", "1")
    cs.reset_for_tests()
    yield
    cs.reset_for_tests()


@pytest.mark.parametrize("whole", [True, False],
                         ids=["one_submission_fast_path", "two_coalesced"])
def test_stage_histograms_follow_a_scripted_sequence(fresh, whole):
    before = _book()
    t_start = time.monotonic()
    svc = cs.get_service()
    # 1. nothing submitted: the dispatcher is starved, and says so while
    # it still waits (a tick at a time, not one observation at the end)
    time.sleep(0.2)
    idle_s, idle_n = _delta(_book(), before)["idle"]
    assert idle_n >= 2 and 0.1 <= idle_s <= 0.25
    assert _delta(_book(), before)["pack"] == (0.0, 0)
    # 2. three dispatches, one at a time
    data = np.arange(4 * 3 * 64, dtype=np.uint8).reshape(4, 3, 64)
    for _ in range(3):
        if whole:
            futs = [svc.submit(("k",), _slow_fn, data, width=4)]
        else:  # two operations' stripes staged into one batch
            futs = [svc.submit(("k",), _slow_fn, data[:2], width=4),
                    svc.submit(("k",), _slow_fn, data[2:], width=4)]
        outs = [cs.wait_result(f)[0] for f in futs]
        assert np.array_equal(np.concatenate(outs), data)
    d = _delta(_book(), before)
    assert d["pack"][1] == d["launch"][1] == d["d2h"][1] == 3
    assert d["complete"][1] == 3 and 0 < d["complete"][0] < 3 * D2H_S
    assert d["dispatch"][1] == 3
    assert d["launch"][0] >= 3 * LAUNCH_S
    assert 3 * D2H_S <= d["d2h"][0] < d["launch"][0]
    assert 0 < d["pack"][0] < 3 * LAUNCH_S
    # dispatch_seconds keeps its meaning, launch to host arrays: what is
    # left of it after launch and d2h is the time the batch was held
    hold = d["dispatch"][0] - d["launch"][0] - d["d2h"][0]
    assert -1e-3 <= hold < 0.05
    # the operator's view (/api/codec): the same split, since start
    took = svc.stats()["dispatcher_seconds"]
    assert set(took) == {"idle", "pack", "launch", "d2h", "complete",
                         "hold"}
    assert took["launch"] >= d["launch"][0] and took["hold"] >= 0.0
    # 3. the stages never overlap: together they never exceed the wall
    # time the thread ran, and leave little of it out
    cs.reset_for_tests()  # joins the dispatcher
    wall = time.monotonic() - t_start
    d = _delta(_book(), before)
    booked = sum(d[k][0] for k in STAGES)
    assert 0.8 * wall <= booked <= wall, (booked, wall, d)


def test_a_busy_dispatcher_books_no_idle_while_work_is_in_flight(fresh):
    """Idle is only `_cond.wait` with nothing ready AND nothing in
    flight: a batch waiting to be pulled is completed, not idled on."""
    svc = cs.get_service()
    data = np.zeros((4, 3, 64), dtype=np.uint8)
    cs.wait_result(svc.submit(("k",), _slow_fn, data, width=4))
    before = _book()
    t0 = time.monotonic()
    futs = [svc.submit(("k",), _slow_fn, data, width=4) for _ in range(6)]
    for f in futs:
        cs.wait_result(f)
    wall = time.monotonic() - t0
    d = _delta(_book(), before)
    assert d["launch"][1] == 6
    busy = d["pack"][0] + d["launch"][0] + d["d2h"][0]
    assert busy >= 6 * (LAUNCH_S + D2H_S)
    assert d["idle"][0] <= max(0.0, wall - busy) + 0.01, (d, wall)


def test_a_failed_launch_still_books_its_stage(fresh):
    svc = cs.get_service()
    before = _book()

    def broken(batch):
        time.sleep(LAUNCH_S)
        raise RuntimeError("no such program")

    fut = svc.submit(("k",), broken, np.zeros((4, 3, 8), np.uint8), width=4)
    with pytest.raises(RuntimeError, match="no such program"):
        fut.result(timeout=10)
    d = _delta(_book(), before)
    assert d["launch"][1] == 1 and d["launch"][0] >= LAUNCH_S
    assert d["d2h"][1] == 0 and d["dispatch"][1] == 0


def test_stages_reach_the_profiler_trace_and_spans_do_not(fresh, tmp_path):
    """In a profiling session the host plane holds the four leaf stages
    on the dispatcher's thread, on the device trace's own clock, and no
    other span of the program."""
    import glob

    import jax
    from jax.profiler import ProfileData

    svc = cs.get_service()
    data = np.zeros((4, 3, 64), dtype=np.uint8)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with Tracer.instance().operation("client:put"):
            with Tracer.instance().span("ec:flush"):
                cs.wait_result(svc.submit(("k",), _slow_fn, data, width=4))
        time.sleep(0.12)  # two idle ticks inside the session
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
    by_line: dict[str, set[str]] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                names = {e.name for e in line.events}
                if any(n.startswith(("codec:", "client:", "ec:"))
                       for n in names):
                    by_line[line.name] = names
    assert len(by_line) == 1, by_line  # one thread: the dispatcher's
    (names,) = by_line.values()
    ours = {n for n in names if n.startswith(("codec:", "client:", "ec:"))}
    assert ours == {"codec:idle", "codec:pack", "codec:launch",
                    "codec:d2h", "codec:complete"}


def test_a_stage_outside_a_session_is_cheap_and_imports_no_jax():
    """Outside a profiling session a stage is one is_enabled() call on
    top of its histogram; in a process without JAX it is the histogram
    alone and tracing never imports JAX."""
    code = (
        "import sys\n"
        "from ozone_tpu.utils.metrics import Histogram\n"
        "from ozone_tpu.utils.tracing import Stage\n"
        "h = Histogram()\n"
        "with Stage('codec:idle', h):\n"
        "    pass\n"
        "assert h.count == 1\n"
        "assert 'jax' not in sys.modules, 'tracing imported jax'\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)

    import jax  # noqa: F401  (this process has it: the mirror is armed)

    class Null:
        def observe(self, seconds):
            pass

    null, n = Null(), 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with Stage("codec:idle", null):
            pass
    per_stage_us = 1e6 * (time.perf_counter() - t0) / n
    assert per_stage_us < 20.0, per_stage_us  # ~1 us; 20 on a loaded rig
