"""One sampler thread a process keeps what the process costs its host:
CPU seconds, the host's busy share, live threads and its own lateness,
on the monotonic clock, so a reader cuts a window out of the series."""

import threading
import time

import pytest

from ozone_tpu.utils import tracing
from ozone_tpu.utils.tracing import ProcessSampler, Tracer


@pytest.fixture(scope="module", autouse=True)
def sampler():
    Tracer._instance = None
    Tracer.instance()  # a process that has a tracer has a sampler
    Tracer._instance = None
    return ProcessSampler._started


def _spin(cpu_s: float) -> None:
    end = time.thread_time() + cpu_s
    while time.thread_time() < end:
        pass


def test_one_thread_a_process_however_many_tracers(sampler):
    for _ in range(3):
        Tracer._instance = None
        Tracer.instance()
    Tracer._instance = None
    assert ProcessSampler._started is sampler
    assert [th.name for th in threading.enumerate()].count(
        "proc-sampler") == 1
    assert sampler.thread.daemon
    assert sampler.ring.maxlen == 600 / tracing.IDLE_TICK_S


def test_a_window_cut_from_the_series_holds_its_cpu_seconds():
    time.sleep(3 * tracing.IDLE_TICK_S)  # a quiet lead-in
    t0 = time.monotonic()
    time.sleep(1.5 * tracing.IDLE_TICK_S)
    _spin(0.5)
    time.sleep(1.5 * tracing.IDLE_TICK_S)
    t1 = time.monotonic()
    time.sleep(2 * tracing.IDLE_TICK_S)
    s = tracing.samples(t0, t1)
    assert all(t0 <= x[0] < t1 for x in s)
    assert [x[0] for x in s] == sorted(x[0] for x in s)
    assert len(s) >= 0.5 * (t1 - t0) / tracing.IDLE_TICK_S
    cpu = s[-1][1] - s[0][1]
    assert 0.4 <= cpu <= 0.6 + 0.2 * (t1 - t0 - 0.5)
    # the host's jiffies moved, busy no faster than total
    assert 0 < s[-1][2] - s[0][2] <= s[-1][3] - s[0][3]
    assert all(x[4] >= 2 for x in s)  # this thread and the sampler
    # nothing outside the window, and an empty cut is empty
    assert tracing.samples(t1 + 60.0, t1 + 61.0) == []
    assert len(tracing.samples()) >= len(s)


def test_the_lateness_rises_when_threads_hold_the_interpreter():
    def mean_late(t0, t1):
        s = tracing.samples(t0, t1)
        assert s
        return sum(x[5] for x in s) / len(s)

    t0 = time.monotonic()
    time.sleep(0.6)
    quiet = mean_late(t0, time.monotonic())
    stop = threading.Event()

    def hold():
        while not stop.is_set():
            sum(range(2000))

    spinners = [threading.Thread(target=hold) for _ in range(4)]
    t1 = time.monotonic()
    for th in spinners:
        th.start()
    try:
        time.sleep(0.8)
    finally:
        stop.set()
        for th in spinners:
            th.join()
    busy = mean_late(t1, time.monotonic())
    # a woken thread waits about one switch interval for its turn
    assert busy > 0.001 and busy > 3 * quiet, (quiet, busy)
    h = tracing.METRICS.histogram("interpreter_wait_seconds")
    assert h.count > 0 and h.max >= busy
    assert tracing.METRICS.gauge("process_cpu_seconds").value > 0
    assert tracing.METRICS.gauge("threads").value >= 2


def test_the_series_is_on_prom():
    from ozone_tpu.utils.metrics import prometheus_text

    text = prometheus_text(tracing.METRICS)
    for name in ("tracing_process_cpu_seconds", "tracing_threads",
                 "tracing_interpreter_wait_seconds_count"):
        assert name in text


def test_a_kernel_that_hides_the_hosts_counters_is_read_by_process(
        monkeypatch):
    """The chip machines' sandbox kernel shows /proc/stat as zeros and
    every process of the sandbox under /proc: busy is then the sum of
    their CPU, total the cores' ticks, read once a second."""
    import builtins
    import io
    import os

    real, opened = builtins.open, []

    def fake(path, *a, **kw):
        if path == "/proc/stat":
            return io.BytesIO(b"cpu  0 0 0 0 0 0 0 0 0 0\n")
        opened.append(path)
        return real(path, *a, **kw)

    monkeypatch.setattr(builtins, "open", fake)
    s = ProcessSampler()  # not started: the method alone
    now = time.monotonic()
    busy0, total0 = s._host_jiffies(now)
    assert f"/proc/{os.getpid()}/stat" in opened
    ticks = os.sysconf("SC_CLK_TCK")
    assert busy0 >= os.times().user * ticks * 0.5 > 0
    assert total0 == int(now * ticks) * os.cpu_count()
    n = len(opened)
    assert s._host_jiffies(now + 0.5) == (busy0, total0)  # the last
    assert len(opened) == n                               # no new walk
    _spin(0.3)
    busy1, total1 = s._host_jiffies(now + 1.0)
    assert len(opened) > n
    assert busy1 - busy0 >= 0.2 * ticks
    assert total1 - total0 == pytest.approx(ticks * os.cpu_count(), abs=os.cpu_count())
