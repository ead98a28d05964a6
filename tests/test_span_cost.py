"""What a span cost its thread, beside how long it took: CPU seconds,
the times the thread went to sleep (`blocks`) and the times its core was
taken away (`preempts`), for every span of a costed operation that is
bracketed on one thread; none for an interval another thread measured,
none for a root that one thread works on between others, none in an
operation that is not costed (at most one of a name every
COST_INTERVAL_S, a second, is: the calls at each edge are system calls
made with the interpreter lock held)."""

import time

import pytest

from ozone_tpu.utils import tracing
from ozone_tpu.utils.tracing import Tracer, span_json


@pytest.fixture
def t():
    return Tracer()


def _spin(cpu_s: float) -> None:
    """Burn `cpu_s` of THIS thread's CPU, however often it is preempted."""
    end = time.thread_time() + cpu_s
    while time.thread_time() < end:
        pass


@pytest.mark.parametrize("work,cpu_ms,blocks", [
    ("spin", (15.0, 25.0), None),
    ("sleep", (0.0, 2.0), 1),
], ids=["a_spin_is_cpu", "a_sleep_is_a_block"])
def test_a_span_reads_what_its_thread_spent(t, work, cpu_ms, blocks):
    with t.span("leaf") as s:
        if work == "spin":
            _spin(0.020)
        else:
            time.sleep(0.020)
    assert s.duration >= 0.020
    assert cpu_ms[0] <= s.cpu * 1e3 <= cpu_ms[1]
    assert s.thread != 0 and s.preempts >= 0
    if blocks is not None:
        assert s.blocks >= blocks


def test_a_short_spans_cpu_is_not_rounded_to_a_scheduler_tick(t):
    """Five spins of 0.5 ms each read as 0.5 ms: the CPU is
    `thread_time()`'s. getrusage(RUSAGE_THREAD)'s, scaled from the
    scheduler's ticks, read 3 ms for a 0.2 ms spin on this kernel, which
    is why `_thread_cost` takes only the switches from it."""
    with t.span("root"):
        for _ in range(5):
            with t.span("short") as s:
                _spin(0.0005)
            assert 0.0005 <= s.cpu <= 0.0015


def test_nested_spans_each_read_their_own_edges(t):
    with t.span("outer") as outer:
        _spin(0.005)
        with t.span("inner") as inner:
            _spin(0.010)
    assert inner.thread == outer.thread
    assert 0.010 <= inner.cpu <= 0.014
    assert outer.cpu >= inner.cpu + 0.005


@pytest.mark.parametrize("how", ["record_span", "begin_operation"])
def test_an_interval_nobody_bracketed_on_its_thread_carries_no_cost(t, how):
    if how == "record_span":
        s = t.record_span("codec:queue_wait", start=time.time(),
                          duration=0.25)
    else:
        s = t.begin_operation("tier:key")
        _spin(0.005)
        t.end_operation(s)
    assert (s.thread, s.cpu, s.blocks, s.preempts) == (0, 0.0, 0, 0)
    exported = span_json(s)
    assert "cpuMs" not in exported and "blocks" not in exported


def test_an_exported_span_carries_its_cost(t):
    with t.span("client:get") as s:
        time.sleep(0.002)
        _spin(0.003)
    j = span_json(s, service="client")
    assert j["cpuMs"] == round(s.cpu * 1e3, 3) >= 3.0
    assert j["blocks"] == s.blocks >= 1 and j["preempts"] == s.preempts
    assert j["durationMs"] >= j["cpuMs"]
    # and the slow-trace ring shows it (an SLO of zero keeps everything)
    rec = tracing.FlightRecorder()
    rec.offer({**j, "durationMs": 1e9}, [j])
    (kept,) = rec.trace(j["traceId"])["spans"]
    assert kept["cpuMs"] == j["cpuMs"]


def test_one_operation_of_a_name_a_second_is_costed(t):
    """The first root of a name, then at most one every COST_INTERVAL_S,
    whatever the operation rate; each name has its own turn."""
    took = []
    t0 = time.monotonic()
    while time.monotonic() - t0 < 2.2 * tracing.COST_INTERVAL_S:
        with t.operation("client:get") as root:
            with t.span("ec:read") as child:
                time.sleep(0.002)
        assert bool(child.thread) == bool(root.thread) \
            == tracing.costed(root.trace_id)
        took.append(bool(root.thread))
    assert took[0] and sum(took) == 3 and len(took) > 50
    with t.operation("client:put") as other:
        pass
    assert other.thread  # another name's first
    recs = t.recorder.operations("client:get")
    # and a record that is not costed keeps `stages` alone
    for key in ("cost", "handoffs", "rpc"):
        assert [(key in r) for r in recs] == took


def test_a_trace_knows_on_every_thread_and_in_every_daemon(t):
    """The root's decision rides the trace id's end: a worker and a
    server that take up the context follow it."""
    import threading

    seen = {}

    def serve(ctx, key):
        with t.span("server:GetBlock", child_of=ctx) as s:
            _spin(0.001)
        seen[key] = s

    for key in ("costed", "plain"):
        with t.operation("client:get") as root:
            th = threading.Thread(target=serve, args=(t.inject(), key))
            th.start()
            th.join()
        seen[key + "_root"] = root
    assert seen["costed_root"].thread and seen["costed"].thread
    assert seen["costed"].cpu >= 0.001
    assert not seen["plain_root"].thread
    assert (seen["plain"].thread, seen["plain"].cpu) == (0, 0.0)
    assert "cpuMs" not in span_json(seen["plain"])
    # an id from elsewhere is read the same way, and no id of hex
    # digits alone (another tracer's, an older client's) is costed
    assert tracing.costed("00000000000000a0-c")
    assert not any(tracing.costed(f"00000000000000a{d:x}")
                   for d in range(16)) and not tracing.costed("")


def test_a_kernel_that_counts_no_switches_is_not_asked(t, monkeypatch):
    """The chip machines' sandbox kernel reports 0 context switches:
    the module finds that out once, at import, and the spans leave
    `getrusage` out."""
    import resource

    calls = []
    real = resource.getrusage
    monkeypatch.setattr(resource, "getrusage",
                        lambda who: calls.append(who) or real(who))
    monkeypatch.setattr(tracing, "_SWITCHES_COUNTED", False)
    with t.span("leaf") as s:
        _spin(0.002)
        time.sleep(0.002)
    assert calls == [] and s.cpu >= 0.002 and s.blocks == 0
    monkeypatch.setattr(tracing, "_SWITCHES_COUNTED", True)
    with t.span("leaf2") as s:
        time.sleep(0.002)
    assert len(calls) == 2 and s.blocks >= 1


def test_this_kernel_counts_switches():
    """Decided once at import, before any span opens: a span open
    across a change would book negative `blocks`."""
    assert tracing._SWITCHES_COUNTED is True
