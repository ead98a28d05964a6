"""The stage record of an operation keeps, beside the critical path
(`stages`), what every span of the trace cost (`cost`), on the path or
off it: self wall, self CPU, blocks, preempts by span name, a span's
self leaving out its children on the SAME thread."""

import threading
import time

import pytest

from ozone_tpu.utils import tracing
from ozone_tpu.utils.tracing import Tracer


@pytest.fixture
def t():
    return Tracer()


def _spin(cpu_s: float) -> None:
    end = time.thread_time() + cpu_s
    while time.thread_time() < end:
        pass


def _get_like(t: Tracer, workers: int):
    """A root, a child that fans out to `workers` threads (each with a
    span of its own), a leaf that spins and an interval recorded by
    another thread. Returns (root, the workers' top spans)."""
    tops = []

    def work(ctx):
        with t.activate(ctx), t.span("net:read_chunks") as top:
            time.sleep(0.003)
            with t.span("ec:fill"):
                _spin(0.002)
        tops.append(top)

    with t.operation("client:get") as root:
        with t.span("ec:read"):
            with t.span("ec:fanout"):
                ths = [threading.Thread(target=work, args=(t.handoff(),))
                       for _ in range(workers)]
                for th in ths:
                    th.start()
                for th in ths:
                    th.join()
            t.record_span("codec:dispatch", child_of=t.inject(),
                          start=time.time() - 0.004, duration=0.004,
                          mono=time.monotonic() - 0.004)
            with t.span("ec:assemble"):
                _spin(0.004)
        time.sleep(0.001)
    return root, tops


@pytest.mark.parametrize("workers", [1, 3])
def test_stages_still_sum_to_the_duration_and_cost_covers_every_span(
        t, workers):
    root, tops = _get_like(t, workers)
    (rec,) = t.recorder.operations("client:get")
    assert sum(rec["stages"].values()) == pytest.approx(
        rec["durationUs"], abs=len(rec["stages"]))
    assert rec["durationUs"] == round(root.duration * 1e6)
    # every span name of the trace, on the critical path or off it
    assert set(rec["cost"]) == {"client:get", "ec:read", "ec:fanout",
                                "net:read_chunks", "ec:fill",
                                "codec:dispatch", "ec:assemble"}
    # a leaf's self is the leaf: the spins are CPU, the wait is not
    wall, cpu, blocks, _pre = rec["cost"]["ec:assemble"]
    assert 4000 <= cpu <= wall
    wall, cpu, _b, _p = rec["cost"]["ec:fill"]
    assert cpu >= workers * 2000
    # the interval another thread measured: wall, and no cost
    assert rec["cost"]["codec:dispatch"] == [4000, 0, 0, 0]
    # the fan-out's self waits for its threads: it went to sleep
    assert rec["cost"]["ec:fanout"][2] >= 1
    # the operation's CPU in this process is the sum of the selfs
    total_cpu = sum(c[1] for c in rec["cost"].values())
    assert total_cpu >= 4000 + workers * 2000
    assert total_cpu == pytest.approx(
        1e6 * (root.cpu + sum(s.cpu for s in tops)), abs=50)


def test_the_self_walls_of_one_threads_spans_sum_to_its_top_span(t):
    root, tops = _get_like(t, 2)
    (rec,) = t.recorder.operations("client:get")
    by_thread = {}
    for s in t.traces(root.trace_id):
        if s.thread:
            by_thread.setdefault(s.thread, []).append(s)
    assert len(by_thread) == 3  # the caller's and two workers'
    for thread, spans in by_thread.items():
        top = max(spans, key=lambda s: s.duration)
        ids = {s.span_id: s for s in spans}
        selfs = 0.0
        for s in spans:
            selfs += s.duration - sum(
                c.duration for c in spans if c.parent_id == s.span_id)
        assert selfs == pytest.approx(top.duration, abs=1e-9)
        assert top.parent_id not in ids
    # and the record's sums say the same for the caller's thread, whose
    # names no worker shares
    mine = ("client:get", "ec:read", "ec:fanout", "ec:assemble")
    assert sum(rec["cost"][n][0] for n in mine) == pytest.approx(
        rec["durationUs"], abs=len(mine))


def _copy_like(t: Tracer, leaf: bool):
    """A root whose child hands a copy to a worker and copies itself;
    with `leaf`, each copy is a `cost_leaf` (the worker's with a child
    span of its own). Returns the operation's record."""
    def copy(name, child=""):
        if not leaf:
            _spin(0.002)
            if child:
                with t.span(child):
                    time.sleep(0.002)
            return
        with t.cost_leaf(name):
            _spin(0.002)
            if child:
                with t.span(child):
                    time.sleep(0.002)

    def work(ctx):
        with t.activate(ctx):
            copy("ec:fill", child="net:read_chunk")

    with t.operation("client:get"):
        with t.span("ec:fanout"):
            th = threading.Thread(target=work, args=(t.handoff(),))
            th.start()
            th.join()
        time.sleep(0.002)
        t.record_span("codec:dispatch", child_of=t.inject(),
                      start=time.time() - 0.001, duration=0.001,
                      mono=time.monotonic() - 0.001)
        copy("ec:assemble")
    return t.recorder.operations("client:get")[-1]


def test_a_cost_leaf_is_in_the_cost_and_never_in_the_stages(t):
    """`stages` are what they were before the leaf existed: the leaf's
    time is its parent's self, its child its parent's child."""
    with_leaf = _copy_like(t, leaf=True)
    plain = _copy_like(Tracer(), leaf=False)
    assert list(with_leaf["stages"]) == list(plain["stages"]) == [
        "client:get", "ec:fanout", "net:read_chunk", "codec:dispatch"]
    assert sum(with_leaf["stages"].values()) == pytest.approx(
        with_leaf["durationUs"], abs=len(with_leaf["stages"]))
    assert with_leaf["stages"]["net:read_chunk"] >= 2000
    assert with_leaf["stages"]["client:get"] >= 2000  # ec:assemble's
    assert {"ec:fill", "ec:assemble"} <= set(with_leaf["cost"])
    assert not {"ec:fill", "ec:assemble"} & set(plain["cost"])
    for name in ("ec:fill", "ec:assemble"):
        wall, cpu, _b, _p = with_leaf["cost"][name]
        assert 2000 <= cpu <= wall + 50  # two clocks
    spans = [tracing.span_json(s) for s in t.traces()]
    assert sum(1 for s in spans if s.get("costOnly")) == 2
    path = {st["stage"] for st in tracing.critical_path(spans)}
    assert not {"ec:fill", "ec:assemble"} & path


def test_an_operation_that_is_not_costed_opens_no_leaf(t):
    _copy_like(t, leaf=True)
    n = len(t.spans)
    rec = _copy_like(t, leaf=True)  # the second of its name in a second
    assert set(rec) == {"root", "traceId", "end", "durationUs", "stages"}
    assert len(t.spans) - n == 4  # root, fan-out, the read, the dispatch
    with t.cost_leaf("ec:assemble") as outside_any_trace:
        pass
    assert outside_any_trace is None


def test_a_root_of_begin_operation_keeps_its_childrens_cost_apart(t):
    """One thread works on several roots in turn: the root carries no
    cost of its own, its children, bracketed on the thread, do."""
    a = t.begin_operation("tier:key")
    time.sleep(tracing.COST_INTERVAL_S)  # both are costed
    b = t.begin_operation("tier:key")
    for root, spin in ((a, 0.002), (b, 0.004), (a, 0.002)):
        with t.span("tier:pack", child_of=t.context(root)):
            _spin(spin)
    t.end_operation(a)
    t.end_operation(b)
    ra, rb = t.recorder.operations("tier:key")
    for rec, n_us in ((ra, 4000), (rb, 4000)):
        assert rec["cost"]["tier:key"][1:] == [0, 0, 0]
        assert rec["cost"]["tier:key"][0] == rec["durationUs"]
        assert n_us <= rec["cost"]["tier:pack"][1] <= n_us + 1500
        assert sum(rec["stages"].values()) == pytest.approx(
            rec["durationUs"], abs=3)


def test_a_record_of_an_older_shape_is_still_read(t):
    """`root_finished` with two arguments, as the collector and older
    callers make it: no hand-off, and the new keys are there."""
    with t.span("x") as root:
        pass
    root.op = True
    t.recorder.root_finished(root, [root])
    rec = t.recorder.operations("x")[-1]
    assert rec["handoffs"] == {"n": 0, "waitUs": 0, "maxUs": 0, "pools": {}}
    assert rec["rpc"] == {} and set(rec["cost"]) == {"x"}


@pytest.mark.parametrize("threads,ring", [(1, 1000), (8, 1000),
                                          (8, tracing.MAX_SPANS)])
def test_no_span_is_lost_where_a_child_spans_end_takes_no_lock(threads, ring):
    """A child's end is an append to the ring and one to its trace's
    list, no lock taken (eight readers' spans queued for it): every
    operation of every thread still finds all of its own spans, its
    workers' among them, nothing is left open, and what a short ring
    pushed out is counted."""
    t = Tracer(max_spans=ring)
    evicted0 = tracing.METRICS.counter("spans_evicted").value
    ops, kids = 150, 12

    def work(ctx, i):
        with t.activate(ctx), t.span("net:read_chunks", i=i):
            pass

    def reader(r):
        for n in range(ops):
            with t.operation(f"client:get-{r}", n=n):
                worker = threading.Thread(target=work,
                                          args=(t.handoff(), n))
                worker.start()
                for i in range(kids):
                    with t.span("ec:read", i=i):
                        pass
                worker.join()

    ths = [threading.Thread(target=reader, args=(r,))
           for r in range(threads)]
    for th in ths:
        th.start()
    for th in ths:
        th.join()
    for r in range(threads):
        recs = t.recorder.operations(f"client:get-{r}")
        assert len(recs) == ops
        for rec in recs:
            assert set(rec["stages"]) <= {f"client:get-{r}", "ec:read",
                                          "net:read_chunks"}
            assert sum(rec["stages"].values()) == pytest.approx(
                rec["durationUs"], abs=3)
        for rec in (x for x in recs if "cost" in x):
            assert rec["handoffs"]["n"] == 1
            assert set(rec["cost"]) == {f"client:get-{r}", "ec:read",
                                        "net:read_chunks"}
    assert not t._open and not t._handed
    total = threads * ops * (kids + 2)
    counted = tracing.METRICS.counter("spans_evicted").value - evicted0
    if total <= ring:
        # the ring holds them all: each trace has every one of its spans
        by_trace = {}
        for s in t.traces():
            by_trace[s.trace_id] = by_trace.get(s.trace_id, 0) + 1
        assert len(by_trace) == threads * ops
        assert set(by_trace.values()) == {kids + 2} and counted == 0
    else:
        assert len(t.spans) == ring == len(t.traces())
        assert total - ring - threads <= counted <= total - ring
