"""A hand-off is counted and timed where it happens: in a costed
operation the context a submitter makes for a pool worker
(`Tracer.handoff`) carries the moment it was made, and `Tracer.activate`
on a DIFFERENT thread books one hand-off against the trace: its count,
its wait, its pool. The wire form of a context does not change, and an
operation that is not costed hands on the wire form and books nothing."""

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import pytest

from ozone_tpu.utils import tracing
from ozone_tpu.utils.tracing import Tracer


@pytest.fixture
def t():
    return Tracer()


def _record(t: Tracer, root: str = "op") -> dict:
    return t.recorder.operations(root)[-1]


def test_a_worker_books_one_handoff_that_waited_for_its_pool(t):
    """The pool's one thread is held for 30 ms after the work is handed
    over: the hand-off waited at least that long."""
    n0 = tracing.METRICS.counter("handoffs").value
    h0 = tracing.METRICS.histogram("handoff_seconds").count
    seen = []

    def work(ctx):
        with t.activate(ctx), t.span("net:read_chunks"):
            seen.append(t.current().trace_id)

    with ThreadPoolExecutor(max_workers=1,
                            thread_name_prefix="ec-read") as pool:
        gate = threading.Event()
        pool.submit(gate.wait, 5.0)
        with t.operation("op") as root:
            fut = pool.submit(work, t.handoff())
            time.sleep(0.030)
            gate.set()
            fut.result(timeout=5.0)
    rec = _record(t)
    assert seen == [root.trace_id]
    assert rec["handoffs"]["n"] == 1
    assert 30_000 <= rec["handoffs"]["waitUs"] == rec["handoffs"]["maxUs"]
    assert rec["handoffs"]["waitUs"] <= rec["durationUs"]
    assert rec["handoffs"]["pools"] == {
        "ec-read": [1, rec["handoffs"]["waitUs"]]}
    assert tracing.METRICS.counter("handoffs").value == n0 + 1
    assert tracing.METRICS.histogram("handoff_seconds").count == h0 + 1


def test_handoffs_are_split_by_the_workers_pool(t):
    def work(ctx):
        with t.activate(ctx):
            pass

    with t.operation("op"):
        for name, n in (("ec-read", 3), ("hedge", 2), ("ec-records", 1)):
            with ThreadPoolExecutor(max_workers=4,
                                    thread_name_prefix=name) as pool:
                for f in [pool.submit(work, t.handoff()) for _ in range(n)]:
                    f.result()
        th = threading.Thread(target=work, args=(t.handoff(),),
                              name="storm-7")
        th.start()
        th.join()
    h = _record(t)["handoffs"]
    assert h["n"] == 7
    assert {p: v[0] for p, v in h["pools"].items()} == {
        "ec-read": 3, "hedge": 2, "ec-records": 1, "storm": 1}
    assert h["waitUs"] == sum(v[1] for v in h["pools"].values())
    assert h["maxUs"] <= h["waitUs"]


@pytest.mark.parametrize("ctx_of", ["handoff", "context", "inject"])
def test_the_same_thread_books_none(t, ctx_of):
    """`lifecycle/executor._on_key` activates a key's context on the
    sweeper's own thread: nothing was handed over."""
    with t.operation("op") as root:
        ctx = {"handoff": t.handoff, "inject": t.inject,
               "context": lambda: t.context(root)}[ctx_of]()
        with t.activate(ctx), t.span("tier:pack") as child:
            pass
    assert child.parent_id == root.span_id
    assert _record(t)["handoffs"]["n"] == 0


def test_a_context_off_the_wire_still_activates_and_books_none(t):
    """`x-trace-id` and the `traceId` header carry `traceid:spanid`, as
    they did: `inject()` makes it, another thread activates it."""
    got = []

    def serve(ctx):
        with t.activate(ctx), t.span("server:GetBlock") as s:
            got.append(s)

    with t.operation("op") as root:
        wire = t.inject()
        assert wire == f"{root.trace_id}:{root.span_id}"
        assert wire.count(":") == 1
        assert t.handoff().startswith(wire + ":")
        th = threading.Thread(target=serve, args=(wire,))
        th.start()
        th.join()
        for odd in ("", "onlyatraceid"):
            with t.activate(odd):
                pass
    assert (got[0].trace_id, got[0].parent_id) == (root.trace_id,
                                                   root.span_id)
    assert _record(t)["handoffs"]["n"] == 0


def test_a_submission_with_riders_hands_its_contexts_on_unchanged(t):
    a, b = t.begin_operation("tier:key"), t.begin_operation("tier:key")
    with t.riders([t.context(a), t.context(b)]):
        assert t.handoff() == t.inject() == \
            f"{t.context(a)},{t.context(b)}"
    t.end_operation(a)
    t.end_operation(b)


def test_the_hedge_group_hands_off_to_its_pool(t):
    """One of the five sites, end to end: a primary that wins at once is
    one hand-off to the `hedge` pool."""
    from ozone_tpu.client import resilience

    Tracer._instance = t
    try:
        with t.operation("op"):
            win = resilience.HedgeGroup().run(lambda: 7, [lambda: 8],
                                              delay_s=1.0)
    finally:
        Tracer._instance = None
    assert win.value == 7
    h = _record(t)["handoffs"]
    assert h["n"] == 1 and list(h["pools"]) == ["hedge"]


def test_an_operation_that_is_not_costed_books_none(t):
    """The second operation of a name inside COST_INTERVAL_S: its
    worker's context is the wire form, nothing is booked or counted."""
    n0 = tracing.METRICS.counter("handoffs").value
    seen = []

    def work(ctx):
        with t.activate(ctx), t.span("net:read_chunks") as s:
            seen.append(s)

    for _ in range(2):
        with t.operation("op") as root:
            ctx = t.handoff()
            th = threading.Thread(target=work, args=(ctx,), name="ec-read_0")
            th.start()
            th.join()
    assert ctx == f"{root.trace_id}:{root.span_id}" == t.context(root)
    assert seen[1].trace_id == root.trace_id  # it still joins the trace
    first, second = t.recorder.operations("op")
    assert first["handoffs"]["n"] == 1 and "handoffs" not in second
    assert tracing.METRICS.counter("handoffs").value == n0 + 1
