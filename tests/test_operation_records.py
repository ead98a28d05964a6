"""Per-operation stage records: every finished operation root (a PUT, a
GET, a container repair) leaves {root, end, duration, stage -> micros}
in a ring of its own, whatever happens to the span ring around it; a
root's trace is collected by trace id as its spans finish, and a root
under its SLO costs no copy of anything."""

import threading
import time

import numpy as np
import pytest

from ozone_tpu.utils import tracing
from ozone_tpu.utils.tracing import FlightRecorder, Span, Tracer


@pytest.fixture
def t():
    """A fresh process tracer: the client and the coordinator record
    into Tracer.instance()."""
    Tracer._instance = None
    yield Tracer.instance()
    Tracer._instance = None


def _put_like(t: Tracer) -> Span:
    """One operation with the shape of a PUT: nested spans, a sibling on
    a worker thread, an interval recorded by another thread."""
    with t.operation("client:put", key="k") as root:
        with t.span("om:open_key"):
            time.sleep(0.002)
        ctx = t.inject()
        enq_wall, enq = time.time(), time.monotonic()
        time.sleep(0.001)  # measured by "another thread", recorded after
        t.record_span("codec:queue_wait", child_of=ctx, start=enq_wall,
                      duration=time.monotonic() - enq, mono=enq)

        def worker(ctx):
            with t.activate(ctx), t.span("net:write_chunks_commit"):
                time.sleep(0.004)

        with t.span("ec:flush"):
            th = threading.Thread(target=worker, args=(t.inject(),))
            th.start()
            th.join()
        time.sleep(0.001)  # the root's own time
    return root


def test_an_operation_record_survives_a_flood_of_other_spans(t):
    root = _put_like(t)
    for i in range(tracing.MAX_SPANS // 2):  # the ring's length again
        with t.span("client:/ozone.tpu.ScmService/GetContainer"):
            with t.span("server:GetContainer"):
                pass
    assert t.traces(root.trace_id) == []  # the span ring forgot it
    (rec,) = t.recorder.operations("client:put")
    assert rec["traceId"] == root.trace_id
    assert rec["durationUs"] == round(root.duration * 1e6)
    assert {"client:put", "om:open_key", "ec:flush",
            "net:write_chunks_commit"} <= set(rec["stages"])
    # parentless RPC spans are roots, and no operations
    assert len(t.recorder.operations()) == 1


def test_a_records_stages_sum_to_the_roots_duration(t):
    for _ in range(5):
        _put_like(t)
    for rec in t.recorder.operations("client:put"):
        # each stage is rounded to a microsecond on its own
        assert abs(sum(rec["stages"].values()) - rec["durationUs"]) \
            <= len(rec["stages"])
        # the worker thread's span sits inside its parent's window
        assert rec["stages"]["net:write_chunks_commit"] >= 3_500
        assert rec["stages"]["om:open_key"] >= 1_500
        assert rec["stages"]["codec:queue_wait"] >= 800
        assert rec["stages"]["client:put"] >= 500


def test_durations_come_from_the_monotonic_clock(t, monkeypatch):
    """A wall clock that steps mid-span moves `start` and no duration."""
    wall = [1_000_000.0]
    monkeypatch.setattr(tracing.time, "time", lambda: wall[0])
    with t.operation("client:get") as root:
        with t.span("ec:read") as child:
            wall[0] -= 3600.0  # the wall clock steps back an hour
            time.sleep(0.002)
    assert root.start == 1_000_000.0 and child.start == 1_000_000.0
    assert 0.002 <= child.duration <= root.duration < 1.0
    (rec,) = t.recorder.operations("client:get")
    assert rec["stages"]["ec:read"] >= 2_000
    assert abs(rec["end"] - time.monotonic()) < 1.0


def test_a_rounds_side_by_side_children_are_swept_once():
    """A block-record round's `net:get_block` spans run side by side
    (the caller's and the record pool's threads): the critical path
    gives every instant of the round to one of them or to the round
    itself, never to two."""
    ms = 1e-3

    def span(i, parent, name, start_ms, dur_ms):
        return {"spanId": i, "parentId": parent, "name": name,
                "start": 100.0 + start_ms * ms, "durationMs": dur_ms}

    path = tracing.critical_path([
        span("r", "", "client:get", 0, 50),
        span("l", "r", "net:get_blocks", 5, 21),
        # six answers side by side, begun 0.5 ms apart from 6.0, ended
        # 1.5 ms apart from 18.0: the slowest is out at 25.5
        *[span(f"g{u}", "l", "net:get_block", 6 + u / 2, 12 + u)
          for u in range(6)],
        span("x", "g5", "client:/ozone.tpu.DatanodeService/GetBlock",
             10, 15),
        span("f", "r", "ec:fanout", 30, 10)])
    got = {p["stage"]: p["micros"] for p in path}
    assert sum(got.values()) == 50_000
    # first started, first swept: g0 has 6.0 -> 18.0, each later one what
    # is left of it after the one before (g5: 24.0 -> 25.5, 1.0 of that
    # under its RPC); the round keeps 1.0 before them and 0.5 after
    assert got == {"client:get": 19_000, "net:get_blocks": 1_500,
                   "net:get_block": 18_500, "ec:fanout": 10_000,
                   "client:/ozone.tpu.DatanodeService/GetBlock": 1_000}


def _planted(rec: FlightRecorder, name: str, start: float, seconds: float):
    root = Span("t" + name + str(start), "s", "", name, 0.0, seconds,
                mono=start, op=True)
    rec.root_finished(root, [root])


def test_the_window_rule_on_planted_records():
    """An operation belongs to the window its root ENDED in: t0 <= end <
    t1 on the monotonic clock."""
    rec = FlightRecorder()
    _planted(rec, "client:put", 8.5, 1.0)    # ends 9.5: before
    _planted(rec, "client:put", 9.5, 0.5)    # ends 10.0: first inside
    _planted(rec, "client:put", 9.0, 3.0)    # began before, ends 12.0
    _planted(rec, "client:get", 11.0, 1.0)   # another operation
    _planted(rec, "client:put", 19.0, 1.0)   # ends 20.0: first outside
    ends = lambda ops: [o["end"] for o in ops]  # noqa: E731
    assert ends(rec.operations("client:put", 10.0, 20.0)) == [10.0, 12.0]
    assert ends(rec.operations("client:get", 10.0, 20.0)) == [12.0]
    assert ends(rec.operations("", 10.0, 20.0)) == [10.0, 12.0, 12.0]
    assert ends(rec.operations("client:put")) == [9.5, 10.0, 12.0, 20.0]
    assert rec.operations("repair:container") == []
    # the ring is bounded, and large enough for a window after set-up
    assert rec._ops.maxlen >= 4096


def test_stage_means_is_the_table_an_operator_reads():
    """What a freon summary prints as `op_stage_ms`: per root, the mean
    operation's critical-path milliseconds by stage, largest first."""
    rec = FlightRecorder()
    for seconds in (0.1, 0.3):
        root = Span("t%f" % seconds, "r", "", "client:put", 0.0, seconds,
                    mono=1.0, op=True)
        child = Span(root.trace_id, "c", "r", "net:write_chunks_commit",
                     0.0, seconds * 0.75, mono=1.0)
        rec.root_finished(root, [child, root])
    _planted(rec, "client:get", 5.0, 0.05)
    table = rec.stage_means()
    assert table["client:put"] == {
        "n": 2, "mean_ms": 200.0,
        "stage_ms": {"net:write_chunks_commit": 150.0, "client:put": 50.0}}
    assert list(table["client:put"]["stage_ms"]) == [
        "net:write_chunks_commit", "client:put"]
    assert rec.stage_means("client:get") == {"client:get": {
        "n": 1, "mean_ms": 50.0, "stage_ms": {"client:get": 50.0}}}
    assert FlightRecorder().stage_means() == {}


def test_only_operation_roots_leave_a_record(t):
    with t.span("slab:flush"):           # a root, no operation
        pass
    t.record_span("codec:device_dispatch", child_of="abcd:",
                  start=time.time(), duration=0.001)
    with t.operation("client:put"):      # the operation
        with t.operation("client:get"):  # nested: an ordinary child
            pass
    (rec,) = t.recorder.operations()
    assert rec["root"] == "client:put" and "client:get" in rec["stages"]


def test_a_root_under_its_slo_copies_nothing(t, monkeypatch):
    """`_finish` hands the recorder the trace it collected by id; it
    never scans the span ring, and serialises nothing for a fast root."""
    def never(*a, **kw):
        raise AssertionError("a fast root must not copy or serialise")

    monkeypatch.setattr(t, "traces", never)
    monkeypatch.setattr(tracing, "span_json", never)
    with t.operation("client:put"):
        with t.span("ec:flush"):
            pass
    t.record_span("codec:device_dispatch", child_of="abcd:",
                  start=time.time(), duration=0.001)
    assert len(t.recorder.operations()) == 1 and t.recorder.slow() == []
    assert t._open == {}


def test_a_slow_root_is_pinned_with_the_spans_collected_by_trace_id(
        t, monkeypatch):
    monkeypatch.setenv("OZONE_TPU_TRACE_SLO_CLIENT_PUT_MS", "0")
    with t.span("client:/other/Rpc"):  # another trace, finished earlier
        pass
    root = _put_like(t)
    entry = t.recorder.trace(root.trace_id)
    assert {s["name"] for s in entry["spans"]} == {
        "client:put", "om:open_key", "ec:flush",
        "net:write_chunks_commit", "codec:queue_wait"}
    assert entry["criticalPath"][0]["stage"] == "client:put"


def test_traces_whose_root_never_finishes_here_stay_bounded(t):
    """A daemon's spans hang under the caller's: their root never
    finishes in this process, and what is held for it is bounded."""
    for i in range(3 * tracing.MAX_OPEN_TRACES):
        with t.span("server:WriteChunk", child_of=f"{i:016x}:beef"):
            pass
    assert len(t._open) == tracing.MAX_OPEN_TRACES
    with t.span("server:Stream", child_of="feed:beef"):
        for _ in range(tracing.MAX_TRACE_SPANS + 10):
            with t.span("raft:commit_wait"):
                pass
    assert len(t._open["feed"]) == tracing.MAX_TRACE_SPANS


@pytest.mark.parametrize("queue", ["codec", "mesh"],
                         ids=["handed_no_executor", "handed_the_mesh_executor"])
def test_repair_spans_arrive_under_the_root_from_the_recon_pool(
        t, tmp_path, queue):
    """`repair:container` is the repair's root; its blocks run on the
    `ec-recon` pool and their spans join it through Tracer.activate,
    the decode's spans from whichever queue the door
    (`parallel/dispatch.py`) sent the coordinator's decodes to."""
    from ozone_tpu.codec.api import CoderOptions
    from ozone_tpu.parallel import mesh_executor
    from ozone_tpu.storage.reconstruction import (
        ECReconstructionCoordinator,
        ReconstructionCommand,
    )
    from ozone_tpu.testing.minicluster import MiniOzoneCluster

    executor = mesh_executor.maybe_executor() if queue == "mesh" else None
    dispatch_span = {"codec": "codec:dispatch",
                     "mesh": "mesh:device_dispatch"}[queue]
    cell = 4096
    c = MiniOzoneCluster(tmp_path, num_datanodes=7, block_size=4 * cell,
                         container_size=1024 * 1024,
                         stale_after_s=1000.0, dead_after_s=2000.0)
    try:
        oz = c.client()
        b = oz.create_volume("v").create_bucket(
            "b", replication="rs-3-2-4096")
        data = np.random.default_rng(0).integers(
            0, 256, 3 * 4 * 3 * cell, dtype=np.uint8)
        b.write_key("k", data)  # three block groups, one container
        groups = oz.om.key_block_groups(b.lookup_key_info("k"))
        g = groups[0]
        assert {x.container_id for x in groups} == {g.container_id}
        nodes = g.pipeline.nodes
        spare = next(d.id for d in c.datanodes if d.id not in nodes)
        c.datanode(nodes[1]).delete_container(g.container_id, force=True)
        cmd = ReconstructionCommand(
            g.container_id, CoderOptions.parse("rs-3-2-4096"),
            {u + 1: nodes[u] for u in range(5) if u != 1}, {2: spare})
        ECReconstructionCoordinator(
            c.clients, bytes_per_checksum=1024, executor=executor,
        ).reconstruct_container_group(cmd)
    finally:
        c.close()
    root = next(s for s in t.traces() if s.name == "repair:container")
    assert root.parent_id == "" and root.op
    assert root.tags["container"] == g.container_id
    assert root.tags["lost"] == [2] and root.tags["bytes"] == 12 * cell
    spans = t.traces(root.trace_id)
    children = [s.name for s in spans if s.parent_id == root.span_id]
    assert sorted(children) == ["repair:block"] * 3 + [
        "repair:close", "repair:prepare"]
    blocks = {s.span_id for s in spans if s.name == "repair:block"}
    under_blocks = {s.name for s in spans if s.parent_id in blocks}
    assert {"repair:write", "ec:fanout", "net:get_blocks",
            f"{queue}:queue_wait", dispatch_span} <= under_blocks
    # the block records are asked for in one round a block: five units
    # sought, the lost one's node is nobody to ask
    rounds = {s.span_id: s for s in spans if s.name == "net:get_blocks"}
    assert [(s.tags["records_asked"], s.tags["records_present"])
            for s in rounds.values()] == [(5, 4)] * 3
    asked = [s.parent_id for s in spans if s.name == "net:get_block"]
    assert len(asked) == 15 and set(asked) == set(rounds)
    # the survivor reads run on the reader's own pool, under the fan-in
    fanouts = {s.span_id for s in spans if s.name == "ec:fanout"}
    assert {s.parent_id for s in spans
            if s.name == "net:read_chunks"} <= fanouts
    (rec,) = t.recorder.operations("repair:container")
    assert abs(sum(rec["stages"].values()) - rec["durationUs"]) \
        <= len(rec["stages"])
    assert {"repair:prepare", "repair:block", "repair:write",
            "repair:close", "ec:fanout", "net:read_chunks",
            dispatch_span} <= set(rec["stages"])
    # PUTs of the set-up are operations of their own, told apart by root
    assert [o["root"] for o in t.recorder.operations()] == [
        "client:put", "repair:container"]


def test_interleaved_roots_one_thread_and_a_submission_with_many_riders(t):
    """The tiering sweep's shape: one thread works on several operation
    roots in turn (`begin_operation` / `end_operation`, stages under
    `activate(context(root))`), and one submission carries stripes of
    them all: inside `riders(...)` the context `inject()` hands the
    scheduler names every rider, and the interval the scheduler records
    against it lands in each rider's trace."""
    a = t.begin_operation("tier:key", key="a")
    b = t.begin_operation("tier:key", key="b")
    assert t.current() is None  # a begun root is nobody's current span
    for root in (a, b, a):
        with t.activate(t.context(root)), t.span("tier:read"):
            time.sleep(0.001)
    with t.riders([t.context(a), t.context(b), t.context(a), ""]):
        ctx = t.inject()
        assert ctx == f"{t.context(a)},{t.context(b)}"  # once each
    assert t.inject() == ""  # outside the block: the thread's own
    enq_wall, enq = time.time(), time.monotonic()
    time.sleep(0.002)
    first = t.record_span("mesh:device_dispatch", child_of=ctx,
                          start=enq_wall, duration=time.monotonic() - enq,
                          mono=enq, stripes=8)
    assert first.trace_id == a.trace_id and first.tags == {"stripes": 8}
    t.end_operation(b)
    time.sleep(0.001)
    t.end_operation(a)
    recs = {r["traceId"]: r for r in t.recorder.operations("tier:key")}
    assert set(recs) == {a.trace_id, b.trace_id}
    for root in (a, b):
        stages = recs[root.trace_id]["stages"]
        assert set(stages) == {"tier:key", "tier:read",
                               "mesh:device_dispatch"}
        assert stages["mesh:device_dispatch"] >= 2000
        assert sum(stages.values()) == pytest.approx(
            recs[root.trace_id]["durationUs"], abs=3)
    assert recs[a.trace_id]["durationUs"] > recs[b.trace_id]["durationUs"]
