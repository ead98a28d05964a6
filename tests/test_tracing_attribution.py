"""Request-path latency attribution: cross-request dispatch-span
linkage, tail-based slow-trace retention, and critical-path reduction.

The acceptance contract of the attribution tentpole: a fault-injected
slow PUT against the in-process cluster leaves a retained slow trace
whose critical path attributes >=90% of the root duration across named
stages (queue wait, dispatch, network, commit); per-submission codec
spans record the SHARED device-dispatch span id across >=2 concurrent
operations; and the codec histograms export non-empty `_bucket` lines.
"""

import threading
import time

import numpy as np
import pytest

from ozone_tpu.codec import service as cs
from ozone_tpu.codec.api import CoderOptions
from ozone_tpu.codec.fused import FusedSpec, make_fused_encoder
from ozone_tpu.storage.datanode import Datanode
from ozone_tpu.testing.minicluster import MiniOzoneCluster
from ozone_tpu.utils import metrics as m
from ozone_tpu.utils.checksum import ChecksumType
from ozone_tpu.utils.tracing import Tracer, critical_path

CELL = 4096
EC = "rs-3-2-4096"
OPTS = CoderOptions(3, 2, "rs", cell_size=CELL)
SPEC = FusedSpec(OPTS, ChecksumType.CRC32C, 1024)


@pytest.fixture(autouse=True)
def fresh_tracer():
    """Fresh tracer (and flight-recorder ring) per test: retention
    assertions must not see traces pinned by earlier tests."""
    Tracer._instance = None
    yield
    Tracer._instance = None


@pytest.fixture
def svc():
    cs.reset_for_tests()
    yield cs.get_service()
    cs.reset_for_tests()


@pytest.fixture
def cluster(tmp_path):
    c = MiniOzoneCluster(
        tmp_path,
        num_datanodes=7,
        block_size=4 * CELL,
        container_size=1024 * 1024,
        stale_after_s=1000.0,
        dead_after_s=2000.0,
    )
    yield c
    c.close()


def _rand(shape, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size=shape, dtype=np.uint8)


# ---------------------------------------------- cross-request linkage
@pytest.fixture
def patient_svc(monkeypatch):
    """A service whose linger is no race between two submits on a
    loaded rig (the default is 2 ms); a batch they fill waits for none."""
    monkeypatch.setenv("OZONE_TPU_CODEC_LINGER_MS", "500")
    cs.reset_for_tests()
    yield cs.get_service()
    cs.reset_for_tests()


def test_concurrent_ops_record_shared_dispatch_span(patient_svc):
    """Two operations whose stripes coalesce into ONE fused device
    dispatch each record a codec:dispatch span carrying the SAME
    dispatch_span id — and that id names the shared
    codec:device_dispatch span, so an operator holding either trace can
    pivot to the batch (and from it to every rider)."""
    svc = patient_svc
    t = Tracer.instance()
    fn = make_fused_encoder(SPEC)
    a, b = _rand((2, 3, CELL), 1), _rand((2, 3, CELL), 2)
    with t.span("op:a") as ra:
        f1 = svc.submit(cs.encode_key(SPEC), fn, a, width=4)
    with t.span("op:b") as rb:
        f2 = svc.submit(cs.encode_key(SPEC), fn, b, width=4)
    cs.wait_result(f1)
    cs.wait_result(f2)

    def dispatch_of(trace_id):
        spans = [s for s in t.traces(trace_id)
                 if s.name == "codec:dispatch"]
        assert len(spans) == 1, [s.name for s in t.traces(trace_id)]
        return spans[0]

    da, db = dispatch_of(ra.trace_id), dispatch_of(rb.trace_id)
    assert ra.trace_id != rb.trace_id  # genuinely separate operations
    shared_id = da.tags["dispatch_span"]
    assert shared_id and db.tags["dispatch_span"] == shared_id
    # the shared span exists, is its own trace, and counted both riders
    shared = [s for s in t.traces()
              if s.name == "codec:device_dispatch"
              and s.span_id == shared_id]
    assert len(shared) == 1
    assert shared[0].tags["ops"] == 2
    assert shared[0].trace_id not in (ra.trace_id, rb.trace_id)
    # each rider also closed out its queue-wait against the same batch,
    # and its own copy into the batch lies before that wait
    for tid in (ra.trace_id, rb.trace_id):
        waits = [s for s in t.traces(tid) if s.name == "codec:queue_wait"]
        assert waits and waits[0].tags["dispatch_span"] == shared_id
        (copy,) = [s for s in t.traces(tid)
                   if s.name == "codec:submit_pack"]
        assert copy.mono + copy.duration <= waits[0].mono + 1e-6


def test_codec_histograms_export_bucket_lines(svc):
    """After real traffic the codec latency histograms render non-empty
    Prometheus `_bucket` lines (cumulative counts reach _count)."""
    fn = make_fused_encoder(SPEC)
    cs.wait_result(svc.submit(cs.encode_key(SPEC), fn,
                              _rand((4, 3, CELL), 3), width=4))
    text = m.prometheus_text(cs.METRICS)
    for fam in ("codec_service_queue_wait_seconds",
                "codec_service_dispatch_seconds"):
        buckets = [ln for ln in text.splitlines()
                   if ln.startswith(f'{fam}_bucket{{le="')]
        assert buckets, text
        # cumulative: the +Inf bucket equals the observation count
        inf = next(ln for ln in buckets if 'le="+Inf"' in ln)
        assert int(inf.split("}")[1].split()[0]) >= 1


# --------------------------------------------- slow-PUT flight record
def test_slow_put_retained_and_critical_path_attributes(
        cluster, monkeypatch):
    """Fault-injected slow chunk writes push a PUT past its SLO: the
    trace is pinned by the flight recorder and its critical path
    attributes >=90% of the root duration to named child stages."""
    oz = cluster.client()
    b = oz.create_volume("v").create_bucket("b", replication=EC)
    b.write_key("warm", _rand(3 * CELL, 5))  # compile the encoder
    monkeypatch.setenv("OZONE_TPU_TRACE_SLO_CLIENT_PUT_MS", "100")
    orig = Datanode.write_chunk

    def slow_write(self, *a, **kw):
        time.sleep(0.25)
        return orig(self, *a, **kw)

    monkeypatch.setattr(Datanode, "write_chunk", slow_write)
    b.write_key("slow", _rand(3 * CELL, 6))
    monkeypatch.setattr(Datanode, "write_chunk", orig)

    t = Tracer.instance()
    puts = sorted((s for s in t.traces() if s.name == "client:put"),
                  key=lambda s: s.start)
    tid = puts[-1].trace_id  # the injected-slow PUT, not the warm-up
    assert t.recorder.is_pinned(tid)
    assert any(e["traceId"] == tid for e in t.recorder.slow())
    entry = t.recorder.trace(tid)
    assert entry["root"] == "client:put"
    assert entry["sloMs"] == 100.0
    cp = entry["criticalPath"]
    root_us = entry["durationMs"] * 1e3
    total_us = sum(st["micros"] for st in cp)
    # the reduction is exhaustive: every instant lands in some stage
    assert abs(total_us - root_us) <= max(0.01 * root_us, 500.0)
    stages = {st["stage"] for st in cp}
    # the named request-path stages all appear
    assert any(s.startswith("net:") for s in stages), stages
    assert "om:commit" in stages, stages
    assert "ec:flush" in stages, stages
    assert {"codec:queue_wait", "codec:dispatch"} & stages, stages
    # >=90% of the root's wall clock is attributed BELOW the root
    named_us = sum(st["micros"] for st in cp
                   if st["stage"] != "client:put")
    assert named_us >= 0.90 * root_us, (named_us, root_us, cp)
    # the stage that actually carries the injected fault dominates
    net_us = sum(st["micros"] for st in cp
                 if st["stage"].startswith("net:"))
    assert net_us >= 0.5 * root_us, cp


# ------------------------------------------ hedged degraded-read path
def test_hedged_degraded_read_critical_path(cluster, monkeypatch):
    """A degraded read whose surviving unit straggles hedges into the
    decode pipeline; the pinned trace records the hedge decision as a
    span event and its critical path still sums to the root."""
    from ozone_tpu.client import resilience

    oz = cluster.client()
    b = oz.create_volume("v").create_bucket("b", replication=EC)
    data = _rand(4 * 3 * CELL, 9)
    b.write_key("k", data)
    b.read_key("k")  # warm: compile decode paths outside the slow read
    info = b.lookup_key_info("k")
    groups = oz.om.key_block_groups(info)
    nodes = groups[0].pipeline.nodes
    # degrade: unit 0's replica is gone; slow unit 1 so it straggles
    cluster.datanode(nodes[0]).delete_container(
        groups[0].container_id, force=True)
    cluster.clients.health = resilience.HealthRegistry(
        hedge_floor_s=0.02)
    orig = Datanode.read_chunk

    def maybe_slow(self, *a, **kw):
        if self.id == nodes[1]:
            time.sleep(0.5)
        return orig(self, *a, **kw)

    monkeypatch.setattr(Datanode, "read_chunk", maybe_slow)
    monkeypatch.setenv("OZONE_TPU_TRACE_SLO_CLIENT_GET_MS", "50")
    got = b.read_key("k")
    assert np.array_equal(got, data)

    t = Tracer.instance()
    gets = sorted((s for s in t.traces() if s.name == "client:get"),
                  key=lambda s: s.start)
    tid = gets[-1].trace_id
    assert t.recorder.is_pinned(tid)
    entry = t.recorder.trace(tid)
    cp = entry["criticalPath"]
    root_us = entry["durationMs"] * 1e3
    assert abs(sum(st["micros"] for st in cp) - root_us) \
        <= max(0.01 * root_us, 500.0)
    stages = {st["stage"] for st in cp}
    assert "ec:read" in stages, stages
    assert any(s.startswith("net:") for s in stages), stages
    # the hedge decision is on the record
    events = [e["name"] for sp in entry["spans"]
              for e in sp.get("events", [])]
    assert {"hedge_fired", "straggler_replan"} & set(events), events


# --------------------------------------------- reducer unit contracts
def test_critical_path_clips_overlapping_siblings():
    """Parallel hops (a hedge racing its primary) must not double-count:
    overlapping siblings are swept first-started-first and the total
    still equals the root duration exactly."""
    mk = lambda sid, pid, name, start, dur: {
        "traceId": "t", "spanId": sid, "parentId": pid, "name": name,
        "start": start, "durationMs": dur * 1e3}
    spans = [
        mk("r", "", "client:get", 0.0, 1.0),
        # two overlapping fetches: primary [0.1,0.9], hedge [0.5,0.8]
        mk("a", "r", "net:read_chunk", 0.1, 0.8),
        mk("b", "r", "net:read_chunk", 0.5, 0.3),
        # child of the primary
        mk("c", "a", "codec:dispatch", 0.2, 0.1),
    ]
    cp = critical_path(spans)
    total = sum(st["micros"] for st in cp)
    assert total == 1_000_000  # exactly the root's 1s
    by = {st["stage"]: st["micros"] for st in cp}
    # root keeps only the uncovered head+tail: 0.1 + 0.1
    assert by["client:get"] == 200_000
    # primary window minus its child; hedge contributes nothing new
    assert by["net:read_chunk"] == 700_000
    assert by["codec:dispatch"] == 100_000
    # ordered by first start
    assert [st["stage"] for st in cp] == [
        "client:get", "net:read_chunk", "codec:dispatch"]


def test_flight_recorder_ring_is_bounded(monkeypatch):
    monkeypatch.setenv("OZONE_TPU_TRACE_SLO_MS", "1")
    from ozone_tpu.utils.tracing import FlightRecorder, Span

    rec = FlightRecorder(max_traces=3)
    for i in range(5):
        root = Span(f"t{i}", f"s{i}", "", "op", float(i), 0.5)
        assert rec.offer(root, [root])
    slow = rec.slow()
    assert len(slow) == 3
    assert [e["traceId"] for e in slow] == ["t4", "t3", "t2"]
    assert rec.trace("t0") is None and rec.trace("t4") is not None
