"""The metrics that read the program's own account of its time: the codec
dispatcher's stage histograms, its idle share of the window, the share of
the device's idle time that lies under its `codec:idle`, and the mean
critical-path time of a window's operations by stage group — on planted
numbers, on a small recorded trace, and on a CPU pass of every cell."""

import argparse
import json
import re

import pytest

import bench_minicluster as bm
from benchmarks.harness import manifest as mf
from benchmarks.harness import spans
from benchmarks.harness import trace as tr
from benchmarks.harness.record import Run

FIXTURES = mf.BENCH_DIR / "fixtures"
MANIFEST = mf.load()
#: PR 25's metrics by family: (reader, source) of each
FAMILIES = {
    **{f"{b}.{c}": ("histogram_mean_ms", "program_counter")
       for b in ("codec_pack_ms", "codec_launch_ms", "codec_d2h_ms")
       for c in ("put", "get", "repair")},
    **{f"codec_idle_pct.{c}": ("window_share_pct", "program_counter")
       for c in ("put", "get", "repair")},
    **{f"device_idle_unfed_pct.{c}": ("device_idle_unfed_pct",
                                      "device_trace")
       for c in ("put", "get", "repair")},
    **{n: ("op_stage_ms", "program_span") for n in (
        "put_om_ms", "put_codec_ms", "put_dn_write_ms", "put_client_ms",
        "get_dn_read_ms", "get_codec_ms", "get_client_ms",
        "repair_fixed_ms", "repair_read_ms", "repair_codec_ms",
        "repair_write_ms")},
}
NEW = [m for m in MANIFEST["per_layer"] if m["name"] in FAMILIES]
#: the readers that mean a root's operations by stage group
STAGE_READERS = ("op_stage_ms", "mesh_op_stage_ms")


def _run(**kw) -> Run:
    base = dict(cell={}, config={}, traffic={}, setup_s=1.0, ops=[],
                t0=100.0, t1=110.0, counters0={}, counters1={})
    return Run(**{**base, **kw})


def _read(name: str, run: Run):
    params = mf.metric_params(name)
    return mf.reader_of(params)(params, run)


def _groups(cell: str, manifest: dict = MANIFEST,
            bench_dir=mf.BENCH_DIR) -> dict[str, dict[str, list]]:
    """{root: {metric: compiled stage patterns}} of the cell's stage
    group metrics: a cell whose traffic has several kinds of operation
    has a root for each."""
    out: dict[str, dict[str, list]] = {}
    for m in mf.metrics_for(manifest, "per_layer", cell):
        p = mf.metric_params(m["name"], bench_dir)
        if p["reader"] in STAGE_READERS:
            out.setdefault(p["root"], {})[m["name"]] = [
                re.compile(x) for x in p["stages"]]
    return out


def metric_rules(manifest: dict, root=mf.ROOT) -> None:
    """PR 25's metrics are there, each read by its reader and of its
    source; others may follow."""
    bench_dir = root / "benchmarks"
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert set(by_name) >= set(FAMILIES)
    for name, (reader, source) in FAMILIES.items():
        assert by_name[name]["source"] == source, name
        assert mf.metric_params(name, bench_dir)["reader"] == reader, name
    assert mf.problems(manifest, root) == []


def test_this_pr_added_the_metrics_the_issue_names():
    metric_rules(MANIFEST)


# ------------------------------------------------------ counter readers
@pytest.mark.parametrize("metric,histogram", [
    ("codec_pack_ms.put", "pack_seconds"),
    ("codec_launch_ms.get", "launch_seconds"),
    ("codec_d2h_ms.repair", "d2h_seconds"),
])
def test_stage_means_are_deltas_of_the_programs_histograms(
        metric, histogram):
    h = f"codec.service/{histogram}"
    run = _run(counters0={h + ".sum": 1.0, h + ".count": 10.0},
               counters1={h + ".sum": 1.6, h + ".count": 40.0})
    assert _read(metric, run) == pytest.approx(20.0)
    assert _read(metric, _run()) is None  # a program without the stage


def test_window_share_is_the_counters_growth_over_the_window():
    c = "codec.service/idle_seconds.sum"
    run = _run(counters0={c: 5.0}, counters1={c: 9.5})
    assert _read("codec_idle_pct.put", run) == pytest.approx(45.0)
    # set-up's idling, before counters0, is in neither snapshot's delta
    assert _read("codec_idle_pct.put",
                 _run(counters0={c: 9.5}, counters1={c: 9.5})) == 0.0
    # the parent's program has no such counter: nothing, and no error
    assert _read("codec_idle_pct.put", _run()) is None


# -------------------------------------------------------- span reader
@pytest.fixture
def recorder():
    from ozone_tpu.utils.tracing import Tracer

    Tracer._instance = None
    yield Tracer.instance().recorder
    Tracer._instance = None


def _plant(recorder, root: str, end: float, stages: dict[str, int]):
    recorder._ops.append({"root": root, "traceId": "t", "end": end,
                          "durationUs": sum(stages.values()),
                          "stages": stages})


def test_op_stage_ms_means_the_windows_operations_by_group(recorder):
    put = {"client:put": 30_000, "om:open_key": 1_000,
           "client:/ozone.tpu.OmService/OpenKey": 9_000,
           "client:/ozone.tpu.OmService/AllocateBlock": 6_000,
           "om:commit": 4_000, "codec:queue_wait": 80_000,
           "codec:dispatch": 60_000, "ec:flush": 20_000,
           "net:write_chunks_commit": 250_000,
           "client:/ozone.tpu.DatanodeService/CreateContainer": 40_000}
    _plant(recorder, "client:put", 99.9, {"client:put": 9_000_000})
    _plant(recorder, "client:put", 100.0, put)
    _plant(recorder, "client:put", 105.0,
           {**put, "net:write_chunks_commit": 350_000})
    _plant(recorder, "client:get", 105.0, {"client:get": 7_000_000})
    _plant(recorder, "client:put", 110.0, {"client:put": 9_000_000})
    run = _run()
    assert _read("put_om_ms", run) == pytest.approx(20.0)
    assert _read("put_codec_ms", run) == pytest.approx(140.0)
    assert _read("put_dn_write_ms", run) == pytest.approx(340.0)
    assert _read("put_client_ms", run) == pytest.approx(50.0)
    # the groups partition the root: they sum to its mean duration
    ops = spans.operations("client:put", run.t0, run.t1)
    assert [o["end"] for o in ops] == [100.0, 105.0]
    assert sum(_read(m, run) for m in _groups("ockg.rs-6-3")["client:put"]) \
        == pytest.approx(sum(o["durationUs"] for o in ops) / 2 / 1e3)
    # no operation of the root ended in the window
    assert _read("repair_fixed_ms", run) is None
    assert _read("put_om_ms", _run(t0=0.0, t1=50.0)) is None


def test_a_program_without_stage_records_reads_as_nothing(monkeypatch):
    """The parent commit's tracer: the reader finds nothing to read."""
    from ozone_tpu.utils import tracing

    monkeypatch.delattr(tracing.FlightRecorder, "operations")
    assert spans.operations("client:put", 0.0, 1e12) == []
    assert _read("put_om_ms", _run()) is None


# ------------------------------------------------- device-trace reader
def _trace(device_ops, host_events, thread="codec-service/77"):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": tr.OPS_LINE, "events": [list(e) for e in device_ops]}]},
        {"name": "/host:CPU", "lines": [
            {"name": thread, "events": [list(e) for e in host_events]},
            {"name": "tf_pjrt/78", "events": [
                ["XlaLinearize", 3_000_000, 500_000]]}]}]}


def test_unfed_share_is_device_idle_time_under_the_dispatchers_idle():
    ms = 1_000_000
    # busy [2,3) and [7,8) of a 10 ms trace: idle 8 ms = [0,2) [3,7) [8,10)
    t = _trace([("op", 2 * ms, ms), ("op", 7 * ms, ms)],
               [("codec:idle", 0, 1 * ms),            # 1 ms of [0,2)
                ("codec:pack", 1 * ms, 1 * ms),
                ("codec:launch", 2 * ms, 2 * ms),
                ("codec:idle", int(4.5 * ms), 3 * ms),  # [4.5,7): 2.5 ms
                ("codec:d2h", int(7.5 * ms), int(2.5 * ms))])
    run = _run(trace=t)
    assert _read("device_idle_unfed_pct.put", run) == pytest.approx(
        100 * 3.5 / 8)
    # another thread's event of another name does not count; no trace,
    # or a trace without the program's annotation: nothing
    assert _read("device_idle_unfed_pct.put", _run()) is None
    bare = _trace([("op", 2 * ms, ms)], [("XlaLinearize", 0, ms)])
    assert _read("device_idle_unfed_pct.put", _run(trace=bare)) is None
    # and idle_gaps names the gaps after the dispatcher's stages
    gaps = tr.idle_gaps(t, (0, 10 * ms))
    assert gaps[0] == ["codec-service: codec:idle", pytest.approx(0.004)]
    assert ["codec-service: codec:d2h", pytest.approx(0.002)] in gaps


def test_recorded_trace_with_codec_events_reads_its_hand_counted_share():
    """fixtures/ockg-stages.events.json: cut from a --trace 1 run of
    ockg.rs-6-3 on the chip with the dispatcher's annotations in it;
    ockg-stages.expected.json holds what was counted apart from the
    harness."""
    t = json.loads((FIXTURES / "ockg-stages.events.json").read_text())
    want = json.loads((FIXTURES / "ockg-stages.expected.json").read_text())
    names = {name for p in t["planes"] if p["name"].startswith("/host:CPU")
             for line in p["lines"] for name, _s, _d in line["events"]}
    assert {"codec:idle", "codec:pack", "codec:launch",
            "codec:d2h"} <= names
    # the program's stages sit on ONE thread and never overlap
    (line,) = [line for p in t["planes"] for line in p["lines"]
               if any(e[0].startswith("codec:") for e in line["events"])]
    stages = sorted((s, s + d) for n, s, d in line["events"]
                    if n.startswith("codec:"))
    assert all(a[1] <= b[0] for a, b in zip(stages, stages[1:]))
    assert _read("device_idle_unfed_pct.put", _run(trace=t)) \
        == pytest.approx(want["device_idle_unfed_pct"], rel=1e-9)
    first, last = tr.span_ns(t)
    gaps = tr.idle_gaps(t, (first, last))
    assert [g[0] for g in gaps[:len(want["idle_gaps"])]] \
        == want["idle_gaps"]


# ---------------------------------------------- a CPU pass of each cell
def _traced_rehearsal(tmp_path, name: str, seed: int = 11) -> tuple:
    """Like bench_minicluster.run_cell, with trace=1: the per-layer
    section is read, no profile is taken. Returns (result, stage names
    of the window's operations by root)."""
    import benchmarks.run as bench_run
    from benchmarks.harness import record

    manifest, cell, config, traffic = bm.tiny_cell(name)
    cluster = bm.MiniCluster(tmp_path, config["cluster"]["datanodes"])
    args = argparse.Namespace(workload=name, seed=seed, seconds=1.0,
                              trace=1, rehearse=True, control="",
                              dump_trace="")
    seen: list[Run] = []

    class Keep(Run):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            seen.append(self)

    real, record.Run = record.Run, Keep
    try:
        out = json.loads(json.dumps(bench_run.measure(
            args, manifest, cluster, cell, config, traffic)))
    finally:
        record.Run = real
        cluster.close()
    return out, seen[0]


@pytest.mark.parametrize("cell", sorted(bm.TINY))
def test_a_cpu_pass_reads_every_new_counter_and_span_metric(
        tmp_path, cell, capsys, monkeypatch):
    # as on the one-chip machine: the tests' eight virtual devices would
    # send the repair cell's decodes to the mesh executor, whose
    # dispatcher has no stages yet (PERF.md section 7)
    from ozone_tpu.parallel import mesh_executor

    monkeypatch.setattr(mesh_executor, "maybe_executor", lambda: None)
    out, run = _traced_rehearsal(tmp_path, cell)
    assert out["correct"] is True and out["rehearsal"] is True
    want = {m["name"] for m in NEW if cell in m["workloads"]
            and m["source"] != "device_trace"}
    got = {k: v["value"] for k, v in out["metrics"].items() if k in want}
    with capsys.disabled():
        print(f"\n{cell} (CPU rehearsal, no measurement): " + ", ".join(
            f"{k}={v:.3f}" for k, v in sorted(got.items())))
    assert set(got) == want
    assert all(v >= 0 for v in got.values())
    # no profile was taken: the device-trace metrics are left out
    assert not any(k.startswith("device_") for k in out["metrics"])
    # the dispatcher's thread is accounted for (loosely here: a 1 s
    # window on a shared CPU; the chip run's sum is in PERF.md)
    from benchmarks.harness.program import delta

    busy = sum(delta(run.counters1, run.counters0,
                     f"codec.service/{k}_seconds.sum")
               for k in ("idle", "pack", "launch", "d2h"))
    assert 0.5 <= busy / (run.t1 - run.t0) <= 1.1

    # under each root, the cell's stage groups partition the stage names
    # this run produced, and sum to the mean duration of the root's spans
    by_root = _groups(cell)
    assert by_root
    for root, groups in by_root.items():
        ops = spans.operations(root, run.t0, run.t1)
        assert ops, root
        for stage in {name for o in ops for name in o["stages"]}:
            owners = [m for m, pats in groups.items()
                      if any(p.match(stage) for p in pats)]
            assert len(owners) == 1, (root, stage, owners)
        mean_ms = sum(o["durationUs"] for o in ops) / len(ops) / 1e3
        assert sum(out["metrics"][m]["value"] for m in groups) \
            == pytest.approx(mean_ms, rel=0.01)


def test_stage_groups_take_the_names_a_served_cluster_adds():
    """Over gRPC the same operations also hold `client:/<service>/<verb>`
    spans (the in-process mini-cluster has none): each known one falls in
    exactly one group of its cell too."""
    served = {
        ("ockg.rs-6-3", "client:put"): [
            "client:/ozone.tpu.OmService/OpenKey",
            "client:/ozone.tpu.OmService/AllocateBlock",
            "client:/ozone.tpu.OmService/CommitKey",
            "client:/ozone.tpu.ScmService/GetContainer",
            "client:/ozone.tpu.DatanodeService/CreateContainer",
            "client:/ozone.tpu.DatanodeService/WriteChunksCommit",
            "net:write_chunks_commit", "net:put_block", "om:open_key",
            "om:commit", "ec:flush", "codec:queue_wait",
            "codec:dispatch", "client:put", "client:write"],
        ("ockv-degraded.rs-10-4", "client:get"): [
            "client:/ozone.tpu.DatanodeService/GetBlock",
            "client:/ozone.tpu.DatanodeService/ReadChunks",
            "client:/ozone.tpu.DatanodeService/ReadChunk",
            "net:get_block", "net:read_chunks", "net:read_chunk",
            "ec:read", "ec:fanout", "ec:decode_from_parity",
            "codec:queue_wait", "codec:dispatch", "client:get"],
        ("ecrd.rs-6-3", "repair:container"): [
            "client:/ozone.tpu.DatanodeService/CreateContainer",
            "client:/ozone.tpu.DatanodeService/ListBlock",
            "client:/ozone.tpu.DatanodeService/CloseContainer",
            "client:/ozone.tpu.DatanodeService/GetBlock",
            "client:/ozone.tpu.DatanodeService/ReadChunks",
            "client:/ozone.tpu.DatanodeService/WriteChunksCommit",
            "client:/ozone.tpu.DatanodeService/PutBlock",
            "repair:container", "repair:prepare", "repair:block",
            "repair:write", "repair:close", "ec:fanout", "net:get_block",
            "net:read_chunks", "codec:queue_wait", "codec:dispatch"],
    }
    for (cell, root), names in served.items():
        groups = _groups(cell)[root]
        for stage in names:
            owners = [m for m, pats in groups.items()
                      if any(p.match(stage) for p in pats)]
            assert len(owners) == 1, (cell, stage, owners)
