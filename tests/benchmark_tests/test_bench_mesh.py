"""The four-chip cell `ecrd-mesh.rs-6-3`: the manifest with it, its
configuration and metric files, the two readers it brings on hand-made
four-plane traces, a CPU pass through the in-process mini-cluster (clean,
with planted faults, and with decodes sent past the mesh), and a
rehearsal through the real launcher on 4 forced host devices."""

import argparse
import copy
import json
import os
import re
import subprocess
import sys

import pytest

import bench_minicluster as bm
from benchmarks.harness import manifest as mf
from benchmarks.harness import spans
from benchmarks.harness import trace as tr
from benchmarks.harness import work
from benchmarks.harness.record import Run

CELL = "ecrd-mesh.rs-6-3"
MIB = 2 ** 20
MANIFEST = mf.load()
#: what ISSUE 27's table names, beside the reader each file names (the
#: two `mesh_` readers are PR 25's under another name). The
#: table's `device_idle_unfed_pct.repair-mesh` is not brought: its
#: reader finds no `mesh:idle` in a traced slice of this cell
MESH_METRICS = {
    "mesh_fill_pct.repair": "counter_ratio_pct",
    "mesh_coalesced_pct.repair": "counter_ratio_pct",
    "mesh_queue_wait_ms.repair": "histogram_mean_ms",
    "mesh_dispatch_ms.repair": "histogram_mean_ms",
    "mesh_pack_ms.repair": "histogram_mean_ms",
    "mesh_launch_ms.repair": "histogram_mean_ms",
    "mesh_d2h_ms.repair": "histogram_mean_ms",
    "mesh_idle_pct.repair": "mesh_window_share_pct",
    "repair_mesh_ms": "mesh_op_stage_ms",
    "sharded_decode_roofline.repair": "mesh_kernel_roofline",
    "mesh_device_balance_pct.repair": "device_balance_pct",
}
#: the repair cells' metrics the cell reports beside its own
ACCEPTED = {"repair_fixed_ms", "repair_read_ms", "repair_write_ms",
            "device_idle_pct.repair"}
TINY = {"stripes_per_key": 2, "keys_per_container": [1, 2, 1, 2, 1],
        "verify_replicas": 4, "settle_s": 0.0}


# ------------------------------------------------------- the manifest
def cell_rules(manifest: dict, root=mf.ROOT) -> None:
    """The cell takes four chips on its four-chip deployment, inside the
    contract's rule for chips, which problems() holds: a cell takes 1 or
    4, at most max(1, n // 2) of n cells take 4, and a configuration's
    `cluster.chips` is the most its cells take."""
    assert mf.problems(manifest, root) == []
    assert mf.cell(manifest, CELL) == {
        "name": CELL, "config": "rs-6-3-1024k-mesh4",
        "traffic": "ecrd-mesh", "chips": 4,
        "why": mf.cell(manifest, CELL)["why"]}


def test_the_four_chip_cell_is_inside_the_contracts_rule_for_chips():
    cell_rules(MANIFEST)


def test_the_deployment_keeps_the_shapes_of_rs_6_3_and_states_its_own():
    mesh = json.loads((mf.BENCH_DIR / "configs"
                       / "rs-6-3-1024k-mesh4.json").read_text())
    base = json.loads((mf.BENCH_DIR / "configs"
                       / "rs-6-3-1024k.json").read_text())
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "rs-6-3-1024k-mesh4")
    assert mesh["source"] == entry["source"] and len(entry["source"]) <= 200
    for key in ("replication", "scheme", "flush_policy"):
        assert mesh[key] == base[key], key
    for key in ("launcher", "datanodes", "metadata_replicas", "datapath"):
        assert mesh["cluster"][key] == base["cluster"][key], key
    assert mesh["cluster"]["chips"] == 4
    assert mesh["reconstruction_streams"] == 10
    assert mesh["guarantees"][:len(base["guarantees"])] == base["guarantees"]
    assert "all four devices" in mesh["guarantees"][-1]
    assert set(mesh["reduced"]) == set(base["reduced"]) | {"chips",
                                                           "node_loss"}
    assert {"reconstruction_streams", "key_bytes",
            "max_parallel_blocks"} <= set(mesh["assumed"])
    assert mesh["reference"].startswith("benchmarks/harness/reference.py")
    traffic = mf.traffic_of(mf.cell(MANIFEST, CELL))
    assert traffic["keys_per_container"] == [1, 2] * 10
    assert traffic["stripes_per_key"] == 12
    assert traffic["verify_replicas"] == 16
    assert traffic["generator"] == "repair_storm"


def metric_rules(manifest: dict, name: str, root=mf.ROOT) -> None:
    """A metric the cell brought lists the cell and moves repair_mib_s
    through the reader it was brought with; the cell reports every such
    metric and the accepted repair metrics that read the same thing on
    the mesh, and others may follow."""
    bench_dir = root / "benchmarks"
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert CELL in by_name[name]["workloads"]
    assert by_name[name]["moves"] == "repair_mib_s"
    assert mf.metric_params(name, bench_dir)["reader"] == MESH_METRICS[name]
    got = {m["name"] for m in mf.metrics_for(manifest, "per_layer", CELL)}
    assert got >= set(MESH_METRICS) | ACCEPTED
    assert {m["name"] for m in mf.metrics_for(
        manifest, "end_to_end", CELL)} >= {"repair_mib_s", "setup_s"}
    # the roofline metric that over-reads on a mesh is not the cell's
    assert CELL not in by_name["fused_decode_roofline.repair"]["workloads"]
    assert by_name["repair_mesh_ms"]["source"] == "program_span"
    assert by_name["mesh_idle_pct.repair"]["source"] == "program_counter"
    # the two renamed readers ARE PR 25's functions
    for alias in ("window_share_pct", "op_stage_ms"):
        assert mf.reader_of({"reader": f"mesh_{alias}"},
                            bench_dir).__module__ \
            == f"benchmarks.readers.{alias}"


@pytest.mark.parametrize("name", sorted(MESH_METRICS))
def test_the_cells_metrics_are_the_issues_and_take_the_accepted_ones(name):
    metric_rules(MANIFEST, name)


def _repair_groups() -> dict[str, list]:
    """{metric: compiled stage patterns} of the cell's stage groups under
    the root `repair:container`."""
    groups = {}
    for m in mf.metrics_for(MANIFEST, "per_layer", CELL):
        p = mf.metric_params(m["name"])
        if p["reader"] in ("op_stage_ms", "mesh_op_stage_ms") \
                and p["root"] == "repair:container":
            groups[m["name"]] = [re.compile(x) for x in p["stages"]]
    return groups


def test_the_cells_stage_groups_partition_a_mesh_repairs_stage_names():
    groups = _repair_groups()
    assert set(groups) >= {"repair_fixed_ms", "repair_read_ms",
                           "repair_write_ms", "repair_mesh_ms"}
    served = [
        "client:/ozone.tpu.DatanodeService/CreateContainer",
        "client:/ozone.tpu.DatanodeService/ListBlock",
        "client:/ozone.tpu.DatanodeService/CloseContainer",
        "client:/ozone.tpu.DatanodeService/GetBlock",
        "client:/ozone.tpu.DatanodeService/ReadChunks",
        "client:/ozone.tpu.DatanodeService/WriteChunksCommit",
        "client:/ozone.tpu.DatanodeService/PutBlock",
        "repair:container", "repair:prepare", "repair:block",
        "repair:write", "repair:close", "ec:fanout", "net:get_block",
        "net:read_chunks", "mesh:queue_wait", "mesh:device_dispatch"]
    for stage in served:
        owners = [m for m, pats in groups.items()
                  if any(p.match(stage) for p in pats)]
        assert len(owners) == 1, (stage, owners)
    assert [m for m, pats in groups.items()
            if any(p.match("mesh:queue_wait") for p in pats)] \
        == ["repair_mesh_ms"]


# ---------------------------------------- the two readers, by hand
def _plane(n: int, ops, modules):
    return {"name": f"/device:TPU:{n}", "lines": [
        {"name": tr.OPS_LINE, "events": [list(e) for e in ops]},
        {"name": tr.MODULES_LINE, "events": [list(e) for e in modules]}]}


def _run(trace, slice_counters) -> Run:
    cfg = {"scheme": {"k": 6, "p": 3, "cell": MIB, "bpc": 16384}}
    return Run(cell={}, config=cfg, traffic={}, setup_s=1.0, ops=[],
               t0=0.0, t1=30.0, counters0={}, counters1={},
               peaks=work.peaks_for("TPU v5 lite"), trace=trace,
               slice0=10.0, slice1=15.0, slice_counters0={},
               slice_counters1=slice_counters)


def _read(name: str, run: Run):
    params = mf.metric_params(name)
    return mf.reader_of(params)(params, run)


def test_mesh_roofline_holds_all_planes_time_to_one_chips_roof():
    name = "sharded_decode_roofline.repair"
    # 5 dispatches traced, each one execution of 2 ms on each of four
    # planes (20 events, 40 ms of device time); 4 dispatches counted in
    # the slice with 40 useful stripes of their 128 slots. Names other
    # programs would have are not matched.
    def events(prog):
        return [(prog, i * 10_000_000, 2_000_000) for i in range(5)]

    t = {"planes": [_plane(n, [], events("jit_sharded_decode_apply(7)"))
                    for n in range(4)]}
    t["planes"][0]["lines"][1]["events"] += [
        list(e) for e in events("jit_fn(1)")
        + events("jit__decode_apply_jit(2)")
        + events("jit_sharded_fused_encode(3)")]
    counters = {"mesh/stripes_dispatched": 40.0, "mesh/dispatches": 4.0}
    # decode of e = 1 at k = 6: reads 6 cells, writes 1 and its 64 CRCs
    per_stripe = 7 * MIB + 4 * 64
    assert work.decode_work(6, 1, MIB, 16384)["bytes"] == per_stripe
    least = 40 * per_stripe / 819e9
    device_seconds = 4 * 4 * 0.002  # 4 dispatches x 4 planes x 2 ms
    assert _read(name, _run(t, counters)) == pytest.approx(
        100 * least / device_seconds)
    # the accepted reader on the same trace reads the device count too
    # high: one chip's roof over the MEAN per-device time
    params = dict(mf.metric_params("fused_decode_roofline.repair"),
                  program=mf.metric_params(name)["program"])
    old = mf.reader_of(params)(params, _run(t, counters))
    assert old == pytest.approx(4 * _read(name, _run(t, counters)))
    # a plane that ran nothing is no plane of the program's: 2 planes
    # x 5 executions for the same 4 dispatches
    two = {"planes": t["planes"][1:3] + [_plane(3, [], [])]}
    assert _read(name, _run(two, counters)) == pytest.approx(
        100 * least / (4 * 2 * 0.002))
    # nothing to read: a program that names it otherwise (the parent
    # commit: `jit_fn(`), no dispatch counted, no trace
    parent = {"planes": [_plane(n, [], events("jit_fn(7)"))
                         for n in range(4)]}
    assert _read(name, _run(parent, counters)) is None
    assert _read(name, _run(t, {})) is None
    assert _read(name, _run(None, counters)) is None
    # counted from the single-chip service's counters it reads nothing
    assert _read(name, _run(t, {"codec.service/stripes_dispatched": 40.0,
                                "codec.service/dispatches": 4.0})) is None


def test_device_balance_is_the_least_busy_plane_over_the_busiest():
    name = "mesh_device_balance_pct.repair"
    # busy = the union of a plane's op intervals: 30, 40 (two ops that
    # overlap by 10), 20 and 40 ms
    t = {"planes": [
        _plane(0, [("a", 0, 30_000_000)], []),
        _plane(1, [("a", 0, 30_000_000), ("b", 20_000_000, 20_000_000)],
               []),
        _plane(2, [("a", 5_000_000, 20_000_000)], []),
        _plane(3, [("a", 0, 40_000_000)], []),
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["x", 0, 100_000_000]]}]}]}
    assert _read(name, _run(t, {})) == pytest.approx(100 * 20 / 40)
    even = {"planes": [_plane(n, [("a", n, 1_000_000)], [])
                       for n in range(4)]}
    assert _read(name, _run(even, {})) == pytest.approx(100.0)
    # one device, none busy, no trace: nothing
    assert _read(name, _run({"planes": t["planes"][:1]}, {})) is None
    assert _read(name, _run({"planes": [_plane(0, [], []),
                                        _plane(1, [], [])]}, {})) is None
    assert _read(name, _run(None, {})) is None


def test_the_accepted_unfed_reader_has_nothing_to_read_in_this_cell():
    """Why the table's `device_idle_unfed_pct.repair-mesh` is not
    brought: on the chip the mesh dispatcher idles a few times at the
    window's start and never inside the traced slice, and the accepted
    reader cannot tell "never starved" from "not annotated": nothing in
    both (PERF.md section 7, for a `benchmark` PR)."""
    accepted = dict(mf.metric_params("device_idle_unfed_pct.repair"),
                    event="mesh:idle")

    def read(host_events):
        return mf.reader_of(accepted)(accepted, _run({"planes": [
            _plane(0, [("a", 0, 40_000_000)], []),
            _plane(1, [("a", 0, 10_000_000)], []),
            {"name": "/host:CPU", "lines": [{
                "name": "mesh-executor", "events": host_events}]}]}, {}))

    # the first device is idle 60 of 100 ms; `mesh:idle` covers 30 ms of
    # that and 10 ms of its busy time
    assert read([["mesh:idle", 30_000_000, 40_000_000],
                 ["mesh:d2h", 70_000_000, 30_000_000]]) \
        == pytest.approx(50.0)
    assert read([["mesh:pack", 0, 20_000_000],
                 ["mesh:d2h", 20_000_000, 80_000_000]]) is None
    assert read([["codec:idle", 0, 100_000_000]]) is None


def test_mesh_counter_readers_read_the_mesh_registry():
    run = _run(None, {})
    run.t0, run.t1 = 100.0, 110.0
    run.counters0 = {"mesh/stripes_dispatched": 8.0,
                     "mesh/slots_dispatched": 32.0,
                     "mesh/dispatches": 1.0,
                     "mesh/multi_op_dispatches": 0.0,
                     "mesh/pack_seconds.sum": 0.1,
                     "mesh/pack_seconds.count": 1.0,
                     "mesh/idle_seconds.sum": 2.0}
    run.counters1 = {"mesh/stripes_dispatched": 56.0,
                     "mesh/slots_dispatched": 160.0,
                     "mesh/dispatches": 5.0,
                     "mesh/multi_op_dispatches": 3.0,
                     "mesh/pack_seconds.sum": 0.3,
                     "mesh/pack_seconds.count": 5.0,
                     "mesh/idle_seconds.sum": 6.5}
    assert _read("mesh_fill_pct.repair", run) == pytest.approx(
        100 * 48 / 128)
    assert _read("mesh_coalesced_pct.repair", run) == pytest.approx(75.0)
    assert _read("mesh_pack_ms.repair", run) == pytest.approx(50.0)
    assert _read("mesh_idle_pct.repair", run) == pytest.approx(45.0)
    assert _read("mesh_launch_ms.repair", run) is None  # observed nothing
    # a program without the stage (the parent commit): nothing, no error
    run.counters0.pop("mesh/idle_seconds.sum")
    run.counters1.pop("mesh/idle_seconds.sum")
    assert _read("mesh_idle_pct.repair", run) is None


# ---------------------------------------------- a CPU pass of the cell
def _tiny():
    cell = mf.cell(MANIFEST, CELL)
    config = copy.deepcopy(mf.config_of(MANIFEST, cell))
    s = config["scheme"]
    s["cell"], s["bpc"] = 4096, 4096
    config["replication"] = f"rs-{s['k']}-{s['p']}-4096"
    return cell, config, {**mf.traffic_of(cell), **TINY}


def _pass(tmp_path, trace: int = 0, control: str = "", seed: int = 2 ** 31 + 7):
    """The rest of a run against the in-process mini-cluster, as
    bench_minicluster.run_cell drives the accepted cells; the mesh is the
    tests' forced host devices, its programs the host twin. Returns the
    result line's dict and the Run the readers were handed."""
    import benchmarks.run as bench_run
    from benchmarks.harness import record

    cell, config, traffic = _tiny()
    cluster = bm.MiniCluster(tmp_path, config["cluster"]["datanodes"])
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=1.0,
                              trace=trace, rehearse=True, control=control,
                              dump_trace="")
    seen: list[Run] = []

    class Keep(Run):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            seen.append(self)

    real, record.Run = record.Run, Keep
    try:
        out = json.loads(json.dumps(bench_run.measure(
            args, MANIFEST, cluster, cell, config, traffic)))
    finally:
        record.Run = real
        cluster.close()
    return out, seen[0]


def test_a_clean_pass_is_correct_and_never_leaves_the_mesh(tmp_path):
    out, _run_ = _pass(tmp_path)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 10 and out["rehearsal"] is True
    assert set(out["metrics"]) == {"repair_mib_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    c = out["compared"]
    assert list(out)[-1] == "compared"
    assert c["single_chip_decode_stripes"] == {"value": 0.0, "limit": 0}
    assert c["mesh_decode_stripes"]["value"] >= 2 * out["attempted"]
    for number in ("rebuilt_records_wrong", "rebuilt_bytes_differ",
                   "rebuilt_crcs_differ"):
        assert c[number] == {"value": 0, "limit": 0}
    assert c["replicas_compared"]["value"] == TINY["verify_replicas"]
    # on the CPU the program picks the host twin by its own rule: the
    # two numbers that hold a TPU run to its devices are not compared
    assert "mesh_output_shards" not in c
    assert "mesh_host_twin_programs" not in c


def test_a_traced_pass_reads_every_counter_and_span_metric(
        tmp_path, capsys):
    out, run = _pass(tmp_path, trace=1)
    assert out["correct"] is True
    want = {m["name"] for m in mf.metrics_for(MANIFEST, "per_layer", CELL)
            if m["source"] != "device_trace"}
    got = {k: v["value"] for k, v in out["metrics"].items()}
    with capsys.disabled():
        print(f"\n{CELL} (CPU rehearsal, no measurement): " + ", ".join(
            f"{k}={v:.3f}" for k, v in sorted(got.items())))
    assert set(got) == want  # no profile: the device-trace ones left out
    assert all(v >= 0 for v in got.values())
    assert got["repair_mesh_ms"] > 0 and got["mesh_fill_pct.repair"] > 0
    # the mesh dispatcher's thread is accounted for (loosely: a 1 s
    # window on a shared CPU; the chip run's sum is in PERF.md)
    from benchmarks.harness.program import delta

    busy = sum(delta(run.counters1, run.counters0, f"mesh/{k}_seconds.sum")
               for k in ("idle", "pack", "launch", "d2h"))
    assert 0.5 <= busy / (run.t1 - run.t0) <= 1.1
    # the root's groups sum to the mean duration of the same root spans,
    # and no stage of the single-chip service is among them
    ops = spans.operations("repair:container", run.t0, run.t1)
    assert ops and not any(s.startswith("codec:")
                           for o in ops for s in o["stages"])
    mean_ms = sum(o["durationUs"] for o in ops) / len(ops) / 1e3
    assert sum(got[m] for m in _repair_groups()) \
        == pytest.approx(mean_ms, rel=0.01)


@pytest.mark.parametrize("control,number", [
    ("wipe_replica", "rebuilt_records_wrong"),
    ("byte_flip", "rebuilt_bytes_differ"),
])
def test_a_planted_fault_makes_the_pass_not_correct(
        tmp_path, control, number):
    out, _run_ = _pass(tmp_path, control=control)
    assert out["correct"] is False and out["control"] == control
    c = out["compared"][number]
    assert c["value"] > c["limit"] == 0
    assert out["compared"]["single_chip_decode_stripes"]["value"] == 0


def test_a_decode_that_reaches_the_single_chip_service_is_not_correct(
        tmp_path, monkeypatch):
    """The door's silent fall-through (`parallel/dispatch.py`: a key the
    mesh executor has no program for goes to the codec service) is what
    the cell reports: every replica is still rebuilt right, and the run
    is not correct. Each stream is asked for at the door under a key of
    a kind no executor has a program for, with the same decoder."""
    from ozone_tpu.parallel import dispatch

    door = dispatch.pipeline

    def off_the_mesh(key, fn, **kw):
        return door(("no-mesh-program", *key), fn, **kw)

    monkeypatch.setattr(dispatch, "pipeline", off_the_mesh)
    out, _run_ = _pass(tmp_path)
    c = out["compared"]
    assert out["correct"] is False and out["failed"] == 0
    assert c["single_chip_decode_stripes"]["value"] > 0
    assert c["mesh_decode_stripes"]["value"] == 0
    assert c["rebuilt_bytes_differ"] == {"value": 0, "limit": 0}


def test_a_program_without_the_storms_method_ends_the_run_early(
        tmp_path, monkeypatch):
    """The parent commit under this PR's benchmark files: the generator
    refuses before anything is preloaded, and nothing is printed."""
    from ozone_tpu.client import reconstruction

    monkeypatch.delattr(reconstruction.ReconstructionStorm,
                        "repair_container")
    with pytest.raises(RuntimeError, match="no per-container method"):
        _pass(tmp_path)
    assert not list((tmp_path / "cluster").rglob("*.block"))


# -------------------------- through the real launcher, 4 host devices
@pytest.mark.serial
def test_a_rehearsal_on_four_forced_host_devices_ends_correct(tmp_path):
    """run.py loads JAX before the generator exists, so the 4 devices
    are forced in the subprocess's environment."""
    (tmp_path / "tmp").mkdir()
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path / "tmp"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, str(mf.BENCH_DIR / "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 27), "--seconds", "2", "--trace", "1",
         "--rehearse"], cwd=mf.ROOT, env=env, capture_output=True,
        text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["failed"] == 0 and line["attempted"] >= 4
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 4
    assert line["compared"]["single_chip_decode_stripes"]["value"] == 0
    assert line["compared"]["mesh_decode_stripes"]["value"] >= 1
    assert {"repair_mesh_ms", "mesh_fill_pct.repair", "mesh_pack_ms.repair",
            "mesh_idle_pct.repair"} <= set(line["metrics"])
    assert not bm.processes_mentioning(str(tmp_path))
    assert not list((tmp_path / "tmp").iterdir())
