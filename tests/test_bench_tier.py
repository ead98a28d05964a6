"""The cells ISSUE 33 adds: `tier-mesh.rs-6-3` (the deployment
`ratis3-to-rs-6-3-1024k-mesh4`, its traffic, generator and metric files)
and the kept cell `ockg.rs-10-4`: the manifest with them, the metric
files on planted numbers, a CPU pass of the sweep through the in-process
mini-cluster (clean, and with each control), its stage groups, and a
rehearsal through the real launcher on 4 forced host devices."""

import argparse
import copy
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).parent / "benchmark_tests"))

import bench_minicluster as bm  # noqa: E402
from benchmarks.harness import manifest as mf  # noqa: E402
from benchmarks.harness import spans  # noqa: E402
from benchmarks.harness import trace as tr  # noqa: E402
from benchmarks.harness import work  # noqa: E402
from benchmarks.harness.record import Run  # noqa: E402

CELL = "tier-mesh.rs-6-3"
KEPT = "ockg.rs-10-4"
CONFIG = "ratis3-to-rs-6-3-1024k-mesh4"
MIB = 2 ** 20
MANIFEST = mf.load()
#: every metric the cell brings, beside the reader its file names
TIER_METRICS = {
    "mesh_fill_pct.tier": "counter_ratio_pct",
    "mesh_idle_pct.tier": "mesh_window_share_pct",
    "mesh_completer_idle_pct.tier": "mesh_window_share_pct",
    "mesh_window_full_pct.tier": "mesh_window_share_pct",
    "mesh_pack_ms.tier": "histogram_mean_ms",
    "mesh_launch_ms.tier": "histogram_mean_ms",
    "mesh_d2h_ms.tier": "histogram_mean_ms",
    "mesh_complete_ms.tier": "histogram_mean_ms",
    "mesh_dispatch_ms.tier": "histogram_mean_ms",
    "mesh_queue_wait_ms.tier": "histogram_mean_ms",
    "mesh_device_balance_pct.tier": "device_balance_pct",
    "device_idle_pct.tier": "device_idle_pct",
    "sharded_encode_roofline.tier": "mesh_kernel_roofline",
    "tier_read_ms": "mesh_op_stage_ms",
    "tier_mesh_ms": "mesh_op_stage_ms",
    "tier_write_ms": "mesh_op_stage_ms",
    "tier_om_ms": "mesh_op_stage_ms",
    "tier_client_ms": "mesh_op_stage_ms",
}
#: what ISSUE 38 appended for the cell: the cost of a key in this
#: process, and the process's own series (shared with the `ockg` cells)
COST_METRICS = {
    "tier_cpu_ms": "op_cost_ms",
    "client_cpu_cores.put": "process_cpu_cores",
    "interp_wait_ms.put": "process_interp_wait_ms",
    "host_busy_pct.put": "host_busy_pct",
}
GROUPS = ("tier_read_ms", "tier_mesh_ms", "tier_write_ms", "tier_om_ms",
          "tier_client_ms")
#: the sweep at a size a test can hold: 4 KiB cells, keys of 2 and 4
#: stripes, calls of 6 keys (each drains before the next, so the sweep
#: reaches few keys ahead of those it has converted)
TINY = {"stripes_per_key": [2, 4], "source_keys": 160, "warm_keys": 4,
        "batch_keys": 6, "preload_threads": 4, "verify_converted": 4,
        "verify_unconverted": 2}


# ------------------------------------------------------- the manifest
def test_both_cells_and_the_deployment_are_entries_appended_to_the_lists():
    assert mf.problems(MANIFEST) == []
    # appended together, in that order, after the four cells the
    # benchmark had; any later cell comes after them (lists only grow)
    names = [w["name"] for w in MANIFEST["workloads"]]
    at = names.index(KEPT)
    assert names[at:at + 2] == [KEPT, CELL]
    assert set(names[:at]) == {"ockg.rs-6-3", "ockv-degraded.rs-10-4",
                               "ecrd.rs-6-3", "ecrd-mesh.rs-6-3"}
    assert mf.cell(MANIFEST, KEPT) == {
        "name": KEPT, "config": "rs-10-4-1024k", "traffic": "ockg",
        "chips": 1, "why": mf.cell(MANIFEST, KEPT)["why"]}
    assert mf.cell(MANIFEST, CELL) == {
        "name": CELL, "config": CONFIG, "traffic": "tier-sweep",
        "chips": 4, "why": mf.cell(MANIFEST, CELL)["why"]}
    assert MANIFEST["configs"][3]["name"] == CONFIG
    # the contract's rule for chips: of n cells at most n // 2 (and one
    # always) take four, the sweep among them
    chips = [w["chips"] for w in MANIFEST["workloads"]]
    assert 2 <= chips.count(4) <= max(1, len(chips) // 2)
    # both cells report the write rate, after the write cell; a later
    # cell that reports it is appended
    (put,) = [m for m in MANIFEST["end_to_end"] if m["name"] == "put_mib_s"]
    assert put["workloads"][:3] == ["ockg.rs-6-3", KEPT, CELL]
    for cell in (KEPT, CELL):
        assert {m["name"] for m in mf.metrics_for(
            MANIFEST, "end_to_end", cell)} == {"put_mib_s", "setup_s"}


def test_the_kept_cell_reports_every_metric_the_write_cell_does():
    """One entry and names appended: no file of its own."""
    for m in MANIFEST["per_layer"]:
        assert ("ockg.rs-6-3" in m["workloads"]) == (KEPT in m["workloads"])
        if KEPT in m["workloads"]:
            # listed right after the write cell; the cells that report
            # the metric too (the sweep's, an S3 cell's) come after
            assert m["workloads"][:2] == ["ockg.rs-6-3", KEPT], m["name"]
    assert len(mf.metrics_for(MANIFEST, "per_layer", KEPT)) == len(
        mf.metrics_for(MANIFEST, "per_layer", "ockg.rs-6-3")) > 10
    assert mf.config_of(MANIFEST, mf.cell(MANIFEST, KEPT))["scheme"]["k"] == 10


def test_the_sweeps_metrics_list_the_cell_alone_and_move_put_mib_s():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name, reader in TIER_METRICS.items():
        # the sweep's own metrics: listed first by the sweep cell, and
        # by four-chip cells alone (they read the mesh executor)
        cells = by_name[name]["workloads"]
        assert cells[0] == CELL, name
        assert all(mf.cell(MANIFEST, w)["chips"] == 4 for w in cells), name
        assert by_name[name]["moves"] == "put_mib_s"
        assert mf.metric_params(name)["reader"] == reader, name
    # what the cell reports holds both sets; a later PR may list it
    # under more
    assert {m["name"] for m in mf.metrics_for(
        MANIFEST, "per_layer", CELL)} >= set(TIER_METRICS) | set(COST_METRICS)
    for name, reader in COST_METRICS.items():
        assert CELL in by_name[name]["workloads"], name
        assert by_name[name]["moves"] == "put_mib_s"
        assert mf.metric_params(name)["reader"] == reader, name
    # appended in one run, after everything the benchmark had then
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index(next(iter(TIER_METRICS)))
    assert names[at:at + len(TIER_METRICS)] == list(TIER_METRICS)
    assert at > names.index("mesh_completer_idle_pct.repair")
    roof = mf.metric_params("sharded_encode_roofline.tier")
    assert roof["work"] == "encode"
    assert re.search(roof["program"], "jit_sharded_fused_encode(12)")
    assert not re.search(roof["program"], "jit_sharded_decode_apply(3)")
    assert by_name["sharded_encode_roofline.tier"]["unit"] == "%"


def test_the_deployment_states_its_source_guarantees_cuts_and_assumptions():
    cfg = json.loads((mf.BENCH_DIR / "configs" / f"{CONFIG}.json")
                     .read_text())
    base = json.loads((mf.BENCH_DIR / "configs" / "rs-6-3-1024k.json")
                      .read_text())
    entry = MANIFEST["configs"][3]
    assert cfg["source"] == entry["source"] and len(entry["source"]) <= 200
    assert "BASELINE.json config 4" in cfg["source"]
    assert cfg["source_replication"] == "RATIS/THREE"
    assert cfg["replication"] == base["replication"] == "rs-6-3-1024k"
    assert cfg["scheme"] == base["scheme"]
    for key in ("launcher", "datanodes", "metadata_replicas", "datapath"):
        assert cfg["cluster"][key] == base["cluster"][key], key
    assert cfg["cluster"]["chips"] == 4
    assert cfg["sweeper"]["batch_keys"] == 128
    assert cfg["sweeper"]["throttle"] is None
    assert cfg["reference"].startswith("benchmarks/harness/reference.py")
    assert len(cfg["guarantees"]) == 4
    for needle, g in zip(("byte-exact", "not yet converted",
                          "KEY_MODIFIED", "all four devices"),
                         cfg["guarantees"]):
        assert needle in g
    assert set(cfg["reduced"]) == set(entry["reduced"]) == {
        "metadata_replicas", "hosts", "data_scale", "chips",
        "sweeper_placement"}
    assert {"sweepers", "batch_keys", "key_bytes", "whole_stripes",
            "age_days"} <= set(cfg["assumed"])
    traffic = mf.traffic_of(mf.cell(MANIFEST, CELL))
    assert traffic["generator"] == "tier_sweep"
    assert traffic["stripes_per_key"] == [4, 12]
    assert traffic["batch_keys"] == 128 and traffic["warm_keys"] == 8
    assert traffic["verify_converted"] == 8
    assert traffic["verify_unconverted"] == 4


# ------------------------------------- the metric files, by hand
def _run(**kw) -> Run:
    base = dict(cell={}, config={"scheme": {"k": 6, "p": 3, "cell": MIB,
                                            "bpc": 16384}},
                traffic={}, setup_s=1.0, ops=[], t0=100.0, t1=110.0,
                counters0={}, counters1={})
    return Run(**{**base, **kw})


def _read(name: str, run: Run):
    params = mf.metric_params(name)
    return mf.reader_of(params)(params, run)


def test_the_counter_metrics_read_planted_numbers_of_the_mesh_registry():
    c0 = {"mesh/stripes_dispatched": 32.0, "mesh/slots_dispatched": 32.0,
          "mesh/idle_seconds.sum": 1.0, "mesh/window_full_seconds.sum": 0.0,
          "mesh/completer_idle_seconds.sum": 2.0}
    c1 = {"mesh/stripes_dispatched": 312.0, "mesh/slots_dispatched": 320.0,
          "mesh/idle_seconds.sum": 8.5, "mesh/window_full_seconds.sum": 0.25,
          "mesh/completer_idle_seconds.sum": 11.0}
    for stage, (total, n) in {"pack": (0.9, 9), "launch": (0.18, 9),
                              "d2h": (0.45, 9), "complete": (0.09, 9),
                              "dispatch": (1.8, 9),
                              "queue_wait": (0.027, 9)}.items():
        c0[f"mesh/{stage}_seconds.sum"] = 0.5
        c0[f"mesh/{stage}_seconds.count"] = 1.0
        c1[f"mesh/{stage}_seconds.sum"] = 0.5 + total
        c1[f"mesh/{stage}_seconds.count"] = 1.0 + n
    run = _run(counters0=c0, counters1=c1)
    assert _read("mesh_fill_pct.tier", run) == pytest.approx(100 * 280 / 288)
    assert _read("mesh_idle_pct.tier", run) == pytest.approx(75.0)
    assert _read("mesh_completer_idle_pct.tier", run) == pytest.approx(90.0)
    assert _read("mesh_window_full_pct.tier", run) == pytest.approx(2.5)
    assert _read("mesh_pack_ms.tier", run) == pytest.approx(100.0)
    assert _read("mesh_launch_ms.tier", run) == pytest.approx(20.0)
    assert _read("mesh_d2h_ms.tier", run) == pytest.approx(50.0)
    assert _read("mesh_complete_ms.tier", run) == pytest.approx(10.0)
    assert _read("mesh_dispatch_ms.tier", run) == pytest.approx(200.0)
    assert _read("mesh_queue_wait_ms.tier", run) == pytest.approx(3.0)
    # a program without the mesh registry (one chip): nothing, no error
    for name, reader in TIER_METRICS.items():
        if reader in ("counter_ratio_pct", "mesh_window_share_pct",
                      "histogram_mean_ms"):
            assert _read(name, _run()) is None, name


def _plane(n: int, ops, modules):
    return {"name": f"/device:TPU:{n}", "lines": [
        {"name": tr.OPS_LINE, "events": [list(e) for e in ops]},
        {"name": tr.MODULES_LINE, "events": [list(e) for e in modules]}]}


def test_the_device_metrics_read_a_hand_made_four_plane_trace():
    # 5 dispatches traced, each one execution of 3 ms on each of four
    # planes; 4 dispatches counted in the slice, 120 useful stripes of
    # their 128 slots; a decode program beside it is not matched
    def events(prog):
        return [(prog, i * 10_000_000, 3_000_000) for i in range(5)]

    t = {"planes": [_plane(n, [("fusion", i * 10_000_000, 3_000_000)
                               for i in range(5)],
                           events("jit_sharded_fused_encode(9)"))
                    for n in range(4)]}
    t["planes"][0]["lines"][1]["events"] += [
        list(e) for e in events("jit_sharded_decode_apply(2)")]
    run = _run(trace=t, peaks=work.peaks_for("TPU v5 lite"), slice0=102.0,
               slice1=107.0, slice_counters0={},
               slice_counters1={"mesh/stripes_dispatched": 120.0,
                                "mesh/dispatches": 4.0})
    per_stripe = 9 * MIB + 4 * 9 * 64
    assert work.encode_work(6, 3, MIB, 16384)["bytes"] == per_stripe
    least = 120 * per_stripe / 819e9
    assert _read("sharded_encode_roofline.tier", run) == pytest.approx(
        100 * least / (4 * 4 * 0.003))
    assert _read("mesh_device_balance_pct.tier", run) == pytest.approx(100.0)
    assert 99.0 < _read("device_idle_pct.tier", run) < 100.0
    # the parent's program names it the same; a one-chip run names its
    # encode `jit_fn(`: nothing to read there, and no error
    one = {"planes": [_plane(0, [], events("jit_fn(1)"))]}
    assert _read("sharded_encode_roofline.tier",
                 _run(trace=one, peaks=run.peaks,
                      slice_counters1=run.slice_counters1)) is None
    for name in ("sharded_encode_roofline.tier", "device_idle_pct.tier",
                 "mesh_device_balance_pct.tier"):
        assert _read(name, _run()) is None


def test_the_stage_groups_partition_a_converted_keys_stage_names():
    groups = {m: [re.compile(x) for x in mf.metric_params(m)["stages"]]
              for m in GROUPS}
    for m in GROUPS:
        assert mf.metric_params(m)["root"] == "tier:key"
    served = {
        "tier:key": "tier_client_ms", "tier:pack": "tier_client_ms",
        "tier:read": "tier_read_ms", "tier:write": "tier_write_ms",
        "tier:finalize": "tier_write_ms",
        "mesh:queue_wait": "tier_mesh_ms",
        "mesh:device_dispatch": "tier_mesh_ms",
        "client:/ozone.tpu.OmService/OpenKey": "tier_om_ms",
        "client:/ozone.tpu.OmService/AllocateBlock": "tier_om_ms",
        "client:/ozone.tpu.OmService/CommitKey": "tier_om_ms",
        "client:/ozone.tpu.DatanodeService/CreateContainer": "tier_om_ms",
        "client:/ozone.tpu.DatanodeService/GetBlock": "tier_read_ms",
        "client:/ozone.tpu.DatanodeService/ReadChunks": "tier_read_ms",
        "client:/ozone.tpu.DatanodeService/GetDatapathInfo":
            "tier_read_ms",
        "client:/ozone.tpu.DatanodeService/WriteChunksCommit":
            "tier_write_ms",
        "client:/ozone.tpu.DatanodeService/PutBlock": "tier_write_ms",
    }
    for stage, owner in served.items():
        assert [m for m, pats in groups.items()
                if any(p.match(stage) for p in pats)] == [owner], stage


# ---------------------------------------------- a CPU pass of the cell
def _tiny():
    cell = mf.cell(MANIFEST, CELL)
    config = copy.deepcopy(mf.config_of(MANIFEST, cell))
    s = config["scheme"]
    s["cell"], s["bpc"] = 4096, 4096
    config["replication"] = f"rs-{s['k']}-{s['p']}-4096"
    return cell, config, {**mf.traffic_of(cell), **TINY}


def _pass(tmp_path, trace: int = 0, control: str = "",
          seed: int = 2 ** 31 + 33, seconds: float = 0.4):
    """The rest of a run against the in-process mini-cluster, as
    tests/benchmark_tests/test_bench_mesh.py drives its cell; the mesh
    is the tests' forced host devices, its programs the host twin."""
    import benchmarks.run as bench_run
    from benchmarks.harness import record

    cell, config, traffic = _tiny()
    cluster = bm.MiniCluster(tmp_path, config["cluster"]["datanodes"])
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=seconds,
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


def test_a_traced_pass_is_correct_and_reads_every_counter_and_span_metric(
        tmp_path, capsys, monkeypatch):
    from ozone_tpu.utils import tracing

    # the program costs one root of a name a second, and this window is
    # 0.4 s: cost every key, so `tier_cpu_ms` has one to read
    monkeypatch.setattr(tracing, "COST_INTERVAL_S", 0.0)
    out, run = _pass(tmp_path, trace=1)
    assert out["correct"] is True and out["failed"] == 0, out["compared"]
    assert out["attempted"] >= 4 and out["rehearsal"] is True
    c = out["compared"]
    assert list(out)[-1] == "compared"
    for number in ("acked_keys_missing", "readback_keys_differ",
                   "converted_keys_not_ec", "stored_records_wrong",
                   "stored_bytes_differ", "stored_crcs_differ",
                   "unconverted_keys_wrong", "raced_user_bytes_lost"):
        assert c[number] == {"value": 0, "limit": 0}, number
    assert c["single_chip_encode_stripes"] == {"value": 0.0, "limit": 0}
    assert c["mesh_encode_stripes"]["value"] >= 8
    assert c["raced_conversion_conflicts"] == {"value": 1, "limit": 1}
    assert c["units_compared"]["value"] == 9 * c["keys_compared"]["value"]
    assert c["keys_compared"]["value"] == TINY["verify_converted"]
    assert c["unconverted_keys_compared"]["value"] == 2
    # the window is the lane's width on whatever mesh this is
    assert c["packer_window_stripes"]["value"] \
        == c["packer_window_stripes"]["limit"] >= 32
    # on the CPU the program picks the host twin by its own rule
    assert "mesh_output_shards" not in c
    assert "mesh_host_twin_programs" not in c
    notes = out["notes"]
    assert notes["keys_converted_in_window"] >= 4
    assert notes["source_keys_left"] >= 3
    assert notes["lifecycle"]["windows_submitted"] >= 1
    assert notes["lifecycle"]["stripes_packed"] >= 12

    want = {m["name"] for m in mf.metrics_for(MANIFEST, "per_layer", CELL)
            if m["source"] != "device_trace"}
    got = {k: v["value"] for k, v in out["metrics"].items()}
    with capsys.disabled():
        print(f"\n{CELL} (CPU rehearsal, no measurement): " + ", ".join(
            f"{k}={v:.3f}" for k, v in sorted(got.items())))
    assert set(got) == want  # no profile: the device-trace ones left out
    assert all(v >= 0 for v in got.values())
    assert got["tier_mesh_ms"] > 0 and got["mesh_fill_pct.tier"] > 0
    # the five groups sum to the mean duration of the same root spans,
    # and every stage of them belongs to exactly one group
    ops = spans.operations("tier:key", run.t0, run.t1)
    assert ops and not any(s.startswith("codec:")
                           for o in ops for s in o["stages"])
    mean_ms = sum(o["durationUs"] for o in ops) / len(ops) / 1e3
    assert sum(got[m] for m in GROUPS) == pytest.approx(mean_ms, rel=0.01)
    patterns = [re.compile(x) for m in GROUPS
                for x in mf.metric_params(m)["stages"]]
    for stage in {s for o in ops for s in o["stages"]}:
        assert sum(bool(p.match(stage)) for p in patterns) == 1, stage


@pytest.mark.parametrize("control,number", [
    ("byte_flip", "stored_bytes_differ"),
    ("fence_dropped", "raced_user_bytes_lost"),
])
def test_each_control_makes_the_pass_not_correct(tmp_path, control, number):
    out, _run_ = _pass(tmp_path, control=control)
    assert out["correct"] is False and out["control"] == control
    c = out["compared"][number]
    assert c["value"] > c["limit"] == 0
    if control == "fence_dropped":
        # the conversion clobbered the user's overwrite unrefused
        assert out["compared"]["raced_conversion_conflicts"]["value"] == 0
    else:
        assert out["compared"]["raced_conversion_conflicts"]["value"] == 1
    assert out["compared"]["single_chip_encode_stripes"]["value"] == 0


def test_a_sweep_that_leaves_the_mesh_is_not_correct(tmp_path, monkeypatch):
    """The door's fall-through (a key the mesh has no program for goes
    to the codec service) is what the cell reports."""
    from ozone_tpu.parallel import mesh_executor

    def no_program(self, key, **kw):
        raise KeyError(f"no mesh program for {key!r}")

    monkeypatch.setattr(mesh_executor.MeshExecutor, "pipeline", no_program)
    out, _run_ = _pass(tmp_path)
    c = out["compared"]
    assert out["correct"] is False and out["failed"] == 0
    assert c["single_chip_encode_stripes"]["value"] > 0
    assert c["mesh_encode_stripes"]["value"] == 0
    assert c["stored_bytes_differ"] == {"value": 0, "limit": 0}


def test_a_program_whose_packer_has_a_width_of_its_own_ends_the_run_early(
        tmp_path, monkeypatch):
    """The parent commit under this PR's benchmark files: the generator
    refuses before anything is preloaded, and nothing is printed."""
    from ozone_tpu.lifecycle import executor

    real = executor.TieringExecutor.__init__

    def parents(self, *a, **kw):
        real(self, *a, **kw)
        del self.last_window

    monkeypatch.setattr(executor.TieringExecutor, "__init__", parents)
    with pytest.raises(RuntimeError, match="a width of its own"):
        _pass(tmp_path)
    assert not list((tmp_path / "cluster").rglob("*.block"))


# -------------------------- through the real launcher, 4 host devices
@pytest.mark.serial
def test_a_rehearsal_on_four_forced_host_devices_ends_correct(tmp_path):
    """The sweeper in the chip-owning process, the OM behind RPC, the
    RATIS/THREE preload through the datanodes' Raft rings."""
    (tmp_path / "tmp").mkdir()
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path / "tmp"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, str(mf.BENCH_DIR / "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 33), "--seconds", "3", "--trace", "1",
         "--rehearse"], cwd=mf.ROOT, env=env, capture_output=True,
        text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True, \
        line["compared"]
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 4
    assert line["compared"]["single_chip_encode_stripes"]["value"] == 0
    assert line["compared"]["mesh_encode_stripes"]["value"] >= 1
    assert line["compared"]["packer_window_stripes"]["value"] == 32
    assert line["compared"]["raced_conversion_conflicts"]["value"] == 1
    assert set(GROUPS) | {"mesh_fill_pct.tier", "mesh_idle_pct.tier"} \
        <= set(line["metrics"])
    assert not bm.processes_mentioning(str(tmp_path))
    assert not list((tmp_path / "tmp").iterdir())
