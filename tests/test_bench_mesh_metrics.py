"""The three per-layer metrics ISSUE 28 adds to the mesh cell, as data:
entries of BENCHMARK.json and files under benchmarks/metrics/ that name
readers the benchmark already has. Also what
tests/benchmark_tests/test_bench_mesh.py can no longer reach behind the
statement that pins the cell's metric set (tests/conftest.py)."""

import json
from pathlib import Path

import pytest

from benchmarks.harness import manifest as mf
from benchmarks.harness.record import Run

ROOT = Path(__file__).resolve().parents[1]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "ecrd-mesh.rs-6-3"
NEW = {
    "mesh_complete_ms.repair":
        ("histogram_mean_ms", "mesh queue", "lower", "ms"),
    "mesh_window_full_pct.repair":
        ("mesh_window_share_pct", "link", "lower", "%"),
    "mesh_completer_idle_pct.repair":
        ("mesh_window_share_pct", "mesh queue", "higher", "%"),
}


def _run(**kw) -> Run:
    base = dict(cell={}, config={}, traffic={}, setup_s=1.0, ops=[],
                t0=100.0, t1=110.0, counters0={}, counters1={})
    return Run(**{**base, **kw})


def _read(name: str, run: Run):
    params = mf.metric_params(name)
    return mf.reader_of(params)(params, run)


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_metric_is_an_entry_of_the_mesh_cell_alone(name):
    reader, layer, better, unit = NEW[name]
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": "program_counter", "layer": layer,
                     "moves": "repair_mib_s", "workloads": [CELL]}
    assert mf.metric_params(name)["reader"] == reader
    # appended in one run after the 60 the benchmark had; later PRs
    # append after them
    at = [m["name"] for m in MANIFEST["per_layer"]].index(next(iter(NEW)))
    assert at >= 60
    assert [m["name"] for m in MANIFEST["per_layer"][at:at + len(NEW)]] \
        == list(NEW)


def test_the_manifest_is_sound_and_the_cell_reports_what_it_did_and_three():
    assert mf.problems(MANIFEST) == []
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    got = {m["name"] for m in mf.metrics_for(MANIFEST, "per_layer", CELL)}
    # 15 it had, PR 28's three, and ISSUE 38's cost of a repair: its
    # CPU, hand-offs and RPC sides, and the process's three series
    cost = {"repair_cpu_ms", "repair_handoffs", "repair_handoff_wait_ms",
            "repair_rpc_daemon_ms", "repair_rpc_client_side_ms",
            "client_cpu_cores.repair", "interp_wait_ms.repair",
            "host_busy_pct.repair"}
    # the 15 it had at least (a later PR may list it under more)
    assert set(NEW) | cost <= got and len(got) >= 15 + len(NEW) + len(cost)
    # what test_bench_mesh.py asserts behind its pinned statement
    assert {m["name"] for m in mf.metrics_for(
        MANIFEST, "end_to_end", CELL)} == {"repair_mib_s", "setup_s"}
    assert CELL not in by_name["fused_decode_roofline.repair"]["workloads"]
    assert by_name["repair_mesh_ms"]["source"] == "program_span"
    assert by_name["mesh_idle_pct.repair"]["source"] == "program_counter"
    for alias in ("window_share_pct", "op_stage_ms"):
        assert mf.reader_of({"reader": f"mesh_{alias}"}).__module__ \
            == f"benchmarks.readers.{alias}"


def test_the_readers_read_the_two_threads_stage_histograms():
    """Deltas over the window of registry `mesh`; a program without the
    stages (the parent) reads as nothing, and a stage that never
    happened as 0."""
    c0 = {"mesh/complete_seconds.sum": 1.0,
          "mesh/complete_seconds.count": 10.0,
          "mesh/window_full_seconds.sum": 2.0,
          "mesh/completer_idle_seconds.sum": 0.5}
    c1 = {"mesh/complete_seconds.sum": 3.5,
          "mesh/complete_seconds.count": 110.0,
          "mesh/window_full_seconds.sum": 6.5,
          "mesh/completer_idle_seconds.sum": 0.5}
    run = _run(counters0=c0, counters1=c1)
    assert _read("mesh_complete_ms.repair", run) == pytest.approx(25.0)
    assert _read("mesh_window_full_pct.repair", run) == pytest.approx(45.0)
    assert _read("mesh_completer_idle_pct.repair", run) == 0.0
    parent = _run(counters0={}, counters1={"mesh/d2h_seconds.sum": 1.0})
    for name in NEW:
        assert _read(name, parent) is None


def test_the_executor_has_every_stage_histogram_from_its_start():
    """`mesh_window_full_pct.repair` lists the cell, so a traced run
    must report it even where the window never filled."""
    from ozone_tpu.parallel import mesh_executor as me
    from ozone_tpu.parallel.sharded import make_mesh

    me.reset_for_tests()
    ex = me.MeshExecutor(mesh=make_mesh(4), depth=2)
    try:
        from benchmarks.harness import program

        snap = program.snapshot()
    finally:
        ex.close()
    for stage in me.STAGES:
        assert f"mesh/{stage}_seconds.sum" in snap, stage
