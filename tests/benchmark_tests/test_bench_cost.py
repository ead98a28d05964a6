"""The metrics ISSUE 38 adds: what an operation COST the host (CPU, the
hand-offs it made and their waits, its RPCs' time on either side of the
wire, the copies' time off the CPU), what the client process cost over
the window (cores, the wait for the interpreter, the host's busy share)
and the codec dispatcher's fifth stage: as entries and files, on planted
records, on a program without the new fields, and on a CPU pass of a GET
and a repair cell."""

import argparse
import copy
import json

import pytest

import bench_minicluster as bm
from benchmarks.harness import manifest as mf
from benchmarks.harness import process_series, spans
from benchmarks.harness.record import Run

MANIFEST = mf.load()
GET = ["ockv-degraded.rs-10-4", "ockv-degraded.rs-6-3"]
PUT = ["ockg.rs-6-3", "ockg.rs-10-4"]
REPAIR = ["ecrd.rs-6-3", "ecrd-mesh.rs-6-3", "ecrd.lrc-12-2-2"]
TIER = ["tier-mesh.rs-6-3"]
#: name -> (reader, unit, source, layer, moves, workloads)
NEW = {}
for _op, _cells, _moves, _rpc_layer in (
        ("get", GET, "get_mib_s", "datanode wire + disk"),
        ("put", PUT, "put_mib_s", "metadata"),
        ("repair", REPAIR, "repair_mib_s", "datanode wire + disk")):
    NEW[f"{_op}_cpu_ms"] = ("op_cost_ms", "ms", "program_span", "client",
                            _moves, _cells)
    NEW[f"{_op}_handoffs"] = ("op_handoff_mean", "handoffs", "program_span",
                              "client", _moves, _cells)
    NEW[f"{_op}_handoff_wait_ms"] = ("op_handoff_mean", "ms", "program_span",
                                     "client", _moves, _cells)
    for _side in ("daemon", "client_side"):
        NEW[f"{_op}_rpc_{_side}_ms"] = ("op_rpc_ms", "ms", "program_span",
                                        _rpc_layer, _moves, _cells)
    _proc = _cells + TIER if _op == "put" else _cells
    NEW[f"client_cpu_cores.{_op}"] = ("process_cpu_cores", "cores",
                                      "program_counter", "client", _moves,
                                      _proc)
    NEW[f"interp_wait_ms.{_op}"] = ("process_interp_wait_ms", "ms",
                                    "program_counter", "client", _moves,
                                    _proc)
    NEW[f"host_busy_pct.{_op}"] = ("host_busy_pct", "%", "host_clock",
                                   "client", _moves, _proc)
    NEW[f"codec_complete_ms.{_op}"] = (
        "histogram_mean_ms", "ms", "program_counter", "codec queue", _moves,
        [c for c in _cells if "mesh" not in c])
NEW["tier_cpu_ms"] = ("op_cost_ms", "ms", "program_span", "client",
                      "put_mib_s", TIER)
NEW["get_copy_offcpu_pct"] = ("op_cost_offcpu_pct", "%", "program_span",
                              "client", "get_mib_s", GET)
#: readers this PR brings (`histogram_mean_ms` was there)
READERS = {"op_cost_ms", "op_cost_offcpu_pct", "op_handoff_mean",
           "op_rpc_ms", "process_cpu_cores", "process_interp_wait_ms",
           "host_busy_pct"}


def _run(**kw) -> Run:
    base = dict(cell={}, config={}, traffic={}, setup_s=1.0, ops=[],
                t0=100.0, t1=110.0, counters0={}, counters1={})
    return Run(**{**base, **kw})


def _read(name: str, run: Run):
    params = mf.metric_params(name)
    return mf.reader_of(params)(params, run)


# ----------------------------------------------------------- the manifest
def entry_rules(manifest: dict, name: str, root=mf.ROOT) -> None:
    """The entry as PR 38 brought it, field for field, listing at least
    the cells it listed then; its file names its reader."""
    bench_dir = root / "benchmarks"
    reader, unit, source, layer, moves, cells = NEW[name]
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": name, "unit": unit, "better": "lower", "source": source,
        "layer": layer, "moves": moves}
    assert set(entry["workloads"]) >= set(cells)
    params = mf.metric_params(name, bench_dir)
    assert params["reader"] == reader
    assert callable(mf.reader_of(params, bench_dir))
    assert (bench_dir / "readers" / f"{reader}.py").is_file()


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_metric_is_an_appended_entry_with_a_file_and_a_new_reader(
        name):
    entry_rules(MANIFEST, name)


def list_rules(manifest: dict, root=mf.ROOT) -> None:
    """PR 38's 29 metrics are there and the per-layer list keeps the
    contract's cap; a layer is any name problems() lets through."""
    assert mf.problems(manifest, root) == []
    names = [m["name"] for m in manifest["per_layer"]]
    assert set(names) >= set(NEW)
    assert len(names) <= 128
    assert {mf.metric_params(n, root / "benchmarks")["reader"]
            for n in NEW} == READERS | {"histogram_mean_ms"}


def test_the_manifest_is_sound_and_nothing_else_was_added():
    list_rules(MANIFEST)


# ------------------------------------------------- on planted records
@pytest.fixture
def recorder():
    from ozone_tpu.utils.tracing import Tracer

    Tracer._instance = None
    yield Tracer.instance().recorder
    Tracer._instance = None


def _plant(recorder, root: str, end: float, **extra):
    recorder._ops.append({"root": root, "traceId": "t", "end": end,
                          "durationUs": 200_000,
                          "stages": {root: 200_000}, **extra})


GETBLOCK = "/ozone.tpu.DatanodeService/GetBlock"
LOOKUP = "/ozone.tpu.OmService/LookupKey"


def test_the_op_readers_mean_the_windows_records(recorder):
    a = dict(
        cost={"client:get": [1_000, 900, 1, 0],
              "ec:fanout": [50_000, 2_000, 30, 1],
              "ec:fill": [30_000, 9_000, 60, 2],
              "ec:assemble": [70_000, 21_000, 80, 3],
              "codec:dispatch": [20_000, 0, 0, 0]},
        handoffs={"n": 30, "waitUs": 150_000, "maxUs": 9_000,
                  "pools": {"ec-read": [24, 120_000],
                            "ec-records": [6, 30_000]}},
        rpc={GETBLOCK: [7, 35_000, 14_000], LOOKUP: [1, 3_000, 1_000]})
    b = dict(
        cost={"client:get": [1_000, 1_100, 0, 0],
              "ec:fill": [10_000, 5_000, 10, 0],
              "ec:assemble": [10_000, 5_000, 10, 0]},
        handoffs={"n": 10, "waitUs": 50_000, "maxUs": 8_000,
                  "pools": {"ec-read": [10, 50_000]}},
        rpc={GETBLOCK: [5, 12_000, 6_000]})
    _plant(recorder, "client:get", 99.9, **a)     # before the window
    _plant(recorder, "client:get", 101.0, **a)
    _plant(recorder, "client:get", 105.0, **b)
    _plant(recorder, "client:put", 105.0, **a)    # another root
    _plant(recorder, "client:get", 110.0, **a)    # at its close: out
    run = _run()
    assert _read("get_cpu_ms", run) == pytest.approx(
        (900 + 2_000 + 9_000 + 21_000 + 1_100 + 5_000 + 5_000) / 2 / 1e3)
    assert _read("get_handoffs", run) == pytest.approx(20.0)
    assert _read("get_handoff_wait_ms", run) == pytest.approx(100.0)
    assert _read("get_rpc_daemon_ms", run) == pytest.approx(21.0 / 2)
    assert _read("get_rpc_client_side_ms", run) == pytest.approx(29.0 / 2)
    # the two sides sum to the client spans' durations
    assert _read("get_rpc_daemon_ms", run) + _read(
        "get_rpc_client_side_ms", run) == pytest.approx(50.0 / 2)
    # off the CPU: of the two copy leaves alone, never `ec:fanout`
    assert _read("get_copy_offcpu_pct", run) == pytest.approx(
        100.0 * (120_000 - 40_000) / 120_000)
    assert _read("put_cpu_ms", run) == pytest.approx(32.9)
    # no operation of the root in the window: nothing
    assert _read("repair_cpu_ms", run) is None
    assert _read("tier_cpu_ms", run) is None
    assert _read("get_handoffs", _run(t0=0.0, t1=50.0)) is None


def test_an_operation_that_made_no_call_reads_zero_not_nothing(recorder):
    """The in-process mini-cluster has no gRPC: the record keeps `rpc`,
    empty."""
    _plant(recorder, "repair:container", 105.0, cost={}, rpc={},
           handoffs={"n": 0, "waitUs": 0, "maxUs": 0, "pools": {}})
    run = _run()
    assert _read("repair_rpc_daemon_ms", run) == 0.0
    assert _read("repair_rpc_client_side_ms", run) == 0.0
    assert _read("repair_handoffs", run) == 0.0
    assert _read("repair_cpu_ms", run) == 0.0


@pytest.mark.parametrize("name", sorted(
    n for n, v in NEW.items() if v[0].startswith("op_")))
def test_a_record_without_the_new_keys_reads_as_nothing(recorder, name):
    """The parent commit's records: `stages` alone."""
    root = mf.metric_params(name)["root"]
    _plant(recorder, root, 105.0)
    assert spans.operations(root, 100.0, 110.0)
    assert _read(name, _run()) is None


def test_a_program_without_stage_records_reads_as_nothing(monkeypatch):
    from ozone_tpu.utils import tracing

    monkeypatch.delattr(tracing.FlightRecorder, "operations")
    for name, v in NEW.items():
        if v[0].startswith("op_"):
            assert _read(name, _run()) is None, name


# -------------------------------------------- the process's own series
def _series(monkeypatch, rows):
    from ozone_tpu.utils import tracing

    monkeypatch.setattr(
        tracing, "samples",
        lambda t0, t1: [r for r in rows if t0 <= r[0] < t1])


def test_the_process_readers_take_deltas_inside_the_window(monkeypatch):
    rows = [(99.95, 50.0, 1_000, 10_000, 30, 0.5),      # before it
            (100.0, 50.2, 1_010, 10_065, 40, 0.004),
            (105.0, 60.0, 3_000, 16_565, 90, 0.006),
            (109.95, 75.07, 6_980, 23_000, 92, 0.002),
            (110.0, 80.0, 9_000, 23_065, 92, 0.9)]      # its close: out
    _series(monkeypatch, rows)
    run = _run()
    assert _read("client_cpu_cores.get", run) == pytest.approx(
        (75.07 - 50.2) / 9.95)
    assert _read("interp_wait_ms.get", run) == pytest.approx(4.0)
    assert _read("host_busy_pct.get", run) == pytest.approx(
        100.0 * (6_980 - 1_010) / (23_000 - 10_065))
    # fewer than two samples: no delta; none: no mean either
    one = _run(t0=104.0, t1=106.0)
    assert _read("client_cpu_cores.put", one) is None
    assert _read("host_busy_pct.put", one) is None
    assert _read("interp_wait_ms.put", one) == pytest.approx(6.0)
    assert _read("interp_wait_ms.repair", _run(t0=0.0, t1=1.0)) is None
    # a host without /proc/stat: the totals do not move
    _series(monkeypatch, [(101.0, 1.0, 0, 0, 3, 0.0),
                          (102.0, 2.0, 0, 0, 3, 0.0)])
    assert _read("host_busy_pct.repair", run) is None
    assert _read("client_cpu_cores.repair", run) == pytest.approx(1.0)


def test_a_program_without_a_sampler_reads_as_nothing(monkeypatch):
    """The parent commit's `utils/tracing.py` has no `samples`."""
    from ozone_tpu.utils import tracing

    monkeypatch.delattr(tracing, "samples")
    assert process_series.samples(0.0, 1e12) == []
    for name, v in NEW.items():
        if v[0] in ("process_cpu_cores", "process_interp_wait_ms",
                    "host_busy_pct"):
            assert _read(name, _run()) is None, name


def test_the_fifth_stage_is_a_delta_of_the_programs_histogram():
    h = "codec.service/complete_seconds"
    run = _run(counters0={h + ".sum": 0.10, h + ".count": 10.0},
               counters1={h + ".sum": 0.16, h + ".count": 40.0})
    for op in ("put", "get", "repair"):
        assert _read(f"codec_complete_ms.{op}", run) == pytest.approx(2.0)
        assert _read(f"codec_complete_ms.{op}", _run()) is None


# ---------------------------------------------- a CPU pass of two cells
TINY = {**bm.TINY, "ockv-degraded.rs-6-3": bm.TINY["ockv-degraded.rs-10-4"]}


def _traced_rehearsal(tmp_path, name: str, seed: int = 38):
    import benchmarks.run as bench_run

    manifest = mf.load()
    cell = mf.cell(manifest, name)
    config = copy.deepcopy(mf.config_of(manifest, cell))
    s = config["scheme"]
    s["cell"], s["bpc"] = 4096, 4096
    config["replication"] = f"rs-{s['k']}-{s['p']}-4096"
    traffic = {**mf.traffic_of(cell), **TINY[name]}
    cluster = bm.MiniCluster(tmp_path, config["cluster"]["datanodes"])
    args = argparse.Namespace(workload=name, seed=seed, seconds=1.0,
                              trace=1, rehearse=True, control="",
                              dump_trace="")
    try:
        return json.loads(json.dumps(bench_run.measure(
            args, manifest, cluster, cell, config, traffic)))
    finally:
        cluster.close()


@pytest.mark.parametrize("cell", ["ockv-degraded.rs-6-3", "ecrd.rs-6-3"])
def test_a_cpu_pass_lists_every_new_metric_of_the_cell(
        tmp_path, cell, capsys, monkeypatch):
    from ozone_tpu.parallel import mesh_executor
    from ozone_tpu.utils.tracing import Tracer, costed

    # as on the one-chip machine (test_bench_spans.py does the same)
    monkeypatch.setattr(mesh_executor, "maybe_executor", lambda: None)
    Tracer._instance = None  # a ring of this pass alone
    try:
        out = _traced_rehearsal(tmp_path, cell)
        ring = Tracer.instance().traces()
        ops = Tracer.instance().recorder.operations()
    finally:
        Tracer._instance = None
    assert out["correct"] is True and out["rehearsal"] is True
    want = {n for n, v in NEW.items() if cell in v[5]}
    got = {k: v["value"] for k, v in out["metrics"].items() if k in want}
    with capsys.disabled():
        print(f"\n{cell} (CPU rehearsal, no measurement): " + ", ".join(
            f"{k}={v:.3f}" for k, v in sorted(got.items())))
    assert set(got) == want
    assert all(v >= 0 for v in got.values())
    op = "get" if cell.startswith("ockv") else "repair"
    # work was done (the means are over the window's costed
    # operations): CPU, hand-offs and their wait are not zero; the
    # in-process cluster makes no RPC, so both sides read 0
    assert got[f"{op}_cpu_ms"] > 0
    assert got[f"{op}_handoffs"] >= 6  # at least one unit stream each
    assert got[f"{op}_handoff_wait_ms"] > 0
    assert got[f"{op}_rpc_daemon_ms"] == got[f"{op}_rpc_client_side_ms"] == 0
    assert got[f"client_cpu_cores.{op}"] > 0.05
    assert 0 < got[f"host_busy_pct.{op}"] <= 100
    assert got[f"interp_wait_ms.{op}"] > 0
    assert got[f"codec_complete_ms.{op}"] > 0
    if op == "get":
        assert got["get_copy_offcpu_pct"] <= 100  # not cut off at 0
    # every record's stages still sum to its duration and name no leaf
    # that only the cost has; the costed ones keep the rest
    root = "client:get" if op == "get" else "repair:container"
    recs = [o for o in ops if o["root"] == root]
    assert recs
    for o in recs:
        assert sum(o["stages"].values()) == pytest.approx(
            o["durationUs"], abs=len(o["stages"]))
        assert not {"ec:fill", "ec:assemble"} & set(o["stages"])
        assert ("cost" in o) == ("handoffs" in o) == ("rpc" in o) \
            == costed(o["traceId"])
    kept = [o for o in recs if "cost" in o]
    # one every COST_INTERVAL_S (tests/conftest.py: a tenth of a second)
    assert kept and len(kept) < len(recs)
    for o in kept:
        assert "ec-read" in o["handoffs"]["pools"]
        assert "ec:fill" in o["cost"]
    # the copy leaves (`ec:fill`; a degraded GET's `ec:assemble` beside
    # it, the two `get_copy_offcpu_pct` reads): cost-only spans, off
    # every critical path (no record's stages name them, above), opened
    # in costed operations alone; a costed degraded GET opens both
    leaves = {"ec:fill", "ec:assemble"}
    names = {}
    for sp in ring:
        names.setdefault(sp.trace_id, set()).add(sp.name)
    assert all(sp.cost_only for sp in ring if sp.name in leaves)
    assert {costed(t) for t in names} == {True, False}
    for t, seen in names.items():
        assert costed(t) or not leaves & seen, t
    if op == "get":
        reads = {sp.trace_id for sp in ring if sp.name == "ec:read"
                 and sp.tags.get("cells_reused")}
        assert {costed(t) for t in reads} == {True, False}
        for t in filter(costed, reads):
            assert leaves <= names[t], t
