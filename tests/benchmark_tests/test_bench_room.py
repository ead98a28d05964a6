"""Room for the next cell. A copy of the benchmark grown by one cell of
the shape an S3 mix has (two kinds of operation, each a root of its own,
one of them in a layer the benchmark does not name yet, listed under
metrics that other cells report too) is sound, and every rule the
accepted tests hold the manifest to holds on it; problems() names a cell
outside the contract's rule for chips. And the counter snapshot reads
every named registry of the program, the two it read before exactly as
it read them."""

import copy
import itertools
import json
import shutil
import sys
import types

import pytest

import test_bench_cost as cost
import test_bench_lrc as lrc
import test_bench_manifest as manifest_tests
import test_bench_mesh as mesh
import test_bench_spans as spans_tests
from benchmarks.harness import manifest as mf
from benchmarks.harness import program
from benchmarks.harness.record import Run

ROOM = "room.rs-6-3"
CONFIG = "room-rs-6-3-1024k"
TRAFFIC = "room-mixed"
#: the metrics other cells report that the room's cell is listed under:
#: one of the first 84, one of PR 38's, and both end-to-end rates
SHARED = ("codec_fill_pct.put", "interp_wait_ms.put", "get_mib_s",
          "put_mib_s")
#: the cell's own stage groups: {metric: (root, layer, moves, stages)}
GROUPS = {
    "room_get_front_ms": ("s3:get", "gateway", "get_mib_s", ["s3:"]),
    "room_get_rest_ms": ("s3:get", "gateway", "get_mib_s", ["(?!s3:)"]),
    "room_put_front_ms": ("s3:put", "client", "put_mib_s", ["s3:"]),
    "room_put_rest_ms": ("s3:put", "client", "put_mib_s", ["(?!s3:)"]),
}
STUB = '''"""Stands in for a generator of two kinds of operation: the harness
loads it to check the manifest, and nothing here runs it."""


class Generator:
    def __init__(self, ctx):
        self.ctx = ctx
'''


def _config_file(bench, name: str, chips: int) -> str:
    cfg = json.loads((bench / "configs" / "rs-6-3-1024k.json").read_text())
    cfg["name"] = name
    cfg["cluster"]["chips"] = chips
    (bench / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    return f"benchmarks/configs/{name}.json"


def _add_cell(manifest: dict, bench, name: str, config: str, traffic: str,
              chips: int) -> None:
    """A cell of the stub generator, listed under the shared metrics and
    the room's own groups."""
    (bench / "traffic" / f"{traffic}.json").write_text(json.dumps(
        {"name": traffic, "generator": "room_stub"}))
    manifest["workloads"].append({
        "name": name, "config": config, "traffic": traffic, "chips": chips,
        "why": "two kinds of operation in one closed loop"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] in SHARED or m["name"] in GROUPS:
            m["workloads"].append(name)


@pytest.fixture(scope="module")
def room(tmp_path_factory):
    """(root of the grown copy, its manifest): new files and appended
    entries, no edit of a file that was there."""
    root = tmp_path_factory.mktemp("room")
    shutil.copytree(mf.BENCH_DIR, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = root / "benchmarks"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "generators" / "room_stub.py").write_text(STUB)
    for name, (root_span, _layer, _moves, stages) in GROUPS.items():
        (bench / "metrics" / f"{name}.json").write_text(json.dumps(
            {"reader": "op_stage_ms", "root": root_span, "stages": stages}))
    manifest = mf.load(mf.ROOT)
    base = next(c for c in manifest["configs"]
                if c["name"] == "rs-6-3-1024k")
    manifest["configs"].append(dict(
        base, name=CONFIG, file=_config_file(bench, CONFIG, 1),
        why="an S3 front end over the default EC scheme"))
    manifest["per_layer"] += [
        {"name": name, "unit": "ms", "better": "lower", "source":
         "program_span", "layer": layer, "moves": moves, "workloads": []}
        for name, (_root, layer, moves, _stages) in GROUPS.items()]
    _add_cell(manifest, bench, ROOM, CONFIG, TRAFFIC, 1)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    assert {p: p.read_bytes() for p in before} == before
    return root, manifest


def test_a_cell_with_two_roots_and_a_new_layer_is_sound(room):
    root, manifest = room
    assert mf.problems(manifest, root) == []
    assert mf.load(root) == manifest
    layers = {m["layer"] for m in mf.load(mf.ROOT)["per_layer"]}
    assert "gateway" not in layers
    groups = spans_tests._groups(ROOM, manifest, root / "benchmarks")
    assert {r: set(g) for r, g in groups.items()} == {
        "s3:get": {"room_get_front_ms", "room_get_rest_ms"},
        "s3:put": {"room_put_front_ms", "room_put_rest_ms"}}
    for name in SHARED:
        (entry,) = [m for m in manifest["end_to_end"] + manifest["per_layer"]
                    if m["name"] == name]
        assert ROOM in entry["workloads"], name


def _mesh_metrics(manifest: dict, root) -> None:
    for name in mesh.MESH_METRICS:
        mesh.metric_rules(manifest, name, root)


def _cost_entries(manifest: dict, root) -> None:
    for name in cost.NEW:
        cost.entry_rules(manifest, name, root)


#: every rule of the accepted tests, as a function of a manifest
RULES = {
    "manifest": manifest_tests.manifest_rules,
    "lrc_cells": lrc.manifest_rules,
    "mesh_cell": mesh.cell_rules,
    "mesh_metrics": _mesh_metrics,
    "stage_metrics": spans_tests.metric_rules,
    "cost_entries": _cost_entries,
    "cost_list": cost.list_rules,
}


@pytest.mark.parametrize("rule", sorted(RULES))
def test_every_rule_of_the_accepted_tests_holds_on_the_grown_manifest(
        room, rule):
    root, manifest = room
    RULES[rule](manifest, root)


@pytest.fixture
def recorder():
    from ozone_tpu.utils.tracing import Tracer

    Tracer._instance = None
    yield Tracer.instance().recorder
    Tracer._instance = None


def test_each_roots_groups_partition_its_stages_and_sum_to_its_mean(
        room, recorder):
    """What the CPU passes hold of every cell, on planted records of the
    room's two roots, read through the copy's own files."""
    root, manifest = room
    bench = root / "benchmarks"
    records = {
        "s3:get": [{"s3:get": 2_000, "s3:auth": 1_000, "client:get": 500,
                    "ec:read": 6_500},
                   {"s3:get": 4_000, "s3:auth": 1_000, "client:get": 500,
                    "ec:read": 4_500}],
        "s3:put": [{"s3:put": 3_000, "s3:body": 9_000, "client:put": 1_000,
                    "codec:dispatch": 7_000}],
    }
    for root_span, recs in records.items():
        for stages in recs:
            recorder._ops.append({
                "root": root_span, "traceId": "t", "end": 105.0,
                "durationUs": sum(stages.values()), "stages": stages})
    run = Run(cell={}, config={}, traffic={}, setup_s=1.0, ops=[],
              t0=100.0, t1=110.0, counters0={}, counters1={})
    groups = spans_tests._groups(ROOM, manifest, bench)
    for root_span, recs in records.items():
        for stage in {s for stages in recs for s in stages}:
            owners = [m for m, pats in groups[root_span].items()
                      if any(p.match(stage) for p in pats)]
            assert len(owners) == 1, (root_span, stage, owners)
        got = {}
        for name in groups[root_span]:
            params = mf.metric_params(name, bench)
            got[name] = mf.reader_of(params, bench)(params, run)
        mean_ms = sum(sum(s.values()) for s in recs) / len(recs) / 1e3
        assert sum(got.values()) == pytest.approx(mean_ms)
    assert got["room_put_front_ms"] == pytest.approx(12.0)


def _over_the_cap(manifest: dict, root) -> None:
    """Four-chip cells appended until one more than the rule allows; at
    the cap itself the manifest is still sound."""
    bench = root / "benchmarks"
    config = "room-rs-6-3-1024k-mesh4"
    entry = dict(next(c for c in manifest["configs"] if c["name"] == CONFIG),
                 name=config, file=_config_file(bench, config, 4))
    manifest["configs"].append(entry)

    def four() -> int:
        return sum(w["chips"] == 4 for w in manifest["workloads"])

    for n in itertools.count():
        _add_cell(manifest, bench, f"room-four.{n}", config,
                  f"room-four-{n}", 4)
        if four() > max(1, len(manifest["workloads"]) // 2):
            return
        assert mf.problems(manifest, root) == []


@pytest.mark.parametrize("edit,complaint", [
    (lambda m, root: mf.cell(m, ROOM).update(chips=2), "chips 2"),
    (_over_the_cap, "take four chips"),
], ids=["two_chips", "four_chips_over_the_cap"])
def test_problems_names_a_cell_outside_the_rule_for_chips(
        room, edit, complaint):
    root, manifest = room
    manifest = copy.deepcopy(manifest)
    edit(manifest, root)
    found = mf.problems(manifest, root)
    assert any(complaint in p for p in found), found


# ------------------------------------------------------ the snapshot
def _two_registries() -> dict[str, float]:
    """`program.snapshot()` as it read before it took every named
    registry: `codec.service`, `mesh` and the compile counts."""
    from ozone_tpu.codec import service as codec_service
    from ozone_tpu.parallel import mesh_executor
    from ozone_tpu.utils.compile_cache import compile_counts

    out: dict[str, float] = {}
    for prefix, reg in (("codec.service", codec_service.METRICS),
                        ("mesh", mesh_executor.METRICS)):
        for name, c in list(reg._counters.items()):
            out[f"{prefix}/{name}"] = float(c.value)
        for name, h in list(reg._histograms.items()):
            out[f"{prefix}/{name}.sum"] = float(h.total)
            out[f"{prefix}/{name}.count"] = float(h.count)
    for name, v in compile_counts().items():
        out[f"compile/{name}"] = float(v)
    return out


def _plant(monkeypatch, reg, count: int, seconds: list[float]) -> None:
    from ozone_tpu.utils.metrics import Counter, Histogram

    monkeypatch.setitem(reg._counters, "planted", Counter())
    monkeypatch.setitem(reg._histograms, "planted_seconds", Histogram())
    reg.counter("planted").inc(count)
    for s in seconds:
        reg.histogram("planted_seconds").observe(s)


def test_the_snapshot_reads_the_two_registries_as_it_did(monkeypatch):
    from ozone_tpu.codec import service as codec_service
    from ozone_tpu.parallel import mesh_executor

    # no dispatcher of an earlier test books its idle time meanwhile
    codec_service.reset_for_tests()
    mesh_executor.reset_for_tests()
    _plant(monkeypatch, codec_service.METRICS, 7, [0.25, 0.5])
    _plant(monkeypatch, mesh_executor.METRICS, 3, [1.5])
    old = _two_registries()
    new = program.snapshot()
    assert {k: v for k, v in new.items() if k.partition("/")[0] in (
        "codec.service", "mesh", "compile")} == old
    assert old["codec.service/planted"] == 7.0
    assert old["codec.service/planted_seconds.sum"] == 0.75
    assert old["mesh/planted_seconds.count"] == 1.0


def test_the_snapshot_reads_every_named_registry_and_no_objects_own(
        monkeypatch):
    import ozone_tpu.client.ozone_client  # noqa: F401 - client.ops
    import ozone_tpu.codec.hostmem  # noqa: F401 - datapath
    import ozone_tpu.lifecycle.executor  # noqa: F401 - lifecycle
    from ozone_tpu.utils import metrics

    monkeypatch.setattr(metrics, "_all_registries",
                        dict(metrics._all_registries))
    # a module of the program that makes its registry as it is imported,
    # as a gateway's would: read with no edit of the harness
    gateway = types.ModuleType("ozone_tpu.gateway.planted")
    gateway.METRICS = metrics.registry("s3.gateway")
    monkeypatch.setitem(sys.modules, gateway.__name__, gateway)

    class Coordinator:
        """Makes a registry of its own, as each repair coordinator does
        (`storage/reconstruction.py`): the next one replaces it."""

        def __init__(self):
            self.metrics = metrics.MetricsRegistry("ec.reconstruction")

    coordinator = Coordinator()
    coordinator.metrics.counter("repairs_local").inc()
    named = ("client.ops", "datapath", "lifecycle", "tracing", "s3.gateway")
    for name in named:
        _plant(monkeypatch, metrics.registry(name), 3, [0.5])
    snap = program.snapshot()
    for name in named:
        assert snap[f"{name}/planted"] == 3.0, name
        assert snap[f"{name}/planted_seconds.sum"] == 0.5, name
        assert snap[f"{name}/planted_seconds.count"] == 1.0, name
    assert not [k for k in snap if k.startswith("ec.reconstruction/")]
