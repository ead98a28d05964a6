"""BENCHMARK.json and the files it names; and that a cell, a
configuration, a traffic mix with its own generator and a per-layer
metric with its own reader are each ADDED by creating files and
appending entries only."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import bench_minicluster as bm
from benchmarks.harness import manifest as mf
from benchmarks.harness.context import Context, PayloadPool


def manifest_rules(manifest: dict, root=mf.ROOT) -> None:
    """What every manifest the harness runs holds: problems() finds
    nothing (the contract's rule for chips among it), and each per-layer
    metric and configuration is as the harness reads it."""
    assert mf.problems(manifest, root) == []
    assert len(json.dumps(manifest)) < 64 * 1024
    assert manifest["command"] == ["python3", "benchmarks/run.py"]
    for m in manifest["per_layer"]:
        # explicit lists, and each listed cell reports what it moves
        assert m["workloads"], m["name"]
        for w in m["workloads"]:
            assert m["moves"] in [
                x["name"] for x in mf.metrics_for(manifest, "end_to_end", w)]
    for c in manifest["configs"]:
        assert any(w["config"] == c["name"] for w in manifest["workloads"])
        cfg = json.loads((root / c["file"]).read_text())
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert cfg["guarantees"] and cfg["assumed"] and cfg["source"]


def test_the_manifest_and_every_file_it_names_are_sound():
    manifest_rules(mf.load())


def _cell(m: dict, name: str = "ockg.rs-6-3") -> dict:
    return mf.cell(m, name)


def _metric(m: dict, name: str = "codec_fill_pct.put") -> dict:
    (entry,) = [x for x in m["per_layer"] + m["end_to_end"]
                if x["name"] == name]
    return entry


@pytest.mark.parametrize("edit,complaint", [
    (lambda m: _cell(m).update(name="bad name"), "allowed name"),
    (lambda m: _cell(m).update(name="x" * 65), "allowed name"),
    (lambda m: _metric(m, "put_mib_s").update(unit="MiB per s"), "unit"),
    (lambda m: _metric(m, "put_mib_s").update(bound=0.5), "bound"),
    (lambda m: _metric(m).update(moves="nothing"), "moves"),
    (lambda m: _metric(m).pop("workloads"), "no workloads list"),
    (lambda m: _metric(m).update(workloads=["ecrd.rs-6-3"]),
     "does not report"),
    (lambda m: _metric(m).update(why="x"), "keys"),
    (lambda m: _cell(m).update(traffic="nowhere"), "cannot read"),
    (lambda m: m["configs"].append(dict(m["configs"][0], name="spare",
                                        file="benchmarks/configs/none.json")),
     "does not exist"),
    (lambda m: m["end_to_end"].remove(_metric(m, "setup_s")), "setup_s"),
    (lambda m: _cell(m).update(chips=2), "chips 2"),
    # a one-chip deployment's cell on four chips: the config says 1
    (lambda m: _cell(m).update(chips=4), "cluster.chips 1"),
])
def test_problems_names_what_is_wrong(edit, complaint):
    manifest = mf.load()
    edit(manifest)
    assert any(complaint in p for p in mf.problems(manifest)), \
        mf.problems(manifest)


def test_payloads_are_deterministic_in_the_seed_and_differ_per_key():
    def pool(seed):
        ctx = Context(cell={}, config={}, traffic={}, seed=seed,
                      client=None, scm=None, cluster=None)
        return PayloadPool(ctx.rng(1), 64 * 1024)

    a, b, c = pool(2 ** 31 + 5), pool(2 ** 31 + 5), pool(2 ** 31 + 6)
    assert np.array_equal(a.payload(17), b.payload(17))
    assert not np.array_equal(a.payload(17), c.payload(17))
    seen = {a.payload(i).tobytes() for i in range(200)}
    assert len(seen) == 200
    with pytest.raises(ValueError):
        a.payload(0)[0] = 1  # a window must not be able to alter one


def test_get_order_and_victims_come_from_the_seed(tmp_path):
    """Two runs of one seed do the same work; the generator's choices
    (victims, GET order) are functions of the seed and the placement."""
    outs = [bm.run_cell(tmp_path / str(n), "ockv-degraded.rs-10-4",
                        seed=2 ** 31 + 11, seconds=0.5) for n in range(2)]
    assert outs[0]["correct"] and outs[1]["correct"]
    assert outs[0]["notes"]["killed"] == outs[1]["notes"]["killed"]
    # every key lost as many DATA units as datanodes were killed
    assert outs[0]["notes"]["keys_by_lost_data_units"] == {"2": 6}


ADDED_GENERATOR = '''
"""A traffic mix of its own: a few sequential PUTs of one stripe."""
from benchmarks.harness.context import PayloadPool, check
from benchmarks.harness.loop import closed_loop


class Generator:
    def __init__(self, ctx):
        self.ctx = ctx
        self.pool = PayloadPool(ctx.rng(1), ctx.stripe_bytes)

    def prepare(self):
        self.bucket = self.ctx.bucket("added")
        self.bucket.write_key("warm", self.pool.payload(0))

    def window(self, seconds):
        def op(i):
            self.bucket.write_key(f"a-{i}", self.pool.payload(i + 1))
            return "put", self.pool.key_bytes, i
        return closed_loop(self.ctx.traffic["threads"], seconds, op)

    def verify(self, ops, t0, t1):
        bad = sum(not (self.bucket.read_key(f"a-{o.tag}")
                       == self.pool.payload(o.tag + 1)).all()
                  for o in ops if o.ok)
        return {"added_keys_differ": check(bad, 0)}
'''

ADDED_READER = '''
"""A per-layer metric of its own: codec dispatches launched per PUT."""
from benchmarks.harness.program import delta
from benchmarks.harness.stats import in_window


def read(params, run):
    puts = len(in_window(run.ops, "put", run.t0, run.t1))
    if not puts:
        return None
    return delta(run.counters1, run.counters0, params["counter"]) / puts
'''


@pytest.mark.serial
def test_a_cell_config_traffic_and_metric_are_added_as_files_only(tmp_path):
    """In a temporary copy: new files, appended entries, no edit of any
    file that was there; the harness validates and RUNS the new cell
    (tiny, on the CPU, through the real launcher: 5 datanodes, where
    every existing configuration has 9 or 14)."""
    root = tmp_path / "copy"
    shutil.copytree(mf.BENCH_DIR, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(mf.ROOT / "ozone_tpu", root / "ozone_tpu")
    before = {p: p.read_bytes() for p in (root / "benchmarks").rglob("*")
              if p.is_file()}
    bench = root / "benchmarks"
    (bench / "configs" / "rs-3-2-1024k.json").write_text(json.dumps({
        "name": "rs-3-2-1024k", "source": "Apache Ozone docs, RS-3-2-1024k",
        "replication": "rs-3-2-1024k",
        "scheme": {"codec": "rs", "k": 3, "p": 2, "cell": 1048576,
                   "checksum": "CRC32C", "bpc": 16384,
                   "block_bytes": 16777216},
        "cluster": {"datanodes": 5, "metadata_replicas": 1, "chips": 1},
        "guarantees": ["an acknowledged PUT reads back byte-exact"],
        "reduced": {"hosts": "one host"}, "assumed": {"client_threads": 2}}))
    (bench / "traffic" / "added-mix.json").write_text(json.dumps({
        "name": "added-mix", "generator": "added_puts", "threads": 2,
        "need_free_gib": 1}))
    (bench / "generators" / "added_puts.py").write_text(ADDED_GENERATOR)
    (bench / "metrics" / "dispatches_per_put.json").write_text(json.dumps({
        "reader": "per_put", "counter": "codec.service/dispatches"}))
    (bench / "readers" / "per_put.py").write_text(ADDED_READER)

    manifest = mf.load()
    manifest["configs"].append({
        "name": "rs-3-2-1024k", "source": "Apache Ozone docs, RS-3-2-1024k",
        "file": "benchmarks/configs/rs-3-2-1024k.json",
        "reduced": ["hosts"], "why": "the narrow scheme"})
    manifest["workloads"].append({
        "name": "added.rs-3-2", "config": "rs-3-2-1024k",
        "traffic": "added-mix", "chips": 1, "why": "added by files alone"})
    # an existing entry is never edited (put_mib_s lists its cells):
    # the added cell brings an end-to-end metric of its own
    manifest["end_to_end"].append({
        "name": "added_put_mib_s", "unit": "MiB/s", "better": "higher",
        "bound": 0.05, "source": "host_clock",
        "workloads": ["added.rs-3-2"]})
    (bench / "metrics" / "added_put_mib_s.json").write_text(json.dumps(
        {"reader": "op_rate_mib_s", "kind": "put"}))
    manifest["per_layer"].append({
        "name": "dispatches_per_put", "unit": "1/op", "better": "lower",
        "source": "program_counter", "layer": "codec queue",
        "moves": "added_put_mib_s", "workloads": ["added.rs-3-2"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))

    assert mf.problems(manifest, root) == []
    (tmp_path / "tmp").mkdir()
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path / "tmp"))
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, str(bench / "run.py"), "--workload", "added.rs-3-2",
         "--seed", str(2 ** 31 + 99), "--seconds", "2", "--trace", "0",
         "--rehearse"], cwd=root, env=env, capture_output=True, text=True,
        timeout=240)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    assert line["metrics"]["added_put_mib_s"]["value"] > 0
    assert "setup_s" in line["metrics"]
    assert list(line)[-1] == "compared"
    assert line["compared"] == {
        "added_keys_differ": {"value": 0, "limit": 0}}
    # nothing that was there changed, no daemon outlived the run, and
    # the cluster's root is gone
    after = {p: p.read_bytes() for p in before}
    assert after == before
    assert not bm.processes_mentioning(str(tmp_path))
    assert not list((tmp_path / "tmp").iterdir())

    # the added per-layer metric, read by its own reader, from the same
    # copy's files (in-process, tiny cells)
    import argparse

    import benchmarks.run as bench_run

    cell = mf.cell(manifest, "added.rs-3-2")
    config = mf.config_of(manifest, cell, root)
    config["scheme"].update(cell=4096, bpc=4096)
    config["replication"] = "rs-3-2-4096"
    cluster = bm.MiniCluster(tmp_path / "mini", 5)
    try:
        traced = bench_run.measure(
            argparse.Namespace(workload="added.rs-3-2", seed=5, seconds=0.5,
                               trace=1, rehearse=True, control="",
                               dump_trace=""),
            manifest, cluster, cell, config, mf.traffic_of(cell, bench),
            bench_dir=bench)
    finally:
        cluster.close()
    assert traced["metrics"]["dispatches_per_put"]["value"] > 0
    assert set(traced["metrics"]) == {"dispatches_per_put"}
