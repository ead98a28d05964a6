"""The cells ISSUE 35 adds: `ecrd.lrc-12-2-2` (the deployment
`lrc-12-2-2-1024k`: Azure's LRC(12,2,2), its plain reference, traffic,
generator and three metric files) and the kept cell
`ockv-degraded.rs-6-3`. The reference against the program on EVERY
erasure pattern of up to four units (bytes, CRCs and read sets); the
manifest with both cells; the new readers on planted numbers; a drill on
the in-process mini-cluster at 16 datanodes; the cell driven as a run
drives it, clean and with each control."""

import argparse
import copy
import itertools
import json

import numpy as np
import pytest

import bench_minicluster as bm
from benchmarks.harness import manifest as mf
from benchmarks.harness import reference, reference_lrc, storecheck, work
from benchmarks.harness import trace as tr
from benchmarks.harness.record import Run
from benchmarks.harness.stats import Op

CELL = "ecrd.lrc-12-2-2"
KEPT = "ockv-degraded.rs-6-3"
CONFIG = "lrc-12-2-2-1024k"
MIB = 2 ** 20
MANIFEST = mf.load()
SCHEME = mf.config_of(MANIFEST, mf.cell(MANIFEST, CELL))["scheme"]
#: how many of the C(16, n) patterns of n lost units decode (the paper:
#: all of up to 3, 86 % of those of 4)
DECODABLE = {1: 16, 2: 120, 3: 560, 4: 1557}
#: the cell's own metrics, beside the reader each file names
LRC_METRICS = {
    "lrc_decode_roofline.repair": "lrc_kernel_roofline",
    "lrc_read_width.repair": "counter_ratio",
    "lrc_local_kept_pct.repair": "span_tag_share_pct",
}
TINY = {"stripes_per_key": 2, "keys_per_container": [1, 2, 1],
        "verify_replicas": 4, "settle_s": 0.0}


# ------------------------------------------------------------ the reference
def _options(cell: int):
    from ozone_tpu.codec.api import CoderOptions

    return CoderOptions(SCHEME["k"], SCHEME["p"], "lrc", cell_size=cell,
                        local_groups=SCHEME["l"])


def test_the_reference_shares_nothing_with_the_program():
    import benchmarks.harness.reference_lrc as mod

    text = open(mod.__file__).read()
    assert "import ozone_tpu" not in text and "from ozone_tpu" not in text


def test_the_references_generator_is_the_programs_entry_for_entry():
    from ozone_tpu.codec import lrc_math

    gen = np.array(reference_lrc.generator(SCHEME), dtype=np.uint8)
    assert gen.shape == (16, 12)
    assert np.array_equal(gen, lrc_math.encode_matrix(_options(4096)))
    # the paper's geometry, written out
    assert gen[12].tolist() == [1] * 6 + [0] * 6
    assert gen[13].tolist() == [0] * 6 + [1] * 6
    assert np.all(gen[14:] != 0)
    assert reference_lrc.group_of(SCHEME, 3) == [0, 1, 2, 3, 4, 5, 12]
    assert reference_lrc.group_of(SCHEME, 13) == [6, 7, 8, 9, 10, 11, 13]
    assert reference_lrc.group_of(SCHEME, 14) is None


def test_a_lone_loss_reads_its_group_by_the_papers_rule_and_by_the_walk():
    for u in range(14):
        group = reference_lrc.group_of(SCHEME, u)
        want = [x for x in group if x != u]
        assert reference_lrc.read_set(SCHEME, [u]) == want
        assert reference_lrc.general_read_set(SCHEME, [u]) == want
        assert len(want) == 6
    for u in (14, 15):
        assert reference_lrc.read_set(SCHEME, [u]) == list(range(12))


@pytest.mark.parametrize("lost", [1, 2, 3, 4])
def test_every_pattern_is_rebuilt_by_the_fused_path_as_the_reference_says(
        monkeypatch, lost):
    """All C(16, lost) patterns: the program plans a read set and its
    jitted fused decode rebuilds the lost units and their CRCs; bytes and
    CRCs equal the reference's own units, and the reference rebuilds the
    same from ITS read set. A pattern that cannot be decoded is refused
    by both."""
    monkeypatch.setenv("OZONE_TPU_FUSED_BACKEND", "jax")
    from ozone_tpu.codec import fused, lrc_math
    from ozone_tpu.utils.checksum import ChecksumType

    cell, bpc = 256, 128
    scheme = {**SCHEME, "cell": cell, "bpc": bpc}
    opts = _options(cell)
    spec = fused.FusedSpec(opts, ChecksumType.CRC32C, bpc)
    payload = np.random.default_rng([35, lost]).integers(
        0, 256, 2 * 12 * cell, dtype=np.uint8)
    units = reference_lrc.expected_units(scheme, payload)  # [2, 16, cell]
    crcs = reference.crc32c_slices(units, bpc).reshape(2, 16, cell // bpc)
    decoded = refused = 0
    for pattern in itertools.combinations(range(16), lost):
        erased = list(pattern)
        alive = [u for u in range(16) if u not in pattern]
        try:
            want_valid = reference_lrc.read_set(scheme, erased)
        except ValueError:
            with pytest.raises(ValueError):
                lrc_math.plan_valid(opts, erased, alive)
            refused += 1
            continue
        valid, _kind = lrc_math.plan_valid(opts, erased, alive)
        rec, rec_crcs = (np.asarray(x) for x in fused.make_fused_decoder(
            spec, valid, erased)(np.ascontiguousarray(units[:, valid])))
        assert np.array_equal(rec, units[:, erased]), pattern
        assert np.array_equal(rec_crcs, crcs[:, erased]), pattern
        assert np.array_equal(
            reference_lrc.recover(scheme, want_valid, erased,
                                  units[:, want_valid]),
            units[:, erased]), pattern
        decoded += 1
    assert decoded == DECODABLE[lost]
    assert decoded + refused == len(list(
        itertools.combinations(range(16), lost)))


@pytest.mark.parametrize("lost", [1, 2])
def test_the_programs_read_set_is_the_references(lost):
    from ozone_tpu.codec import lrc_math

    opts = _options(4096)
    widths = {}
    for pattern in itertools.combinations(range(16), lost):
        alive = [u for u in range(16) if u not in pattern]
        valid, kind = lrc_math.plan_valid(opts, list(pattern), alive)
        assert sorted(valid) == reference_lrc.read_set(
            SCHEME, list(pattern)), pattern
        widths[kind, len(valid)] = widths.get((kind, len(valid)), 0) + 1
    assert widths == {1: {("local", 6): 14, ("global", 12): 2},
                      2: {("local", 12): 49, ("global", 12): 71}}[lost]


def test_the_fused_encoders_units_and_crcs_are_the_references(monkeypatch):
    monkeypatch.setenv("OZONE_TPU_FUSED_BACKEND", "jax")
    from ozone_tpu.codec import fused
    from ozone_tpu.utils.checksum import ChecksumType

    cell, bpc = 512, 256
    scheme = {**SCHEME, "cell": cell, "bpc": bpc}
    spec = fused.FusedSpec(_options(cell), ChecksumType.CRC32C, bpc)
    payload = np.random.default_rng(351).integers(
        0, 256, 3 * 12 * cell, dtype=np.uint8)
    units = reference_lrc.expected_units(scheme, payload)
    parity, crcs = (np.asarray(x) for x in fused.make_fused_encoder(spec)(
        np.ascontiguousarray(units[:, :12])))
    assert np.array_equal(parity, units[:, 12:])
    assert np.array_equal(
        crcs, reference.crc32c_slices(units, bpc).reshape(3, 16, cell // bpc))
    for u in (0, 11, 12, 13, 14, 15):
        assert np.array_equal(
            reference_lrc.expected_unit(scheme, payload, u), units[:, u])


# ------------------------------------------------------------- the manifest
def manifest_rules(manifest: dict, root=mf.ROOT) -> None:
    """Both cells of PR 35 with their configuration and traffic, and the
    LRC cell's metrics, in any manifest that grows from this one."""
    bench_dir = root / "benchmarks"
    assert mf.problems(manifest, root) == []  # the chips rule among it
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells[CELL]["chips"] == cells[KEPT]["chips"] == 1
    assert (cells[CELL]["config"], cells[CELL]["traffic"]) == (
        CONFIG, "ecrd-lrc")
    assert (cells[KEPT]["config"], cells[KEPT]["traffic"]) == (
        "rs-6-3-1024k", "ockv-degraded")
    config = mf.config_of(manifest, cells[CELL], root)
    assert config["cluster"]["datanodes"] == 16
    assert config["scheme"]["p"] == config["scheme"]["l"] + \
        config["scheme"]["r"] == 4
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, reader in LRC_METRICS.items():
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "repair_mib_s"
        assert mf.metric_params(name, bench_dir)["reader"] == reader
    # counted at k = 12 a local repair would claim twice its bytes
    assert CELL not in by_name["fused_decode_roofline.repair"]["workloads"]
    e2e = {m["name"] for m in mf.metrics_for(manifest, "end_to_end", CELL)}
    assert e2e >= {"repair_mib_s", "setup_s"}
    assert {m["name"] for m in mf.metrics_for(
        manifest, "end_to_end", KEPT)} >= {"get_mib_s", "setup_s"}
    # the kept cell reports what its wide sibling reports
    for m in manifest["per_layer"]:
        assert (KEPT in m["workloads"]) == (
            "ockv-degraded.rs-10-4" in m["workloads"]), m["name"]


def test_the_manifest_holds_both_cells_and_their_metrics():
    manifest_rules(MANIFEST)


# --------------------------------------------------------------- the readers
def _plane(modules):
    return {"name": "/device:TPU:0", "lines": [
        {"name": tr.OPS_LINE, "events": []},
        {"name": tr.MODULES_LINE, "events": [list(e) for e in modules]}]}


def _run(trace=None, slice_counters=None, ops=(), counters=({}, {})):
    return Run(cell={}, config={"scheme": SCHEME}, traffic={}, setup_s=1.0,
               ops=list(ops), t0=0.0, t1=30.0, counters0=counters[0],
               counters1=counters[1], peaks=work.peaks_for("TPU v5 lite"),
               trace=trace, slice0=10.0, slice1=15.0, slice_counters0={},
               slice_counters1=slice_counters or {})


def test_the_roofline_counts_each_repair_at_the_references_read_width():
    params = mf.metric_params("lrc_decode_roofline.repair")
    read = mf.reader_of(params)
    t = {"planes": [_plane([("jit__decode_apply_jit(7)", 0, 1_000_000)])]}
    counters = {"codec.service/stripes_dispatched": 36.0,
                "codec.service/dispatches": 1.0}
    ops = [Op("repair", 9.0, 11.0, 24 * MIB, True, tag=(1, 3)),   # half in
           Op("repair", 12.0, 13.0, 12 * MIB, True, tag=(2, 14)),  # global
           Op("repair", 13.0, 14.0, 12 * MIB, True, tag=(3, 13)),  # local
           Op("repair", 20.0, 21.0, 12 * MIB, True, tag=(4, 15))]  # outside
    width = (12 * 6 + 12 * 12 + 12 * 6) / 36
    least = work.least_seconds(
        work.decode_work(width, 1, MIB, 16384, 36),
        work.peaks_for("TPU v5 lite"))["seconds"]
    got = read(params, _run(t, counters, ops))
    assert got == pytest.approx(100 * least / 0.001)
    # the accepted reader, at k = 12, claims more for the same slice
    at_k = mf.reader_of(mf.metric_params("fused_decode_roofline.repair"))(
        mf.metric_params("fused_decode_roofline.repair"),
        _run(t, counters, ops))
    assert at_k / got == pytest.approx(
        (13 * MIB + 256) / ((width + 1) * MIB + 256))
    # a window of local repairs alone: exactly the group's six
    assert mf.reader_of(params).__globals__["slice_width"](
        "repair", _run(t, counters, ops[2:3])) == 6
    # nothing to read
    assert read(params, _run(None, counters, ops)) is None
    assert read(params, _run(t, {}, ops)) is None
    assert read(params, _run(t, counters, ops[3:])) is None
    assert read(params, _run({"planes": [_plane([])]}, counters, ops)) is None


def test_the_read_width_is_cells_read_over_stripes_decoded():
    params = mf.metric_params("lrc_read_width.repair")
    read = mf.reader_of(params)
    c1 = {"codec.service/decode_survivor_cells": 14 * 12 * 6 + 2 * 12 * 12.0,
          "codec.service/stripes_dispatched": 16 * 12.0}
    assert read(params, _run(counters=({}, c1))) == pytest.approx(6.75)
    # a program without the counter (the parent): nothing, not 0
    assert read(params, _run(counters=(
        {}, {"codec.service/stripes_dispatched": 5.0}))) is None
    assert read(params, _run(counters=(c1, c1))) is None


def test_the_kept_share_is_of_the_windows_local_plans():
    import time

    from ozone_tpu.utils.tracing import Tracer

    params = mf.metric_params("lrc_local_kept_pct.repair")
    read = mf.reader_of(params)
    tracer = Tracer.instance()
    run = _run()
    run.t0 = time.monotonic()
    for tags in ({"kind": "local", "width": 6},
                 {"kind": "local", "width": 6},
                 {"kind": "local", "width": 12, "widened": "hedge"},
                 {"kind": "global", "width": 12},   # never a local plan
                 {"kind": "rs", "width": 6},
                 {"kind": "local", "width": 6}):
        with tracer.span("repair:block", **tags):
            pass
    with tracer.span("repair:write", kind="local", widened="hedge"):
        pass  # another span's tags count nowhere
    run.t1 = time.monotonic() + 1.0
    # four local plans, one of them widened
    assert read(params, run) == pytest.approx(75.0)
    # a window with no local plan, or a program that tags none: nothing
    run.t0 = run.t1
    assert read(params, run) is None


# ------------------------------------------- the cell, on the mini-cluster
@pytest.fixture
def one_chip(monkeypatch):
    """As on the one-chip machine: the tests' eight virtual devices
    would send the drill's decodes to the mesh executor."""
    from ozone_tpu.parallel import mesh_executor

    monkeypatch.setattr(mesh_executor, "maybe_executor", lambda: None)


def _tiny():
    """(cell, config, traffic) of the LRC cell at 4 KiB cells."""
    cell = mf.cell(MANIFEST, CELL)
    config = copy.deepcopy(mf.config_of(MANIFEST, cell))
    config["scheme"]["cell"] = config["scheme"]["bpc"] = 4096
    config["replication"] = "lrc-12-2-2-4096"
    return cell, config, {**mf.traffic_of(cell), **TINY}


def _run_cell(tmp_path, seed: int, control: str = "") -> dict:
    import benchmarks.run as bench_run

    cell, config, traffic = _tiny()
    cluster = bm.MiniCluster(tmp_path, config["cluster"]["datanodes"])
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=1.0, trace=1,
                              rehearse=True, control=control, dump_trace="")
    try:
        return json.loads(json.dumps(bench_run.measure(
            args, MANIFEST, cluster, cell, config, traffic)))
    finally:
        cluster.close()


def test_a_drill_at_16_datanodes_rebuilds_each_class_of_unit(tmp_path,
                                                             one_chip):
    """One data unit, one local parity and one global parity, through
    the generator's own set-up and `_repair`: each rebuilt replica equals
    the reference unit for unit and CRC for CRC, the first two fetched
    exactly their group's six survivors, and the spans and counters of
    ISSUE 35 say so."""
    from benchmarks.harness import program
    from benchmarks.harness.context import Context
    from ozone_tpu.utils.tracing import Tracer

    cell, config, traffic = _tiny()
    cluster = bm.MiniCluster(tmp_path, 16)
    try:
        client, scm = cluster.connect()
        ctx = Context(cell=cell, config=config, traffic=traffic,
                      seed=2 ** 31 + 35, client=client, scm=scm,
                      cluster=cluster)
        gen = mf.generator_of(traffic)(ctx)
        gen.prepare()
        # the two warm repairs: one of each kind
        assert sorted(b["repairs_local"] > 0 for b in gen.booked) == [
            False, True]
        cid = next(c for c, v in gen.containers.items()
                   if len(v["groups"]) == 1)
        assert len(set(gen.containers[cid]["nodes"])) == 16
        before = program.snapshot()
        n_spans = len(Tracer.instance().traces())
        for unit in (3, 13, 15):
            gen._repair(cid, unit)
        after = program.snapshot()
        data, local, glob = gen.booked[-3:]
        for b in (data, local):
            assert (b["repairs_local"], b["repairs_global"],
                    b["repairs_widened"]) == (1, 0, 0)
            assert b["survivor_units_read"] == 6
            assert b["survivor_bytes_read"] == 6 * 2 * 4096
        assert (glob["repairs_local"], glob["repairs_global"]) == (0, 1)
        assert glob["survivor_units_read"] == 12
        # the codec service decoded at the width it was asked to
        stripes = program.delta(after, before,
                                "codec.service/stripes_dispatched")
        assert stripes == 3 * 2
        assert program.delta(
            after, before, "codec.service/decode_survivor_cells") == \
            2 * (6 + 6 + 12)
        assert program.delta(
            after, before, "codec.service/decode_recovered_cells") == 3 * 2
        blocks = [s for s in Tracer.instance().traces()[n_spans:]
                  if s.name == "repair:block"]
        assert [(s.tags["kind"], s.tags["width"], s.tags["units_read"],
                 s.tags.get("widened")) for s in blocks] == [
            ("local", 6, 6, None), ("local", 6, 6, None),
            ("global", 12, 12, None)]
        # what the repairs left on the datanodes, against the reference
        tally = storecheck.Tally()
        (i, g), = gen.containers[cid]["groups"]
        for unit in (3, 13, 15):
            dn = client.clients.get(gen.containers[cid]["nodes"][unit])
            storecheck.check_unit(
                dn, g.block_id, g.length, reference_lrc.expected_unit(
                    ctx.scheme, gen.pool.payload(i), unit),
                ctx.scheme, tally, f"unit {unit}")
        storecheck.finish(tally, ctx.scheme)
        assert tally.units_compared == 3 and tally.crc_slices_compared == 6
        assert (tally.records_wrong, tally.stored_bytes_differ,
                tally.stored_crcs_differ) == (0, 0, 0), tally.first_error
    finally:
        cluster.close()


def test_a_clean_run_of_the_cell_is_correct_and_reads_its_metrics(tmp_path,
                                                                  one_chip):
    out = _run_cell(tmp_path, seed=2 ** 31 + 36)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["rehearsal"] is True
    compared = out["compared"]
    assert compared["local_repairs_reading_their_group_alone"]["value"] >= 1
    assert compared["rebuilt_stripes_not_dispatched"]["value"] == 0
    assert compared["compile_events_in_window"]["value"] == 0
    planned = out["notes"]["repairs_planned"]
    assert planned["widened"] == 0
    assert planned["read_their_group_alone"] == \
        planned["local_by_the_reference"] > 0
    assert set(planned["survivor_units_read_per_block"]) <= {"6", "12"}
    # the sample holds the last repair and every class the window rebuilt
    classes = {min(u, 12) if u < 14 else 14 for u in out["notes"]["sample_units"]}
    assert 12 in classes or planned["local_by_the_reference"] < 14
    # the counter- and span-sourced metrics of the cell (no device here)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert 6.0 <= m["lrc_read_width.repair"] <= 12.0
    assert m["lrc_local_kept_pct.repair"] == 100.0
    assert "lrc_decode_roofline.repair" not in m
    for name in ("repair_read_ms", "repair_codec_ms", "repair_write_ms",
                 "repair_fixed_ms", "codec_fill_pct.repair"):
        assert m[name] > 0, name


@pytest.mark.parametrize("control,number", [
    # a rebuilt replica with one byte off under the right CRCs
    ("byte_flip", "rebuilt_bytes_differ"),
    # a repair that left nothing on its target (`crc_wrong` cannot be
    # planted here: it commits a block record, and the drill's
    # containers are CLOSED)
    ("wipe_replica", "rebuilt_records_wrong"),
])
def test_a_planted_fault_makes_the_cell_not_correct(tmp_path, one_chip,
                                                    control, number):
    out = _run_cell(tmp_path, seed=2 ** 31 + 37, control=control)
    assert out["correct"] is False and out["control"] == control
    c = out["compared"][number]
    assert c["value"] > c["limit"] == 0 and out["failed"] == 0


def test_a_program_that_does_not_say_what_it_read_is_refused(monkeypatch):
    """The parent commit under this PR's benchmark files: the generator
    ends the run before it writes a byte."""
    from ozone_tpu.client import ec_reader

    monkeypatch.delattr(ec_reader, "RecoveryTally")
    cell, config, traffic = _tiny()
    from benchmarks.harness.context import Context

    ctx = Context(cell=cell, config=config, traffic=traffic, seed=1,
                  client=None, scm=None, cluster=None)
    with pytest.raises(RuntimeError, match="what a repair planned"):
        mf.generator_of(traffic)(ctx)


def test_the_kept_cell_runs_clean_on_the_mini_cluster(tmp_path):
    """`ockv-degraded.rs-6-3`: the k=10 cell's traffic and generator on
    `rs-6-3-1024k`, cut as `bench_minicluster.TINY` cuts its sibling."""
    import benchmarks.run as bench_run

    cell = mf.cell(MANIFEST, KEPT)
    config = copy.deepcopy(mf.config_of(MANIFEST, cell))
    config["scheme"]["cell"] = config["scheme"]["bpc"] = 4096
    config["replication"] = "rs-6-3-4096"
    traffic = {**mf.traffic_of(cell), **bm.TINY["ockv-degraded.rs-10-4"]}
    cluster = bm.MiniCluster(tmp_path, config["cluster"]["datanodes"])
    args = argparse.Namespace(workload=KEPT, seed=2 ** 31 + 38, seconds=1.0,
                              trace=0, rehearse=True, control="",
                              dump_trace="")
    try:
        out = json.loads(json.dumps(bench_run.measure(
            args, MANIFEST, cluster, cell, config, traffic)))
    finally:
        cluster.close()
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"get_mib_s", "setup_s"}
    assert out["compared"]["gets_that_decoded"]["value"] >= 1
    assert set(out["notes"]["decoded_stripes_by_e"]) <= {"1", "2"}


def test_the_deployment_states_its_source_guarantees_cuts_and_assumptions():
    entry, = [c for c in MANIFEST["configs"] if c["name"] == CONFIG]
    cfg = json.loads((mf.ROOT / entry["file"]).read_text())
    base = json.loads((mf.BENCH_DIR / "configs" / "rs-6-3-1024k.json")
                      .read_text())
    assert cfg["source"] == entry["source"] and len(entry["source"]) <= 200
    assert "USENIX ATC 2012" in cfg["source"] and "LRC(12,2,2)" in cfg["source"]
    assert cfg["replication"] == "lrc-12-2-2-1024k"
    for key in ("cell", "checksum", "bpc", "block_bytes"):
        assert cfg["scheme"][key] == base["scheme"][key], key
    for key in ("launcher", "metadata_replicas", "datapath"):
        assert cfg["cluster"][key] == base["cluster"][key], key
    assert cfg["cluster"]["chips"] == 1
    assert cfg["flush_policy"] == base["flush_policy"]
    for needle in ("byte-exact", "any 3 of the 16", "CRC32C",
                   "equals the one lost", "6 other members of its group"):
        assert any(needle in g for g in cfg["guarantees"]), needle
    assert set(cfg["reduced"]) == set(entry["reduced"]) == {
        "metadata_replicas", "hosts", "data_scale"}
    assert "global_parity_coefficients" in cfg["assumed"]
    # what the guarantees count is what the reference enumerates
    assert "1,557" in cfg["guarantees"][1]
