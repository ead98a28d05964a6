"""What decides `correct`, driven as a run drives it (everything after
the look for a chip), at a tiny size on the CPU with the in-process
mini-cluster: clean runs pass; every planted fault and every break of
the timed path underneath makes the run report not-correct; a window
that is no measurement ends the run."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import bench_minicluster as bm
from benchmarks.harness import manifest as mf


@pytest.mark.parametrize("cell", sorted(bm.TINY))
def test_a_clean_run_is_correct(tmp_path, cell):
    out = bm.run_cell(tmp_path, cell, seed=2 ** 31 + 3)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["rehearsal"] is True
    assert list(out)[-1] == "compared"
    assert all(set(c) == {"value", "limit"} for c in out["compared"].values())
    manifest = mf.load()
    want = {m["name"] for m in mf.metrics_for(manifest, "end_to_end", cell)}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())


@pytest.mark.parametrize("cell,control,number", [
    # a flipped byte in one stored PARITY chunk: a read-back through the
    # client fetches the k data units and never sees it
    ("ockg.rs-6-3", "byte_flip", "stored_bytes_differ"),
    # a wrong stored CRC under right bytes
    ("ockg.rs-6-3", "crc_wrong", "stored_crcs_differ"),
    # a survivor's data altered with its CRC rewritten to match: the
    # reader cannot tell, the GET's bytes differ from the payload
    ("ockv-degraded.rs-10-4", "silent_corruption", "get_bytes_differ"),
    # a repair that left nothing on its target
    ("ecrd.rs-6-3", "wipe_replica", "rebuilt_records_wrong"),
    # a rebuilt replica with one byte off under the right CRCs
    ("ecrd.rs-6-3", "byte_flip", "rebuilt_bytes_differ"),
])
def test_a_planted_fault_makes_the_run_not_correct(
        tmp_path, cell, control, number):
    out = bm.run_cell(tmp_path, cell, seed=2 ** 31 + 4, control=control)
    assert out["correct"] is False and out["control"] == control
    c = out["compared"][number]
    assert c["value"] > c["limit"] == 0
    if number != "get_bytes_differ":
        # the guarantee a reader can vouch for still held
        assert out["failed"] == 0


def _wrong_encoder(monkeypatch):
    from ozone_tpu.codec import fused

    real = fused.make_fused_encoder

    def make(spec):
        fn = real(spec)

        def altered(data):
            parity, crcs = fn(data)
            parity = np.array(parity)
            parity[:, 0, 0] ^= 1  # a parity byte of every stripe, where produced
            return parity, crcs
        return altered

    monkeypatch.setattr(fused, "make_fused_encoder", make)
    import ozone_tpu.client.ec_writer as w

    if hasattr(w, "make_fused_encoder"):
        monkeypatch.setattr(w, "make_fused_encoder", make)


def _wrong_decoder(monkeypatch):
    from ozone_tpu.client import ec_reader

    real = ec_reader.make_fused_decoder

    def make(spec, valid, erased):
        fn = real(spec, valid, erased)

        def altered(units):
            rec, crcs = fn(units)
            rec = np.array(rec)
            rec[..., 0] ^= 1  # the first byte of every recovered cell
            return rec, crcs
        return altered

    monkeypatch.setattr(ec_reader, "make_fused_decoder", make)


def _wrong_recovery(monkeypatch):
    """Every recovered cell altered where the repair path produces it,
    whichever executor decoded it (the single-chip service or the mesh)."""
    from ozone_tpu.client import ec_reader

    real = ec_reader.ECBlockGroupReader.recover_cells_iter
    masks = iter(np.random.default_rng(8).integers(1, 256, 100_000,
                                                   dtype=np.uint8))

    def altered(self, *a, **kw):
        for sb, (cells, crcs) in real(self, *a, **kw):
            cells = np.array(cells)
            cells[..., 0] ^= next(masks)
            yield sb, (cells, crcs)

    monkeypatch.setattr(ec_reader.ECBlockGroupReader, "recover_cells_iter",
                        altered)


def _noop_coordinator(monkeypatch):
    from ozone_tpu.storage import reconstruction

    monkeypatch.setattr(reconstruction.ECReconstructionCoordinator,
                        "reconstruct_container_group",
                        lambda self, cmd: None)


def _wrong_rebuild(monkeypatch):
    """Rebuilds the wrong bytes, checksummed as such: the target takes
    them, and only a comparison with the reference tells."""
    from ozone_tpu.storage import reconstruction

    real = reconstruction.build_chunk_pairs
    masks = iter(np.random.default_rng(9).integers(1, 256, 100_000,
                                                   dtype=np.uint8))

    def wrong(block_id, sb, cells, crcs, *rest):
        # a fresh mask each time: with one fixed mask, repairs that read
        # earlier wrong replicas can cancel the error out again
        return real(block_id, sb, np.asarray(cells) ^ next(masks),
                    crcs[..., :0], *rest)

    monkeypatch.setattr(reconstruction, "build_chunk_pairs", wrong)


@pytest.mark.parametrize("cell,breakage,numbers", [
    ("ockg.rs-6-3", _wrong_encoder, ["stored_bytes_differ"]),
    ("ockv-degraded.rs-10-4", _wrong_decoder, ["get_bytes_differ"]),
    ("ecrd.rs-6-3", _wrong_recovery,
     ["rebuilt_bytes_differ", "rebuilt_records_wrong"]),
    ("ecrd.rs-6-3", _wrong_rebuild,
     ["rebuilt_bytes_differ", "rebuilt_crcs_differ"]),
])
def test_the_timed_path_broken_underneath_is_not_correct(
        tmp_path, monkeypatch, cell, breakage, numbers):
    from ozone_tpu.codec import service as codec_service

    breakage(monkeypatch)
    codec_service.reset_for_tests()  # no lane keeps an unbroken callable
    try:
        out = bm.run_cell(tmp_path, cell, seed=2 ** 31 + 5)
    finally:
        monkeypatch.undo()
        codec_service.reset_for_tests()
    assert out["correct"] is False
    assert any(out["compared"][n]["value"] > 0 for n in numbers), \
        out["compared"]


def test_a_coordinator_that_rebuilds_nothing_is_no_run(tmp_path, monkeypatch):
    """It launches no dispatch, so the run ends before the comparison;
    the comparison's own verdict on a replica that is not there is the
    `wipe_replica` control above."""
    import benchmarks.run as bench_run

    _noop_coordinator(monkeypatch)
    with pytest.raises(bench_run.RunFailure, match="no codec dispatch"):
        bm.run_cell(tmp_path, "ecrd.rs-6-3")


def test_a_window_without_a_dispatch_or_with_a_compile_is_no_run(
        tmp_path, monkeypatch):
    import benchmarks.run as bench_run
    from benchmarks.harness import program

    real = program.snapshot
    frozen = real()
    monkeypatch.setattr(program, "snapshot", lambda: dict(frozen))
    with pytest.raises(bench_run.RunFailure, match="no codec dispatch"):
        bm.run_cell(tmp_path / "a", "ockg.rs-6-3")

    calls = []

    def compiling():
        calls.append(1)
        snap = real()
        snap["compile/compiles"] = snap.get("compile/compiles", 0) + len(calls)
        return snap

    monkeypatch.setattr(program, "snapshot", compiling)
    with pytest.raises(bench_run.RunFailure, match="compiled inside"):
        bm.run_cell(tmp_path / "b", "ockg.rs-6-3")


@pytest.mark.serial
def test_a_cell_has_no_cpu_mode_and_leaves_nothing_behind(tmp_path):
    """Without --rehearse a machine with no TPU ends the run: non-zero
    exit, no result line, no daemon and no cluster root left."""
    (tmp_path / "tmp").mkdir()
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path / "tmp"))
    out = subprocess.run(
        [sys.executable, str(mf.BENCH_DIR / "run.py"), "--workload",
         "ecrd.rs-6-3", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=mf.ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert out.returncode != 0 and out.stdout == ""
    assert "no TPU found" in out.stderr
    assert not list((tmp_path / "tmp").iterdir())
    assert not bm.processes_mentioning(str(tmp_path))


def test_alone_in_a_directory_the_benchmark_exits_non_zero(tmp_path):
    import shutil

    shutil.copytree(mf.BENCH_DIR, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(mf.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "ockg.rs-6-3",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=60)
    assert out.returncode != 0 and out.stdout == ""


def test_unknown_workload_is_refused_before_anything_boots(tmp_path):
    out = subprocess.run(
        [sys.executable, str(mf.BENCH_DIR / "run.py"), "--workload",
         "no-such-cell", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=mf.ROOT, capture_output=True, text=True, timeout=60)
    assert out.returncode != 0 and out.stdout == ""
    assert "no workload" in out.stderr


def test_the_result_line_of_a_rehearsal_carries_the_contracts_keys(tmp_path):
    out = bm.run_cell(tmp_path, "ecrd.rs-6-3")
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in out
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    json.dumps(out)
