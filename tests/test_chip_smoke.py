"""chip_smoke.py and the compile-cache rule, as far as a CPU can show.

The smoke itself only passes on a TPU (the chip tool runs it there);
what is checked here is its refusal: no TPU, or nothing of the repo
beside it, means a non-zero exit, fast, with no result line and no
cluster booted."""

import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax

REPO = Path(__file__).resolve().parent.parent


def _run_smoke(cwd: Path, script: Path, tmp_path: Path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TMPDIR": str(tmp_path)}
    t0 = time.time()
    proc = subprocess.run([sys.executable, str(script)], cwd=str(cwd),
                          env=env, capture_output=True, text=True,
                          timeout=180)
    return proc, time.time() - t0


def test_chip_smoke_has_no_cpu_mode(tmp_path):
    proc, took = _run_smoke(REPO, REPO / "chip_smoke.py", tmp_path)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout and "cluster up" not in proc.stdout
    assert took < 90, f"took {took:.0f}s to find out there is no TPU"
    # it got as far as the first chip-owning child and no further
    (work,) = tmp_path.glob("chip_smoke_*")
    assert sorted(f.name for f in work.iterdir()) == ["kernels.err"]


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    lone = tmp_path / "lone"
    lone.mkdir()
    shutil.copy(REPO / "chip_smoke.py", lone)
    proc, _ = _run_smoke(lone, lone / "chip_smoke.py", tmp_path)
    assert proc.returncode != 0 and '"ok"' not in proc.stdout


def test_kernel_checker_runs_at_a_small_size(monkeypatch):
    """The kernels phase's own logic (references, layouts, names) at a
    size a CPU can run, jitted programs forced; the chip runs it at
    production shapes with the Pallas kernel added."""
    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(REPO))
    monkeypatch.setenv("OZONE_TPU_FUSED_BACKEND", "jax")
    out = chip_smoke.check_kernels(batches=(2,), cell=32 * 1024,
                                   pallas=False)
    assert len(out["checked"]) == 9 and "crc_fn [36, 16384]" in out["checked"]
    assert out["device"]["platform"] == "cpu"


def test_compile_cache_rule(monkeypatch, tmp_path):
    """A preset JAX_COMPILATION_CACHE_DIR means nothing is touched (JAX
    reads it itself, no code names another directory); unset, the cache
    goes to a fixed path inside the checkout — and nothing else in the
    tree places the cache."""
    from ozone_tpu.utils.compile_cache import ensure_compile_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    before = {n: getattr(jax.config, n) for n in names}
    try:
        floor = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
        monkeypatch.delenv(floor, raising=False)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert ensure_compile_cache() == str(tmp_path)
        assert {n: getattr(jax.config, n) for n in names} == before
        assert floor not in os.environ

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = str(REPO / ".jax_cache")
        assert ensure_compile_cache() == want
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == want  # children
        assert jax.config.jax_compilation_cache_dir == want
        assert os.environ[floor] == "0.0"  # every program is cached
    finally:
        os.environ.pop(floor, None)
        for n, v in before.items():
            jax.config.update(n, v)

    placing = subprocess.run(
        ["git", "grep", "-l", "-i", "compilation_cache", "--", "*.py"],
        cwd=REPO, capture_output=True, text=True).stdout.split()
    assert set(placing) <= {"ozone_tpu/utils/compile_cache.py",
                            "tests/conftest.py",
                            "tests/test_chip_smoke.py"}, placing
