"""Repo hygiene: build artifacts must never be tracked.

A `__pycache__` directory committed alongside source (PR 15 removed a
batch of them) poisons review diffs and ships stale bytecode that
shadows edited modules on some import paths; this pins the cleanup.
Native libraries and the compile cache are made at run time on the
machine that uses them: a tracked `-march=native` .so can SIGILL on
another CPU, and a tracked cache is keyed to a path that moved."""

import subprocess
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _tracked() -> list[str]:
    out = subprocess.run(
        ["git", "ls-files"], cwd=REPO, capture_output=True, text=True)
    if out.returncode != 0:  # not a git checkout (sdist, vendored copy)
        return []
    return out.stdout.splitlines()


def test_no_bytecode_tracked():
    bad = [f for f in _tracked()
           if "__pycache__" in f or f.endswith((".pyc", ".pyo"))]
    assert not bad, f"bytecode artifacts tracked in git: {bad[:10]}"


def test_gitignore_covers_bytecode():
    text = (REPO / ".gitignore").read_text()
    assert "__pycache__" in text and "*.pyc" in text


def test_no_native_or_compile_cache_artifacts_tracked():
    bad = [f for f in _tracked()
           if f.endswith((".so", ".so.stamp")) or ".jax_cache" in f
           or f.startswith("chiprun_out/")]
    assert not bad, f"run-time artifacts tracked in git: {bad[:10]}"
    text = (REPO / ".gitignore").read_text().split()
    for pattern in ("*.so", "*.so.stamp", ".jax_cache/", "chiprun_out/"):
        assert pattern in text, f".gitignore lacks {pattern}"
