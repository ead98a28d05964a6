"""`op_stage_ms` under a name of the mesh cells' own: the same function.
No test pins the metrics that name `op_stage_ms` any longer; the mesh
and `.tier` stage groups keep this name while tests/test_bench_tier.py
and tests/test_bench_mesh_metrics.py hold their files to it (PERF.md
section 7)."""

from benchmarks.readers.op_stage_ms import read  # noqa: F401
