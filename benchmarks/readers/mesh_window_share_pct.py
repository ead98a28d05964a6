"""`window_share_pct` under a name of the mesh cells' own: the same
function. No test pins the metrics that name `window_share_pct` any
longer; the mesh and `.tier` window shares keep this name while
tests/test_bench_tier.py and tests/test_bench_mesh_metrics.py hold their
files to it (PERF.md section 7)."""

from benchmarks.readers.window_share_pct import read  # noqa: F401
