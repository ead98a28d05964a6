"""`op_stage_ms` under a name of the mesh cell's own: the same function.
tests/benchmark_tests/test_bench_spans.py pins the set of metrics whose
files name `op_stage_ms` to those PR 25 brought, and a PR that adds a
cell may not edit it; until a `benchmark` PR lifts the pin,
`repair_mesh_ms` names the reader so (PERF.md section 7)."""

from benchmarks.readers.op_stage_ms import read  # noqa: F401
