"""Share of the traced slice in which no operation ran on the device:
100 x (1 - union of the device's op intervals / slice)."""

from benchmarks.harness import trace as tr


def read(params: dict, run) -> float | None:
    if run.trace is None:
        return None
    window = tr.traced_seconds(run.trace, run.slice1 - run.slice0)
    return 100.0 * (1.0 - min(1.0, tr.busy_seconds(run.trace) / window))
