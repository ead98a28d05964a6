"""Mean, in ms, of what a program histogram observed over the window:
delta(sum) / delta(count). Nothing where it observed nothing."""

from benchmarks.harness.program import delta


def read(params: dict, run) -> float | None:
    n = delta(run.counters1, run.counters0, params["histogram"] + ".count")
    if n <= 0:
        return None
    total = delta(run.counters1, run.counters0, params["histogram"] + ".sum")
    return 1e3 * total / n
