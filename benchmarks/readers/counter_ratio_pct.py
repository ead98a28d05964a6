"""100 x delta(numerator) / delta(denominator) of two program counters
over the window. Nothing where the denominator did not move."""

from benchmarks.harness.program import delta


def read(params: dict, run) -> float | None:
    den = delta(run.counters1, run.counters0, params["denominator"])
    if den <= 0:
        return None
    num = delta(run.counters1, run.counters0, params["numerator"])
    return 100.0 * num / den
