"""delta(numerator) / delta(denominator) of two program counters over
the window, as it is (`counter_ratio_pct` gives a share in %). Nothing
where the program has no such numerator, or the denominator did not
move."""

from benchmarks.harness.program import delta


def read(params: dict, run) -> float | None:
    if params["numerator"] not in run.counters1:
        return None
    den = delta(run.counters1, run.counters0, params["denominator"])
    if den <= 0:
        return None
    return delta(run.counters1, run.counters0, params["numerator"]) / den
