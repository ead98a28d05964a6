"""Cores the chip-owning client process kept busy over the window: its
CPU seconds (user + system, every thread) between the first and the
last sample the program's process sampler took inside [t0, t1), over
the time between those two samples. Nothing where the program keeps no
such series, or the window holds fewer than two samples."""

from benchmarks.harness import process_series


def read(params: dict, run) -> float | None:
    s = process_series.samples(run.t0, run.t1)
    if len(s) < 2 or s[-1][0] <= s[0][0]:
        return None
    return (s[-1][1] - s[0][1]) / (s[-1][0] - s[0][0])
