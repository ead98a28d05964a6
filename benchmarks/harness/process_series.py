"""The program's own samples of what its PROCESS costs the host: beside
`spans.py` (the per-operation stage records) and `span_tags.py` (the
tags of finished spans) the third and last place of the benchmark that
imports `ozone_tpu.utils.tracing`.

The program keeps one sampler thread a process (`tracing.ProcessSampler`,
a sample every 50 ms, ten minutes kept): (time.monotonic(), the
process's CPU seconds so far, the host's busy and total jiffies from
/proc/stat, live threads, and the sampler's own lateness in seconds:
how long a freshly woken thread of the process waited for its turn at
the interpreter). The clock is the window's, so a reader takes deltas
between a window's first and last sample without a snapshot of its own.
A program that keeps no such series (an older commit) gives none here,
never an error.
"""

from __future__ import annotations


def samples(t0: float, t1: float) -> list[tuple]:
    """The samples taken in [t0, t1) on the monotonic clock, oldest
    first: (monotonic, process CPU s, host busy jiffies, host total
    jiffies, threads, lateness s)."""
    from ozone_tpu.utils import tracing

    read = getattr(tracing, "samples", None)
    return read(t0, t1) if read is not None else []
