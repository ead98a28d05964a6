"""Mean, in ms over the window's samples, of the program's process
sampler's own lateness: how long after the tick it had asked for it was
running again, which is what a freshly woken thread of the client
process waits for its turn at the interpreter (timer slack, ~0.1 ms,
included). Nothing where the program keeps no such series or the window
holds no sample."""

from benchmarks.harness import process_series


def read(params: dict, run) -> float | None:
    s = process_series.samples(run.t0, run.t1)
    if not s:
        return None
    return 1e3 * sum(x[5] for x in s) / len(s)
