"""Share of the window, in %, that a program counter of seconds grew
by: 100 x delta(counter) / (t1 - t0). For a loop that owns one thread
(the codec dispatcher) it is the share of that thread's time. Nothing
where the program has no such counter."""

from benchmarks.harness.program import delta


def read(params: dict, run) -> float | None:
    if params["counter"] not in run.counters1 or run.t1 <= run.t0:
        return None
    return 100.0 * delta(run.counters1, run.counters0,
                         params["counter"]) / (run.t1 - run.t0)
