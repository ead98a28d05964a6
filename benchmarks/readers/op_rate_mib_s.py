"""MiB/s of the operations of `kind` completed inside the window: all
their bytes over all the window's seconds."""

from benchmarks.harness.stats import in_window, rate_mib_s


def read(params: dict, run) -> float | None:
    if not in_window(run.ops, params["kind"], run.t0, run.t1):
        return None
    return rate_mib_s(run.ops, params["kind"], run.t0, run.t1)
