"""The q-th percentile (nearest rank) of the latency, call to reply, of
every operation of `kind` completed inside the window."""

from benchmarks.harness.stats import in_window, latency_ms


def read(params: dict, run) -> float | None:
    if not in_window(run.ops, params["kind"], run.t0, run.t1):
        return None
    return latency_ms(run.ops, params["kind"], run.t0, run.t1, params["q"])
