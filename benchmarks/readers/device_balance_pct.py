"""How evenly the devices of a mesh were kept busy over the traced
slice: 100 x the busy seconds of the least busy device plane over those
of the busiest (busy = the union of a plane's op intervals). 100: every
device did the same; low: one device waited while another worked. Nothing
without a trace, on fewer than two device planes, or where no device
ran anything."""

from benchmarks.harness import trace as tr


def read(params: dict, run) -> float | None:
    if run.trace is None:
        return None
    busy = [sum(e - s for s, e in tr.busy_intervals(p))
            for p in tr.device_planes(run.trace)]
    if len(busy) < 2 or max(busy) <= 0:
        return None
    return 100.0 * min(busy) / max(busy)
