"""Of the time the first device ran no operation in the traced slice,
the share, in %, that lies under a host event named `event` (the codec
dispatcher's `codec:idle`: it had nothing to launch and nothing to
complete). High: the chip waits for work that has not reached the
dispatcher. Low: it waits while the dispatcher packs, launches or pulls
results. Nothing without a trace, or where the trace holds no such
event (a program that does not annotate its dispatcher).

params: event  the host event's name
"""

from benchmarks.harness import trace as tr


def read(params: dict, run) -> float | None:
    if run.trace is None:
        return None
    planes = tr.device_planes(run.trace)
    marked = tr.union([
        (s, s + d) for p in run.trace["planes"]
        if p["name"].startswith("/host:CPU")
        for line in p["lines"] for name, s, d in line["events"]
        if name == params["event"]])
    if not planes or not marked:
        return None
    # every event lies inside the trace's span, so the device's idle time
    # is the span less its busy time, and what of the marked time is not
    # busy is idle
    first, last = tr.span_ns(run.trace)
    busy = tr.busy_intervals(planes[0])
    idle = (last - first) - sum(e - s for s, e in busy)
    if idle <= 0:
        return None
    under = sum(e - s for s, e in marked) - _overlap(busy, marked)
    return 100.0 * under / idle


def _overlap(a: list[tuple], b: list[tuple]) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total
