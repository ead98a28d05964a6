"""A codec program's share of its roofline over the traced slice: the
least time the chip could take for the USEFUL stripes the codec service
dispatched (work from shapes and the scheme, harness/work.py) over the
device time of the program that did them (device trace).

params: program  regex of the program's name on the trace's module line
        work     "encode" or "decode"
        erased   for decode: units recovered per stripe, a number, or
                 {"op_kind": k} to take it from the generator's own tag
                 of the operations of kind k that overlap the slice
                 (tag[1]), weighted by their overlap

Useful stripes are delta(stripes_dispatched) between the two counter
snapshots that bracket the slice; the trace may hold a few more
executions than those snapshots span (the profiler starts before and
stops after them), so the program's time is scaled by dispatches
counted / executions traced. Every execution of a lane is the same
compiled program whatever its fill, so the scaling is exact to the
spread of one execution's time. Padding is waste: it lowers the share.
"""

from benchmarks.harness import trace as tr
from benchmarks.harness import work
from benchmarks.harness.program import delta


def read(params: dict, run) -> float | None:
    if run.trace is None or run.peaks is None:
        return None
    seconds, executions = tr.program_seconds(run.trace, params["program"])
    c0, c1 = run.slice_counters0, run.slice_counters1
    stripes = delta(c1, c0, "codec.service/stripes_dispatched") \
        + delta(c1, c0, "mesh/stripes_dispatched")
    dispatches = delta(c1, c0, "codec.service/dispatches") \
        + delta(c1, c0, "mesh/dispatches")
    if executions == 0 or seconds <= 0 or stripes <= 0 or dispatches <= 0:
        return None
    s = run.scheme
    if params["work"] == "encode":
        w = work.encode_work(s["k"], s["p"], s["cell"], s["bpc"], stripes)
    else:
        e = _erased(params["erased"], run)
        if e is None:
            return None
        w = work.decode_work(s["k"], e, s["cell"], s["bpc"], stripes)
    least = work.least_seconds(w, run.peaks)["seconds"]
    return 100.0 * least / (seconds * dispatches / executions)


def _erased(spec, run) -> float | None:
    if not isinstance(spec, dict):
        return float(spec)
    num = den = 0.0
    for o in run.ops:
        if not o.ok or o.kind != spec["op_kind"] or not o.tag[1]:
            continue
        overlap = min(o.end, run.slice1) - max(o.start, run.slice0)
        if overlap > 0:
            share = overlap / max(o.end - o.start, 1e-9)
            num += share * o.tag[1]
            den += share
    return num / den if den else None
