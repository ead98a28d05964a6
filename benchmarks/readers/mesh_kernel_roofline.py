"""A sharded (SPMD) codec program's share of its roofline over the
traced slice: the least device time the USEFUL stripes the mesh executor
dispatched could take (work from shapes and the scheme, harness/work.py,
at ONE chip's peaks) over the device time the program took on ALL the
device planes that ran it. Device seconds add up over chips, so this is
the same as holding the wall time of a dispatch to a roof of one chip's
peak times the planes: a batch spread over four devices has four chips'
bandwidth to answer for, and padding, which every device computes, is
waste that lowers the share.

(`kernel_roofline` divides one chip's roof by the MEAN per-device
program time, `seconds * dispatches / executions` with one execution a
plane a dispatch: on a mesh it reads the device count too high.)

params: program  regex of the program's name on the trace's module line
        work     "encode" or "decode"
        erased   for decode: units recovered per stripe, a number

Useful stripes and dispatches are the deltas of `mesh/stripes_dispatched`
and `mesh/dispatches` between the two counter snapshots that bracket the
slice; the trace may hold a few more executions than those snapshots
span, so the program's summed time is scaled by (dispatches counted x
planes) / executions traced. Nothing without a trace, or where no plane
ran a program of that name (a program that does not name it so).
"""

import re

from benchmarks.harness import trace as tr
from benchmarks.harness import work
from benchmarks.harness.program import delta


def read(params: dict, run) -> float | None:
    if run.trace is None or run.peaks is None:
        return None
    rx = re.compile(params["program"])
    seconds, executions, planes = 0.0, 0, 0
    for plane in tr.device_planes(run.trace):
        mine = [d for name, _s, d in tr.line_events(plane, tr.MODULES_LINE)
                if rx.search(name)]
        if mine:
            seconds += sum(mine) / 1e9
            executions += len(mine)
            planes += 1
    c0, c1 = run.slice_counters0, run.slice_counters1
    stripes = delta(c1, c0, "mesh/stripes_dispatched")
    dispatches = delta(c1, c0, "mesh/dispatches")
    if executions == 0 or seconds <= 0 or stripes <= 0 or dispatches <= 0:
        return None
    s = run.scheme
    if params["work"] == "encode":
        w = work.encode_work(s["k"], s["p"], s["cell"], s["bpc"], stripes)
    else:
        w = work.decode_work(s["k"], float(params["erased"]), s["cell"],
                             s["bpc"], stripes)
    least = work.least_seconds(w, run.peaks)["seconds"]
    return 100.0 * least / (seconds * dispatches * planes / executions)
