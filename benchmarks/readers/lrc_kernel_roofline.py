"""The fused decode program's share of its roofline over the traced
slice, for a code whose repairs read sets of different widths: as
`kernel_roofline`, but the work of a decoded stripe is counted at the
width the REFERENCE says its repair reads (`reference_lrc.read_set` of
the repair's lost unit: a group's 6 survivors for a lone data unit or
local parity of LRC(12,2,2), the 12 data units for a global parity), not
at k. Counted at k = 12, a local repair's stripe would claim twice the
bytes it moves and the share could pass 100 %.

params: program  regex of the program's name on the trace's module line
        op_kind  the operations whose tag is (container, lost unit)

The width of the slice is the mean, over the operations of `op_kind`
that overlap it, of the reference's read width for each one's lost unit,
weighted by the stripes each rebuilt inside the slice (its stripes times
the share of its duration that lies in the slice). From shapes, the
scheme and the generator's own tags: never from the program's counters
of what it read. One unit is rebuilt per stripe (e = 1).
"""

import dataclasses

from benchmarks.harness import reference_lrc
from benchmarks.readers import kernel_roofline


def read(params: dict, run) -> float | None:
    if run.trace is None or run.peaks is None:
        return None
    width = slice_width(params["op_kind"], run)
    if width is None:
        return None
    # the accepted reader's arithmetic, handed the width as the scheme's k
    at_width = dataclasses.replace(run, config={
        **run.config, "scheme": {**run.scheme, "k": width}})
    return kernel_roofline.read(
        {"program": params["program"], "work": "decode", "erased": 1},
        at_width)


def slice_width(op_kind: str, run) -> float | None:
    s = run.scheme
    widths: dict[int, int] = {}
    num = den = 0.0
    for o in run.ops:
        if not o.ok or o.kind != op_kind:
            continue
        overlap = min(o.end, run.slice1) - max(o.start, run.slice0)
        if overlap <= 0:
            continue
        unit = o.tag[1]
        if unit not in widths:
            widths[unit] = len(reference_lrc.read_set(s, [unit]))
        rebuilt = o.nbytes / s["cell"] * overlap / max(o.end - o.start, 1e-9)
        num += rebuilt * widths[unit]
        den += rebuilt
    return num / den if den else None
