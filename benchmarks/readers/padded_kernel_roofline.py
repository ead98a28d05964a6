"""A codec program's share of its roofline over the traced slice, as
`kernel_roofline` reads it, for traffic whose keys end in a partial
stripe: the zero data cells the writers submitted to fill those stripes
(the counter `pad_counter` names, over the same slice) are padding, not
work, so the useful stripes are the dispatched ones less pad cells / k.
The least time is linear in the stripes, so the share is
`kernel_roofline`'s times useful / dispatched. Padding is waste: it
lowers the share.

params: as `kernel_roofline`'s, and
        pad_counter  "<registry>/<counter>" of the pad cells submitted

Nothing where the program counts no padding (a program without the
counter), or `kernel_roofline` reads nothing.
"""

from benchmarks.harness.program import delta
from benchmarks.readers import kernel_roofline


def read(params: dict, run) -> float | None:
    c0, c1 = run.slice_counters0, run.slice_counters1
    if params["pad_counter"] not in c1:
        return None
    share = kernel_roofline.read(params, run)
    stripes = delta(c1, c0, "codec.service/stripes_dispatched") \
        + delta(c1, c0, "mesh/stripes_dispatched")
    useful = stripes - delta(c1, c0, params["pad_counter"]) / run.scheme["k"]
    if share is None or useful <= 0:
        return None
    return share * useful / stripes
