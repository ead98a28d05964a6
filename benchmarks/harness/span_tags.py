"""The tags of the program's finished spans, by name: beside `spans.py`
(the per-operation stage records) the one other place of the benchmark
that imports `ozone_tpu.utils.tracing`.

The program keeps its finished spans in a ring (`Tracer.spans`, 10,000
of them): spans a long window has pushed out are not counted, so a share
taken of these is a share of those the ring still holds.
"""

from __future__ import annotations


def ended(name: str, t0: float, t1: float) -> list[dict]:
    """The tags of each span named `name` that ENDED in [t0, t1) on the
    monotonic clock, oldest first."""
    from ozone_tpu.utils.tracing import Tracer

    return [dict(s.tags) for s in Tracer.instance().traces()
            if s.name == name and t0 <= s.mono + s.duration < t1]
