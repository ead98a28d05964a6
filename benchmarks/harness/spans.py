"""The program's per-operation stage records: the one place of the
benchmark that imports `ozone_tpu.utils.tracing`, so a renamed recorder
breaks one file.

The program keeps, for every finished operation root (`client:put`,
`client:get`, `repair:container`), where each instant of the root span
went: {"root", "end" (time.monotonic(), the window's clock),
"durationUs", "stages": {stage name: microseconds}}; the stages of one
record sum to its duration. A program that keeps no such records (an
older commit) gives none here, never an error.
"""

from __future__ import annotations


def operations(root: str, t0: float, t1: float) -> list[dict]:
    """The records of the operations named `root` whose root span ENDED
    in [t0, t1) on the monotonic clock."""
    from ozone_tpu.utils.tracing import Tracer

    read = getattr(Tracer.instance().recorder, "operations", None)
    return read(root, t0, t1) if read is not None else []
