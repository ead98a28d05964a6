"""Arithmetic over a window's operations (copied in spirit from
`tools/freon.py` FreonReport.summary: nearest-rank percentiles over the
raw latencies, rates over all completed work and all the window's time).
"""

from __future__ import annotations

from dataclasses import dataclass

MIB = 2 ** 20


@dataclass
class Op:
    """One operation of the load generator, on the monotonic clock."""

    kind: str
    start: float
    end: float
    nbytes: int
    ok: bool
    error: str = ""
    tag: object = None  # generator's own (key index, container, unit)


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile, q in [0, 1]."""
    if not values:
        raise ValueError("no values")
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1, int(q * len(ordered)))]


def in_window(ops: list[Op], kind: str, t0: float, t1: float) -> list[Op]:
    """The operations that count: succeeded, of `kind`, and finished
    inside [t0, t1]. One still in flight at t1 is finished and checked
    by the harness but counts towards no rate and no percentile."""
    return [o for o in ops
            if o.ok and o.kind == kind and t0 <= o.start and o.end <= t1]


def rate_mib_s(ops: list[Op], kind: str, t0: float, t1: float) -> float:
    done = in_window(ops, kind, t0, t1)
    return sum(o.nbytes for o in done) / MIB / (t1 - t0)


def latency_ms(ops: list[Op], kind: str, t0: float, t1: float,
               q: float) -> float:
    done = in_window(ops, kind, t0, t1)
    return 1e3 * percentile([o.end - o.start for o in done], q)
