"""What one run hands to the metric readers."""

from __future__ import annotations

from dataclasses import dataclass, field

from benchmarks.harness.stats import Op


@dataclass
class Run:
    cell: dict
    config: dict
    traffic: dict
    setup_s: float
    ops: list[Op]
    t0: float                 # window, monotonic clock
    t1: float
    counters0: dict           # program counters at the window's start
    counters1: dict           # ... and at its close
    notes: dict = field(default_factory=dict)   # the generator's
    # only in a traced run:
    peaks: dict | None = None       # the device's row of the peaks table
    trace: dict | None = None       # harness.trace extracted form
    slice0: float = 0.0             # traced slice, monotonic clock
    slice1: float = 0.0
    slice_counters0: dict = field(default_factory=dict)
    slice_counters1: dict = field(default_factory=dict)

    @property
    def scheme(self) -> dict:
        return self.config["scheme"]
