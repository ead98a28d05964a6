"""From a profiler trace (`*.xplane.pb`) to busy/idle time, a program's
device time and the longest idle gaps. The benchmark's own reduction:
every PR computes these numbers the same way.

Two steps, so the arithmetic can be checked on a small recorded trace
without JAX: `extract()` turns the file into plain lists (what
`fixtures/*.events.json` holds), everything else works on those.

An extracted trace is
  {"planes": [{"name": str, "lines": [{"name": str,
               "events": [[name, start_ns, duration_ns], ...]}]}]}
with all planes on one clock.
"""

from __future__ import annotations

import glob
import os
import re

#: device planes as the TPU runtime names them: "/device:TPU:0". The
#: same chip's other planes ("/device:TPU:0 SparseCore ...") are not the
#: TensorCore and are left out.
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: the line of a device plane that holds one event per executed op, and
#: the line that holds one event per executed program
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: host events shorter than this are dropped at extraction (they cannot
#: explain an idle gap worth reporting, and there are very many)
HOST_MIN_NS = 50_000


class TraceError(Exception):
    """The trace does not hold what the reduction needs."""


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def extract(path: str) -> dict:
    """Read an .xplane.pb with nothing but JAX."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:CPU"):
            continue
        lines = []
        for line in plane.lines:
            floor = 0 if device else HOST_MIN_NS
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events if e.duration_ns >= floor]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: dict) -> list[dict]:
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def line_events(plane: dict, line_name: str) -> list[list]:
    for line in plane["lines"]:
        if line["name"] == line_name:
            return line["events"]
    return []


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge overlapping [start, end) intervals."""
    merged: list[tuple[float, float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def busy_intervals(plane: dict) -> list[tuple[float, float]]:
    """Where an operation ran on this device: the union of its op
    events (of its program events, where the plane has no op line)."""
    events = line_events(plane, OPS_LINE) or line_events(plane, MODULES_LINE)
    return union([(s, s + d) for _n, s, d in events if d > 0])


def busy_seconds(trace: dict) -> float:
    """Seconds an operation ran on the device, averaged over the device
    planes the trace holds."""
    planes = device_planes(trace)
    if not planes:
        raise TraceError("the trace holds no device plane")
    total = sum(e - s for p in planes for s, e in busy_intervals(p))
    return total / len(planes) / 1e9


def program_seconds(trace: dict, pattern: str) -> tuple[float, int]:
    """(summed device seconds, count) of the executed programs whose
    name matches `pattern`, over all device planes."""
    rx = re.compile(pattern)
    total, count = 0, 0
    for plane in device_planes(trace):
        for name, _s, d in line_events(plane, MODULES_LINE):
            if rx.search(name):
                total += d
                count += 1
    return total / 1e9, count


def top_device_ops(trace: dict, n: int = 10) -> list[list]:
    """[name, seconds] of the device operations that took most time."""
    sums: dict[str, int] = {}
    for plane in device_planes(trace):
        for name, _s, d in line_events(plane, OPS_LINE):
            sums[name] = sums.get(name, 0) + d
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    # an op's name is its whole HLO line: the head of it tells it apart
    return [[name[:120], d / 1e9] for name, d in ranked]


def idle_gaps(trace: dict, window_ns: tuple[float, float],
              n: int = 10) -> list[list]:
    """[what the host was doing, seconds] for the longest gaps in which
    no operation ran on the first device, inside `window_ns`. A gap is
    named after the host event that overlaps most of it."""
    planes = device_planes(trace)
    if not planes:
        raise TraceError("the trace holds no device plane")
    t0, t1 = window_ns
    busy = [(max(s, t0), min(e, t1)) for s, e in busy_intervals(planes[0])
            if e > t0 and s < t1]
    gaps, at = [], t0
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if t1 > at:
        gaps.append((at, t1))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    host = [(line["name"], name, s, s + d)
            for p in trace["planes"] if p["name"].startswith("/host:CPU")
            for line in p["lines"] for name, s, d in line["events"]]
    out = []
    for g0, g1 in gaps:
        best, best_overlap = "no host event recorded", 0
        for thread, name, s, e in host:
            overlap = min(e, g1) - max(s, g0)
            if overlap > best_overlap:
                best, best_overlap = f"{thread.split('/')[0]}: {name}", overlap
        out.append([best, (g1 - g0) / 1e9])
    return out


def span_ns(trace: dict) -> tuple[float, float]:
    """First start and last end of any event in the trace."""
    starts, ends = [], []
    for p in trace["planes"]:
        for line in p["lines"]:
            for _n, s, d in line["events"]:
                starts.append(s)
                ends.append(s + d)
    if not starts:
        raise TraceError("the trace holds no event")
    return min(starts), max(ends)


def traced_seconds(trace: dict, slice_s: float) -> float:
    """The length of the traced window: the longer of the slice by the
    host's clock (start_trace returned .. stop_trace called) and the span
    of the trace's own events — the profiler records a little before and
    after."""
    first, last = span_ns(trace)
    return max(slice_s, (last - first) / 1e9)
