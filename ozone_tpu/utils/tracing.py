"""Distributed tracing: spans with cross-RPC propagation.

Capability mirror of the reference's tracing layer (hadoop-hdds/common
hdds/tracing/TracingUtil.java — Jaeger spans with the trace context
carried as a string `traceID` field on every proto request,
DatanodeClientProtocol.proto:184; GrpcClientInterceptor/
GrpcServerInterceptor propagate it). Here spans are collected in-process
(ring buffer, queryable/exportable) and the context string rides the
net/wire.py JSON header under "traceId"; the RPC layer injects/extracts
automatically.

Clocks: a span's `start` is wall-clock time, because it is exported
and laid beside other processes' spans; its `duration`, and everything
attributed inside one process (the per-operation stage record), comes
from `time.monotonic()`, the clock a load generator's window is on.
`stage()` brackets a leaf interval of a loop that owns its thread and
mirrors it to the JAX profiler, the device trace's clock.

Cost: beside how long it took, a span bracketed on one thread says what
that thread spent between its edges: CPU seconds, the times it went to
sleep (`blocks`) and the times the kernel took its core away
(`preempts`), in the operations that are costed (`COST_INTERVAL_S`: at
most one of a name a second, because the two calls at each edge are
system calls made with the interpreter lock held). In a costed
operation a hand-off to a pool worker is counted and timed where the
worker takes it up (`Tracer.handoff` / `Tracer.activate`), an RPC's
client span carries how long the daemon had it (net/rpc.py), and the
copies that had no span get a leaf of their own (`Tracer.cost_leaf`);
its stage record sums them (`FlightRecorder`). An operation that is not
costed pays none of this. One sampler thread a process keeps the
process's CPU seconds, the host's busy share and how long a woken
thread waits for the interpreter (`samples`).
"""

from __future__ import annotations

import os
import random
import re
import resource
import sys
import threading
import time
from collections import OrderedDict, deque
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Optional

from ozone_tpu.utils import metrics as _metrics

_local = threading.local()

#: on /prom: `handoffs`, `handoff_seconds` (of the costed operations: a
#: sample, not a total), `spans_evicted`, and the sampler's
#: `process_cpu_seconds`, `threads`, `interpreter_wait_seconds`
METRICS = _metrics.registry("tracing")
_HANDOFFS = METRICS.counter("handoffs")
_HANDOFF_SECONDS = METRICS.histogram("handoff_seconds")


@dataclass
class Span:
    trace_id: str
    span_id: str
    parent_id: str
    name: str
    start: float
    duration: float = 0.0
    tags: dict = field(default_factory=dict)
    #: point-in-time annotations ({"t", "name", ...attrs}); retry /
    #: hedge / breaker decisions land here rather than as child spans
    events: list = field(default_factory=list)
    #: start on time.monotonic() (this process only; never exported)
    mono: float = 0.0
    #: opened by Tracer.operation(): as a root it leaves a stage record
    op: bool = False
    #: what ITS thread spent between its edges: CPU seconds (user +
    #: system), voluntary context switches (it went to sleep: a lock, a
    #: future, a socket, the interpreter) and involuntary ones (its core
    #: was taken away). `thread` is that thread's id; 0, and no cost,
    #: for a span of a trace that is not costed (`costed`), for an
    #: interval another thread measured (`record_span`: waiting by
    #: construction) and for a root of `begin_operation` (its thread
    #: works on several roots in turn). `cost_only` marks a leaf of
    #: `Tracer.cost_leaf`: in the record's `cost`, never in its `stages`
    #: (`critical_path` gives its time to its parent). Defaults of the
    #: class, not fields: only a span of a costed trace sets them, and
    #: every other span is made and kept as cheaply as before
    cpu = 0.0
    blocks = 0
    preempts = 0
    thread = 0
    cost_only = False


#: whether this kernel counts a thread's context switches: a sandbox
#: kernel (gVisor, the chip machines') reports none, and there
#: `_thread_cost` leaves the call out. Asked once, of the importing
#: thread after a sleep: a kernel that counts them has counted one
time.sleep(1e-6)
_SWITCHES_COUNTED = resource.getrusage(resource.RUSAGE_THREAD).ru_nvcsw > 0

#: an operation is costed (every span of its trace takes its thread's
#: cost at both edges, its hand-offs and RPCs are booked, its copies
#: get leaves) if the last costed root of its name began at least this
#: long ago: the first of a name, then one a second at most, whatever
#: the operation rate. The two calls at an edge are system calls made
#: with the interpreter lock held: ~1 us together on Linux, 12-40 us
#: under a sandbox kernel, where eight readers that paid them at every
#: span of every GET read 15 % fewer bytes, and a lone repair
#: coordinator with every second repair costed 5 % (PERF.md section 6,
#: PR 38)
COST_INTERVAL_S = 1.0

#: what a costed trace's id ends in: the root decides
#: (`Tracer._new_trace_id`), and every thread and every daemon the
#: trace reaches knows without being told. No id of sixteen hex digits,
#: this tracer's or another's, ends so
_COSTED = "-c"


def costed(trace_id: str) -> bool:
    """Whether the spans of this trace take their threads' cost."""
    return trace_id.endswith(_COSTED)


def _thread_cost() -> tuple[float, int, int]:
    """(CPU seconds, voluntary, involuntary context switches) of the
    calling thread so far. The CPU is `thread_time()`'s: rusage's is
    scaled from the scheduler's ticks and reads 3 ms for a 0.2 ms spin
    (tests/test_span_cost.py)."""
    if not _SWITCHES_COUNTED:
        return time.thread_time(), 0, 0
    ru = resource.getrusage(resource.RUSAGE_THREAD)
    return time.thread_time(), ru.ru_nvcsw, ru.ru_nivcsw


#: traces with finished spans whose root has not finished here yet (in
#: a daemon most never will: their root is the caller's), and the spans
#: kept of any one of them; the oldest trace goes first
MAX_OPEN_TRACES = 1024
MAX_TRACE_SPANS = 4096


#: the span ring: the fullest cell's run (a GET cell's, set-up included)
#: ends at up to 13,600 spans (PERF.md section 7), well under two thirds
#: of the ring, so a reader of a window's spans (`lrc_local_kept_pct`)
#: sees all of them; `tracing/spans_evicted` counts what was pushed out
MAX_SPANS = 32_768


class Tracer:
    """Process-wide tracer with a bounded span buffer."""

    _instance: Optional["Tracer"] = None

    def __init__(self, max_spans: int = MAX_SPANS):
        self.spans: deque[Span] = deque(maxlen=max_spans)
        self._lock = threading.Lock()
        #: filled by an attached SpanExporter; None = local-only mode
        self._export_q: Optional[deque] = None
        #: tail-based slow-trace retention (per-op SLO, env-tunable) and
        #: the per-operation stage records
        self.recorder = FlightRecorder()
        #: trace id -> its finished spans, handed to the recorder when
        #: the root finishes: a root never scans the span ring
        self._open: "OrderedDict[str, list[Span]]" = OrderedDict()
        #: trace id -> the hand-offs booked against it so far, (pool,
        #: seconds waited) each; the root takes them with it
        self._handed: "OrderedDict[str, list[tuple]]" = OrderedDict()
        #: root name -> when its last costed root began (monotonic)
        self._costed_at: dict[str, float] = {}

    @classmethod
    def instance(cls) -> "Tracer":
        if cls._instance is None:
            cls._instance = cls()
            ProcessSampler.ensure_started()
        return cls._instance

    @staticmethod
    def _new_id() -> str:
        return f"{random.getrandbits(64):016x}"

    def _new_trace_id(self, root: str) -> str:
        """An id for a trace that starts here, marked if the trace is
        costed (`costed`): the first root of a name, and then one every
        COST_INTERVAL_S at most."""
        now = time.monotonic()
        if now - self._costed_at.get(root, float("-inf")) < COST_INTERVAL_S:
            return self._new_id()
        self._costed_at[root] = now
        return self._new_id() + _COSTED

    def current(self) -> Optional[Span]:
        return getattr(_local, "span", None)

    @contextmanager
    def operation(self, name: str, **tags):
        """A span around one user-visible operation (a PUT, a GET, a
        container repair). Where it is the root of its trace, the
        flight recorder keeps its stage record (FlightRecorder.
        operations) whether or not it was slow; nested under another
        operation it is an ordinary child."""
        with self.span(name, **tags) as s:
            s.op = True
            yield s

    @contextmanager
    def span(self, name: str, child_of: Optional[str] = None, **tags):
        """Start a span; child_of is an imported context string
        ("traceid:spanid") from a remote caller."""
        parent = self.current()
        if child_of:
            trace_id, parent_id = (child_of.split(":") + [""])[:2]
        elif parent is not None:
            trace_id, parent_id = parent.trace_id, parent.span_id
        else:
            trace_id, parent_id = self._new_trace_id(name), ""
        s = Span(trace_id, self._new_id(), parent_id, name, time.time(),
                 tags=dict(tags), mono=time.monotonic())
        _local.span = s
        if trace_id.endswith(_COSTED):
            s.thread = threading.get_ident()
            cpu0, blocks0, preempts0 = _thread_cost()
        try:
            yield s
        finally:
            if s.thread:
                cpu1, blocks1, preempts1 = _thread_cost()
                s.cpu = cpu1 - cpu0
                s.blocks = blocks1 - blocks0
                s.preempts = preempts1 - preempts0
            s.duration = time.monotonic() - s.mono
            _local.span = parent
            self._finish(s)

    @contextmanager
    def cost_leaf(self, name: str, **tags):
        """A leaf span around work that only copies memory, in a costed
        trace alone (elsewhere nothing is opened and None is yielded):
        its wall less its CPU is time its thread was runnable and not
        running. It is in the record's `cost` and never in its `stages`,
        which stay what they were before the leaf existed."""
        cur = self.current()
        if cur is None or not costed(cur.trace_id):
            yield None
            return
        with self.span(name, **tags) as s:
            s.cost_only = True
            yield s

    def begin_operation(self, name: str, **tags) -> Span:
        """Open an operation root that no `with` can bracket: one of
        several a single thread works on in turn (the tiering sweep
        opens a key, packs it, and commits it windows later, other keys
        in between). It is never the thread's current span: its stages
        are spans opened with `child_of=context(root)`. Finish it with
        `end_operation`, exactly once."""
        s = Span(self._new_trace_id(name), self._new_id(), "", name,
                 time.time(), tags=dict(tags), mono=time.monotonic())
        s.op = True
        return s

    def end_operation(self, s: Span) -> None:
        s.duration = time.monotonic() - s.mono
        self._finish(s)

    @staticmethod
    def context(s: Span) -> str:
        """`s` as a `child_of` context string."""
        return f"{s.trace_id}:{s.span_id}"

    @contextmanager
    def riders(self, contexts):
        """While open, `inject()` on this thread gives ALL of
        `contexts`, comma-joined: a submission that carries stripes of
        several operations (a tiering window) hands the scheduler every
        rider's context, and `record_span` then leaves the submission's
        queue-wait and dispatch spans in each rider's trace."""
        prev = getattr(_local, "riders", "")
        _local.riders = ",".join(dict.fromkeys(c for c in contexts if c))
        try:
            yield
        finally:
            _local.riders = prev

    def _finish(self, s: Span) -> None:
        # A child span's end takes no lock: eight threads of bare spans
        # queued for one, and the convoy cost three times what the
        # spans themselves did (a CPU loop; the chip's GET cell, whose
        # threads are mostly outside the interpreter, did not feel it:
        # PERF.md section 6, PR 38). Each step is one call the
        # interpreter makes whole: a deque's and a list's append, a
        # dict's get. The lock is for what changes `_open`'s and
        # `_handed`'s keys.
        spans = self.spans
        if len(spans) == spans.maxlen:
            # (two threads at the very append that fills the ring may
            # count one eviction between them; from then on each counts)
            METRICS.counter("spans_evicted").inc()
        spans.append(s)
        if self._export_q is not None:
            self._export_q.append(s)
        if s.parent_id:
            held = self._open.get(s.trace_id)
            if held is None:
                with self._lock:
                    held = self._open.setdefault(s.trace_id, [])
                    if len(self._open) > MAX_OPEN_TRACES:
                        self._open.popitem(last=False)
            if len(held) < MAX_TRACE_SPANS:
                held.append(s)
            return
        with self._lock:
            held = self._open.pop(s.trace_id, [])
            handed = self._handed.pop(s.trace_id, ())
        # the root finished last: `held` is the whole local trace
        held.append(s)
        self.recorder.root_finished(s, held, handed)

    def record_span(self, name: str, *, child_of: str = "",
                    start: float, duration: float, span_id: str = "",
                    mono: Optional[float] = None, **tags) -> Span:
        """Record an already-measured interval as a finished span.

        Needed where the measuring thread is not the owning thread —
        e.g. the codec-service dispatcher closing out a submission's
        queue-wait on behalf of the submitting request — so a
        contextmanager span can't bracket the interval. `mono` is the
        interval's start on time.monotonic(); without it the interval
        is taken to have just ended."""
        if mono is None:
            mono = time.monotonic() - duration
        if "," in child_of:
            # a submission with several riders (`riders`): the same
            # interval in each rider's trace; the first is returned
            return [self.record_span(name, child_of=ctx, start=start,
                                     duration=duration, mono=mono, **tags)
                    for ctx in child_of.split(",")][0]
        if child_of:
            trace_id, parent_id = (child_of.split(":") + [""])[:2]
        else:
            cur = self.current()
            if cur is not None:
                trace_id, parent_id = cur.trace_id, cur.span_id
            else:
                trace_id, parent_id = self._new_id(), ""
        s = Span(trace_id, span_id or self._new_id(), parent_id, name,
                 start, duration, tags=dict(tags), mono=mono)
        self._finish(s)
        return s

    def event(self, name: str, **attrs) -> None:
        """Annotate the current span (no-op outside any span). Retry,
        breaker-skip, hedge and deadline decisions record as events so
        a slow trace shows *why* the path was taken."""
        s = self.current()
        if s is not None:
            s.events.append({"t": time.time(), "name": name, **attrs})

    @contextmanager
    def activate(self, ctx: str):
        """Re-establish a trace context on a worker thread. The span
        stack is thread-local, so pool workers (ec-writer, ec-read,
        hedge) must carry the submitter's context explicitly — the
        exact analog of resilience.activate for deadlines."""
        if not ctx:
            yield
            return
        tid, sid, *made = (ctx.split(":") + [""])[:4]
        if len(made) == 2 and made[1] != str(threading.get_ident()):
            # a context of `handoff()`, taken up by another thread
            self._book_handoff(tid, time.monotonic() - float(made[0]))
        prev = self.current()
        # context holder only — never finished, never recorded
        _local.span = Span(tid, sid, "", "<activated>", time.time())
        try:
            yield
        finally:
            _local.span = prev

    def inject(self) -> str:
        """Export the current context for the wire ("traceID" field analog);
        empty string when not tracing."""
        riders = getattr(_local, "riders", "")
        if riders:
            return riders
        s = self.current()
        return self.context(s) if s else ""

    def handoff(self) -> str:
        """The current context for a pool worker of THIS process: as
        `inject()`, and in a costed trace the moment it was made and by
        which thread. `activate` on another thread then books one
        hand-off against the trace: its count and how long the work
        waited to be taken up (a thread started or woken, the pool's
        queue, the worker's turn at the interpreter). Never sent over
        the wire."""
        ctx = self.inject()
        if "," in ctx or not costed(ctx.partition(":")[0]):
            return ctx
        return f"{ctx}:{time.monotonic():.6f}:{threading.get_ident()}"

    def _book_handoff(self, trace_id: str, waited: float) -> None:
        # the worker's pool, from its thread's name: `ec-read_3`
        pool = threading.current_thread().name.rstrip("0123456789") \
            .rstrip("-_")
        _HANDOFFS.inc()
        _HANDOFF_SECONDS.observe(waited, trace_id)
        with self._lock:
            booked = self._handed.get(trace_id)
            if booked is None:
                booked = self._handed[trace_id] = []
                if len(self._handed) > MAX_OPEN_TRACES:
                    self._handed.popitem(last=False)
            booked.append((pool, waited))

    def traces(self, trace_id: Optional[str] = None) -> list[Span]:
        out = list(self.spans)  # one call: no append comes between
        if trace_id:
            out = [s for s in out if s.trace_id == trace_id]
        return out


_annotation = None  # jax.profiler.TraceAnnotation, once JAX is loaded


def _profiler_annotation():
    """jax.profiler.TraceAnnotation where this process has imported
    JAX, else None: tracing itself never imports it."""
    global _annotation
    if _annotation is None:
        prof = getattr(sys.modules.get("jax"), "profiler", None)
        _annotation = getattr(prof, "TraceAnnotation", None)
    return _annotation


#: a loop that books its idle wait as a `Stage` waits at most this long
#: at a time, so that its `idle_seconds` advances while it waits: a
#: scrape, a benchmark window or a profiler session that opens mid-wait
#: is off by at most one tick
IDLE_TICK_S = 0.05


class Stage:
    """One leaf stage of a loop that owns its thread (the codec and
    the mesh dispatcher's idle / pack / launch / d2h): `with
    Stage(name, h):`.
    Its seconds on the monotonic clock go into the histogram `h`; while
    a profiler session is on, it is also an event `name` on this thread
    in the profiler's own trace, on the device trace's clock, so a
    device idle gap can be named after it. Outside a session the mirror
    costs one `is_enabled()` call. Stages never nest. Spans that
    enclose other work are not mirrored: one would cover every gap
    whole and name none."""

    __slots__ = ("name", "histogram", "_annotation", "_t0")

    def __init__(self, name: str, histogram):
        self.name = name
        self.histogram = histogram

    def __enter__(self) -> "Stage":
        annotation = _profiler_annotation()
        if annotation is not None and annotation.is_enabled():
            self._annotation = annotation(self.name)
            self._annotation.__enter__()
        else:
            self._annotation = None
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        seconds = time.monotonic() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        self.histogram.observe(seconds)


class ProcessSampler:
    """One thread a process (`proc-sampler`, started with the first
    `Tracer.instance()`): every IDLE_TICK_S it books one sample of what
    the PROCESS costs its host, kept for ten minutes:

      (time.monotonic(), the process's CPU seconds so far, the host's
       busy and total jiffies from the first line of /proc/stat, live
       threads, and how LATE the sampler itself was: how long after the
       tick it asked for it was running again, which is what a freshly
       woken thread of this process waits for its turn at the
       interpreter).

    Registry `tracing`: gauges `process_cpu_seconds` and `threads`,
    histogram `interpreter_wait_seconds`. While a profiler session is
    on, a lateness above one switch interval is also an event
    `interp:wait` on this thread in the profiler's trace; an annotation
    cannot be back-dated, so the event marks the END of the wait and its
    `late_us` says how far back it began."""

    KEEP_S = 600.0
    _started: Optional["ProcessSampler"] = None
    _start_lock = threading.Lock()

    def __init__(self):
        self.ring: deque[tuple] = deque(
            maxlen=int(self.KEEP_S / IDLE_TICK_S))
        self._lock = threading.Lock()
        #: (when, busy, total) of the last walk over /proc/<pid>/stat
        self._scanned = (float("-inf"), 0, 0)
        self.thread = threading.Thread(target=self._loop, daemon=True,
                                       name="proc-sampler")

    @classmethod
    def ensure_started(cls) -> "ProcessSampler":
        with cls._start_lock:
            if cls._started is None or not cls._started.thread.is_alive():
                cls._started = cls()
                cls._started.thread.start()
            return cls._started

    def _host_jiffies(self, now: float) -> tuple[int, int]:
        """(busy, total) clock ticks of all cores up to `now`: the first
        line of /proc/stat. A sandbox kernel (gVisor, the chip machines')
        shows that line as zeros and every process of the sandbox under
        /proc: there busy is the sum of their CPU, and total the cores'
        ticks; read once a second, a sample between two readings repeats
        the last. (0, 0) where there is no /proc."""
        try:
            with open("/proc/stat", "rb") as f:
                v = [int(x) for x in f.readline().split()[1:9]]
        except (OSError, ValueError):
            return 0, 0
        total = sum(v)
        if total:
            return total - v[3] - v[4], total  # idle, iowait
        if now - self._scanned[0] >= 1.0:
            busy = 0
            for pid in os.listdir("/proc"):
                if pid.isdigit():
                    try:
                        with open(f"/proc/{pid}/stat", "rb") as f:
                            # after the name: state is field 3, utime
                            # and stime fields 14 and 15
                            v = f.read().rpartition(b")")[2].split()
                        busy += int(v[11]) + int(v[12])
                    except (OSError, ValueError, IndexError):
                        continue  # it exited meanwhile
            ticks = os.sysconf("SC_CLK_TCK")
            self._scanned = (now, busy,
                             int(now * ticks) * (os.cpu_count() or 1))
        return self._scanned[1:]

    def _loop(self) -> None:
        cpu = METRICS.gauge("process_cpu_seconds")
        threads = METRICS.gauge("threads")
        late_h = METRICS.histogram("interpreter_wait_seconds",
                                   _metrics.log_buckets(1e-5, 10.0))
        due = time.monotonic()
        while True:
            due = max(due + IDLE_TICK_S, time.monotonic())
            time.sleep(max(0.0, due - time.monotonic()))
            now = time.monotonic()
            late = max(0.0, now - due)
            busy, total = self._host_jiffies(now)
            n = threading.active_count()
            used = time.process_time()
            with self._lock:
                self.ring.append((now, used, busy, total, n, late))
            cpu.set(used)
            threads.set(n)
            late_h.observe(late)
            # longer than the timer's slack: it waited for the interpreter
            if late > sys.getswitchinterval():
                annotation = _profiler_annotation()
                if annotation is not None and annotation.is_enabled():
                    with annotation("interp:wait",
                                    late_us=int(late * 1e6)):
                        pass


def samples(t0: float = float("-inf"),
            t1: float = float("inf")) -> list[tuple]:
    """The process sampler's samples taken in [t0, t1) on
    time.monotonic(), oldest first: (monotonic, process CPU seconds,
    host busy jiffies, host total jiffies, live threads, lateness
    seconds) each. A reader takes deltas of the first four between the
    window's first and last sample and means the last."""
    sampler = ProcessSampler._started
    if sampler is None:
        return []
    with sampler._lock:
        kept = list(sampler.ring)
    return [s for s in kept if t0 <= s[0] < t1]


def dispatcher_seconds(metrics, stages=("idle", "pack", "launch", "d2h",
                                        "complete")) -> dict:
    """Where a dispatcher's time went since start, from the stage
    histograms of its registry (`stages`: those of every thread it
    runs): a large idle share says it is starved, a large pack / launch
    / d2h share says which of its own stages paces the chip; `hold` is
    how long batches sat in flight beyond their own launch and pull."""
    took = {k: metrics.histogram(f"{k}_seconds").total for k in stages}
    took["hold"] = max(0.0, metrics.histogram("dispatch_seconds").total
                       - took["launch"] - took["d2h"])
    return took


def span_json(s: Span, service: str = "") -> dict:
    return {
        "traceId": s.trace_id,
        "spanId": s.span_id,
        "parentId": s.parent_id,
        "name": s.name,
        "start": s.start,
        "durationMs": round(s.duration * 1e3, 3),
        # what its thread spent (a span bracketed on one thread)
        **({"cpuMs": round(s.cpu * 1e3, 3), "blocks": s.blocks,
            "preempts": s.preempts} if s.thread else {}),
        **({"costOnly": True} if s.cost_only else {}),
        "tags": s.tags,
        **({"events": list(s.events)} if s.events else {}),
        **({"service": service} if service else {}),
    }


def critical_path(spans: list[dict]) -> list[dict]:
    """Reduce a trace to ordered (stage, micros) wall-clock attribution.

    Every instant of the root span's duration is attributed to exactly
    one span: a parent keeps the time no child covers, overlapping
    siblings are swept first-started-first so parallel hops (hedges,
    fan-out) never double-count. Output is aggregated by span name,
    ordered by first occurrence; the micros sum equals the root span's
    duration by construction. A `costOnly` span (`Tracer.cost_leaf`)
    is left out: its children are its parent's, its own time its
    parent's self, as before the leaf existed."""
    skipped = {s["spanId"]: s.get("parentId", "") for s in spans
               if "costOnly" in s and s.get("spanId")}
    if skipped:
        kept = []
        for s in spans:
            if s.get("spanId") in skipped:
                continue
            pid = s.get("parentId", "")
            while pid in skipped:
                pid = skipped[pid]
            kept.append(s if pid == s.get("parentId", "")
                        else {**s, "parentId": pid})
        spans = kept
    spans = [s for s in spans if s.get("spanId")]
    if not spans:
        return []
    ids = {s["spanId"] for s in spans}
    children: dict[str, list[dict]] = {}
    roots = []
    for s in spans:
        pid = s.get("parentId", "")
        if pid and pid in ids:
            children.setdefault(pid, []).append(s)
        else:
            roots.append(s)
    root = min(roots or spans, key=lambda s: s["start"])
    stages: dict[str, list] = {}  # name -> [seconds, first_start]

    def visit(s: dict, w0: float, w1: float) -> None:
        kids = sorted(children.get(s["spanId"], []),
                      key=lambda c: c["start"])
        cur = w0
        consumed = 0.0
        for c in kids:
            c0 = max(c["start"], cur)
            c1 = min(c["start"] + c.get("durationMs", 0.0) / 1e3, w1)
            if c1 <= c0:
                continue
            visit(c, c0, c1)
            consumed += c1 - c0
            cur = c1
        st = stages.setdefault(s["name"], [0.0, w0])
        st[0] += max(0.0, (w1 - w0) - consumed)
        st[1] = min(st[1], w0)

    visit(root, root["start"],
          root["start"] + root.get("durationMs", 0.0) / 1e3)
    return [
        {"stage": name, "micros": int(round(sec * 1e6))}
        for name, (sec, _first) in sorted(stages.items(),
                                          key=lambda kv: kv[1][1])
    ]


def _cost(spans: list[Span]) -> dict:
    """{name: [self wall us, self CPU us, blocks, preempts]}: see
    `FlightRecorder.operations`."""
    by_id = {s.span_id: s for s in spans}
    own = {s.span_id: [s.duration, s.cpu, s.blocks, s.preempts]
           for s in spans}
    for s in spans:
        parent = by_id.get(s.parent_id)
        if parent is not None and s.thread and parent.thread == s.thread:
            left = own[parent.span_id]
            left[0] -= s.duration
            left[1] -= s.cpu
            left[2] -= s.blocks
            left[3] -= s.preempts
    out: dict[str, list] = {}
    for s in spans:
        wall, cpu, blocks, preempts = own[s.span_id]
        c = out.setdefault(s.name, [0, 0, 0, 0])
        c[0] += int(round(wall * 1e6))
        c[1] += int(round(cpu * 1e6))
        c[2] += blocks
        c[3] += preempts
    return out


def _handoffs(handoffs) -> dict:
    pools: dict[str, list] = {}
    for pool, waited in handoffs:
        p = pools.setdefault(pool, [0, 0])
        p[0] += 1
        p[1] += int(round(waited * 1e6))
    return {"n": len(handoffs),
            "waitUs": sum(p[1] for p in pools.values()),
            "maxUs": int(round(max((w for _p, w in handoffs),
                                   default=0.0) * 1e6)),
            "pools": pools}


def _rpc(spans: list[Span]) -> dict:
    out: dict[str, list] = {}
    for s in spans:
        if s.name.startswith("client:/"):
            r = out.setdefault(s.name[len("client:"):], [0, 0, 0])
            r[0] += 1
            r[1] += int(round(s.duration * 1e6))
            r[2] += int(s.tags.get("server_us", 0))
    return out


class FlightRecorder:
    """Tail-based slow-trace retention: any trace whose ROOT span
    exceeds its per-op SLO is pinned — with its critical path — into a
    bounded ring, surviving the span buffer / collector LRU. The
    always-on flight recorder that answers "where did that P99 PUT
    spend its time" after the fact (tail sampling, not head sampling).

    Beside the slow traces it keeps, for EVERY finished operation root
    (Tracer.operation), slow or not, one compact stage record in a ring
    of its own that no child span and no parentless RPC span can evict:
    where the mean operation spends its time, not only the P99."""

    #: operation records kept; a 10 s benchmark window holds ~150
    MAX_OPERATIONS = 8192

    def __init__(self, max_traces: int = 0):
        from ozone_tpu.utils.config import env_int

        self.max_traces = max_traces or env_int(
            "OZONE_TPU_TRACE_SLOW_RING", 64)
        self._ring: "OrderedDict[str, dict]" = OrderedDict()
        self._ops: deque[dict] = deque(maxlen=self.MAX_OPERATIONS)
        self._lock = threading.Lock()

    @staticmethod
    def slo_s(op: str) -> float:
        """Per-op SLO threshold: OZONE_TPU_TRACE_SLO_<OP>_MS (op is the
        root span name, uppercased, non-alnum -> _), falling back to
        OZONE_TPU_TRACE_SLO_MS (default 1000 ms). Read live so
        operators can retune a running daemon's env between restarts
        and tests can tighten it per-case."""
        from ozone_tpu.utils.config import env_float

        default = env_float("OZONE_TPU_TRACE_SLO_MS", 1000.0)
        key = re.sub(r"[^A-Za-z0-9]+", "_", op).strip("_").upper()
        return env_float(f"OZONE_TPU_TRACE_SLO_{key}_MS", default) / 1e3

    def root_finished(self, root: Span, spans: list[Span],
                      handoffs=()) -> None:
        """A root span finished in this process; `spans` is its whole
        local trace, the root included, `handoffs` the (pool, seconds
        waited) of every hand-off booked against it."""
        if root.op:
            # on the monotonic clock: the stages then partition the
            # root's own duration, whatever the wall clock did meanwhile
            path = critical_path([
                {"spanId": s.span_id, "parentId": s.parent_id,
                 "name": s.name, "start": s.mono,
                 "durationMs": s.duration * 1e3,
                 **({"costOnly": True} if s.cost_only else {})}
                for s in spans])
            rec = {"root": root.name, "traceId": root.trace_id,
                   "end": root.mono + root.duration,
                   "durationUs": int(round(root.duration * 1e6)),
                   "stages": {st["stage"]: st["micros"] for st in path}}
            if costed(root.trace_id):
                rec.update(cost=_cost(spans), handoffs=_handoffs(handoffs),
                           rpc=_rpc(spans))
            with self._lock:
                self._ops.append(rec)
        self.offer(root, spans)

    def operations(self, root: str = "", t0: float = float("-inf"),
                   t1: float = float("inf")) -> list[dict]:
        """Stage records of the finished operations named `root` (all,
        if empty) whose END lies in [t0, t1) on time.monotonic(),
        oldest first: {root, traceId, end, durationUs,
        `stages`: {stage: critical-path micros}, which sum to durationUs
        (to the rounding of each stage), and in the record of a costed
        operation (`costed`) alone:
        `cost`: {span name: [self wall us, self CPU us, blocks,
        preempts]} over EVERY local span of the trace, on the critical
        path or off it, a span's self being what is left of it without
        its children on the SAME thread: the CPUs sum to the operation's
        CPU in this process as far as spans cover its threads, and for a
        leaf that only copies memory wall - CPU is time its thread was
        runnable and not running (the interpreter lock, or a core taken
        away: `preempts` says which);
        `handoffs`: {n, waitUs, maxUs, pools: {pool: [n, waitUs]}} of
        the work it handed to pool workers (`Tracer.handoff`);
        `rpc`: {"/service/method": [calls, client us, server us]} of
        its `client:/...` spans, server us as the daemon reported it
        (0 from one that reports none)}."""
        with self._lock:
            ops = list(self._ops)
        return [r for r in ops
                if (not root or r["root"] == root) and t0 <= r["end"] < t1]

    def stage_means(self, root: str = "") -> dict:
        """{root: {"n", "mean_ms", "stage_ms": {stage: mean ms per
        operation, largest first}}} over the records kept: where the
        MEAN operation spent its time (a freon summary's `op_stage_ms`)."""
        by_root: dict[str, list[dict]] = {}
        for r in self.operations(root):
            by_root.setdefault(r["root"], []).append(r)
        out = {}
        for name, ops in by_root.items():
            sums: dict[str, int] = {}
            for r in ops:
                for stage, us in r["stages"].items():
                    sums[stage] = sums.get(stage, 0) + us
            out[name] = {
                "n": len(ops),
                "mean_ms": round(sum(r["durationUs"] for r in ops)
                                 / len(ops) / 1e3, 3),
                "stage_ms": {st: round(us / len(ops) / 1e3, 3)
                             for st, us in sorted(sums.items(),
                                                  key=lambda kv: -kv[1])}}
        return out

    def offer(self, root, spans: list) -> bool:
        """Retain the trace if its root exceeded the op's SLO. `root`
        and `spans` may be Span objects or span_json dicts."""
        name, seconds = (
            (root.name, root.duration) if isinstance(root, Span)
            else (root["name"], root.get("durationMs", 0.0) / 1e3))
        if seconds < self.slo_s(name):
            return False  # before anything is copied or serialised
        rj = span_json(root) if isinstance(root, Span) else root
        sj = [span_json(s) if isinstance(s, Span) else s for s in spans]
        entry = {
            "traceId": rj["traceId"],
            "root": rj["name"],
            "start": rj["start"],
            "durationMs": rj["durationMs"],
            "sloMs": round(self.slo_s(rj["name"]) * 1e3, 3),
            "spans": sj,
            "criticalPath": critical_path(sj),
        }
        with self._lock:
            self._ring[rj["traceId"]] = entry
            while len(self._ring) > self.max_traces:
                self._ring.popitem(last=False)
        return True

    def append(self, trace_id: str, spans: list[dict]) -> None:
        """Late span arrivals for an already-pinned trace (collector
        assembly is cross-service and out of order)."""
        with self._lock:
            e = self._ring.get(trace_id)
            if e is None:
                return
            e["spans"].extend(spans)
            e["criticalPath"] = critical_path(e["spans"])

    def is_pinned(self, trace_id: str) -> bool:
        with self._lock:
            return trace_id in self._ring

    def slow(self, limit: int = 50) -> list[dict]:
        """Newest-first summaries of retained slow traces."""
        with self._lock:
            entries = list(self._ring.values())[-limit:]
        return [
            {k: e[k] for k in
             ("traceId", "root", "start", "durationMs", "sloMs")}
            | {"spans": len(e["spans"])}
            for e in reversed(entries)
        ]

    def trace(self, trace_id: str) -> Optional[dict]:
        with self._lock:
            e = self._ring.get(trace_id)
            return None if e is None else {
                **e, "spans": list(e["spans"]),
                "criticalPath": list(e["criticalPath"]),
            }


TRACING_SERVICE = "ozone.tpu.Tracing"


class SpanExporter:
    """Ship finished spans to a cluster collector (the reference sends
    every span to Jaeger via the jaeger-client sender — spans here ride
    the existing gRPC plane in batches). Lossy by design: the deque is
    bounded and a down collector just drops batches; tracing must never
    backpressure the datapath."""

    def __init__(self, tracer: Tracer, service: str, address: str = "",
                 tls=None, interval_s: float = 2.0,
                 max_batch: int = 512, collector=None):
        self.tracer = tracer
        self.service = service
        self.address = address
        self.tls = tls
        #: in-process collector: the metadata server feeds its own
        #: spans straight in, no loopback RPC
        self.collector = collector
        self.interval_s = interval_s
        self.max_batch = max_batch
        self.exported = 0
        self._q: deque[Span] = deque(maxlen=10_000)
        tracer._export_q = self._q
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._ch = None

    def start(self) -> None:
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=f"trace-export-{self.service}")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=2.0)
        self.flush()
        if self._ch is not None:
            self._ch.close()
            self._ch = None

    def flush(self) -> None:
        """Drain and ship everything pending (one batch per call chunk);
        errors drop the batch (collector down != datapath problem)."""
        from ozone_tpu.net import wire as _wire

        batch = []
        while self._q and len(batch) < self.max_batch:
            s = self._q.popleft()
            if TRACING_SERVICE in s.name:
                continue  # never trace the tracing plane itself
            batch.append(s)
        if not batch:
            return
        if self.collector is not None:
            self.collector.add(self.service,
                               [span_json(s) for s in batch])
            self.exported += len(batch)
            return
        try:
            if self._ch is None:
                from ozone_tpu.net.rpc import RpcChannel

                self._ch = RpcChannel(self.address, tls=self.tls,
                                      traced=False)
            self._ch.call(TRACING_SERVICE, "Report", _wire.pack({
                "service": self.service,
                "spans": [span_json(s) for s in batch],
            }))
            self.exported += len(batch)
        except Exception:
            # reconnect next round; spans already popped are dropped
            if self._ch is not None:
                try:
                    self._ch.close()
                except Exception:
                    pass
                self._ch = None

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            self.flush()


class TraceCollector:
    """Cluster-wide trace assembly (the Jaeger-collector role): every
    daemon's exporter reports finished spans here; queries see ONE
    trace stitched across services. Bounded LRU over trace ids."""

    def __init__(self, server=None, max_traces: int = 2000):
        self._traces: "OrderedDict[str, dict]" = OrderedDict()
        self.max_traces = max_traces
        self._lock = threading.Lock()
        #: cluster-side flight recorder: roots reported over the wire
        #: pin their whole assembled trace past the LRU
        self.recorder = FlightRecorder()
        if server is not None:
            server.add_service(TRACING_SERVICE, {
                "Report": self._report,
                "Query": self._query,
                "Recent": self._recent,
                "Slow": self._slow,
            })

    # ------------------------------------------------------------ ingest
    def add(self, service: str, spans: list[dict]) -> None:
        slow_roots = []
        late: dict[str, list[dict]] = {}
        with self._lock:
            for sp in spans:
                tid = sp.get("traceId", "")
                if not tid:
                    continue
                sp = dict(sp)
                sp.setdefault("service", service)
                t = self._traces.get(tid)
                if t is None:
                    t = self._traces[tid] = {
                        "spans": [], "services": set(),
                        "start": sp["start"], "end": 0.0,
                    }
                    while len(self._traces) > self.max_traces:
                        self._traces.popitem(last=False)
                t["spans"].append(sp)
                t["services"].add(sp.get("service") or service)
                t["start"] = min(t["start"], sp["start"])
                t["end"] = max(t["end"],
                               sp["start"] + sp["durationMs"] / 1e3)
                if not sp.get("parentId"):
                    slow_roots.append(sp)
                elif self.recorder.is_pinned(tid):
                    late.setdefault(tid, []).append(sp)
        # tail retention outside the assembly lock: offer() re-reads the
        # trace and evaluates the SLO, never blocking concurrent Reports
        for root in slow_roots:
            self.recorder.offer(root, self.trace(root["traceId"]))
        for tid, sps in late.items():
            self.recorder.append(tid, sps)

    def _report(self, req: bytes) -> bytes:
        from ozone_tpu.net import wire as _wire

        m, _ = _wire.unpack(req)
        self.add(m.get("service", ""), m.get("spans", []))
        return _wire.pack({"ok": True})

    # ------------------------------------------------------------- query
    def trace(self, trace_id: str) -> list[dict]:
        with self._lock:
            t = self._traces.get(trace_id)
            if t is not None:
                return sorted((dict(s) for s in t["spans"]),
                              key=lambda s: s["start"])
        # evicted from the LRU but pinned as slow: still answerable
        pinned = self.recorder.trace(trace_id)
        return (sorted(pinned["spans"], key=lambda s: s["start"])
                if pinned else [])

    def recent(self, limit: int = 50) -> list[dict]:
        with self._lock:
            # deep-enough copies: concurrent Report RPCs mutate the
            # per-trace spans list and services set under the lock
            items = [
                (tid, {"spans": list(t["spans"]),
                       "services": set(t["services"]),
                       "start": t["start"], "end": t["end"]})
                for tid, t in list(self._traces.items())[-limit:]
            ]
        out = []
        for tid, t in reversed(items):
            roots = [s["name"] for s in t["spans"]
                     if not s.get("parentId")]
            out.append({
                "traceId": tid,
                "spans": len(t["spans"]),
                "services": sorted(t["services"]),
                "root": roots[0] if roots else t["spans"][0]["name"],
                "start": t["start"],
                "durationMs": round((t["end"] - t["start"]) * 1e3, 3),
            })
        return out

    def _query(self, req: bytes) -> bytes:
        from ozone_tpu.net import wire as _wire

        m, _ = _wire.unpack(req)
        return _wire.pack({"spans": self.trace(m.get("trace_id", ""))})

    def _recent(self, req: bytes) -> bytes:
        from ozone_tpu.net import wire as _wire

        m, _ = _wire.unpack(req)
        return _wire.pack({"traces": self.recent(m.get("limit", 50))})

    def _slow(self, req: bytes) -> bytes:
        from ozone_tpu.net import wire as _wire

        m, _ = _wire.unpack(req)
        tid = m.get("trace_id", "")
        if tid:
            return _wire.pack({"trace": self.recorder.trace(tid)})
        return _wire.pack(
            {"traces": self.recorder.slow(m.get("limit", 50))})


def _current_trace_id() -> str:
    s = getattr(_local, "span", None)
    return s.trace_id if s is not None else ""


# Histogram exemplars stamp the active trace id (outlier observations
# link a scraped tail bucket to a retained slow trace); registered here
# so metrics stays import-independent of tracing.
_metrics.set_trace_id_provider(_current_trace_id)
