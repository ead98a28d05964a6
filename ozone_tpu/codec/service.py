"""Shared codec service: cross-request continuous batching for the chip.

At millions-of-users concurrency the real traffic shape is many small
concurrent PUTs/GETs, each far too small to fill a stripe batch, all
contending for the device. This module applies the
continuous-batching idea from LLM serving (Orca, OSDI '22) to the
GF(2^8) codec: a per-process, thread-safe `CodecService` owns the device
and takes stripe work (encode, decode/recover, re-encode) from ANY
concurrent operation. Same-shape stripes are packed WHERE THEY ARE
PRODUCED: `submit` reserves the next free rows of its lane's open
staging batch under the service lock (FIFO; a submission larger than the
free rows continues into the next batch) and then copies its rows into
them on the submitter's own thread, outside the lock — so concurrent
submitters copy in parallel, under the launch, device pass and D2H of
the batch before. Batches are constant-shape (padded tail, so the plan
caches in `codec/fused.py` keep serving ONE compiled program per shape —
no new XLA compiles). The ONE dispatcher thread never copies payload
bytes: it launches what is already packed, keeps one older dispatch
in flight under the next (a depth-1 double buffer), and completes
per-submitter futures as results land. The same consolidation argument
f4 (OSDI '14) makes for warm-blob IO, applied to device dispatches.

Staging buffers are recycled, never allocated per dispatch: page-aligned
leases of `codec/hostmem.py`'s pool, kept on the service's own small
free list per (shape, dtype) and handed back in `_complete`, once the
batch's outputs are host arrays (only then is the asynchronous H2D of
the launch certainly over). Pad rows are NOT zeroed: they hold whatever
an earlier batch left, and every output row of a pad row is dropped by
the per-rider slicing in `_complete`. A submission that alone covers a
whole batch width while its lane has no partly reserved batch open is
not copied at all: the dispatcher launches its own contiguous rows (the
bulk-sweep path). That choice reads only the submission's shape and the
lane's state.

Policy layer:

- **Deadline-aware flush**: a submitter's ambient `resilience.Deadline`
  nearing expiry forces a partial batch instead of waiting for fill, so
  a tight budget gets a padded dispatch, never DEADLINE_EXCEEDED spent
  queueing.
- **Max linger** (``OZONE_TPU_CODEC_LINGER_MS``): bounds the added
  latency for lone stripes — a submission that cannot fill its lane's
  batch width dispatches (padded) after at most the linger.
- **Weighted fair scheduling** (``OZONE_TPU_CODEC_QOS``): per-class
  service weights so a bulk lifecycle or reconstruction sweep cannot
  starve interactive reads; a starvation guard preempts fairness when a
  queue head has waited past ``OZONE_TPU_CODEC_STARVE_MS``.

Lanes: submissions coalesce per (semantic key, batch width, QoS class)
— the key carries the fused spec plus, for decode, the erasure pattern
(different recovery matrices cannot share one dispatch), and classes
stay in separate lanes so FIFO packing can never schedule interactive
stripes at a bulk submission's weight. Lanes are ephemeral: a
lane exists only while it has queued stripes, and binds the fused
callable (and the row shape) its first submitter brought — so backend choice (device vs
native twin) and test instrumentation stay with the submitting layer.

Consumers do not call this module to submit: `parallel/dispatch.py`
decides which queue a batch joins, this one or the mesh executor's.
What the two schedulers share is written here, the lower of the two:
the submission record (`_Sub`), the join of a split submission
(`_resolve_sub`, `_settle`, `_resolve_error`) and `wait_result`.
"""

from __future__ import annotations

import logging
import math
import os
import threading
import time
from collections import deque
from concurrent.futures import Future, InvalidStateError
from concurrent.futures import TimeoutError as _FutTimeout
from typing import Callable, Optional

import numpy as np

from ozone_tpu.codec import hostmem
from ozone_tpu.codec.pipeline import _start_d2h
from ozone_tpu.storage.ids import StorageError
from ozone_tpu.utils.config import env_float
from ozone_tpu.utils.metrics import MetricsRegistry, registry
from ozone_tpu.utils.tracing import (
    IDLE_TICK_S,
    Stage,
    Tracer,
    dispatcher_seconds,
)

log = logging.getLogger(__name__)

#: every service signal in ONE registry (prometheus: codec_service_*)
METRICS: MetricsRegistry = registry("codec.service")

#: default added-latency bound for a lone stripe waiting for co-batching
DEFAULT_LINGER_MS = 2.0
#: default starvation bound: a queue head older than this preempts the
#: weighted fair pick outright (and counts starvation_guard_trips)
DEFAULT_STARVE_MS = 250.0
#: default per-class QoS weights (OZONE_TPU_CODEC_QOS overrides, e.g.
#: "interactive=4,bulk=1"): interactive reads outweigh background sweeps
DEFAULT_QOS = {"interactive": 4.0, "bulk": 1.0}
#: seed for the dispatch-time EWMA before the first dispatch lands
_DISPATCH_EWMA_SEED_S = 0.005
#: staging buffers kept per (shape, dtype): the one being filled, the two
#: the depth-1 double buffer can have in flight (one just launched, one
#: being completed) and one spare. A burst beyond it leases from the
#: shared pool and gives back to it.
_STAGING_KEEP = 4


def qos_weights() -> dict[str, float]:
    """Parse OZONE_TPU_CODEC_QOS ("cls=weight,cls=weight"); unknown
    classes default to weight 1."""
    out = dict(DEFAULT_QOS)
    raw = os.environ.get("OZONE_TPU_CODEC_QOS", "")
    for part in raw.split(","):
        if "=" not in part:
            continue
        cls, _, w = part.partition("=")
        try:
            out[cls.strip()] = max(1e-6, float(w))
        except ValueError:  # ozlint: allow[error-swallowing] -- malformed OZONE_TPU_CODEC_QOS entry: skip it, defaults cover the class
            continue
    return out


def _ambient_deadline():
    """The submitter's operation deadline, if any: the one place where
    the schedulers read the client layer's context (lazy import: codec
    must stay importable without it)."""
    from ozone_tpu.client import resilience

    return resilience.current()


class _Sub:
    """One submission: `n` same-shape stripes from one operation, in
    either scheduler's lanes."""

    __slots__ = ("stripes", "n", "future", "cls", "deadline", "t_enq",
                 "t_enq_wall", "t_ready", "trace_ctx", "tail", "taken",
                 "pending_parts", "parts")

    def __init__(self, stripes: np.ndarray, future: Future, cls: str,
                 deadline, tail: bool):
        #: kept until the rows have launched: the service's borrowed
        #: batch is a view of it, and the mesh executor packs from it
        self.stripes = stripes
        self.n = int(stripes.shape[0])
        self.future = future
        self.cls = cls
        self.deadline = deadline
        self.t_enq = time.monotonic()
        self.t_enq_wall = time.time()
        #: when its rows were all in place (monotonic): the start of its
        #: queue wait in the service, `inf` while the submitter is still
        #: copying. The mesh executor packs on its dispatcher and counts
        #: from `t_enq`
        self.t_ready = self.t_enq
        #: submitter's trace context: the dispatcher runs on its own
        #: thread, so per-submission spans must join the operation's
        #: trace explicitly, not via the thread-local span stack
        self.trace_ctx = Tracer.instance().inject()
        self.tail = tail
        self.taken = 0          # stripes already taken for a launch
        self.pending_parts = 0  # dispatched parts not yet completed
        self.parts: list[tuple] = []  # (offset, take, host outs tuple)

    def deadline_t(self) -> float:
        return self.deadline.t_end if self.deadline is not None else math.inf


class _Batch:
    """One constant-shape dispatch being assembled. `buf` is either a
    recycled staging buffer ([width, ...]) whose reserved rows the
    submitters fill, or (`borrowed`) one submission's own contiguous
    `width` rows, launched as they are."""

    __slots__ = ("buf", "borrowed", "entries", "rows", "unfilled")

    def __init__(self, buf: np.ndarray, borrowed: bool):
        self.buf = buf
        self.borrowed = borrowed
        #: the riders, (sub, offset in sub, take, first row in buf)
        self.entries: list[tuple[_Sub, int, int, int]] = []
        self.rows = 0       # rows reserved (a failed fill's pads included)
        self.unfilled = 0   # reserved parts whose copy has not landed


class _Lane:
    """One coalescing lane: same semantic key, same stripe shape, same
    batch width, same QoS class (classes get separate lanes so a bulk
    submission queued ahead of an interactive one in FIFO order can
    never drag it down to bulk scheduling weight). FIFO of the batches
    being filled, and of the submissions with unlaunched stripes in
    them."""

    __slots__ = ("lane_key", "fn", "width", "cls", "row_shape", "dtype",
                 "subs", "batches", "queued", "min_deadline_t",
                 "last_served")

    def __init__(self, lane_key: tuple, fn: Callable, width: int,
                 cls: str, row_shape: tuple, dtype: np.dtype):
        self.lane_key = lane_key
        self.fn = fn
        self.width = max(1, int(width))
        self.cls = cls
        self.row_shape = row_shape
        self.dtype = dtype
        #: only the last can have free rows
        self.batches: deque[_Batch] = deque()  # ozlint: allow[bounded-queue] -- holds the reserved rows of lane.subs, which the scheduler's queue_depth gauge governs (see below)
        self.subs: deque[_Sub] = deque()  # ozlint: allow[bounded-queue] -- lane depth is governed by the weighted-fair scheduler's queue_depth gauge, which the admission SLO shedder watches; bounding here would drop accepted work
        self.queued = 0  # reserved, unlaunched rows across batches
        self.min_deadline_t = math.inf
        self.last_served = 0.0  # 0 = never dispatched from


class CodecService:
    """The per-process dispatcher owning fused device dispatches.

    `submit(key, fn, stripes, ...)` enqueues `[n, ...]` stripe work and
    returns a Future resolving to the tuple of host arrays `fn` produces
    for exactly those `n` stripes (outputs are sliced out of the fused
    batch along axis 0). Submissions sharing (key, width) coalesce into
    one dispatch; every batch is padded to the lane width so each lane
    runs ONE compiled program. `submit` returns once the caller's rows
    are in their batch (reserve under the lock, fill outside it, commit
    under it): it takes as long as copying those rows.
    """

    def __init__(self):
        self.linger_s = env_float("OZONE_TPU_CODEC_LINGER_MS",
                                  DEFAULT_LINGER_MS) / 1000.0
        self.starve_s = env_float("OZONE_TPU_CODEC_STARVE_MS",
                                  DEFAULT_STARVE_MS) / 1000.0
        self.weights = qos_weights()
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._lanes: dict[tuple, _Lane] = {}
        self._vtime: dict[str, float] = {}
        #: system virtual clock (SFQ-style): advances with the least
        #: virtual time among backlogged classes; a class returning
        #: from idle is floored to it on activation, so neither a
        #: stale LOW vtime (idle bulk monopolizing on return) nor a
        #: stale HIGH one (interactive penalized for past service)
        #: survives an idle period
        self._vclock = 0.0
        self._queued_cls: dict[str, int] = {}  # class -> queued subs
        #: recycled staging buffers, (shape, dtype.str) -> free list
        self._staging: dict[tuple, list[np.ndarray]] = {}
        self._inflight: deque[tuple] = deque()  # ozlint: allow[bounded-queue] -- holds only dispatched-to-device batches; depth is bounded by the double-buffer dispatch loop (at most prefetch_depth entries)
        self._dispatch_ewma_s = _DISPATCH_EWMA_SEED_S
        self._running = True
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="codec-service")
        self._thread.start()

    # ----------------------------------------------------------- submit
    def submit(self, key: tuple, fn: Callable, stripes: np.ndarray,
               *, width: int, qos: str = "interactive",
               tail: bool = False, deadline=None) -> Future:
        """Enqueue `stripes` ([n, ...] with n >= 1) for the fused `fn`.

        `key` is the hashable coalescing identity (kind + spec + pattern);
        `width` the constant dispatch batch size this submitter's shape
        family compiles at (a lane is keyed by both, so mismatched
        widths never pad against each other). `fn` is bound to the lane
        by its FIRST submitter and dropped when the lane drains.
        `tail=True` marks a partial final flush: it rides the linger
        path (waiting up to the linger to co-batch with other
        operations) and is counted in the tail_flushes metric when it
        dispatches, whether it ended up co-batched or padded.
        The ambient resilience deadline is captured when none is given.
        """
        if stripes.shape[0] < 1:
            raise ValueError("empty codec submission")
        if deadline is None:
            deadline = _ambient_deadline()
        fut: Future = Future()
        sub = _Sub(stripes, fut, qos, deadline, tail)
        lane_key = (key, width, qos)
        with self._cond:
            if not self._running:
                raise RuntimeError("codec service is shut down")
            lane = self._lanes.get(lane_key)
            if lane is None:
                lane = _Lane(lane_key, fn, width, qos,
                             tuple(stripes.shape[1:]), stripes.dtype)
            elif (lane.row_shape, lane.dtype) != (stripes.shape[1:],
                                                  stripes.dtype):
                raise ValueError(
                    f"codec submission of {stripes.dtype}"
                    f"{list(stripes.shape[1:])} stripes into a lane of "
                    f"{lane.dtype}{list(lane.row_shape)}: {lane_key!r}")
            self._lanes[lane_key] = lane
            if not self._queued_cls.get(qos):
                # WFQ activation floor: a class becoming backlogged
                # joins at the system virtual clock
                self._vtime[qos] = max(self._vtime.get(qos, 0.0),
                                       self._vclock)
            self._queued_cls[qos] = self._queued_cls.get(qos, 0) + 1
            lane.subs.append(sub)
            lane.min_deadline_t = min(lane.min_deadline_t,
                                      sub.deadline_t())
            fills = self._reserve_locked(lane, sub)
            METRICS.counter("submissions").inc()
            METRICS.gauge("queue_depth").set(self._queue_depth_locked())
            self._cond.notify()
        if fills:
            self._fill(lane, sub, fills)
        return fut

    # ---------------------------------------------- reserve, fill, commit
    def _reserve_locked(self, lane: _Lane, sub: _Sub) -> list[tuple]:
        """Give `sub` the lane's next free rows, FIFO: the rest of the
        open staging batch first, then new batches (the cross-request
        coalescing step). Returns the parts the submitter has to copy,
        (batch, entry). A stretch of `sub` that alone covers a whole
        width, with no partly reserved batch open, is not staged: the
        batch borrows those rows as they lie in `sub.stripes`."""
        fills: list[tuple] = []
        off = 0
        try:
            while off < sub.n:
                batch = lane.batches[-1] if lane.batches else None
                if batch is None or batch.rows == lane.width:
                    own = sub.stripes[off:off + lane.width]
                    if len(own) == lane.width and own.flags.c_contiguous:
                        batch = _Batch(own, borrowed=True)
                    else:
                        batch = _Batch(self._lease_staging_locked(lane),
                                       borrowed=False)
                    lane.batches.append(batch)
                take = min(sub.n - off, lane.width - batch.rows)
                entry = (sub, off, take, batch.rows)
                batch.entries.append(entry)
                batch.rows += take
                lane.queued += take
                if not batch.borrowed:
                    batch.unfilled += 1
                    fills.append((batch, entry))
                off += take
        except BaseException:  # a staging lease that cannot be had
            sub.taken += sub.n - off  # never reserved
            self._abandon_locked(lane, sub, fills)
            raise
        if fills:
            sub.t_ready = math.inf
        return fills

    def _fill(self, lane: _Lane, sub: _Sub, fills: list[tuple]) -> None:
        """The staging copy, on the submitter's own thread and outside
        the lock: one slice assignment per part (numpy drops the GIL for
        the bytes), each committed as it lands so its batch can launch.
        A submitter that fails here fails alone: its rows become pads."""
        t0, t0_wall = time.monotonic(), time.time()
        landed = 0
        try:
            with Stage("codec:submit_pack",
                       METRICS.histogram("submit_pack_seconds")):
                for batch, (_, off, take, row) in fills:
                    rows = sub.stripes[off:off + take]
                    batch.buf[row:row + take] = rows
                    hostmem.count_copy(rows.nbytes, warn=False,
                                       site="codec_service.submit")
                    with self._cond:
                        batch.unfilled -= 1
                        landed += 1
                        if landed == len(fills):
                            sub.t_ready = time.monotonic()
                        self._cond.notify()
        except BaseException as e:
            with self._cond:
                self._abandon_locked(lane, sub, fills[landed:])
                self._cond.notify()
            _settle(sub.future, error=e)
            raise
        if sub.trace_ctx:
            Tracer.instance().record_span(
                "codec:submit_pack", child_of=sub.trace_ctx,
                start=t0_wall, duration=sub.t_ready - t0, mono=t0,
                lane=str(lane.lane_key)[:120], qos=sub.cls, stripes=sub.n)

    def _abandon_locked(self, lane: _Lane, sub: _Sub,
                        unlanded: list[tuple]) -> None:
        """`sub`'s submitter failed mid-fill. The rows it did not fill
        stay reserved, as pad rows, and count as taken; parts that had
        landed ride on unanswered. The batches still launch for their
        other riders (one left with none is handed back in `_dispatch`)."""
        for batch, entry in unlanded:
            batch.entries.remove(entry)
            batch.unfilled -= 1
            if batch in lane.batches:
                # (a batch already taken for its launch counted them)
                sub.taken += entry[2]
        if sub.taken == sub.n and sub in lane.subs:
            self._retire_locked(lane, sub)
            self._settle_lane_locked(lane)

    def _lease_staging_locked(self, lane: _Lane) -> np.ndarray:
        shape = (lane.width,) + lane.row_shape
        METRICS.counter("staging_buffers_leased").inc()
        free = self._staging.get((shape, lane.dtype.str))
        if free:
            METRICS.counter("staging_buffers_reused").inc()
            return free.pop()
        # the array pins its lease: the pages go back to the pool when
        # the service drops the buffer
        flat, _fresh = hostmem.pool().lease_array(
            lane.dtype.itemsize * math.prod(shape))
        return flat.view(lane.dtype).reshape(shape)

    def _give_staging_locked(self, batch: _Batch) -> None:
        if batch.borrowed:
            return
        free = self._staging.setdefault(
            (batch.buf.shape, batch.buf.dtype.str), [])
        if len(free) < _STAGING_KEEP:
            free.append(batch.buf)

    def _retire_locked(self, lane: _Lane, sub: _Sub) -> None:
        """`sub` has no unlaunched stripes left in the lane."""
        if lane.subs[0] is sub:
            lane.subs.popleft()
        else:
            lane.subs.remove(sub)
        self._class_left_locked(sub.cls)

    def _class_left_locked(self, cls: str) -> None:
        left = self._queued_cls.get(cls, 1) - 1
        if left > 0:
            self._queued_cls[cls] = left
        else:
            self._queued_cls.pop(cls, None)

    def _settle_lane_locked(self, lane: _Lane) -> None:
        if lane.subs:
            lane.min_deadline_t = min(s.deadline_t() for s in lane.subs)
            return
        # ephemeral lanes: drop the fn binding once drained
        if self._lanes.get(lane.lane_key) is lane:
            del self._lanes[lane.lane_key]
        lane.min_deadline_t = math.inf

    # ------------------------------------------------------- scheduling
    def _queue_depth_locked(self) -> int:
        return sum(lane.queued for lane in self._lanes.values())

    def _flush_margin_s(self) -> float:
        """How far before a deadline a partial batch must flush: the
        linger plus headroom for the in-flight depth's dispatch time."""
        return self.linger_s + 4.0 * self._dispatch_ewma_s

    def _ready_reason(self, lane: _Lane, now: float) -> Optional[str]:
        if not lane.subs:
            return None
        if lane.queued >= lane.width:
            return "full"
        if lane.min_deadline_t - now <= self._flush_margin_s():
            return "deadline"
        if now - lane.subs[0].t_enq >= self.linger_s:
            return "linger"
        return None

    def _pick_lane_locked(self, now: float):
        """Choose the next lane to dispatch: the ready lane whose head
        class has the least weighted service (classic weighted-fair
        virtual time) — unless a starved lane preempts it. Among
        starved lanes the LEAST-RECENTLY-SERVED wins, not the oldest
        head: when a deep bulk backlog keeps its own head perpetually
        over-aged, oldest-first would hand the guard straight back to
        the backlog and starve everyone else anyway."""
        ready: list[tuple[_Lane, str]] = []
        for lane in self._lanes.values():
            reason = self._ready_reason(lane, now)
            if reason is not None:
                ready.append((lane, reason))
        if not ready:
            return None
        # advance the system virtual clock to the least backlogged
        # class's virtual time (it never goes backwards)
        self._vclock = max(self._vclock, min(
            self._vtime.get(lane.subs[0].cls, 0.0) for lane, _ in ready))

        def vkey(lr):
            lane, _ = lr
            cls = lane.subs[0].cls
            return (self._vtime.get(cls, 0.0), lane.subs[0].t_enq)

        fair = min(ready, key=vkey)
        starved = [(lane, r) for lane, r in ready
                   if now - lane.subs[0].t_enq >= self.starve_s]
        if starved:
            lane, reason = min(
                starved,
                key=lambda lr: (lr[0].last_served,
                                lr[0].subs[0].t_enq))
            if lane is not fair[0]:
                # the guard overrode the weighted-fair choice
                METRICS.counter("starvation_guard_trips").inc()
            return lane, reason
        return fair

    def _next_wakeup_locked(self, now: float) -> Optional[float]:
        """Seconds until the earliest linger/deadline trigger."""
        t = math.inf
        margin = self._flush_margin_s()
        for lane in self._lanes.values():
            if not lane.subs:
                continue
            t = min(t, lane.subs[0].t_enq + self.linger_s,
                    lane.min_deadline_t - margin)
        return None if math.isinf(t) else max(0.0, t - now)

    def _take_locked(self, lane: _Lane) -> _Batch:
        """Take the lane's oldest batch for the launch. Its rows were
        reserved at submit; copies into them may still be landing."""
        lane.last_served = time.monotonic()
        batch = lane.batches.popleft()
        lane.queued -= batch.rows
        for sub, _off, take, _row in batch.entries:
            sub.taken += take
            sub.pending_parts += 1
            if sub.taken == sub.n:
                self._retire_locked(lane, sub)
        self._settle_lane_locked(lane)
        return batch

    # ------------------------------------------------------- dispatcher
    def _loop(self) -> None:
        try:
            while True:
                batch = None
                with self._cond:
                    now = time.monotonic()
                    picked = self._pick_lane_locked(now)
                    if picked is not None:
                        lane, reason = picked
                        batch = self._take_locked(lane)
                    elif not self._inflight:
                        if not self._running:
                            if not self._lanes:
                                break
                            # closing with queued-but-untriggered work:
                            # flush it rather than strand the futures
                            lane = next(iter(self._lanes.values()))
                            reason = "linger"
                            batch = self._take_locked(lane)
                        else:
                            # starved: no lane ready (nothing queued, or
                            # a partial batch lingering), nothing in
                            # flight to complete
                            wake = self._next_wakeup_locked(now)
                            with Stage("codec:idle",
                                       METRICS.histogram("idle_seconds")):
                                self._cond.wait(
                                    IDLE_TICK_S if wake is None
                                    else min(wake, IDLE_TICK_S))
                            continue
                if batch is not None:
                    self._dispatch(lane, batch, reason)
                    # depth-1 double buffer: keep ONE older batch in
                    # flight; complete it only once the next dispatch
                    # is on the device (the _flush_queue overlap)
                    if len(self._inflight) > 1:
                        self._complete(self._inflight.popleft())
                elif self._inflight:
                    # nothing to launch right now: never hold results
                    # hostage waiting for more work
                    self._complete(self._inflight.popleft())
        except BaseException:  # noqa: BLE001 - dispatcher must not die silently
            log.exception("codec service dispatcher crashed")
            raise
        finally:
            # a dead dispatcher must read as NOT RUNNING: submit()
            # rejects instead of queueing into a drain nobody runs, and
            # get_service() hands out a fresh service
            with self._lock:
                self._running = False
            self._fail_pending(RuntimeError("codec service stopped"))

    def _dispatch(self, lane: _Lane, batch: _Batch, reason: str) -> None:
        # the host work before the launch: waiting out a copy that is
        # still landing, fairness accounting, closing out the riders'
        # queue waits. No payload byte is touched on this thread.
        with Stage("codec:pack", METRICS.histogram("pack_seconds")):
            with self._cond:
                while batch.unfilled:
                    # every part is committed or abandoned under this
                    # lock, with a notify: no wakeup can be missed
                    self._cond.wait()
                entries = batch.entries
                # fairness accounting under the lock: submit()'s SFQ
                # activation floor does a read-modify-write of the same
                # vtime entries from other threads
                for sub, off, take, _row in entries:
                    w = self.weights.get(sub.cls, 1.0)
                    self._vtime[sub.cls] = \
                        self._vtime.get(sub.cls, 0.0) + take / w
                if not entries:
                    # every rider's submitter failed mid-fill
                    self._give_staging_locked(batch)
                    return
            now = time.monotonic()
            ops = len(entries)
            rows = sum(take for _sub, _off, take, _row in entries)
            tracer = Tracer.instance()
            # one shared dispatch span id per device dispatch: every
            # coalesced submission's span tags it, making cross-request
            # batching visible from any participating trace
            d_tid, d_sid = tracer._new_id(), tracer._new_id()
            fill_pct = round(100.0 * rows / lane.width, 1)
            lane_desc = str(lane.lane_key)[:120]
            for sub, off, take, _row in entries:
                if off == 0:
                    # from its rows being in place to its first launch;
                    # nothing where a later part of it is still landing
                    wait = max(0.0, now - sub.t_ready)
                    tid = sub.trace_ctx.split(":", 1)[0]
                    METRICS.histogram("queue_wait_seconds").observe(
                        wait, tid)
                    METRICS.histogram(
                        f"queue_wait_{sub.cls}_seconds").observe(wait, tid)
                    if sub.trace_ctx:
                        tracer.record_span(
                            "codec:queue_wait", child_of=sub.trace_ctx,
                            start=time.time() - wait, duration=wait,
                            mono=now - wait, lane=lane_desc, qos=sub.cls,
                            fill_pct=fill_pct, dispatch_span=d_sid)
                    if sub.tail:
                        METRICS.counter("tail_flushes").inc()
        t0 = time.monotonic()
        t0_wall = time.time()
        try:
            # the implicit H2D and the enqueue
            with Stage("codec:launch",
                       METRICS.histogram("launch_seconds")):
                outs = lane.fn(batch.buf)
                if not isinstance(outs, tuple):
                    outs = (outs,)
                for a in outs:
                    # eager D2H under the next batch's host work
                    _start_d2h(a)
        except BaseException as e:  # noqa: BLE001 - per-dispatch fault
            _resolve_error(entries, e)
            with self._lock:
                self._give_staging_locked(batch)
            return
        METRICS.counter("dispatches").inc()
        METRICS.counter("stripes_dispatched").inc(rows)
        METRICS.counter("stripes_borrowed" if batch.borrowed
                        else "stripes_packed_at_submit").inc(rows)
        METRICS.counter("slots_dispatched").inc(lane.width)
        key = lane.lane_key[0]
        if isinstance(key, tuple) and len(key) == 4 and key[0] == "decode":
            # useful work of a decode dispatch (`decode_key`): cells
            # read, at the width the plan really read (k for RS, a
            # group's survivors for an LRC local repair), and rebuilt
            METRICS.counter("decode_survivor_cells").inc(rows * len(key[2]))
            METRICS.counter("decode_recovered_cells").inc(
                rows * len(key[3]))
        METRICS.counter("coalesced_operations").inc(ops)
        if ops > 1:
            METRICS.counter("multi_op_dispatches").inc()
        if reason == "linger":
            METRICS.counter("forced_flushes").inc()
        elif reason == "deadline":
            METRICS.counter("deadline_flushes").inc()
        METRICS.gauge("batch_fill_pct").set(100.0 * rows / lane.width)
        METRICS.gauge("last_coalesced_operations").set(ops)
        with self._lock:
            METRICS.gauge("queue_depth").set(self._queue_depth_locked())
        self._inflight.append((entries, outs, t0, t0_wall,
                               (d_tid, d_sid, fill_pct, reason,
                                lane_desc, ops, rows, lane.width), batch))

    def _complete(self, rec: tuple) -> None:
        entries, outs, t0, t0_wall, dctx, batch = rec
        try:
            with Stage("codec:d2h", METRICS.histogram("d2h_seconds")):
                host = tuple(np.asarray(a) for a in outs)
        except BaseException as e:  # noqa: BLE001 - D2H fault
            _resolve_error(entries, e)
            host = None
        # everything after the pull, until the last rider's future is
        # resolved: the staging buffer back, the riders' dispatch spans,
        # their slices, the joins of split submissions
        with Stage("codec:complete", METRICS.histogram("complete_seconds")):
            self._hand_back(rec, host)

    def _hand_back(self, rec: tuple, host: Optional[tuple]) -> None:
        entries, _outs, t0, t0_wall, dctx, batch = rec
        d_tid, d_sid, fill_pct, reason, lane_desc, ops, rows, width = dctx
        # the outputs are host arrays (or lost): the launch's asynchronous
        # H2D is over and the staging buffer can be refilled. Not one
        # whose memory an output still shows (a `fn` that hands its input
        # back): that one is dropped, not recycled
        if not batch.borrowed and not any(
                np.may_share_memory(a, batch.buf) for a in host or ()):
            with self._lock:
                self._give_staging_locked(batch)
        if host is None:
            return
        dt = time.monotonic() - t0
        self._dispatch_ewma_s += 0.2 * (dt - self._dispatch_ewma_s)
        METRICS.histogram("dispatch_seconds").observe(
            dt, entries[0][0].trace_ctx.split(":", 1)[0])
        tracer = Tracer.instance()
        # the shared dispatch span (own trace, id known to every rider)
        tracer.record_span(
            "codec:device_dispatch", child_of=f"{d_tid}:",
            span_id=d_sid, start=t0_wall, duration=dt, mono=t0,
            lane=lane_desc, ops=ops, rows=rows, width=width,
            fill_pct=fill_pct, reason=reason)
        for sub, off, take, _row in entries:
            # per-submission dispatch span in the *submitter's* trace,
            # carrying the shared span id: two concurrent operations
            # coalesced into one device batch both show dispatch_span=d_sid
            if sub.trace_ctx:
                tracer.record_span(
                    "codec:dispatch", child_of=sub.trace_ctx,
                    start=t0_wall, duration=dt, mono=t0,
                    lane=lane_desc, qos=sub.cls, stripes=take,
                    fill_pct=fill_pct, dispatch_span=d_sid,
                    dispatch_trace=d_tid)
        for sub, off, take, row in entries:
            sub.parts.append(
                (off, take, tuple(a[row:row + take] for a in host)))
            sub.pending_parts -= 1
            if sub.taken == sub.n and sub.pending_parts == 0:
                _resolve_sub(sub)

    def _fail_pending(self, e: BaseException) -> None:
        with self._lock:
            subs = [s for lane in self._lanes.values() for s in lane.subs]
            self._lanes.clear()
            self._queued_cls.clear()
            self._staging.clear()
            inflight, self._inflight = list(self._inflight), deque()  # ozlint: allow[bounded-queue] -- drain/reset of the bounded in-flight deque above, not a new queue
        for rec in inflight:
            for sub, _o, _t, _r in rec[0]:
                subs.append(sub)
        for s in subs:
            _settle(s.future, error=e)

    # ---------------------------------------------------------- control
    def stats(self) -> dict:
        """Operator snapshot (the Recon /api/codec payload)."""
        snap = METRICS.snapshot()
        slots = snap.get("slots_dispatched", 0)
        disp = snap.get("dispatches", 0)
        snap["fill_ratio"] = (snap.get("stripes_dispatched", 0) / slots
                              if slots else 0.0)
        snap["ops_per_dispatch"] = (
            snap.get("coalesced_operations", 0) / disp if disp else 0.0)
        # where the ONE dispatcher thread's time went since start
        snap["dispatcher_seconds"] = dispatcher_seconds(METRICS)
        with self._lock:
            snap["queue_depth"] = self._queue_depth_locked()
            snap["lanes"] = len(self._lanes)
            snap["inflight"] = len(self._inflight)
        snap["linger_ms"] = self.linger_s * 1000.0
        snap["weights"] = dict(self.weights)
        return snap

    def close(self) -> None:
        with self._cond:
            self._running = False
            self._cond.notify_all()
        self._thread.join(timeout=self._flush_margin_s() * 64)
        self._fail_pending(RuntimeError("codec service shut down"))


_service: Optional[CodecService] = None
_service_lock = threading.Lock()


def get_service() -> CodecService:
    """The process-wide service (created on first use)."""
    global _service
    with _service_lock:
        if _service is None or not _service._running:
            _service = CodecService()
        return _service


def reset_for_tests() -> None:
    """Shut down and drop the singleton (fresh knobs per test)."""
    global _service
    with _service_lock:
        svc, _service = _service, None
    if svc is not None:
        svc.close()


# ------------------------------------------------------------- plan keys
def encode_key(spec) -> tuple:
    return ("encode", spec)


def decode_key(spec, valid, erased) -> tuple:
    return ("decode", spec, tuple(valid), tuple(erased))


def reencode_key(spec, lost: int) -> tuple:
    return ("reencode", spec, int(lost))


def wait_result(fut: Future, grace_s: Optional[float] = None):
    """Block on a codec future with deadline-aware patience: the wait
    allows the remaining operation budget PLUS the service's flush
    margin — a near-expiry submission is being force-flushed, so the
    right behavior is to collect that partial-batch result, not to
    declare DEADLINE_EXCEEDED while it is already on the device."""
    d = _ambient_deadline()
    if d is None:
        return fut.result()
    if grace_s is None:
        svc = _service
        grace_s = (svc._flush_margin_s() if svc is not None else 0.0) \
            + 16.0 * _DISPATCH_EWMA_SEED_S
    left = d.remaining()
    try:
        return fut.result(timeout=max(0.0, left) + grace_s)
    except _FutTimeout:
        METRICS.counter("wait_deadline_exceeded").inc()
        raise StorageError(
            "DEADLINE_EXCEEDED",
            f"operation {d.op} deadline exceeded waiting for the codec "
            f"service") from None


# ------------------------------------------- shared by both schedulers
def _settle(future: Future, result=None, error=None) -> None:
    """Resolve `future` unless another thread (or a shutdown) already
    has: a future is settled exactly once."""
    try:
        if error is not None:
            future.set_exception(error)
        else:
            future.set_result(result)
    except InvalidStateError:  # ozlint: allow[error-swallowing] -- settled already by the other thread or a shutdown: exactly once is the contract
        pass


def _resolve_sub(sub: _Sub) -> None:
    """All parts of `sub` are host arrays: join them in offset order."""
    if sub.future.done():
        # an earlier part of this (split) submission already failed
        # the future; later parts complete harmlessly
        return
    if len(sub.parts) == 1:
        _settle(sub.future, sub.parts[0][2])
        return
    sub.parts.sort(key=lambda p: p[0])
    outs = tuple(
        np.concatenate([p[2][i] for p in sub.parts], axis=0)
        for i in range(len(sub.parts[0][2])))
    _settle(sub.future, outs)


def _resolve_error(entries, e: BaseException) -> None:
    """Fail every rider of a batch, (sub, offset, take, row) each."""
    for sub in {id(en[0]): en[0] for en in entries}.values():
        _settle(sub.future, error=e)
