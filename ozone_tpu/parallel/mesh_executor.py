"""Persistent mesh executor: the multi-chip datapath, kept fed.

A raw call of a sharded program re-stages its batch, dispatches
synchronously and blocks for the result. This module gives the mesh
the treatment `CodecService` gave the single chip:

- **Long-lived compiled SPMD programs**, one per (FusedSpec, erasure
  pattern, batch width), resolved once per lane through
  `parallel/sharded.py`'s plan caches — erasure-pattern churn swaps a
  tiny replicated matrix, never the compiled program.
- **Reused host staging buffers**: every dispatch packs into a pooled
  buffer of the lane's constant shape instead of allocating; the pool
  holds depth+1 buffers per shape, the steady-state working set of the
  in-flight window.
- **Depth-N in-flight batches** (``OZONE_TPU_MESH_DEPTH``, default 2):
  dispatch N+1 launches while batches N..N-depth+1 are still on the
  devices; at most `depth` batches are launched and not yet pulled,
  plus the one being pulled.
- **A submission-queue front end mirroring `codec/service.py` lanes**:
  concurrent operations submit stripes keyed by the same semantic keys
  (`encode_key` / `decode_key`); the dispatcher coalesces them into
  full-width mesh dispatches (per-device batch x mesh size), so a
  reconstruction storm over many containers becomes a few wide
  dispatches instead of per-container dribbles.

TWO long-lived threads share the work on a batch, and each books every
stretch of its loop to leaf stages that never nest, each a histogram of
registry `mesh` and, in a profiler session, an event on the profiler's
clock (`utils/tracing.Stage`, as `codec/service.py` books `codec:*`):

- the dispatcher (`mesh-executor`) owns the lanes and everything up to
  the launch: `mesh:idle` (no lane ready and room in the window: it is
  starved), `mesh:pack` (closing out queue waits, zeroing and filling
  the staged batch), `mesh:launch` (the H2D to every device, the
  enqueue and the start of the eager D2H), `mesh:window_full` (`depth`
  batches launched and not yet taken by the completer: it waits for
  that thread, not for work). It never pulls and never resolves.
- the completer (`mesh-completer`) owns a batch from the launch on, in
  launch order and as soon as there is one: `mesh:completer_idle`
  (nothing launched), `mesh:d2h` (pulling the outputs to host arrays),
  `mesh:complete` (everything after the pull: the staging buffer back
  to the pool, the riders' `mesh:device_dispatch` spans, their rows
  sliced, split submissions joined, the futures resolved).

What the two share (the in-flight FIFO, the staging pool, a split
submission's part bookkeeping) is guarded by the executor's one lock;
every wait on either thread has a tick (`IDLE_TICK_S`).
`mesh:queue_wait` and `mesh:device_dispatch` are spans of the
SUBMITTING operation's trace and land in its stage record.

Backend policy mirrors `codec/fused.py`: on CPU-only hosts (where XLA's
GF(2) bit-matmul runs orders of magnitude slower than the AVX2 nibble
coder) a lane's program resolves to the **native host twin sharded
across one worker thread per mesh device** — same contract, same
coalescing, and trivially zero XLA compiles — while accelerator meshes
run the jitted SPMD programs. `stats()["programs_host_twin"]` counts
them.

Consumers do not call this module to submit: `parallel/dispatch.py`
decides which queue a batch joins. The submission record and the join
of a split submission are `codec/service.py`'s, shared by both
schedulers.
"""

from __future__ import annotations

import functools
import logging
import math
import threading
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Callable, Optional

import numpy as np

from ozone_tpu.codec.pipeline import _start_d2h
from ozone_tpu.codec.service import (
    _ambient_deadline,
    _resolve_error,
    _resolve_sub,
    _settle,
    _Sub,
)
from ozone_tpu.parallel import sharded
from ozone_tpu.utils.config import env_float, env_int
from ozone_tpu.utils.metrics import MetricsRegistry, registry
from ozone_tpu.utils.tracing import (
    IDLE_TICK_S,
    Stage,
    Tracer,
    dispatcher_seconds,
)

log = logging.getLogger(__name__)

#: every mesh-executor signal in ONE registry (prometheus: mesh_*)
METRICS: MetricsRegistry = registry("mesh")

#: in-flight mesh batches per lane family (double buffering = 2; triple
#: buffering = 3 hides longer D2H tails at the cost of one more staged
#: batch of memory per shape)
DEFAULT_DEPTH = 2
#: a single mesh dispatch never packs more stripe slots than this, no
#: matter the mesh size — bounds staged-buffer memory ([256, k, cell])
MAX_DISPATCH_WIDTH = 256
#: added-latency bound for a partial mesh batch waiting for co-batching
#: (the codec service's linger, applied to the mesh front end)
DEFAULT_LINGER_MS = 2.0
#: how long `close()` (and a dispatcher that ends by itself) waits for
#: the two threads to drain what was submitted; what is still pending
#: after it fails
CLOSE_TIMEOUT_S = 60.0
#: the leaf stages of the two threads, the dispatcher's then the
#: completer's (`stats()["dispatcher_seconds"]`)
STAGES = ("idle", "pack", "launch", "window_full",
          "completer_idle", "d2h", "complete")


def mesh_depth() -> int:
    """The in-flight depth knob (OZONE_TPU_MESH_DEPTH, min 1)."""
    return max(1, env_int("OZONE_TPU_MESH_DEPTH", DEFAULT_DEPTH))


class _MeshProgram:
    """One resolved, long-lived mesh program for a semantic key.

    `fn(batch [W, ...]) -> tuple of outputs` where W is any multiple of
    the mesh size up to the dispatch width; `jitted` lists the
    underlying compiled callables for the zero-new-compile probe
    (empty on the host-twin path, which has nothing to compile).
    """

    __slots__ = ("fn", "jitted", "host_twin")

    def __init__(self, fn: Callable, jitted: tuple, host_twin: bool):
        self.fn = fn
        self.jitted = jitted
        self.host_twin = host_twin

    def compile_count(self) -> int:
        """Compiled-executable census across this program's jitted
        callables; steady-state dispatches must not move it."""
        return sum(int(f._cache_size()) for f in self.jitted)


class _Lane:
    """One coalescing lane: same semantic key, same per-device batch
    width, same QoS class. FIFO of submissions with undispatched
    stripes; the bound program persists for the executor's lifetime
    (unlike the codec service's ephemeral fn bindings, mesh programs
    are the executor's to own — that persistence IS the point)."""

    __slots__ = ("lane_key", "program", "width", "cls", "subs", "queued",
                 "min_deadline_t")

    def __init__(self, lane_key: tuple, program: _MeshProgram,
                 width: int, cls: str):
        self.lane_key = lane_key
        self.program = program
        self.width = max(1, int(width))
        self.cls = cls
        self.subs: deque[_Sub] = deque()
        self.queued = 0
        self.min_deadline_t = math.inf


class MeshExecutor:
    """Per-process owner of the multi-chip datapath.

    `submit(key, stripes, width=...)` enqueues stripe work under a
    codec-service semantic key and returns a Future of the host output
    tuple for exactly those stripes. Submissions sharing (key, width,
    qos) coalesce into full-width mesh dispatches; up to
    ``mesh_depth()`` dispatches stay in flight, plus the one the
    completer thread is pulling.
    """

    def __init__(self, mesh=None, depth: Optional[int] = None,
                 axis: str = "dn"):
        if mesh is None:
            mesh = sharded.default_codec_mesh(axis=axis)
        if mesh is None:
            raise ValueError(
                "mesh executor needs a multi-device mesh "
                "(jax.device_count() > 1)")
        self.mesh = mesh
        self.axis = axis
        self.n_devices = int(mesh.devices.size)
        self.depth = depth if depth is not None else mesh_depth()
        self.linger_s = env_float("OZONE_TPU_MESH_LINGER_MS",
                                  DEFAULT_LINGER_MS) / 1000.0
        self._lock = threading.Lock()
        #: the dispatcher waits here: a submission, room in the window,
        #: shutdown
        self._cond = threading.Condition(self._lock)
        #: the completer waits here: a batch was launched, shutdown
        self._launched = threading.Condition(self._lock)
        self._lanes: dict[tuple, _Lane] = {}
        self._programs: dict[tuple, Optional[_MeshProgram]] = {}
        #: launched batches the completer has not taken yet, in launch
        #: order: the window, at most `depth` long
        self._inflight: deque[tuple] = deque()
        #: batches packed and not yet resolved: the window, the one
        #: being packed or launched and the one being pulled
        self._open = 0
        #: the batch the completer has taken and not yet resolved
        self._pulling: Optional[tuple] = None
        #: host staging buffers: (shape, dtype str) -> free list; the
        #: in-flight window recycles depth+1 buffers per lane shape
        self._staging: dict[tuple, list[np.ndarray]] = {}
        self._max_inflight = 0
        #: one worker per mesh device for the host-twin programs (the
        #: production mirror of fused._prefer_host_coder: on CPU-only
        #: hosts the native AVX2 coder outruns XLA's bit-matmul by
        #: orders of magnitude, and the "mesh" is the core count)
        self._workers = ThreadPoolExecutor(
            max_workers=self.n_devices, thread_name_prefix="mesh-dev")
        self._dispatch_ewma_s = 0.005
        self._running = True
        #: set when the dispatcher has left its loop: nothing more will
        #: be launched, the completer drains the window and ends
        self._dispatcher_done = False
        METRICS.gauge("devices").set(self.n_devices)
        METRICS.gauge("depth").set(self.depth)
        for stage in STAGES:
            # a stage that never happened reads 0, not nothing
            METRICS.histogram(f"{stage}_seconds")
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="mesh-executor")
        self._completer = threading.Thread(
            target=self._completer_loop, daemon=True, name="mesh-completer")
        self._completer.start()
        self._thread.start()

    # ------------------------------------------------------ program cache
    def dispatch_width(self, width: int) -> int:
        """A lane's mesh dispatch width: the per-device batch times the
        mesh size (every device gets the single-chip batch the
        submitter tuned for), bounded, and always a mesh multiple."""
        w = max(1, int(width)) * self.n_devices
        w = min(w, MAX_DISPATCH_WIDTH)
        return max(self.n_devices, -(-w // self.n_devices) * self.n_devices)

    def accepts(self, key: tuple) -> bool:
        """Whether `key` resolves to a mesh program. The first call for
        a key builds (and on device backends compiles) the program."""
        return self._resolve(key) is not None

    def pipeline(self, key: tuple, *, width: int,
                 qos: str = "bulk") -> Callable[..., Future]:
        """The lane `key`'s work joins, as `submit(stripes, *, tail,
        deadline)` bound to it: the way the door
        (`parallel/dispatch.py`) takes its mesh route. Raises KeyError
        when the key has no mesh program; the door then sends the work
        to the codec service. The benchmark's tests break this method
        to show that a decode which leaves the mesh is reported
        (`tests/benchmark_tests/test_bench_mesh.py`), so the name and
        the KeyError stay."""
        if not self.accepts(key):
            raise KeyError(f"no mesh program for {key!r}")
        return functools.partial(self.submit, key, width=width, qos=qos)

    def _resolve(self, key: tuple) -> Optional[_MeshProgram]:
        with self._lock:
            if key in self._programs:
                return self._programs[key]
        try:
            prog = self._build_program(key)
        except Exception:  # noqa: BLE001 - unresolvable key: caller keeps its single-chip path
            log.exception("mesh program resolution failed for %r", key)
            prog = None
        with self._lock:
            self._programs.setdefault(key, prog)
            return self._programs[key]

    def _build_program(self, key: tuple) -> Optional[_MeshProgram]:
        from ozone_tpu.codec import fused

        kind = key[0]
        if kind == "encode":
            spec = key[1]
            if fused._prefer_host_coder():
                single = fused._native_fused_encoder(
                    spec.options, spec.checksum, spec.bytes_per_checksum)
                if single is not None:
                    return _MeshProgram(self._host_shard(single), (), True)
            jfn = sharded.make_sharded_fused_encoder(
                spec, self.mesh, self.axis)
            return _MeshProgram(jfn, (jfn,), False)
        if kind == "decode":
            spec, valid, erased = key[1], list(key[2]), list(key[3])
            if fused._prefer_host_coder():
                single = fused._native_fused_decoder(
                    spec.options, spec.checksum, spec.bytes_per_checksum,
                    tuple(valid), tuple(erased))
                if single is not None:
                    return _MeshProgram(self._host_shard(single), (), True)
            jfn = sharded.make_sharded_decoder(
                spec, valid, erased, self.mesh, self.axis)
            k_dev, zeros_crc = fused.crc_plan_cached(
                spec.checksum, spec.bytes_per_checksum)
            apply_fn = sharded._sharded_decode_apply_cached(
                self.mesh, self.axis, k_dev is not None, zeros_crc)
            return _MeshProgram(jfn, (apply_fn,), False)
        # reencode and custom fns have no sharded twin (the re-encode
        # kernel's single fused dispatch doesn't decompose across the
        # batch axis for free): the door keeps them on the codec service
        return None

    def _host_shard(self, single: Callable) -> Callable:
        """Shard a batch across one worker thread per mesh device, each
        running the native single-chip twin on its contiguous slice —
        the host mirror of the DP sharding (batch axis over devices)."""
        n = self.n_devices

        def fn(batch: np.ndarray):
            per = batch.shape[0] // n
            if per == 0:
                outs = [single(batch)]
            else:
                futs = [
                    self._workers.submit(single, batch[i * per:(i + 1) * per])
                    for i in range(n)
                ]
                outs = [f.result() for f in futs]
            first = outs[0] if isinstance(outs[0], tuple) else (outs[0],)
            width = len(first)
            return tuple(
                np.concatenate(
                    [(o if isinstance(o, tuple) else (o,))[i]
                     for o in outs], axis=0)
                for i in range(width))

        return fn

    # ---------------------------------------------------------- staging
    def _take_staging(self, shape: tuple, dtype) -> np.ndarray:
        skey = (shape, np.dtype(dtype).str)
        with self._lock:
            free = self._staging.get(skey)
            if free:
                METRICS.counter("staging_reuses").inc()
                return free.pop()
        return np.empty(shape, dtype=dtype)

    def _give_staging(self, buf: np.ndarray) -> None:
        skey = (buf.shape, buf.dtype.str)
        with self._lock:
            free = self._staging.setdefault(skey, [])
            if len(free) <= self.depth:
                free.append(buf)

    # ----------------------------------------------------------- submit
    def submit(self, key: tuple, stripes: np.ndarray, *, width: int,
               qos: str = "bulk", tail: bool = False,
               deadline=None) -> Future:
        """Enqueue `stripes` ([n, ...], n >= 1) under semantic `key`.

        `width` is the submitter's per-device batch width (the lane
        dispatches at ``dispatch_width(width)``). Raises KeyError when
        the key has no mesh program: `pipeline()` answers that first.
        """
        if stripes.shape[0] < 1:
            raise ValueError("empty mesh submission")
        prog = self._resolve(key)
        if prog is None:
            raise KeyError(f"no mesh program for {key!r}")
        if deadline is None:
            deadline = _ambient_deadline()
        fut: Future = Future()
        sub = _Sub(stripes, fut, qos, deadline, tail)
        lane_key = (key, int(width), qos)
        lane_width = self.dispatch_width(width)
        with self._cond:
            if not self._running:
                raise RuntimeError("mesh executor is shut down")
            lane = self._lanes.get(lane_key)
            if lane is None:
                lane = self._lanes[lane_key] = _Lane(
                    lane_key, prog, lane_width, qos)
            lane.subs.append(sub)
            lane.queued += sub.n
            lane.min_deadline_t = min(lane.min_deadline_t,
                                      sub.deadline_t())
            METRICS.counter("submissions").inc()
            METRICS.gauge("queue_depth").set(self._queue_depth_locked())
            self._cond.notify()
        return fut

    # ------------------------------------------------------- scheduling
    def _queue_depth_locked(self) -> int:
        return sum(lane.queued for lane in self._lanes.values())

    def _flush_margin_s(self) -> float:
        return self.linger_s + 4.0 * self._dispatch_ewma_s

    def _ready_lane_locked(self, now: float) -> Optional[_Lane]:
        """Earliest-deadline-then-oldest ready lane: full lanes first,
        then deadline-pressed, then lingered-out. The heavy fairness
        machinery (WFQ vtime, starvation guard) lives in the codec
        service front end; by the time work reaches the mesh it is
        bulk-classed or already fairness-filtered."""
        best: Optional[_Lane] = None
        best_rank: tuple = ()
        margin = self._flush_margin_s()
        for lane in self._lanes.values():
            if not lane.subs:
                continue
            head_age = now - lane.subs[0].t_enq
            if lane.queued >= lane.width:
                rank = (0, -lane.queued, lane.subs[0].t_enq)
            elif lane.min_deadline_t - now <= margin:
                rank = (1, lane.min_deadline_t, lane.subs[0].t_enq)
            elif head_age >= self.linger_s:
                rank = (2, lane.subs[0].t_enq, 0.0)
            else:
                continue
            if best is None or rank < best_rank:
                best, best_rank = lane, rank
        return best

    def _next_wakeup_locked(self, now: float) -> Optional[float]:
        t = math.inf
        margin = self._flush_margin_s()
        for lane in self._lanes.values():
            if not lane.subs:
                continue
            t = min(t, lane.subs[0].t_enq + self.linger_s,
                    lane.min_deadline_t - margin)
        return None if math.isinf(t) else max(0.0, t - now)

    def _pack_locked(self, lane: _Lane):
        entries: list[tuple[_Sub, int, int, int]] = []
        row = 0
        while lane.subs and row < lane.width:
            sub = lane.subs[0]
            take = min(sub.n - sub.taken, lane.width - row)
            entries.append((sub, sub.taken, take, row))
            sub.taken += take
            sub.pending_parts += 1
            if sub.taken == sub.n:
                lane.subs.popleft()
            row += take
            lane.queued -= take
        if not lane.subs:
            lane.min_deadline_t = math.inf
        else:
            lane.min_deadline_t = min(s.deadline_t() for s in lane.subs)
        return entries, row

    # ------------------------------------------------------- dispatcher
    def _loop(self) -> None:
        try:
            while True:
                with self._cond:
                    if len(self._inflight) >= self.depth:
                        if not self._completer.is_alive():
                            break  # nobody will ever make room
                        # ahead of the completer: wait for it to take a
                        # batch, and let the lanes fill meanwhile
                        with Stage("mesh:window_full",
                                   METRICS.histogram("window_full_seconds")):
                            self._cond.wait(IDLE_TICK_S)
                        continue
                    now = time.monotonic()
                    lane = self._ready_lane_locked(now)
                    if lane is None and not self._running:
                        # shutting down: what is queued still goes out
                        lane = next((ln for ln in self._lanes.values()
                                     if ln.subs), None)
                        if lane is None:
                            break
                    if lane is None:
                        # starved: no lane ready
                        wake = self._next_wakeup_locked(now)
                        with Stage("mesh:idle",
                                   METRICS.histogram("idle_seconds")):
                            self._cond.wait(
                                IDLE_TICK_S if wake is None
                                else min(wake, IDLE_TICK_S))
                        continue
                    entries, rows = self._pack_locked(lane)
                    self._open += 1
                    self._max_inflight = max(self._max_inflight, self._open)
                self._dispatch(lane, entries, rows)
                # the batch is the completer's now: were this the last
                # reference, the riders' rows would be freed at the next
                # pack, under the lock
                del entries
        except BaseException:  # noqa: BLE001 - dispatcher must not die silently
            log.exception("mesh executor dispatcher crashed")
            raise
        finally:
            with self._lock:
                self._running = False
                self._dispatcher_done = True
                self._launched.notify_all()
            # what was launched still lands; then whatever is left fails
            self._completer.join(timeout=CLOSE_TIMEOUT_S)
            self._fail_pending(RuntimeError("mesh executor stopped"))

    def _dispatch(self, lane: _Lane, entries, rows: int) -> None:
        ops = len(entries)
        tracer = Tracer.instance()
        lane_desc = str(lane.lane_key)[:120]
        # the host work before the launch, on this one thread: closing
        # out the riders' queue waits, then zeroing and filling the
        # staged batch (every payload byte of a coalesced dispatch)
        with Stage("mesh:pack", METRICS.histogram("pack_seconds")):
            now = time.monotonic()
            for sub, off, take, _row in entries:
                if off == 0:
                    wait = now - sub.t_enq
                    tid = sub.trace_ctx.split(":", 1)[0]
                    METRICS.histogram("queue_wait_seconds").observe(
                        wait, tid)
                    if sub.trace_ctx:
                        tracer.record_span(
                            "mesh:queue_wait", child_of=sub.trace_ctx,
                            start=sub.t_enq_wall, duration=wait,
                            mono=sub.t_enq, lane=lane_desc, qos=sub.cls)
            head = entries[0]
            staged = None
            if ops == 1 and head[2] == rows == lane.width \
                    and head[1] == 0 and head[0].n == lane.width \
                    and head[0].stripes.flags.c_contiguous:
                # one submission covering the whole batch: dispatch its
                # own rows without a staging copy
                batch = head[0].stripes
            else:
                shape = (lane.width,) + tuple(head[0].stripes.shape[1:])
                staged = batch = self._take_staging(
                    shape, head[0].stripes.dtype)
                for sub, off, take, row in entries:
                    batch[row:row + take] = sub.stripes[off:off + take]
                if rows < lane.width:
                    batch[rows:] = 0  # constant-shape zero-padded tail
        t0 = time.monotonic()
        t0_wall = time.time()
        with tracer.span("mesh:dispatch", lane=lane_desc, ops=ops,
                         rows=rows, width=lane.width,
                         devices=self.n_devices):
            try:
                # the implicit H2D to every device and the enqueue
                with Stage("mesh:launch",
                           METRICS.histogram("launch_seconds")):
                    outs = lane.program.fn(batch)
                    if not isinstance(outs, tuple):
                        outs = (outs,)
                    for a in outs:
                        # eager D2H: the pull overlaps the next batch's
                        # staging
                        _start_d2h(a)
            except BaseException as e:  # noqa: BLE001 - per-dispatch fault
                if staged is not None:
                    self._give_staging(staged)
                _resolve_error(entries, e)
                self._close_batch()
                return
        METRICS.counter("dispatches").inc()
        METRICS.counter("stripes_dispatched").inc(rows)
        METRICS.counter("slots_dispatched").inc(lane.width)
        METRICS.counter("coalesced_operations").inc(ops)
        if ops > 1:
            METRICS.counter("multi_op_dispatches").inc()
        METRICS.gauge("batch_fill_pct").set(100.0 * rows / lane.width)
        # devices holding a shard of the last dispatch's output: n on a
        # real SPMD dispatch, 0 for the host twin's numpy arrays
        shards = len(getattr(outs[0], "addressable_shards", ()))
        METRICS.gauge("output_shards").set(shards)
        METRICS.counter("output_shards_dispatched").inc(shards)
        with self._cond:
            METRICS.gauge("queue_depth").set(self._queue_depth_locked())
            # from here on the batch is the completer's
            self._inflight.append(
                (entries, outs, staged, t0, t0_wall,
                 (lane_desc, ops, rows, lane.width)))
            self._launched.notify()
            METRICS.gauge("inflight_depth").set(self._open)
            METRICS.gauge("inflight_per_device").set(self._open)
            METRICS.gauge("max_inflight_depth").set(self._max_inflight)

    def _close_batch(self, pulled: bool = False) -> None:
        """One packed batch is resolved (or failed) and counts no more.
        A pulled batch's record goes with it: its device arrays and the
        riders' rows are freed here, after the lock is released (tens
        of MiB a rider: their `munmap` must not hold up the lanes)."""
        rec = None
        with self._lock:
            if pulled:
                rec, self._pulling = self._pulling, None
            self._open -= 1
            METRICS.gauge("inflight_depth").set(self._open)
        del rec

    # -------------------------------------------------------- completer
    def _completer_loop(self) -> None:
        try:
            while True:
                with self._launched:
                    if not self._inflight:
                        if self._dispatcher_done:
                            break
                        with Stage(
                                "mesh:completer_idle",
                                METRICS.histogram("completer_idle_seconds")):
                            self._launched.wait(IDLE_TICK_S)
                        continue
                    self._pulling = self._inflight.popleft()
                    self._cond.notify_all()  # room in the window
                self._complete()
        except BaseException:  # noqa: BLE001 - completer must not die silently
            log.exception("mesh executor completer crashed")
            raise
        finally:
            # without a completer nothing resolves: refuse what comes
            with self._cond:
                self._running = False
                self._cond.notify_all()

    def _complete(self) -> None:
        """Pull, slice and resolve the batch in `_pulling`, and let go
        of it: whatever fails here fails that batch alone."""
        entries, outs, staged, t0, t0_wall, dctx = self._pulling
        lane_desc, ops, rows, width = dctx
        try:
            with Stage("mesh:d2h", METRICS.histogram("d2h_seconds")):
                host = tuple(np.asarray(a) for a in outs)
        except BaseException as e:  # noqa: BLE001 - D2H fault
            if staged is not None:
                self._give_staging(staged)
            _resolve_error(entries, e)
            self._close_batch(pulled=True)
            return
        # everything after the pull, until the last rider is resolved
        # and the batch's record, device arrays and riders' rows are
        # let go of
        with Stage("mesh:complete", METRICS.histogram("complete_seconds")):
            try:
                if staged is not None:
                    self._give_staging(staged)
                dt = time.monotonic() - t0
                self._dispatch_ewma_s += 0.2 * (dt - self._dispatch_ewma_s)
                METRICS.histogram("dispatch_seconds").observe(
                    dt, entries[0][0].trace_ctx.split(":", 1)[0])
                tracer = Tracer.instance()
                for sub, off, take, _row in entries:
                    if sub.trace_ctx:
                        tracer.record_span(
                            "mesh:device_dispatch", child_of=sub.trace_ctx,
                            start=t0_wall, duration=dt, mono=t0,
                            lane=lane_desc, qos=sub.cls, stripes=take,
                            ops=ops, rows=rows, width=width)
                # a split submission's other parts are packed by the
                # dispatcher meanwhile: its bookkeeping under the lock,
                # the join of its parts outside it
                whole = []
                with self._lock:
                    for sub, off, take, row in entries:
                        sub.parts.append(
                            (off, take,
                             tuple(a[row:row + take] for a in host)))
                        sub.pending_parts -= 1
                        if sub.taken == sub.n and sub.pending_parts == 0:
                            whole.append(sub)
                for sub in whole:
                    _resolve_sub(sub)
            except BaseException as e:  # noqa: BLE001 - this batch's fault alone
                log.exception("mesh completion failed")
                _resolve_error(entries, e)
                if not isinstance(e, Exception):
                    raise
            finally:
                sub = whole = entries = outs = host = None
                self._close_batch(pulled=True)

    def _fail_pending(self, e: BaseException) -> None:
        with self._lock:
            subs = [s for lane in self._lanes.values() for s in lane.subs]
            self._lanes.clear()
            inflight = list(self._inflight)
            self._inflight.clear()
            self._open -= len(inflight)
            self._cond.notify_all()
            # a batch still being pulled stays the completer's (its
            # buffer, its count); its riders wait no longer
            pulling = self._pulling
        for rec in inflight:
            if rec[2] is not None:
                self._give_staging(rec[2])
        for rec in inflight + ([pulling] if pulling else []):
            subs.extend(entry[0] for entry in rec[0])
        for s in subs:
            _settle(s.future, error=e)

    # ---------------------------------------------------------- control
    def compile_counts(self) -> int:
        """Total compiled executables across every resolved mesh
        program — the warm-program proof probes the delta of this
        across steady-state rounds (must be zero)."""
        with self._lock:
            progs = [p for p in self._programs.values() if p is not None]
        return sum(p.compile_count() for p in progs)

    def stats(self) -> dict:
        """Operator snapshot (the Recon /api/mesh payload)."""
        snap = METRICS.snapshot()
        slots = snap.get("slots_dispatched", 0)
        disp = snap.get("dispatches", 0)
        snap["fill_ratio"] = (snap.get("stripes_dispatched", 0) / slots
                              if slots else 0.0)
        snap["ops_per_dispatch"] = (
            snap.get("coalesced_operations", 0) / disp if disp else 0.0)
        snap["dispatcher_seconds"] = dispatcher_seconds(METRICS, STAGES)
        with self._lock:
            snap["queue_depth"] = self._queue_depth_locked()
            snap["lanes"] = len(self._lanes)
            snap["inflight"] = self._open
            progs = [p for p in self._programs.values() if p is not None]
            snap["programs"] = len(progs)
            snap["programs_host_twin"] = sum(
                1 for p in progs if p.host_twin)
        snap["max_inflight"] = self._max_inflight
        snap["devices"] = self.n_devices
        snap["mesh_depth"] = self.depth
        snap["compile_counts"] = sum(p.compile_count() for p in progs)
        return snap

    def quiesce(self, timeout_s: float = 30.0) -> None:
        """Wait until every queued submission has dispatched and
        harvested (tests and drills; production never needs it)."""
        t_end = time.monotonic() + timeout_s
        while time.monotonic() < t_end:
            with self._lock:
                if not self._open and self._queue_depth_locked() == 0:
                    return
            time.sleep(0.002)

    def close(self) -> None:
        """Stop and join both threads: what was submitted still drains,
        for `CLOSE_TIMEOUT_S` at most; what is pending after it fails."""
        t_end = time.monotonic() + CLOSE_TIMEOUT_S
        with self._cond:
            self._running = False
            self._cond.notify_all()
            self._launched.notify_all()
        for thread in (self._thread, self._completer):
            thread.join(timeout=max(0.0, t_end - time.monotonic()))
        self._fail_pending(RuntimeError("mesh executor shut down"))
        self._workers.shutdown(wait=False)


_executor: Optional[MeshExecutor] = None
_executor_lock = threading.Lock()


def get_executor() -> MeshExecutor:
    """The process-wide executor (created on first use)."""
    global _executor
    with _executor_lock:
        if _executor is None or not _executor._running:
            _executor = MeshExecutor()
        return _executor


def maybe_executor() -> Optional[MeshExecutor]:
    """The executor when it can exist here: more than one device
    attached. The door (`parallel/dispatch.py`) asks this for encode
    sweeps; repair harnesses ask it and hand the answer to their
    coordinator. A backend that fails to initialise raises (see
    fused._prefer_host_coder)."""
    import jax

    if jax.device_count() < 2:
        return None
    return get_executor()


def reset_for_tests() -> None:
    """Shut down and drop the singleton (fresh knobs per test)."""
    global _executor
    with _executor_lock:
        ex, _executor = _executor, None
    if ex is not None:
        ex.close()
