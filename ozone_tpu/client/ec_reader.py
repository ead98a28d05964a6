"""EC block-group read paths: normal, degraded, and targeted recovery.

Mirrors the reference's read stack: ECBlockInputStream (round-robin cell
reads from the d data blocks, hadoop-hdds/client ECBlockInputStream.java:55
readWithStrategy:351), with failure fallback to
ECBlockReconstructedStripeInputStream (read any k of d+p units, decode the
missing cells — ECBlockReconstructedStripeInputStream.java:115,
decodeStripe:689) and its targeted-index recovery API used by offline
reconstruction (recoverChunks:103-113).

TPU-first: degraded reads batch every needed stripe of the group into one
device decode dispatch instead of decoding stripe-by-stripe.

Straggler tolerance (client/resilience.py): survivor choice skips
breaker-open peers, every read feeds the per-peer latency EWMA, and a
cell fetch that exceeds the peer's P95 (or OZONE_TPU_HEDGE_MS) is
hedged — the normal path races the fetch against a decode-from-parity
of the same cell, the recovery path drops the straggling survivor and
replans the batched decode around a spare — first result wins, the
loser's bytes are discarded.
"""

from __future__ import annotations

import logging
import math
import threading
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import wait as fwait
from typing import Callable, Optional, Sequence

import numpy as np

from ozone_tpu.client import resilience
from ozone_tpu.client.dn_client import DatanodeClientFactory
from ozone_tpu.client.ec_writer import BlockGroup, block_lengths
from ozone_tpu.codec import hostmem
from ozone_tpu.codec import lrc_math
from ozone_tpu.codec import service as codec_service
from ozone_tpu.codec.api import CoderOptions
from ozone_tpu.codec.fused import FusedSpec, make_fused_decoder
from ozone_tpu.codec.pipeline import (
    DeviceBatchPipeline,
    batched,
    decode_batch_size,
)
from ozone_tpu.parallel import dispatch
from ozone_tpu.storage.ids import BlockData, ChunkInfo, StorageError
from ozone_tpu.utils.checksum import ChecksumType
from ozone_tpu.utils.metrics import registry
from ozone_tpu.utils.tracing import Tracer

log = logging.getLogger(__name__)

#: the client's per-operation registry (`client/ozone_client.py`): a
#: read() books what it moved there, beside `get_seconds`
OPS = registry("client.ops")


#: workers of every reader's block-record rounds, kept for the life of
#: the process: a thread started for a record of a few KB costs more
#: than the answer it waits for (PERF.md section 6, PR 37)
_RECORD_WORKERS = 32
_record_pool: Optional[ThreadPoolExecutor] = None
_record_pool_lock = threading.Lock()


def _record_executor() -> ThreadPoolExecutor:
    global _record_pool
    with _record_pool_lock:
        if _record_pool is None:
            _record_pool = ThreadPoolExecutor(
                max_workers=_RECORD_WORKERS,
                thread_name_prefix="ec-records")
        return _record_pool


class InsufficientLocationsError(Exception):
    """Fewer than k units reachable (reference InsufficientLocationsException)."""


class _UnitReadError(Exception):
    """Internal: a specific unit failed during a multi-unit read."""

    def __init__(self, unit: int, cause: Exception):
        super().__init__(f"unit {unit}: {cause}")
        self.unit = unit
        self.cause = cause


class _StragglerHedge(Exception):
    """Internal: survivor unit(s) exceeded their hedge delay while a
    spare peer could take their place — the retry loop excludes them
    and replans the batched decode (decode-from-parity fall-through).
    Not an error: the straggler's in-flight reads are abandoned, their
    eventual results discarded."""

    def __init__(self, units: list[int]):
        super().__init__(f"straggling units {units}: hedging to spares")
        self.units = units


class _ReadTally:
    """What one read() asked of the datanodes and what it took from its
    own recovery's survivor batches instead: the `ec:read` span's tags
    and the `get_*` counters of `client.ops`. Work done is counted, an
    abandoned attempt's too. The unit reads run on pool threads."""

    def __init__(self):
        self._lock = threading.Lock()
        self.cells_fetched = 0
        self.wire_bytes = 0
        # the reading thread's alone:
        self.cells_reused = 0
        #: bytes of the read's own buffers (its part of the key's
        #: buffer, its survivor batches) that were mapped new for it,
        #: not recycled: first touched, page by page, inside the read
        self.fresh_bytes = 0

    def asked(self, infos: Sequence[ChunkInfo]) -> None:
        with self._lock:
            self.cells_fetched += len(infos)
            self.wire_bytes += sum(i.length for i in infos)


class RecoveryTally:
    """What one recover_cells_iter planned and read: the `kind`, `width`
    and `widened` tags of the `ec:read` and `repair:block` spans and
    the repair counters of `ec.reconstruction`. A plan is `rs` (any k
    survivors), or LRC's `local` (the lost units' groups alone) or
    `global`. Reads are counted as asked, an abandoned attempt's too;
    the unit reads run on pool threads."""

    def __init__(self):
        self._lock = threading.Lock()
        self.kind = ""   # the FIRST plan's kind
        self.width = 0   # units in the read set of the plan that ended it
        #: why a plan that began `local` ended reading outside its
        #: groups: "hedge" (a survivor straggled past its hedge delay
        #: and was dropped) or "unit_failed"; "" where it did not
        self.widened = ""
        self.units: set[int] = set()  # units a payload read was asked of
        self.bytes = 0

    def plan(self, kind: str, width: int, cause: str) -> None:
        self.kind = self.kind or kind
        self.width = width
        if self.kind == "local" and kind != "local":
            self.widened = self.widened or cause

    def asked(self, u: int, infos: Sequence[ChunkInfo]) -> None:
        with self._lock:
            self.units.add(u)
            self.bytes += sum(i.length for i in infos)

    def tags(self) -> dict:
        out = {"kind": self.kind, "width": self.width}
        if self.widened:
            out["widened"] = self.widened
        return out


class ECBlockGroupReader:
    def __init__(
        self,
        group: BlockGroup,
        options: CoderOptions,
        clients: DatanodeClientFactory,
        verify: bool = True,
        checksum: ChecksumType = ChecksumType.CRC32C,
        bytes_per_checksum: int = 16 * 1024,
        mesh=None,
        use_ring: bool = False,
        qos_class: str = "interactive",
        executor=None,
    ):
        #: optional jax.sharding.Mesh: recovery decodes run stripe-
        #: parallel (DP) over it — or survivor-sharded around the
        #: ppermute ring with use_ring=True — instead of single-device
        #: (parallel/sharded.py; the multi-chip production path)
        self.mesh = mesh
        self.use_ring = use_ring
        self.group = group
        self.opts = options
        self.k, self.p, self.cell = (
            options.data_units,
            options.parity_units,
            options.cell_size,
        )
        self.clients = clients
        if getattr(clients, "tokens", None) is not None:
            clients.tokens.put_group(group)  # READ tokens from the lookup
        self.verify = verify
        self.spec = FusedSpec(options, checksum, bytes_per_checksum)
        self._block_meta: dict[int, Optional[BlockData]] = {}
        self._read_pool = None  # lazy; see _recover_batches_once
        #: (unit, stripe) -> full-cell array, filled by _prefetch_unit's
        #: batched ReadChunks and consumed (popped) by _read_cell
        self._cell_cache: dict[tuple[int, int], np.ndarray] = {}
        import os

        self._batch_reads = os.environ.get(
            "OZONE_TPU_BATCH_READS", "1") != "0"
        #: stripes per decode dispatch; recovery runs these through a
        #: depth-1 device pipeline (survivor fetch of batch N+1 overlaps
        #: device decode + D2H of batch N — the writer's _flush_queue
        #: structure mirrored onto the read path)
        self._decode_batch = decode_batch_size()
        # units that failed a read/verify; excluded like missing replicas
        # (reference ECBlockInputStream setFailed + proxy failover)
        self._failed: set[int] = set()
        #: shared per-peer health (EWMA latency, circuit breaker) —
        #: factory-wide when the factory carries one, process-default
        #: otherwise, so every reader sees every client's observations
        self._health = getattr(clients, "health", None) \
            or resilience.default_registry()
        #: operation deadline captured at the public entry points and
        #: re-activated on reader-pool worker threads
        self._deadline: Optional[resilience.Deadline] = None
        #: the running read()'s tally; None outside one (repair's
        #: recover_cells_iter books nothing under the GET's names)
        self._tally: Optional[_ReadTally] = None
        #: the last recover_cells_iter's plan and reads (a repair's
        #: caller and read() tag their spans from it); None before one
        self.recovery: Optional[RecoveryTally] = None
        self._replan_cause = ""  # why the next plan is not the first
        #: the class its decode batches queue in: they coalesce with
        #: other operations sharing the erasure pattern (reconstruction
        #: storms, fleets of degraded readers)
        self._qos = qos_class
        #: optional parallel.mesh_executor.MeshExecutor, handed to the
        #: door with every decode stream: a reader that was handed one
        #: is one of many (a repair storm) and its bulk batches join
        #: that executor's lanes; a reader handed none stays on one chip
        self._executor = executor

    # ---------------------------------------------------------------- helpers
    @property
    def num_stripes(self) -> int:
        return -(-self.group.length // (self.k * self.cell))

    def _unit_block(self, u: int) -> Optional[BlockData]:
        """BlockData of unit u (0-based) or None if unreachable/missing.
        The one place that asks a node and reads its answer as "there"
        or "absent"; on the record pool's threads and the caller's during
        `_ask_block_records`, a cache hit after it."""
        if u not in self._block_meta:
            dn_id = self.group.pipeline.nodes[u]
            try:
                with Tracer.instance().span("net:get_block", dn=dn_id,
                                            unit=u):
                    self._block_meta[u] = self._health.observe(
                        dn_id, self.clients.get(dn_id).get_block,
                        self.group.block_id)
            except (StorageError, KeyError, OSError) as e:
                if isinstance(e, StorageError) \
                        and e.code == resilience.DEADLINE_EXCEEDED:
                    # the OPERATION's budget expired, the peer may be
                    # fine: fail fast instead of reading as "every unit
                    # unreachable" (a false InsufficientLocations)
                    raise
                log.debug("unit %d unavailable: %s", u, e)
                self._block_meta[u] = None
        return self._block_meta[u]

    def _ask_block_records(self) -> None:
        """Fill `_block_meta` for every unit not asked yet in ONE round.
        This thread takes units off one list and asks them in turn, and
        workers of the process's record pool take from the same list:
        whoever starts while more is left than it can ask next sends
        for one more worker, and calls that one off if it has not
        started by the time its own answer is back. Where a worker
        starts at once (a lone repair) every record is in flight within
        a millisecond or two and the round costs what the answers cost
        side by side, not the walk's sum (15 records before an LRC
        repair reads six). Where a hand-off takes longer than an answer
        (many readers in one process, all waiting for the interpreter)
        every thread woken is a turn at the interpreter the readers
        lose: a dozen woken at once made the round SLOWER than the walk
        (PERF.md section 6, PR 37); here this thread asks most itself
        and few workers ever start. A unit in `_failed` is not asked; a
        DEADLINE_EXCEEDED ends the operation, the lowest unit's first,
        once every answer that was sent for is in."""
        ask = [u for u in range(self.k + self.p)
               if u not in self._block_meta and u not in self._failed]
        if len(ask) < 2:
            return  # none, or one that `_unit_block` asks on this thread
        todo = deque(ask)
        workers: list = []
        spent: dict[int, StorageError] = {}
        pool = _record_executor()

        def take() -> None:
            sent_for = None
            if len(todo) > 1:
                sent_for = self._submit_act(pool, take)
                workers.append(sent_for)
            while True:
                try:
                    u = todo.popleft()
                except IndexError:
                    return
                try:
                    self._unit_block(u)
                except StorageError as e:  # DEADLINE_EXCEEDED alone
                    spent[u] = e
                if sent_for is not None:
                    # not started in the time an answer took: called off
                    sent_for.cancel()
                    sent_for = None

        with Tracer.instance().span("net:get_blocks",
                                    records_asked=len(ask)) as sp:
            try:
                take()
            finally:
                # the list grows under the loop, which meets what is
                # appended: a worker's own call for help is in it before
                # that worker ends. One that has not started has nothing
                # left to take and is called off: no wait for a hand-off
                for f in workers:
                    if not f.cancel():
                        fwait([f])
                sp.tags["records_present"] = sum(
                    self._block_meta.get(u) is not None for u in ask)
            for f in workers:
                if not f.cancelled():
                    f.result()
            if spent:
                raise spent[min(spent)]
        OPS.counter("block_record_rounds").inc()
        OPS.counter("block_records_asked").inc(len(ask))

    def available_units(self) -> list[int]:
        """The units that are there, by their block records: asked for
        in one round the first time, answered from `_block_meta` after."""
        self._ask_block_records()
        return [
            u
            for u in range(self.k + self.p)
            if u not in self._failed and self._unit_block(u) is not None
        ]

    def _read_cell(self, u: int, stripe: int) -> np.ndarray:
        """Read unit u's cell of `stripe`, zero-padded to full cell size."""
        cached = self._cell_cache.pop((u, stripe), None)
        if cached is not None:
            return cached
        return self._fetch_cell(u, stripe)

    def _peek_cell(self, u: int, stripe: int) -> np.ndarray:
        """_read_cell that PEEKS the prefetch cache instead of popping:
        the decode-from-parity hedge branch must not consume entries
        the main loop still owns. A fresh fetch is ADDED to the cache
        (win or lose — cells are immutable), so consecutive hedged
        cells of a window never re-fetch the same survivor cells."""
        cached = self._cell_cache.get((u, stripe))
        if cached is not None:
            return cached
        out = self._fetch_cell(u, stripe)
        self._cell_cache.setdefault((u, stripe), out)
        return out

    def _fetch_cell(self, u: int, stripe: int) -> np.ndarray:
        bd = self._unit_block(u)
        if bd is None:
            return np.zeros(self.cell, dtype=np.uint8)
        offset = stripe * self.cell
        info = next((c for c in bd.chunks if c.offset == offset), None)
        if info is None:
            # cell has no data (short final stripe)
            return np.zeros(self.cell, dtype=np.uint8)
        dn_id = self.group.pipeline.nodes[u]
        self._asked(u, [info])
        with Tracer.instance().span("net:read_chunk", dn=dn_id,
                                    unit=u, stripe=stripe):
            data = self._health.observe(
                dn_id, self.clients.get(dn_id).read_chunk,
                self.group.block_id, info, verify=self.verify)
        return self._cell_array(data)

    def _asked(self, u: int, infos: Sequence[ChunkInfo]) -> None:
        tally, recovery = self._tally, self.recovery
        if tally is not None:
            tally.asked(infos)
        if recovery is not None:
            recovery.asked(u, infos)

    def _cell_array(self, data: np.ndarray) -> np.ndarray:
        """Full cells pass through as zero-copy views over the wire
        buffer (cells are immutable once cached); short cells pad into
        a fresh array — one counted copy, inherent to zero-fill."""
        if data.size == self.cell:
            return hostmem.as_array(data)
        out = np.zeros(self.cell, dtype=np.uint8)
        out[: data.size] = data
        hostmem.count_copy(int(data.size), site="ec_reader._cell_array",
                           warn=False)
        return out

    def _prefetch_unit(
        self, u: int, stripes: Sequence[int],
        rows: Optional[dict[int, np.ndarray]] = None,
    ) -> tuple[dict[int, ChunkInfo], int]:
        """Batch-read unit u's cells for `stripes` in ONE ReadChunks
        RPC (the read twin of the batched write path: transport round
        trip per unit, not per cell). Best-effort — any error
        (including a server without the verb) simply leaves the cells
        to the per-chunk path, which surfaces precise per-cell failures.

        Without `rows` the cells land in the cell cache. With `rows`
        (stripe -> that cell's row of a decode batch, writable, a
        cell long) and a transport that offers `read_chunks_into`, the
        answer is written to the rows and passes no other host buffer:
        returns (stripe -> its chunk for every row written, each from
        its start for the chunk's length; how many of them the
        transport RECEIVED there, the rest it copied). A row of a read
        that failed may be half written: its cell is not in the answer,
        and the per-chunk path assigns it whole."""
        none: tuple[dict[int, ChunkInfo], int] = ({}, 0)
        if not self._batch_reads:
            return none
        bd = self._unit_block(u)
        if bd is None:
            return none
        by_offset = {c.offset: c for c in bd.chunks}
        wanted = [
            (s, by_offset[s * self.cell])
            for s in stripes
            if (u, s) not in self._cell_cache
            and s * self.cell in by_offset
        ]
        if len(wanted) < 2:
            return none  # nothing saved over the per-chunk path
        infos = [i for _, i in wanted]
        dn_id = self.group.pipeline.nodes[u]
        try:
            client = self.clients.get(dn_id)
            # looked up on the CLASS: a wrapper that hands unknown names
            # on to the client it wraps has not overridden the verb, and
            # the `read_chunks` it does define must see this read
            into = (getattr(type(client), "read_chunks_into", None)
                    if rows is not None else None)
            fn = getattr(client, "read_chunks", None)
            if into is None and fn is None:
                return none
            self._asked(u, infos)
            with Tracer.instance().span("net:read_chunks", dn=dn_id,
                                        unit=u, cells=len(wanted)) as sp:
                if into is None:
                    datas = self._health.observe(
                        dn_id, fn, self.group.block_id, infos,
                        verify=self.verify)
                else:
                    sp.tags["in_place"] = in_place = self._health.observe(
                        dn_id, client.read_chunks_into, self.group.block_id,
                        infos, [rows[s] for s, _ in wanted],
                        verify=self.verify)
                    return dict(wanted), in_place
        except (StorageError, KeyError, OSError) as e:
            if isinstance(e, StorageError) \
                    and e.code == resilience.DEADLINE_EXCEEDED:
                raise
            log.debug("batched read of unit %d failed (%s); per-chunk "
                      "path will retry", u, e)
            return none
        for (s, _info), data in zip(wanted, datas):
            self._cell_cache[(u, s)] = self._cell_array(data)
        return none

    # ---------------------------------------------------------------- normal
    def read_all(self) -> np.ndarray:
        """Whole-group read, preferring plain data-block reads and falling
        back to reconstruction for missing/corrupt units. Units that fail
        mid-read are marked failed and excluded on retry, up to p times."""
        return self.read(0, self.group.length)

    def _close_pool(self) -> None:
        """Reap the reader threads: readers are per-group-read objects
        with no close() in their contract, so each public entry point
        reaps its own pool instead of leaving k threads to the GC."""
        pool, self._read_pool = self._read_pool, None
        if pool is not None:
            pool.shutdown(wait=False)

    def _cell_span(self, offset: int, length: int, u: int,
                   stripe: int) -> tuple[int, int, int]:
        """(a, b, cell_start) in user-byte space: the part [a, b) of data
        unit u's cell of `stripe` (which starts at cell_start) that lies
        in [offset, offset+length); a >= b where the range misses it."""
        cell_start = (stripe * self.k + u) * self.cell
        return (max(offset, cell_start),
                min(offset + length, cell_start + self.cell), cell_start)

    def _put_cell(self, out: np.ndarray, offset: int, length: int,
                  u: int, stripe: int, cell: np.ndarray) -> int:
        """Copy the part of data unit u's `cell` of `stripe` that the
        range covers to its place in `out`; returns the bytes copied."""
        a, b, cell_start = self._cell_span(offset, length, u, stripe)
        if a >= b:
            return 0
        out[a - offset : b - offset] = cell[a - cell_start : b - cell_start]
        return b - a

    def _count_copy(self, nbytes: int, site: str) -> None:
        if nbytes:
            hostmem.count_copy(nbytes, site=f"ec_reader.{site}", warn=False)

    def _read_range_into(self, out: np.ndarray, offset: int, length: int,
                         missing_data: list[int]) -> None:
        """Fill `out` with user bytes [offset, offset+length): only the
        cells intersecting the range move over the wire, each once, and
        on degraded groups only the covering stripes are reconstructed."""
        row = self.k * self.cell
        s0 = offset // row
        s1 = (offset + length - 1) // row
        # reconstruct ONLY the stripes where a missing unit's cell
        # actually intersects the range — a ranged read that never
        # touches the missing unit costs no recovery at all
        def touched(u: int, s: int) -> bool:
            a, b, _ = self._cell_span(offset, length, u, s)
            return a < b

        need_rec = [s for s in range(s0, s1 + 1)
                    if any(touched(u, s) for u in missing_data)]
        #: stripe -> the data units whose cell of it the recovery put
        #: in `out`: the lost units' (decoded) and the survivors' it
        #: read for the decode (the reference's reconstructed-stripe
        #: stream serves data cells out of its stripe buffers too)
        placed = (self._recover_into(out, offset, length, missing_data,
                                     need_rec) if need_rec else {})
        # what the recovery did not read is fetched here: stripes
        # outside need_rec, data units its plan left out (health,
        # topology, an LRC local repair)
        window = 8  # stripes prefetched per unit per RPC (bounds memory)
        for w0 in range(s0, s1 + 1, window):
            stripes = range(w0, min(w0 + window, s1 + 1))
            wanted = [(s, i) for s in stripes for i in range(self.k)
                      if i not in placed.get(s, ()) and touched(i, s)]
            if self._batch_reads:
                # one batched RPC per needed unit, concurrently; a unit
                # is needed only where the range touches its cells
                needed: dict[int, list[int]] = {}
                for s, i in wanted:
                    if i not in self._failed:
                        needed.setdefault(i, []).append(s)
                if needed:
                    self._prefetch_bounded(needed)
            copied = 0
            for s, i in wanted:
                copied += self._put_cell(out, offset, length, i, s,
                                         self._read_cell_hedged(i, s))
            self._count_copy(copied, "fetched_cell")

    def _recover_into(self, out: np.ndarray, offset: int, length: int,
                      targets: list[int],
                      stripes: list[int]) -> dict[int, set[int]]:
        """Reconstruct the lost data units `targets` of `stripes` into
        `out`, and copy there as well the surviving DATA cells that the
        recovery read for its decode, out of each survivor batch while
        that batch's decode is queued and on the device: no cell crosses
        the wire a second time. Returns stripe -> data units in `out`.

        A unit that fails mid-recovery restarts the plan and every
        batch comes again (recover_cells_iter), so each stripe's entry
        is the LAST plan's: a cell that only an abandoned attempt
        copied is not in it, and the caller fetches or reconstructs
        that cell like any other."""
        placed: dict[int, set[int]] = {}
        tally = self._tally

        def assemble(part: str, sb, cols, src) -> tuple[int, int]:
            # a leaf that only copies memory, one a pass over a batch
            # (its surviving data cells, then its decoded cells), never
            # one a cell, in a costed operation alone: its wall less
            # its CPU is time this thread was runnable and not running
            # (PERF.md section 3)
            with Tracer.instance().cost_leaf(
                    "ec:assemble", part=part,
                    cells=len(sb) * len(cols)) as sp:
                copied, cells, strokes = self._put_cells(
                    out, offset, length, sb, cols, src)
                if sp is not None:
                    sp.tags.update(bytes=copied, strokes=strokes)
            OPS.counter("assemble_cells").inc(cells)
            OPS.counter("assemble_strokes").inc(strokes)
            return copied, cells

        def take_survivors(sb, valid, batch) -> None:
            data = [(vi, u) for vi, u in enumerate(valid) if u < self.k]
            copied, cells = assemble("survivors", sb, data, batch)
            tally.cells_reused += cells
            for s in sb:
                placed[s] = {u for _, u in data}
            self._count_copy(copied, "reuse_survivor")

        # exclude_stragglers=False: a straggling survivor propagates to
        # read()'s retry loop, which folds it into missing_data so the
        # NEXT attempt reconstructs every missing unit in one batched
        # decode instead of recovering twice
        for sb, (rec, _crcs) in self.recover_cells_iter(
                targets, stripes, exclude_stragglers=False,
                on_survivors=take_survivors):
            copied, _ = assemble("decoded", sb, list(enumerate(targets)),
                                 rec)
            for s in sb:
                placed[s].update(targets)
            self._count_copy(copied, "recovered_cell")
        return placed

    def _put_cells(self, out: np.ndarray, offset: int, length: int,
                   sb: Sequence[int], cols: Sequence[tuple[int, int]],
                   src: np.ndarray) -> tuple[int, int, int]:
        """Copy `src[bi, ci]`, data unit u's cell of stripe `sb[bi]`
        for every (ci, u) of `cols`, to its place in `out`. Stripes
        that lie whole inside the range go in strokes: `out` seen as
        [stripe, k, cell], every run of columns whose units are
        consecutive too is ONE assignment over all of a run of
        consecutive stripes (lost units cut the columns into at most
        e + 1 runs); a stripe the range cuts goes cell by cell.
        Returns (bytes copied, cells with a byte in the range, the
        assignments that moved them)."""
        row = self.k * self.cell
        first = -(-offset // row)  # the stripes whole inside the range
        last = (offset + length) // row
        out3 = None
        if last > first and out.flags.c_contiguous:
            out3 = out[first * row - offset:last * row - offset].reshape(
                last - first, self.k, self.cell)
        runs: list[tuple[int, int, int]] = []  # (column, unit, width)
        for ci, u in cols:
            if runs and (ci, u) == (runs[-1][0] + runs[-1][2],
                                    runs[-1][1] + runs[-1][2]):
                runs[-1] = (runs[-1][0], runs[-1][1], runs[-1][2] + 1)
            else:
                runs.append((ci, u, 1))
        copied = cells = strokes = 0
        b0 = 0
        while b0 < len(sb):
            s0 = sb[b0]
            if out3 is None or not first <= s0 < last:
                for ci, u in cols:
                    n = self._put_cell(out, offset, length, u, s0,
                                       src[b0, ci])
                    copied += n
                    cells += bool(n)
                    strokes += bool(n)
                b0 += 1
                continue
            b1 = b0 + 1
            while b1 < len(sb) and sb[b1] == sb[b1 - 1] + 1 \
                    and sb[b1] < last:
                b1 += 1
            for ci, u, w in runs:
                out3[s0 - first:s0 - first + b1 - b0, u:u + w] = \
                    src[b0:b1, ci:ci + w]
            strokes += len(runs)
            cells += (b1 - b0) * len(cols)
            copied += (b1 - b0) * len(cols) * self.cell
            b0 = b1
        return copied, cells, strokes

    def _read_cell_checked(self, u: int, stripe: int) -> np.ndarray:
        try:
            return self._read_cell(u, stripe)
        except (StorageError, KeyError, OSError) as e:
            if isinstance(e, StorageError) \
                    and e.code == resilience.DEADLINE_EXCEEDED:
                raise  # spent budget is the op's verdict, not the unit's
            raise _UnitReadError(u, e)

    def _prefetch_bounded(self, needed: dict[int, list[int]]) -> None:
        """Concurrent per-unit batched prefetch, bounded by the hedge
        delay: a straggling peer's prefetch is ABANDONED (it finishes
        on the orphaned pool; whatever it delivers still lands in the
        cell cache) instead of stalling the window behind it — the
        cells it failed to deliver take the hedged per-cell path."""
        pool = self._ensure_pool()
        futs = [self._submit_act(pool, self._prefetch_unit, u, ss)
                for u, ss in needed.items()]
        nodes = self.group.pipeline.nodes
        # the batched RPC moves up to `window` cells: scale the one-RPC
        # hedge delay by the deepest request so healthy bulk prefetches
        # are never cut short
        depth = max(len(ss) for ss in needed.values())
        delay = max(1, depth) * max(
            self._health.hedge_delay_s(nodes[u]) for u in needed)
        _done, pending = fwait(set(futs),
                               timeout=resilience.op_timeout(
                                   delay, "prefetch"))
        if pending:
            self._abandon_pool()

    def _ensure_pool(self):
        if self._read_pool is None:
            self._read_pool = ThreadPoolExecutor(
                max_workers=self.k, thread_name_prefix="ec-read")
        return self._read_pool

    def _abandon_pool(self) -> None:
        """Walk away from a pool with straggling reads still on it: the
        losers finish on the orphaned pool and their results are
        discarded; the next attempt gets fresh workers instead of
        queueing behind the stragglers. (Same teardown as _close_pool —
        the distinct name marks intent at the call sites.)"""
        self._close_pool()

    def _submit_act(self, pool, fn, *args):
        """Submit with the operation deadline AND trace context
        re-activated on the worker (neither contextvars nor the
        thread-local span stack cross executor threads)."""
        d = self._deadline
        ctx = Tracer.instance().handoff()

        def run():
            with resilience.activate(d), Tracer.instance().activate(ctx):
                return fn(*args)

        return pool.submit(run)

    # ---------------------------------------------------------------- hedging
    def _read_cell_hedged(self, u: int, stripe: int) -> np.ndarray:
        """Data-cell read racing the owning peer against decode-from-
        parity: the primary fetch runs immediately; once it exceeds the
        peer's hedge delay (P95 latency EWMA, floored by
        OZONE_TPU_HEDGE_MS) and enough other units are alive to decode
        without it, a single-stripe decode of the same cell fires —
        first result wins, the loser's bytes are discarded (the
        tail-at-scale hedged request, generalized to EC where the
        'other replica' is the code itself)."""
        if u in self._failed:
            # excluded earlier in this read (straggler/failure during
            # recovery): fail fast so the outer retry reconstructs it
            # instead of re-paying the straggler's latency per cell
            raise _UnitReadError(u, StorageError(
                "UNAVAILABLE", f"unit {u} excluded earlier in this read"))
        if (u, stripe) in self._cell_cache:
            return self._read_cell(u, stripe)
        if len(self.available_units()) <= self.k:
            # no spare capacity to decode around u: wait the peer out
            return self._read_cell_checked(u, stripe)
        node = self.group.pipeline.nodes[u]
        try:
            win = resilience.HedgeGroup().run(
                lambda: self._read_cell_checked(u, stripe),
                [lambda: self._decode_cell_from_parity(u, stripe)],
                delay_s=self._health.hedge_delay_s(node),
                deadline=self._deadline)
        except _UnitReadError:
            raise
        except (StorageError, KeyError, OSError,
                InsufficientLocationsError) as e:
            if isinstance(e, StorageError) \
                    and e.code == resilience.DEADLINE_EXCEEDED:
                raise  # fail-fast budget expiry, not a unit failure
            # both branches failed: surface as the unit's failure so the
            # outer retry loop excludes it like any other read error
            raise _UnitReadError(u, e)
        if win.index > 0:
            # the decode beat the peer: treat it as a straggler like the
            # recovery path does — exclude the unit so the NEXT cell
            # replans the whole read into one batched reconstruction
            # instead of re-paying a hedge window (or, once the loser's
            # slow success trains the EWMA, the peer's full latency)
            # per remaining cell
            self._failed.add(u)
        return win.value

    def _decode_cell_from_parity(self, u: int, stripe: int) -> np.ndarray:
        """The hedge branch: reconstruct unit u's cell of `stripe` from
        k healthy other units through the batched decode pipeline's
        plan cache (one compiled program per erasure pattern). Peeks
        the prefetch cache and mutates no reader state, so a losing
        decode leaves no trace."""
        with Tracer.instance().span("ec:decode_from_parity", unit=u,
                                    stripe=stripe):
            return self._decode_cell_traced(u, stripe)

    def _decode_cell_traced(self, u: int, stripe: int) -> np.ndarray:
        if self.spec.options.codec == "lrc":
            # the repair planner picks the minimal read set (the local
            # group's survivors when u is singly lost in its group)
            valid = self._choose_valid([u])
        else:
            others = [x for x in self.available_units() if x != u]
            nodes = self.group.pipeline.nodes
            order = {dn: i for i, dn in enumerate(
                self._health.preferred([nodes[x] for x in others]))}
            valid = sorted(sorted(
                others,
                key=lambda x: order.get(nodes[x], len(order)))[: self.k])
            if len(valid) < self.k:
                raise InsufficientLocationsError(
                    f"hedge decode needs {self.k} units, reachable: {valid}")
        fn = make_fused_decoder(self.spec, valid, [u])
        batch = np.zeros((1, len(valid), self.cell), dtype=np.uint8)
        for vi, x in enumerate(valid):
            batch[0, vi] = self._peek_cell(x, stripe)
        # lone-stripe decode at width 1: no linger added to the
        # latency-critical hedge, but concurrent hedges on the same
        # pattern still serialize through one dispatcher instead of
        # contending for the chip
        rec, _crcs = codec_service.wait_result(dispatch.submit(
            codec_service.decode_key(self.spec, valid, (u,)), fn,
            batch, width=1, qos=self._qos, deadline=self._deadline))
        return np.asarray(rec)[0, 0]

    def _fanout_survivors(self, pool, fill_unit, valid: list[int],
                          depth: int) -> None:
        """Run the per-survivor batch reads concurrently, watching for
        stragglers: a unit still pending past its hedge delay while a
        spare survivor is alive is dropped (_StragglerHedge) and the
        batched decode replans around it — hedging into the decode
        pipeline instead of waiting the straggler out. Without a spare
        the read must wait (the straggler is the k-th survivor)."""
        # the fan-in as a stage of its own: what the unit reads
        # (net:read_chunks, on the pool) do not cover — each cell's
        # copy into the decode batch, the pool's hand-offs — is the
        # fan-in's own time, not the caller's (a costed operation's
        # `cost` has the copies apart, as `ec:fill`)
        with Tracer.instance().span("ec:fanout", units=len(valid),
                                    stripes=depth):
            self._fanout_traced(pool, fill_unit, valid, depth)

    def _fanout_traced(self, pool, fill_unit, valid: list[int],
                       depth: int) -> None:
        nodes = self.group.pipeline.nodes
        futs = {self._submit_act(pool, fill_unit, (vi, u)): u
                for vi, u in enumerate(valid)}
        # each stream moves up to `depth` cells (one batched prefetch
        # RPC plus cache-miss fallbacks): scale the one-RPC hedge delay
        # by the batch depth like _prefetch_bounded, or a healthy bulk
        # transfer on a thin link reads as a straggler
        delay = (1 + depth) * max(self._health.hedge_delay_s(nodes[u])
                                  for u in valid)
        delay = resilience.op_timeout(delay, "recover_cells")
        done, pending = fwait(set(futs), timeout=delay)
        if pending:
            spares = [x for x in self.available_units()
                      if x not in valid and self._health.usable(nodes[x])]
            # we can only replan around as many slow survivors as there
            # are spares to take their place; the rest must be waited
            # out (excluding them would sink below k reachable units)
            stragglers = sorted(futs[f] for f in pending)[: len(spares)]
            if stragglers:
                resilience.METRICS.counter("hedges_fired").inc()
                Tracer.instance().event("hedge_fired",
                                        stragglers=stragglers,
                                        spares=spares)
                log.warning(
                    "survivor unit(s) %s straggling past %.3fs; hedging "
                    "into decode via spare unit(s) %s",
                    stragglers, delay, spares)
                self._abandon_pool()
                for f in done:
                    f.result()  # a real error beats a straggler signal
                raise _StragglerHedge(stragglers)
            done2, _ = fwait(set(pending))
            done = set(done) | done2
        for f in done:
            f.result()  # propagate _UnitReadError from the workers

    # ------------------------------------------------------------- degraded
    def _choose_valid(self, erased: Sequence[int]) -> list[int]:
        return self._plan_read(erased)[0]

    def _plan_read(self, erased: Sequence[int]) -> tuple[list[int], str]:
        """(read set, kind of plan): `rs`, or LRC's `local` / `global`
        (`RecoveryTally`)."""
        avail = [u for u in self.available_units() if u not in erased]
        nodes = self.group.pipeline.nodes
        if self.spec.options.codec == "lrc":
            # LRC: the repair planner classifies the pattern — single
            # in-group losses read the group's survivors (group_size
            # units instead of k), everything else grows a minimal
            # global read set.  Health and topology shape only the
            # PREFERENCE order fed to the global path; the local read
            # set is forced by geometry.
            pref = sorted(avail)
            if getattr(self.clients, "nearest_first", None) is not None:
                order = {dn: i for i, dn in
                         enumerate(self.clients.nearest_first(
                             [nodes[u] for u in pref]))}
                pref.sort(key=lambda u: order.get(nodes[u], len(order)))
            usable = {u for u in pref if self._health.usable(nodes[u])}
            if usable:
                pref.sort(key=lambda u: u not in usable)  # stable
            try:
                return lrc_math.plan_valid(
                    self.spec.options, list(erased), avail, prefer=pref)
            except ValueError as e:
                raise InsufficientLocationsError(str(e)) from None
        if len(avail) < self.k:
            raise InsufficientLocationsError(
                f"need {self.k} units, reachable: {avail}, erased: {list(erased)}"
            )
        # `avail` is in unit order, data units first, and every choice
        # below keeps that order among equals: of equally usable,
        # equally near survivors the DATA units are read before parity
        # (as the reference's reconstructed-stripe reader does). A
        # degraded read serves the data cells it decoded from straight
        # out of the survivor batch (_recover_into); a parity cell in
        # their place would be read for the decode alone, and the data
        # cell fetched on top.
        nodes = self.group.pipeline.nodes
        if len(avail) > self.k:
            # breaker consult (non-claiming — candidates that end up
            # sliced out by topology must not consume half-open
            # probes): a peer mid-outage is routed around while spares
            # exist, never excluded when it IS the k-th survivor
            usable = [u for u in avail if self._health.usable(nodes[u])]
            if len(usable) >= self.k:
                avail = usable
        if len(avail) > self.k and \
                getattr(self.clients, "nearest_first", None) is not None:
            # more survivors than needed: read the k topology-nearest
            # (the reference reads expectedDataLocations; with topology
            # it sorts replicas nearest-first — here the survivor choice
            # IS the replica choice)
            # (nearest_first and list.sort are stable: ties stay in
            # unit order)
            order = {dn: i for i, dn in
                     enumerate(self.clients.nearest_first(
                         [nodes[u] for u in avail]))}
            avail.sort(key=lambda u: order.get(nodes[u], len(order)))
            avail = sorted(avail[: self.k])
        return avail[: self.k], "rs"

    def recover_cells(
        self, targets: Sequence[int], stripes: Optional[Sequence[int]] = None,
        exclude_stragglers: bool = True,
    ) -> np.ndarray:
        """Reconstruct full cells of `targets` units for the given stripes
        (default: all). Returns uint8 [num_stripes, len(targets), cell].
        The recoverChunks analog driving offline reconstruction."""
        return self.recover_cells_with_crcs(
            targets, stripes, exclude_stragglers=exclude_stragglers)[0]

    def recover_cells_with_crcs(
        self, targets: Sequence[int], stripes: Optional[Sequence[int]] = None,
        exclude_stragglers: bool = True,
    ) -> tuple[np.ndarray, np.ndarray]:
        """recover_cells plus the per-slice device CRCs of the recovered
        cells [num_stripes, len(targets), cell // bpc] — reconstruction
        writes reuse them so recovered data is never re-checksummed on host."""
        stripes = list(
            stripes if stripes is not None else range(self.num_stripes))
        pos = {s: i for i, s in enumerate(stripes)}
        rec = np.zeros((len(stripes), len(targets), self.cell),
                       dtype=np.uint8)
        crcs: Optional[np.ndarray] = None
        for sb, (r, c) in self.recover_cells_iter(
                targets, stripes, exclude_stragglers=exclude_stragglers):
            if crcs is None:
                crcs = np.zeros(
                    (len(stripes), len(targets)) + c.shape[2:], c.dtype)
            for bi, s in enumerate(sb):
                rec[pos[s]] = r[bi]
                crcs[pos[s]] = c[bi]
        if crcs is None:  # zero stripes requested
            crcs = np.zeros((0, len(targets), 0), np.uint32)
        return rec, crcs

    def recover_cells_iter(
        self, targets: Sequence[int], stripes: Optional[Sequence[int]] = None,
        exclude_stragglers: bool = True,
        on_survivors: Optional[Callable[
            [Sequence[int], list[int], np.ndarray], None]] = None,
    ):
        """Streaming recovery: yields (stripe_batch, (rec, crcs)) per
        decode batch — rec [b, len(targets), cell], crcs [b, len(targets),
        cell // bpc] — so consumers (offline reconstruction) write one
        batch's recovered chunks while the device decodes the next. On a
        unit failure mid-stream the whole recovery restarts with the unit
        excluded and ALL batches are re-yielded; consumers must treat
        stripe indexes as overwrite keys (chunk writes are idempotent).

        `on_survivors(stripe_batch, valid, batch)`, where given, sees
        each survivor batch [b, len(valid), cell] once it is read and
        its decode enqueued, before that decode's results are yielded
        (a degraded read takes its surviving data cells from it). The
        batch is the decoder's input: read it, keep no reference."""
        # refresh per call: a reader reused across operations must not
        # re-activate a PREVIOUS operation's (possibly expired) budget
        self._deadline = resilience.current()
        if self._tally is None or self.recovery is None:
            # one tally a repair; a read() keeps its own over the
            # retries that replan it
            self.recovery = RecoveryTally()
            self._replan_cause = ""
        try:
            # p hard failures plus straggler hedges can both consume
            # attempts; hedges are cheap (detected in one hedge window)
            # so they get their own allowance on top of the p+1 budget
            for _ in range(2 * self.p + 1):
                try:
                    yield from self._recover_batches_once(
                        targets, stripes, on_survivors)
                    return
                except _UnitReadError as e:
                    log.warning(
                        "unit %d failed during recovery (%s); excluding",
                        e.unit,
                        e.cause,
                    )
                    self._failed.add(e.unit)
                    self._replan_cause = "unit_failed"
                except _StragglerHedge as e:
                    self._replan_cause = "hedge"
                    # not a failure: the slow survivors are dropped and
                    # the decode replans around spares; their abandoned
                    # reads resolve (and are discarded) in the background.
                    # Counted as a REPLAN, not a hedge win — hedges_won
                    # is reserved for a hedge future actually beating
                    # its primary (HedgeGroup), and the replanned decode
                    # hasn't succeeded yet at this point.
                    resilience.METRICS.counter("straggler_replans").inc()
                    Tracer.instance().event("straggler_replan",
                                            units=e.units)
                    self._failed.update(e.units)
                    if not exclude_stragglers:
                        # the CALLER replans (read() folds the straggler
                        # into missing_data and reconstructs everything
                        # in one batched pass instead of two)
                        raise
            raise InsufficientLocationsError(
                f"recovery failed; failed units {sorted(self._failed)}"
            )
        finally:
            self._close_pool()

    def _recover_batches_once(
        self, targets: Sequence[int],
        stripes: Optional[Sequence[int]] = None, on_survivors=None,
    ):
        """One recovery attempt as a depth-1 device pipeline: survivor
        reads of batch N+1 run while batch N decodes on device and its
        results pull to host (the writer's _flush_queue overlap mirrored
        onto the read path). One device dispatch per stripe batch — not
        per stripe — with the per-pattern plan coming from the
        persistent decode-plan cache."""
        stripes = list(
            stripes if stripes is not None else range(self.num_stripes))
        valid, kind = self._plan_read(list(targets))
        self.recovery.plan(kind, len(valid), self._replan_cause)
        pipe = self._decode_pipe(valid, list(targets))
        pool = self._ensure_pool()
        for sb in batched(stripes, self._decode_batch):
            # width = len(valid), not k: an LRC local repair reads only
            # the lost unit's group (group_size survivors)
            shape = (len(sb), len(valid), self.cell)
            # pool memory, NOT zeroed: a recycled batch holds another
            # key's bytes, and every row is written below before the
            # decoder or `on_survivors` sees it. An attempt that a
            # failed unit or a straggler hedge abandons leaves its batch
            # to the reader threads still writing into it: `fill_unit`
            # pins it, and its pages go back when the last one is done.
            flat, fresh = hostmem.pool().lease_array(math.prod(shape))
            batch = flat.reshape(shape)
            if fresh and self._tally is not None:
                self._tally.fresh_bytes += flat.size

            def fill_unit(vi_u):
                vi, u = vi_u
                # one batched ReadChunks for the unit's cells of this
                # batch first, received straight into the unit's column
                # where the transport can: the batch is then the only
                # host buffer those cells pass on their way in
                served, in_place = self._prefetch_unit(
                    u, sb, rows={s: batch[bi, vi]
                                 for bi, s in enumerate(sb)})
                # what the receive left: one leaf a unit stream (a cell
                # the batched read could not serve is a `net:read_chunk`
                # child, not this span's self)
                with Tracer.instance().cost_leaf(
                        "ec:fill", unit=u, cells=len(sb),
                        in_place=in_place,
                        copied=len(sb) - in_place):
                    for bi, s in enumerate(sb):
                        # EVERY [bi, vi] is written a whole cell long:
                        # a served chunk's row keeps what the transport
                        # wrote and has its tail zeroed, any other cell
                        # is assigned whole, an absent or short one
                        # zero-padded (`_fetch_cell`, `_cell_array`),
                        # so no recycled byte stays
                        info = served.get(s)
                        if info is None:
                            batch[bi, vi] = self._read_cell_checked(u, s)
                        elif info.length < self.cell:
                            batch[bi, vi, info.length:] = 0
                OPS.counter("fill_cells").inc(len(sb))
                OPS.counter("survivor_cells_in_place").inc(in_place)
                # an in-place receive of the stream's cells is one
                # stroke, every cell copied one more
                OPS.counter("fill_strokes").inc(
                    bool(in_place) + len(sb) - in_place)

            # one reader thread per survivor unit: the k unit streams
            # come off k DIFFERENT datanodes, so the read fan-in costs
            # the slowest node, not the sum (the reference reads
            # survivors with parallel stream readers in
            # ECBlockReconstructedStripeInputStream) — and a survivor
            # still pending past its hedge delay is dropped for a spare
            # instead of stalling the whole batch behind it.
            self._fanout_survivors(pool, fill_unit, valid, len(sb))
            out = pipe.submit(batch, sb)
            if on_survivors is not None:
                # the batch's decode is queued or on the device by now
                on_survivors(sb, valid, batch)
            if out is not None:
                yield out
        out = pipe.drain()
        if out is not None:
            yield out

    def _decode_pipe(self, valid: list[int], targets: list[int]):
        """The recovery dispatch pipeline. Through the door
        (`parallel/dispatch.py`) this read's decode batches share device
        dispatches with every other operation on the same erasure
        pattern, a reconstruction storm being MANY groups with ONE
        pattern. A caller-supplied raw `mesh` (the datanode daemons'
        route, ROADMAP D2b) runs the SPMD call per operation, unqueued."""
        if self.mesh is not None:
            return DeviceBatchPipeline(self._mesh_decode_fn(valid, targets))
        return dispatch.pipeline(
            codec_service.decode_key(self.spec, valid, targets),
            make_fused_decoder(self.spec, valid, targets),
            width=self._decode_batch, qos=self._qos,
            executor=self._executor)

    def _mesh_decode_fn(self, valid: list[int], targets: list[int]):
        """Multi-chip decode (ECReconstructionCoordinator.java:146 run on
        a device mesh instead of one device): DP shards the stripe batch;
        the SP ring shards SURVIVORS (one group per chip — the layout
        where each chip fronts one source datanode's bytes). Returns a
        device-array fn pluggable into the decode pipeline."""
        from ozone_tpu.parallel import sharded

        if self.use_ring:
            return sharded.make_ring_decoder(
                self.spec, valid, targets, self.mesh)
        inner = sharded.make_sharded_decoder(
            self.spec, valid, targets, self.mesh)
        n = self.mesh.devices.size

        def fn(batch: np.ndarray):
            padded, orig = sharded.pad_batch(batch, n)
            rec, crcs = inner(padded)
            # lazy device slices: the pipeline pulls them to host later
            return rec[:orig], crcs[:orig]

        return fn

    # ---------------------------------------------------------------- ranged
    def read(self, offset: int, length: int,
             out: Optional[np.ndarray] = None,
             out_fresh: bool = False) -> np.ndarray:
        """Cell-granular range read in user-byte space: only the stripes
        covering [offset, offset+length) are fetched, and on degraded
        groups only those stripes are reconstructed (the reference's
        ECBlockInputStream positioned reads, not whole-block reads).
        Units that fail mid-read are excluded and retried, up to p
        times. The bytes are written to `out` where given (a writable
        one-dimensional uint8 array of `length` bytes: the caller's
        slice of the key's one buffer; `out_fresh` where those pages
        were mapped new for this operation, for the span's
        `fresh_bytes`) and `out` is returned; without it the read
        leases one from the host buffer pool, which the returned array
        pins."""
        if offset < 0 or length < 0 or \
                offset + length > self.group.length:
            raise ValueError("range out of bounds")
        if out is None:
            out, out_fresh = hostmem.pool().lease_array(length)
        elif (out.dtype != np.uint8 or out.shape != (length,)
              or not out.flags.writeable):
            raise ValueError(
                f"out must be a writable uint8 array of {length} bytes")
        if length == 0:
            return out
        # refresh per call (see recover_cells_iter): never re-activate a
        # previous operation's expired budget on a reused reader
        self._deadline = resilience.current()
        tally = self._tally = _ReadTally()
        tally.fresh_bytes = length if out_fresh else 0
        self.recovery = None
        with Tracer.instance().span("ec:read", offset=offset,
                                    bytes=length) as sp:
            try:
                return self._read_traced(out, offset, length)
            finally:
                self._tally = None
                sp.tags.update(cells_reused=tally.cells_reused,
                               cells_fetched=tally.cells_fetched,
                               fresh_bytes=tally.fresh_bytes)
                if self.recovery is not None:  # a degraded read
                    sp.tags.update(self.recovery.tags())
                OPS.counter("get_cells_reused").inc(tally.cells_reused)
                OPS.counter("get_cells_fetched").inc(tally.cells_fetched)
                OPS.counter("get_wire_bytes").inc(tally.wire_bytes)

    def _read_traced(self, out: np.ndarray, offset: int,
                     length: int) -> np.ndarray:
        try:
            # p hard failures plus straggler hedges both consume
            # attempts (hedges are detected within one hedge window,
            # so the extra allowance is cheap)
            for _ in range(2 * self.p + 1):
                avail = set(self.available_units())
                missing_data = [u for u in range(self.k) if u not in avail]
                try:
                    self._read_range_into(out, offset, length, missing_data)
                    return out
                except _UnitReadError as e:
                    log.warning(
                        "unit %d failed (%s); excluding and retrying",
                        e.unit, e.cause
                    )
                    self._failed.add(e.unit)
                    self._replan_cause = "unit_failed"
                except _StragglerHedge:  # ozlint: allow[error-swallowing] -- handled by design: units already excluded and counted by the recovery layer
                    # units already excluded + counted by the recovery
                    # layer: the retry reconstructs them (and anything
                    # already missing) in one batched decode pass
                    pass
            raise InsufficientLocationsError(
                f"read failed; failed units {sorted(self._failed)}"
            )
        finally:
            self._close_pool()


def unit_true_lengths(group: BlockGroup, options: CoderOptions) -> list[int]:
    """True byte length of every unit's block: data blocks striped lengths,
    parity blocks full cells per stripe."""
    k, p, cell = options.data_units, options.parity_units, options.cell_size
    num_stripes = -(-group.length // (k * cell))
    data = block_lengths(group.length, k, cell)
    return data + [num_stripes * cell] * p
