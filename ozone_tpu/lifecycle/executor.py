"""Tiering executor: batched replicated->EC transitions on device.

The datapath half of the lifecycle subsystem. Where `client/re_encode.py`
converts ONE key per call, this executor packs stripe windows from MANY
keys into each pipeline submission, so a sweep over
thousands of small cold keys still drives the fused encode+CRC kernel
at full batch width. Every dispatch has the SAME [window, k, cell] shape — the
final partial window is zero-padded — so the whole sweep compiles ONE
device program, exactly like the decode-plan cache keeps repair to one.
The window is the width of ONE dispatch of the lane the door
(`parallel/dispatch.py`) routed the sweep to: the service's batch on one
chip, that times the devices on the mesh.

A converted key is laid out as a PUT of the same bytes would lay it out
(`ECKeyWriter`'s geometry: `block_size // cell` stripes a block group,
only the key's last stripe partial), whatever blocks the replicated
source was cut into.

Per key the flow is the rewrite flow with a fence:

  read replicated source (window-at-a-time, throttled)
    -> fused encode+CRC on device (batched across keys)
    -> write EC units (write_unit_stream, overlapped with the next
       window's device pass by the depth-1 pipeline)
    -> putBlock commits, then CommitKey with the rewrite fence
       (expect_object_id + expect_generation): a concurrent user
       overwrite aborts the transition instead of clobbering it, and
       the freshly written EC blocks ride the deletion chain.

The OLD replicated blocks are released only after the EC commit acks:
finalize_commit routes the superseded version into the deleted table,
and the OM KeyDeletingService hands its blocks to SCM's DeletedBlockLog
(`scm/block_deletion.py`) from there — never before.

Where the time goes: every key is an operation root `tier:key` (open to
commit ack; `FlightRecorder.operations`) whose stages are `tier:read`
(the source's bytes off a replica), `tier:pack` (their copy into the
window), the scheduler's queue-wait and dispatch spans of every window
the key rode, `tier:write` (the nine unit streams of one window's
stripes), `tier:finalize` (the `PutBlock`s) and the OM's RPCs; what is
left to the root is the sweeper's work on OTHER keys meanwhile (one
thread takes them in turn). The four `tier:*` stages are also `Stage`s
of registry `lifecycle` (`read_seconds`, ...), beside the counters
`stripes_packed`, `pad_stripes`, `windows_submitted`, `keys_split`.
"""

from __future__ import annotations

import logging
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np

from ozone_tpu.client import resilience
from ozone_tpu.om import requests as rq
from ozone_tpu.scm.pipeline import ReplicationConfig, ReplicationType
from ozone_tpu.storage.ids import BlockData, StorageError
from ozone_tpu.utils.checksum import Checksum, ChecksumType
from ozone_tpu.utils.metrics import registry
from ozone_tpu.utils.tracing import Span, Stage, Tracer

log = logging.getLogger(__name__)

#: shared with service.py: every lifecycle signal in ONE registry
METRICS = registry("lifecycle")


def tier_batch_size() -> int:
    """Stripes a device gets of one tiering dispatch
    (OZONE_TPU_TIER_BATCH), the width the sweep's lane is asked for;
    falls back to the decode pipeline's batch knob so both background
    device consumers share one tuning surface by default."""
    from ozone_tpu.codec.pipeline import decode_batch_size
    from ozone_tpu.utils.config import env_int

    n = env_int("OZONE_TPU_TIER_BATCH", 0)
    return max(1, n) if n > 0 else decode_batch_size()


class _DeadlineWithStats(StorageError):
    """DEADLINE_EXCEEDED carrying the partial stats of the drained
    work, so the sweeper can book what DID land before it stops
    (without advancing its cursor past the unprocessed remainder)."""

    def __init__(self, stats: dict):
        super().__init__(
            "DEADLINE_EXCEEDED",
            "lifecycle sweep budget spent mid-batch")
        self.stats = stats


#: how often one `transition_keys` call takes a key again whose EC
#: container closed under its writes (the SCM closes a container once
#: its blocks are all allocated, whoever still writes to them; a PUT
#: meets the same and rolls over to a new group)
CLOSED_CONTAINER_RETRIES = 2


@dataclass
class _GroupState:
    """One target EC group mid-write."""

    #: the BlockGroup, allocated when the group's first stripes are
    #: written out (never a window ahead of them: see above)
    ng: object
    length: int
    lengths: list[int]  # per-unit user-data lengths
    stripes_total: int
    stripes_emitted: int = 0
    unit_infos: list[list] = field(default_factory=list)


@dataclass
class _KeyState:
    volume: str
    bucket: str
    key: str
    info: dict
    session: object
    groups: list[_GroupState] = field(default_factory=list)
    groups_done: int = 0
    total: int = 0
    failed: bool = False
    #: the sweep's stats dict this key reports into
    stats: dict = field(default_factory=dict)
    #: the key's operation root `tier:key`, until it is ended
    span: Optional[Span] = None
    #: `child_of` context of that root: its stages' parent
    ctx: str = ""
    #: the dispatches its stripes rode (more than one: `keys_split`)
    windows: set = field(default_factory=set)
    #: the rule's scheme, and which try of this call this is
    target: str = ""
    attempt: int = 0


@contextmanager
def _on_key(ks: _KeyState):
    """The sweeper's work on ONE key: what is traced inside (an OM RPC,
    a `tier:*` stage) is a child of that key's root."""
    with Tracer.instance().activate(ks.ctx):
        yield


@contextmanager
def _stage(ks: _KeyState, name: str):
    """One leaf stage `tier:<name>` of the sweeper thread, booked twice:
    a span of the key's trace and seconds of `lifecycle/<name>_seconds`
    (and, in a profiler session, an event on the thread's line)."""
    with Stage(f"tier:{name}", METRICS.histogram(f"{name}_seconds")), \
            _on_key(ks), Tracer.instance().span(f"tier:{name}"):
        yield


def _end_key(ks: _KeyState, outcome: str) -> None:
    """Close the key's root, once: committed, conflicted or failed."""
    root, ks.span = ks.span, None
    if root is None:
        return
    root.tags["outcome"] = outcome
    if len(ks.windows) > 1:
        METRICS.counter("keys_split").inc()
    Tracer.instance().end_operation(root)


class _Source:
    """A replicated key's bytes as one range, over the blocks it was
    written in."""

    def __init__(self, groups: list, clients):
        from ozone_tpu.client.replicated import ReplicatedKeyReader

        self._readers = []
        self.length = 0
        for g in groups:
            self._readers.append((self.length,
                                  ReplicatedKeyReader(g, clients)))
            self.length += g.length

    def read(self, lo: int, n: int) -> list[tuple[int, np.ndarray]]:
        """[(offset in the key, bytes)] covering [lo, lo + n): one part
        per source block the range touches, copied nowhere."""
        parts = []
        for start, reader in self._readers:
            a = max(lo, start)
            b = min(lo + n, start + reader.group.length)
            if a < b:
                parts.append((a, reader.read(a - start, b - a)))
        return parts


class TieringExecutor:
    """Feeds eligible replicated keys through the batched fused encode.

    `transition_keys([(volume, bucket, key, target_scheme), ...])`
    converts each replicated key to its rule's EC scheme; keys sharing
    a (scheme, checksum) spec share device dispatches. Returns stats:
    transitioned / conflicts / failed / skipped / bytes / dispatches.
    """

    def __init__(self, om, clients, throttle=None):
        self.om = om
        self.clients = clients
        #: utils.throttle.Throttle pacing source reads so tiering never
        #: starves foreground traffic; None = unthrottled
        self.throttle = throttle
        #: test hook: called as fn(key_state) right before each key's
        #: EC commit (the fence regression tests race an overwrite here)
        self.pre_commit_hook: Optional[Callable] = None
        #: HA barrier invoked after each block allocation: the RPC path
        #: gets this from the OM service (SCM decision records must be
        #: quorum-committed before data lands on the allocation); the
        #: in-daemon executor must honor the same ordering
        self.alloc_barrier: Optional[Callable] = None
        #: device dispatches issued by the last transition_keys call
        self.last_dispatches = 0
        #: stripes a window of the newest packer holds: one dispatch of
        #: the lane the door routed it to
        self.last_window = 0

    # ------------------------------------------------------------- entry
    def transition_keys(self, work: list[tuple]) -> dict:
        """Transition `work`; raises DEADLINE_EXCEEDED (after draining
        the in-flight device batches) when the sweep budget expires
        with items unprocessed — the caller must NOT advance its cursor
        past them (they were neither transitioned nor failed)."""
        stats = {"transitioned": 0, "conflicts": 0, "failed": 0,
                 "skipped": 0, "bytes": 0, "dispatches": 0}
        # one packer per fused spec: keys sharing scheme+checksum share
        # device batches (the common case: one rule, one spec)
        packers: dict[tuple, _SpecPacker] = {}
        expired = False
        for attempt in range(CLOSED_CONTAINER_RETRIES + 1):
            expired = self._pack_all(work, packers, stats, attempt)
            for packer in packers.values():
                try:
                    packer.flush()
                except StorageError as e:
                    if e.code != resilience.DEADLINE_EXCEEDED:
                        raise
                    # the budget ended with a window on the device: its
                    # keys stay as they were and re-tier next sweep
                    expired = True
            # keys whose EC container closed under their writes: again,
            # from the source, into a container that is open
            work = [w for packer in packers.values()
                    for w in packer.take_retries()]
            if expired or not work:
                break
        stats["dispatches"] = sum(p.dispatches for p in packers.values())
        self.last_dispatches = stats["dispatches"]
        for packer in packers.values():
            for ks in packer.keys:
                _end_key(ks, "abandoned")  # the budget's; else a no-op
        if expired:
            # AFTER the drain: packed keys committed, but unprocessed
            # work items must bounce the caller's cursor advance
            raise _DeadlineWithStats(stats)
        return stats

    def _pack_all(self, work: list[tuple], packers: dict, stats: dict,
                  attempt: int) -> bool:
        """Read and pack every key of `work`; True when the sweep budget
        ran out first."""
        from ozone_tpu.client.re_encode import re_encode_xor_key_to_rs

        expired = False
        for volume, bucket, key, target in work:
            try:
                resilience.check_deadline("lifecycle_transition")
            except StorageError:
                # budget spent between keys: stop packing but still
                # DRAIN below — keys already in flight on the device
                # must finalize and commit, not be abandoned
                expired = True
                break
            try:
                info = self.om.lookup_key(volume, bucket, key)
            except rq.OMError:
                stats["skipped"] += 1  # deleted since the scan
                continue
            except StorageError as e:
                # the same answer from an OM behind RPC, whose client
                # raises the wire's StorageError; anything else (the OM
                # cannot be reached) is the sweep's to know
                if e.code not in (rq.KEY_NOT_FOUND, rq.BUCKET_NOT_FOUND,
                                  rq.VOLUME_NOT_FOUND):
                    raise
                stats["skipped"] += 1
                continue
            try:
                repl = ReplicationConfig.parse(info["replication"])
            except ValueError:
                stats["skipped"] += 1
                continue
            if repl.type is ReplicationType.EC:
                if repl.ec.codec == "xor":
                    # XOR(1) sources take the fused decode->re-encode
                    # path per key (its batch geometry is its own)
                    try:
                        re_encode_xor_key_to_rs(
                            self.om, self.clients, volume, bucket, key,
                            ec=target)
                        stats["transitioned"] += 1
                        stats["bytes"] += int(info.get("size", 0))
                        METRICS.counter("transitions").inc()
                        METRICS.counter("bytes_tiered").inc(
                            int(info.get("size", 0)))
                    except (rq.OMError, StorageError) as e:
                        if getattr(e, "code", "") == rq.KEY_MODIFIED:
                            # the re-encode's rewrite fence lost to a
                            # concurrent user overwrite: expected race,
                            # same accounting as the packer path
                            METRICS.counter("transition_conflicts").inc()
                            stats["conflicts"] += 1
                            continue
                        log.warning("lifecycle: xor re-encode of "
                                    "%s/%s/%s failed: %s",
                                    volume, bucket, key, e)
                        stats["failed"] += 1
                        METRICS.counter("transition_failures").inc()
                else:
                    stats["skipped"] += 1  # already RS-coded
                continue
            if not info.get("block_groups"):
                stats["skipped"] += 1  # empty key / directory marker
                continue
            packer = self._packer_for(packers, info, target, stats)
            try:
                self._pack_key(packer, volume, bucket, key, info, target,
                               attempt)
            except (rq.OMError, StorageError, OSError, KeyError) as e:
                if isinstance(e, StorageError) \
                        and e.code == resilience.DEADLINE_EXCEEDED:
                    # a spent budget is NOT a failure: the key was
                    # neither transitioned nor broken, and counting it
                    # would make transition_failures climb on every
                    # budget-bounded sweep of a large namespace
                    expired = True
                    break  # drain what's in flight below
                log.warning("lifecycle: transition of %s/%s/%s failed: "
                            "%s", volume, bucket, key, e)
                stats["failed"] += 1
                METRICS.counter("transition_failures").inc()
        return expired

    # ------------------------------------------------------------ packing
    def _packer_for(self, packers: dict, info: dict, target: str,
                    stats: dict) -> "_SpecPacker":
        from ozone_tpu.codec.fused import (
            FusedSpec,
            effective_bpc,
            make_fused_encoder,
        )

        conf = ReplicationConfig.parse(target)
        ctype = ChecksumType(info.get("checksum_type", "CRC32C"))
        cell = conf.ec.cell_size
        bpc = effective_bpc(cell, info.get("bytes_per_checksum",
                                           16 * 1024))
        key = (target, ctype.value, bpc)
        packer = packers.get(key)
        if packer is None:
            spec = FusedSpec(conf.ec, ctype, bpc)
            packer = packers[key] = _SpecPacker(
                self, make_fused_encoder(spec), conf.ec, ctype, bpc,
                stats, spec)
            self.last_window = packer.window
        return packer

    def _pack_key(self, packer: "_SpecPacker", volume: str, bucket: str,
                  key: str, info: dict, target: str,
                  attempt: int = 0) -> None:
        tracer = Tracer.instance()
        root = tracer.begin_operation("tier:key", volume=volume,
                                      bucket=bucket, key=key)
        ks = _KeyState(volume, bucket, key, info, None, span=root,
                       ctx=tracer.context(root), target=target,
                       attempt=attempt)
        ks.stats = packer.stats
        try:
            with _on_key(ks):
                session = ks.session = self.om.open_key(
                    volume, bucket, key, replication=target)
            # rewrite fence: commit only if the live row is still this
            # version (object id AND generation, see check_rewrite_fence)
            session.expect_object_id = info.get("object_id", "")
            session.expect_generation = int(info.get("generation", -1))
            self._pack_key_groups(packer, ks, info)
        except BaseException:
            # mid-key failure: windows already packed for this key must
            # not finalize/commit a partial version (their allocated
            # blocks are reclaimed by scrubbing, like any dead write)
            ks.failed = True
            _end_key(ks, "failed")
            raise

    def _pack_key_groups(self, packer: "_SpecPacker", ks: _KeyState,
                         info: dict) -> None:
        from ozone_tpu.client.ec_writer import block_lengths

        k, p, cell = (packer.opts.data_units, packer.opts.parity_units,
                      packer.opts.cell_size)
        stripe = k * cell
        source = _Source(self.om.key_block_groups(info), self.clients)
        # ECKeyWriter's geometry, not the source's blocks: the key reads
        # and repairs like one PUT as EC, and only its last stripe is
        # partial
        group_bytes = max(1, self.om.block_size // cell) * stripe
        window = packer.window
        for g0 in range(0, max(1, source.length), group_bytes):
            length = min(group_bytes, source.length - g0)
            stripes = max(1, -(-length // stripe))
            gs = _GroupState(
                ng=None, length=length,
                lengths=block_lengths(length, k, cell)
                + [stripes * cell] * p,
                stripes_total=stripes,
                unit_infos=[[] for _ in range(k + p)],
            )
            ks.groups.append(gs)
            for s0 in range(0, stripes, window):
                resilience.check_deadline("lifecycle_window")
                n = min(window, stripes - s0)
                lo = g0 + s0 * stripe
                want = min(n * stripe, g0 + length - lo)
                if self.throttle is not None and want > 0:
                    self.throttle.take(want)
                with _stage(ks, "read"):
                    parts = source.read(lo, want) if want > 0 else []
                packer.add(ks, gs, s0, n, lo, parts)
            ks.total += length

    def _open_group(self, ks: _KeyState, gs: _GroupState) -> None:
        from ozone_tpu.client.ec_writer import (
            StripeWriteError,
            create_group_containers,
        )

        with _on_key(ks):
            gs.ng = self.om.allocate_block(ks.session)
            if self.alloc_barrier is not None:
                self.alloc_barrier()
            try:
                create_group_containers(self.clients, gs.ng,
                                        replica_indexed=True)
            except StripeWriteError as e:
                # members that cannot be reached (or a spent budget)
                # fail this key, never the sweep
                raise e.cause from e

    # ----------------------------------------------------------- finalize
    def _finalize_group(self, ks: _KeyState, gs: _GroupState) -> None:
        with _stage(ks, "finalize"):
            for u, dn_id in enumerate(gs.ng.pipeline.nodes):
                self.clients.get(dn_id).put_block(
                    BlockData(gs.ng.block_id, gs.unit_infos[u],
                              block_group_length=gs.length))
        gs.ng.length = gs.length
        ks.groups_done += 1
        if ks.groups_done == len(ks.groups):
            self._commit_key(ks)

    def _commit_key(self, ks: _KeyState) -> None:
        if self.pre_commit_hook is not None:
            self.pre_commit_hook(ks)
        try:
            with _on_key(ks):
                self.om.commit_key(ks.session,
                                   [gs.ng for gs in ks.groups], ks.total)
        except (rq.OMError, StorageError) as e:
            # (StorageError: the same refusal from an OM behind RPC)
            if e.code == rq.KEY_MODIFIED:
                # concurrent overwrite won: the fence discarded our EC
                # version into the deletion chain; the user's data is
                # authoritative
                METRICS.counter("transition_conflicts").inc()
                ks.failed = True
                ks.stats["conflicts"] += 1
                _end_key(ks, "conflict")
                return
            raise
        _end_key(ks, "transitioned")
        METRICS.counter("transitions").inc()
        METRICS.counter("bytes_tiered").inc(ks.total)
        ks.stats["transitioned"] += 1
        ks.stats["bytes"] += ks.total
        log.info("lifecycle: tiered %s/%s/%s (%d bytes, %d groups) -> "
                 "EC", ks.volume, ks.bucket, ks.key, ks.total,
                 len(ks.groups))


class _SpecPacker:
    """Accumulates stripe windows across keys into constant-shape
    device batches over one depth-1 pipeline."""

    def __init__(self, executor: TieringExecutor, fn, opts, ctype, bpc,
                 stats: dict, spec):
        from ozone_tpu.codec import service as codec_service
        from ozone_tpu.parallel import dispatch

        self.executor = executor
        self.opts = opts
        self.ctype = ctype
        self.bpc = bpc
        self.stats = stats
        # bulk class: on a multi-chip host a tiering sweep is exactly
        # the traffic the mesh executor exists for; on one chip the
        # codec service's weighted fair scheduler keeps the sweep from
        # starving interactive traffic
        self.pipe = dispatch.pipeline(
            codec_service.encode_key(spec), fn, width=tier_batch_size(),
            qos="bulk")
        # one window is one dispatch of the lane the door chose: a
        # narrower one would linger there and go out partly filled
        self.window = self.pipe.width
        self.host_checksum = Checksum(ctype, bpc)
        self.dispatches = 0
        self._retries: list[tuple] = []
        #: every key packed here, for the call's last look at its root
        self.keys: list[_KeyState] = []
        self._reset_buffer()

    def take_retries(self) -> list[tuple]:
        """The work items of the keys to take again, handed over."""
        out, self._retries = self._retries, []
        return out

    def _reset_buffer(self) -> None:
        k, cell = self.opts.data_units, self.opts.cell_size
        # a FRESH buffer per submission: the pipeline keeps one batch in
        # flight while the next fills, and emit still reads the data
        # columns of the in-flight one (the buffer rides the ctx)
        self._buf = np.zeros((self.window, k, cell), np.uint8)
        self._fill = 0
        self._segments: list[tuple] = []  # (ks, gs, s0, n, row0)

    def add(self, ks: _KeyState, gs: _GroupState, s0: int, n: int,
            lo: int, parts: list) -> None:
        """Append `n` stripes of one group, the first its stripe `s0`
        and the key's byte `lo`, their bytes in `parts` ([(offset in
        the key, bytes)]; what the parts do not cover, the tail of a
        last partial stripe, stays zero); splits across device batches
        as needed so every dispatch is full-width."""
        stripe = self.opts.data_units * self.opts.cell_size
        if not ks.windows:
            self.keys.append(ks)
        pos = 0
        while pos < n:
            take = min(self.window - self._fill, n - pos)
            with _stage(ks, "pack"):
                a = lo + pos * stripe
                rows = self._buf[self._fill:self._fill + take].reshape(-1)
                for off, data in parts:
                    b0, b1 = max(a, off), min(a + take * stripe,
                                              off + data.size)
                    if b0 < b1:
                        rows[b0 - a:b1 - a] = data[b0 - off:b1 - off]
            self._segments.append((ks, gs, s0 + pos, take, self._fill))
            ks.windows.add(self.dispatches)
            self._fill += take
            pos += take
            if self._fill == self.window:
                self._submit()

    def _submit(self) -> None:
        METRICS.counter("windows_submitted").inc()
        METRICS.counter("stripes_packed").inc(self._fill)
        METRICS.counter("pad_stripes").inc(self.window - self._fill)
        # a partial last window goes as the stripes it has: the lane
        # pads it to its constant shape and counts what is useful
        partial = self._fill < self.window
        # the scheduler's queue-wait and dispatch spans of this window
        # go to every key that rides it
        with Tracer.instance().riders(
                ks.ctx for ks, *_ in self._segments if not ks.failed):
            done = self.pipe.submit(
                self._buf[:self._fill] if partial else self._buf,
                (self._segments, self._buf), tail=partial)
        self.dispatches += 1
        self._reset_buffer()
        if done is not None:
            self._emit(*done)

    def flush(self) -> None:
        if self._fill:
            # the tail is zero-padded to the constant dispatch shape in
            # the lane: ONE compiled program for the whole sweep
            self._submit()
        done = self.pipe.drain()
        if done is not None:
            self._emit(*done)

    def _emit(self, ctx: tuple, results: tuple) -> None:
        from ozone_tpu.client.dn_client import (
            build_chunk_pairs,
            write_unit_stream,
        )

        segments, buf = ctx
        parity, crcs = results
        k = self.opts.data_units
        p = self.opts.parity_units
        cell = self.opts.cell_size
        for ks, gs, s0, n, row0 in segments:
            gs.stripes_emitted += n
            if ks.failed:
                continue
            try:
                if gs.ng is None:
                    self.executor._open_group(ks, gs)
                with _stage(ks, "write"):
                    for u in range(k + p):
                        # data columns come back out of the submitted
                        # batch itself (results carry only parity + CRCs)
                        cells = (buf[row0:row0 + n, u] if u < k
                                 else parity[row0:row0 + n, u - k])
                        pairs = build_chunk_pairs(
                            gs.ng.block_id, range(s0, s0 + n), cells,
                            crcs[row0:row0 + n, u], gs.lengths[u], cell,
                            self.bpc, self.ctype, self.host_checksum)
                        if pairs:
                            write_unit_stream(
                                self.executor.clients.get(
                                    gs.ng.pipeline.nodes[u]),
                                gs.ng.block_id, pairs)
                            gs.unit_infos[u].extend(i for i, _ in pairs)
                if gs.stripes_emitted == gs.stripes_total:
                    self.executor._finalize_group(ks, gs)
            except (rq.OMError, StorageError, OSError, KeyError) as e:
                # KeyError: a datanode with no client (dead/unlearned
                # address) — per-key failure, never a sweep abort
                ks.failed = True
                code = e.code if isinstance(e, StorageError) else ""
                if code == "INVALID_CONTAINER_STATE" and \
                        ks.attempt < CLOSED_CONTAINER_RETRIES:
                    # the container closed under the group (full by
                    # allocation): the key is taken again in this call;
                    # what this try wrote is a dead write
                    METRICS.counter("closed_container_retries").inc()
                    _end_key(ks, "retry")
                    self._retries.append(
                        (ks.volume, ks.bucket, ks.key, ks.target))
                    continue
                _end_key(ks, "failed")
                if code == resilience.DEADLINE_EXCEEDED:
                    # spent budget, not a broken key: it re-tiers next
                    # sweep and must not inflate transition_failures
                    continue
                log.warning("lifecycle: EC write for %s/%s/%s failed: "
                            "%s", ks.volume, ks.bucket, ks.key, e)
                self.stats["failed"] += 1
                METRICS.counter("transition_failures").inc()
