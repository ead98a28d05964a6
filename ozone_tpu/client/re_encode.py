"""Replication-to-EC re-encode: convert replicated keys to erasure coding.

Mirror of the reference's container-service conversion capability
(BASELINE config #4 "XOR(1) replication-to-EC re-encode path"): bulk data
written with replication (fast ingest, 2-3x storage) is re-encoded to an
EC layout (1.5x storage for rs-6-3) in the background. The read side
streams from any live replica; the write side is the standard EC stripe
pipeline, so the re-encode inherits the batched fused device encode+CRC;
the key's block list is swapped atomically at commit and the old blocks
go through the SCM deletion chain.
"""

from __future__ import annotations

import functools
import logging

import numpy as np

from ozone_tpu.client import resilience
from ozone_tpu.client.dn_client import DatanodeClientFactory
from ozone_tpu.codec import service as codec_service
from ozone_tpu.client.ec_writer import ECKeyWriter
from ozone_tpu.client.replicated import ReplicatedKeyReader
from ozone_tpu.om.om import OzoneManager
from ozone_tpu.scm.pipeline import ReplicationConfig, ReplicationType
from ozone_tpu.storage.ids import (
    BlockData,
    BlockID,
    ChunkInfo,
    StorageError,
)
from ozone_tpu.utils.checksum import ChecksumType

log = logging.getLogger(__name__)


def _op_boundary(op: str):
    """Operation-boundary decorator: one Deadline covers the whole
    conversion (source reads, device passes, target writes, commit);
    nested hops derive their timeouts from it (client/resilience.py)."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*a, **kw):
            with resilience.start(op):
                return fn(*a, **kw)
        return wrapped
    return deco


@_op_boundary("re_encode")
def re_encode_key_to_ec(
    om: OzoneManager,
    clients: DatanodeClientFactory,
    volume: str,
    bucket: str,
    key: str,
    ec: str = "rs-6-3-1024k",
) -> dict:
    """Convert one replicated or XOR(1)-coded key to RS EC. Returns the
    new key info. A replicated source streams through the standard EC
    writer; an XOR source with a lost data unit takes the fused
    decode->re-encode path (BASELINE config #4) — one device dispatch
    recovers the unit AND produces the RS layout."""
    info = om.lookup_key(volume, bucket, key)
    old_groups = om.key_block_groups(info)
    repl = ReplicationConfig.parse(info["replication"])
    if repl.type is ReplicationType.EC:
        if repl.ec.codec == "xor":
            return re_encode_xor_key_to_rs(om, clients, volume, bucket,
                                           key, ec)
        raise ValueError(f"{key} is already erasure coded ({repl})")

    ec_conf = ReplicationConfig.parse(ec)
    session = om.open_key(volume, bucket, key, replication=ec)
    # rewrite fence on the SCANNED version (the lifecycle transition
    # contract): a user overwrite racing the background conversion must
    # win — an unfenced commit here would replace their fresh data with
    # a stale re-encode. check_rewrite_fence rejects with KEY_MODIFIED
    # and routes the conversion's blocks to the purge chain.
    session.expect_object_id = info.get("object_id", "")
    session.expect_generation = int(info.get("generation", -1))
    writer = ECKeyWriter(
        ec_conf.ec,
        lambda excluded, excluded_containers=():
            om.allocate_block(session, excluded, excluded_containers),
        clients,
        block_size=om.block_size,
        checksum=ChecksumType(info.get("checksum_type", "CRC32C")),
        bytes_per_checksum=info.get("bytes_per_checksum", 16 * 1024),
        qos_class="bulk",  # background conversion must not starve reads
    )
    for g in old_groups:
        writer.write(ReplicatedKeyReader(g, clients).read_all())
    groups = writer.close()
    # the fenced commit replaces the key's block list atomically:
    # finalize_commit routes the superseded replicated version into the
    # purge chain (its blocks retire through scm/block_deletion), so no
    # separate unfenced DeleteKey is needed — the old delete-then-commit
    # pair could silently destroy a concurrent user overwrite
    om.commit_key(session, groups, writer.bytes_written)

    log.info(
        "re-encoded %s/%s/%s: %d bytes, %d replicated groups -> %d EC groups",
        volume, bucket, key, writer.bytes_written, len(old_groups),
        len(groups),
    )
    return om.lookup_key(volume, bucket, key)


def _unit_source(clients, group, unit, cell):
    """(client, {stripe: ChunkInfo}) of one unit's replica, or None if
    the replica is unreachable/missing. The block record is fetched and
    indexed by stripe once per group; cell reads then happen per stripe
    window (_read_unit_window) so the re-encode pipeline can overlap
    them with the device pass. Outcomes feed the shared peer-health
    registry (an unreachable source trips toward its breaker)."""
    dn_id = group.pipeline.nodes[unit]
    health = getattr(clients, "health", None)
    try:
        client = clients.get(dn_id)
        bd = client.get_block(group.block_id)
    except Exception:  # noqa: BLE001 - any failure = unit unavailable
        if health is not None:
            health.failure(dn_id)
        return None
    return client, {info.offset // cell: info for info in bd.chunks}


def _read_unit_window(group, source, s0: int, n: int, cell: int,
                      health=None):
    """One unit's cells for stripes [s0, s0+n) as [n, cell] zero-padded."""
    client, by_stripe = source
    out = np.zeros((n, cell), dtype=np.uint8)
    for s in range(s0, s0 + n):
        info = by_stripe.get(s)
        if info is not None:
            if health is not None:
                data = health.observe(client.dn_id, client.read_chunk,
                                      group.block_id, info)
            else:
                data = client.read_chunk(group.block_id, info)
            out[s - s0, : info.length] = data[: info.length]
    return out


@_op_boundary("re_encode")
def re_encode_xor_key_to_rs(
    om: OzoneManager,
    clients: DatanodeClientFactory,
    volume: str,
    bucket: str,
    key: str,
    ec: str = "rs-6-3-1024k",
) -> dict:
    """Convert an XOR(1)-coded key to RS(k,p), surviving one lost data
    unit per group — the BASELINE config #4 path. The XOR decode and the
    RS parity generation compose into ONE bit-linear device dispatch
    (codec/fused.make_fused_reencoder), and the RS layout is written
    straight to the freshly allocated group with the device-computed
    CRCs (reference analog: XORRawDecoder.decode + RSRawEncoder.encode
    inside the container-service conversion flow)."""
    from ozone_tpu.client.dn_client import (
        build_chunk_pairs,
        write_unit_stream,
    )
    from ozone_tpu.client.ec_writer import (
        block_lengths,
        create_group_containers,
    )
    from ozone_tpu.codec.fused import (
        FusedSpec,
        effective_bpc,
        make_fused_encoder,
        make_fused_reencoder,
        reencode_layout_crcs,
    )
    from ozone_tpu.codec.pipeline import decode_batch_size
    from ozone_tpu.parallel import dispatch
    from ozone_tpu.utils.checksum import Checksum

    info = om.lookup_key(volume, bucket, key)
    old_groups = om.key_block_groups(info)
    src = ReplicationConfig.parse(info["replication"])
    dst = ReplicationConfig.parse(ec)
    if src.type is not ReplicationType.EC or src.ec.codec != "xor":
        raise ValueError(f"{key} is not XOR-coded ({src})")
    if dst.type is not ReplicationType.EC or dst.ec.codec != "rs":
        raise ValueError(f"target must be RS EC, got {dst}")
    k, cell = src.ec.data_units, src.ec.cell_size
    if (dst.ec.data_units, dst.ec.cell_size) != (k, cell):
        raise ValueError(
            f"XOR->RS re-encode needs matching data units and cell size "
            f"({src} -> {dst})")
    ctype = ChecksumType(info.get("checksum_type", "CRC32C"))
    bpc = effective_bpc(cell, info.get("bytes_per_checksum", 16 * 1024))
    spec = FusedSpec(dst.ec, ctype, bpc)
    host_checksum = Checksum(ctype, bpc)
    p = dst.ec.parity_units

    session = om.open_key(volume, bucket, key, replication=ec)
    # same rewrite fence as the replicated->EC path: the conversion
    # loses deterministically (KEY_MODIFIED) to any commit that landed
    # after the scan, instead of clobbering it
    session.expect_object_id = info.get("object_id", "")
    session.expect_generation = int(info.get("generation", -1))
    new_groups = []
    total = 0
    window = decode_batch_size()
    for g in old_groups:
        stripes = -(-g.length // (k * cell))
        # locate the k input slots: data units where alive, the XOR
        # parity in the lost unit's slot (or in slot 0 when nothing is
        # lost — same IO volume, one uniform device program)
        sources = [_unit_source(clients, g, u, cell) for u in range(k)]
        missing = [u for u, x in enumerate(sources) if x is None]
        if len(missing) > 1:
            raise StorageError(
                "INSUFFICIENT_LOCATIONS",
                f"group {g.block_id}: {len(missing)} data units lost, "
                f"XOR(1) tolerates one")
        lost = missing[0] if missing else 0
        parity_src = _unit_source(clients, g, k, cell)
        parity_ok = parity_src is not None
        if parity_ok:
            sources[lost] = parity_src
        elif missing:
            raise StorageError(
                "INSUFFICIENT_LOCATIONS",
                f"group {g.block_id}: data unit {lost} AND the XOR "
                f"parity are gone")
        # With the XOR parity in slot `lost`, the reencoder's recovery
        # column is correct in BOTH cases: with a loss it is the decode;
        # without one it equals the original unit 0 (XOR of parity and
        # units 1..k-1), so writing it doubles as a parity consistency
        # check. When the parity replica itself is gone (and nothing
        # else is), every slot holds original data and the reencoder's
        # decode matrix would fold slot `lost` into the WRONG vector
        # (XOR of all data = the parity) — both for the recovered column
        # and for the RS parity computed from it — so that case runs the
        # plain fused encode over the k data units instead.
        fn = (make_fused_reencoder(spec, lost=lost) if parity_ok
              else make_fused_encoder(spec))
        ng = om.allocate_block(session)
        create_group_containers(clients, ng, replica_indexed=True)
        lengths = block_lengths(g.length, k, cell) + [
            stripes * cell
        ] * p
        unit_infos: list[list[ChunkInfo]] = [[] for _ in range(k + p)]

        def emit(ctx, results):
            """Write one window's RS layout to the new group — runs
            while the NEXT window reads + re-encodes on device."""
            s0, n, batch = ctx
            if parity_ok:
                out, ucrcs, ocrcs = results
                crcs = reencode_layout_crcs(ucrcs, ocrcs, lost)

                def unit_cells(u):
                    if u < k:
                        return out[:, 0] if u == lost else batch[:, u]
                    return out[:, 1 + (u - k)]
            else:
                # plain encode: data columns pass through, the device
                # produced the parity and the full k+p EC-layout CRCs
                parity_cells, crcs = results

                def unit_cells(u):
                    return batch[:, u] if u < k else parity_cells[:, u - k]
            for u in range(k + p):
                pairs = build_chunk_pairs(
                    ng.block_id, range(s0, s0 + n), unit_cells(u),
                    crcs[:, u], lengths[u], cell, bpc, ctype,
                    host_checksum)
                if pairs:
                    # one batched stream per unit per window when the
                    # target serves it (WriteChunksCommit), per-chunk
                    # verbs otherwise
                    write_unit_stream(clients.get(ng.pipeline.nodes[u]),
                                      ng.block_id, pairs)
                    unit_infos[u].extend(i for i, _ in pairs)

        # depth-1 pipeline over stripe windows: the ec_writer's
        # _flush_queue structure on the conversion path — target writes
        # of window N overlap the device pass + D2H of window N+1.
        # Bulk class: conversion windows coalesce with other operations'
        # stripes and defer to interactive traffic.
        pipe = dispatch.pipeline(
            codec_service.reencode_key(spec, lost) if parity_ok
            else codec_service.encode_key(spec),
            fn, width=window, qos="bulk")
        health = getattr(clients, "health", None)
        for s0 in range(0, stripes, window):
            resilience.check_deadline("re_encode_window")
            n = min(window, stripes - s0)
            batch = np.stack(
                [_read_unit_window(g, src, s0, n, cell, health=health)
                 for src in sources],
                axis=1)  # [n, k, C]
            done = pipe.submit(batch, (s0, n, batch))
            if done is not None:
                emit(*done)
        done = pipe.drain()
        if done is not None:
            emit(*done)

        for u in range(k + p):
            clients.get(ng.pipeline.nodes[u]).put_block(BlockData(
                ng.block_id, unit_infos[u], block_group_length=g.length))
        ng.length = g.length
        new_groups.append(ng)
        total += g.length

    om.commit_key(session, new_groups, total)
    log.info(
        "fused XOR->RS re-encode %s/%s/%s: %d bytes, %d groups",
        volume, bucket, key, total, len(new_groups),
    )
    return om.lookup_key(volume, bucket, key)
