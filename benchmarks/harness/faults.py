"""Planted faults: each breaks one guarantee the configuration states,
on the datanodes, where the comparison has to find it. Used by the
controls (`--control <name>` on the chip, the tests on the CPU); a
driver's run never plants one.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

from benchmarks.harness import reference


def _block_file(ctx, dn_id: str, block_id) -> Path:
    found = list((Path(ctx.cluster.root) / dn_id).rglob(
        f"{block_id.local_id}.block"))
    if len(found) != 1:
        raise RuntimeError(f"{len(found)} files for block {block_id} on "
                           f"{dn_id}")
    return found[0]


def _flip(path: Path, at: int | None = None) -> None:
    """XOR one byte of the file in place (the middle one by default)."""
    if at is None:
        at = path.stat().st_size // 2
    with open(path, "r+b") as f:
        f.seek(at)
        b = f.read(1)
        f.seek(at)
        f.write(bytes([b[0] ^ 0x5A]))


def _restore_record(dn, group, blk, chunk, sums: tuple) -> None:
    """Commit the block record again with `chunk`'s CRC list replaced."""
    from ozone_tpu.storage.ids import BlockData, ChunkInfo
    from ozone_tpu.utils.checksum import ChecksumData

    bad = ChunkInfo(chunk.name, chunk.offset, chunk.length, ChecksumData(
        chunk.checksum.type, chunk.checksum.bytes_per_checksum, sums))
    rest = [c for c in blk.chunks if c.offset != chunk.offset]
    dn.put_block(BlockData(group.block_id, [bad, *rest],
                           block_group_length=blk.block_group_length))


def byte_flip(ctx, group, unit: int | None = None) -> None:
    """One byte of a stored unit (the first PARITY unit unless told
    otherwise) flipped on disk; its stored CRCs stay those of the right
    bytes."""
    unit = ctx.scheme["k"] if unit is None else unit
    dn_id = group.pipeline.nodes[unit]
    _flip(_block_file(ctx, dn_id, group.block_id))


def crc_wrong(ctx, group, unit: int | None = None) -> None:
    """One stored CRC of a parity unit altered in its block record; the
    bytes stay right."""
    unit = ctx.scheme["k"] if unit is None else unit
    dn = ctx.client.clients.get(group.pipeline.nodes[unit])
    blk = dn.get_block(group.block_id)
    first = blk.chunks[0]
    sums = list(first.checksum.checksums)
    sums[0] = bytes([sums[0][0] ^ 1]) + sums[0][1:]
    _restore_record(dn, group, blk, first, tuple(sums))


def silent_corruption(ctx, group, unit: int = 0) -> None:
    """One byte of a stored DATA unit flipped on disk AND its stored CRC
    rewritten to match: a reader that trusts CRCs serves wrong bytes."""
    dn_id = group.pipeline.nodes[unit]
    dn = ctx.client.clients.get(dn_id)
    blk = dn.get_block(group.block_id)
    first = min(blk.chunks, key=lambda c: c.offset)
    _flip(_block_file(ctx, dn_id, group.block_id), first.offset + 7)
    data = np.asarray(dn.read_chunk(group.block_id, first, verify=False),
                      dtype=np.uint8).reshape(-1)
    sums = tuple(int(c).to_bytes(4, "big") for c in reference.crc32c_slices(
        data, first.checksum.bytes_per_checksum))
    _restore_record(dn, group, blk, first, sums)


def wipe_replica(ctx, group, unit: int) -> None:
    """The replica of `unit` deleted from its datanode: what a repair
    that rebuilt nothing leaves behind. (On the real daemons the SCM's
    replication manager rebuilds such a replica within seconds, so there
    this control races it; the in-process tests use it.)"""
    ctx.client.clients.get(group.pipeline.nodes[unit]).delete_container(
        group.container_id, force=True)


_FAULTS = {"byte_flip": byte_flip, "crc_wrong": crc_wrong,
           "silent_corruption": silent_corruption,
           "wipe_replica": wipe_replica}


def plant(name: str, ctx, group, **kw) -> None:
    if name not in _FAULTS:
        raise ValueError(f"unknown control {name!r}; known: "
                         f"{sorted(_FAULTS)}")
    _FAULTS[name](ctx, group, **kw)
