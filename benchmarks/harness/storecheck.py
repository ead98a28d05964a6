"""Hold what lies on the datanodes to the plain reference.

A unit (data or parity) of a block group is read straight off the
datanode that holds it — block record, chunk lengths, bytes, stored
CRCs; no reader and so no decode around a bad replica (the way of
`tools/freon.py` `_verify_rebuilt_unit`, extended to parity units) — and
compared with what the reference says that unit holds for the payload
the group was written from. Every comparison is exact.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from benchmarks.harness import reference


@dataclass
class Tally:
    """What a comparison found; every `*_differ`/`*_wrong` has limit 0."""

    units_compared: int = 0
    bytes_compared: int = 0
    crc_slices_compared: int = 0
    records_wrong: int = 0       # block record missing / wrong lengths
    stored_bytes_differ: int = 0  # chunks whose bytes differ
    stored_crcs_differ: int = 0   # CRC slices that differ
    first_error: str = ""
    _pending: list = field(default_factory=list)

    def note(self, what: str) -> None:
        if not self.first_error:
            self.first_error = what

    def merge(self, other: "Tally") -> None:
        for name in ("units_compared", "bytes_compared",
                     "crc_slices_compared", "records_wrong",
                     "stored_bytes_differ", "stored_crcs_differ"):
            setattr(self, name, getattr(self, name) + getattr(other, name))
        self.note(other.first_error)
        self._pending.extend(other._pending)


def expected_units(scheme: dict, payload: np.ndarray) -> np.ndarray:
    """The k+p units uint8 [stripes, k+p, cell] the reference says a
    group written from `payload` (whole stripes) holds."""
    k, p, cell = scheme["k"], scheme["p"], scheme["cell"]
    if payload.size % (k * cell):
        raise ValueError("payload is not whole stripes")
    data = payload.reshape(-1, k, cell)
    return np.concatenate([data, reference.encode(k, p, data)], axis=1)


def expected_unit(scheme: dict, payload: np.ndarray, unit: int) -> np.ndarray:
    """One unit uint8 [stripes, cell] of the above: a data unit is the
    payload's own cells, a parity unit is its one row of the product."""
    k, p, cell = scheme["k"], scheme["p"], scheme["cell"]
    if payload.size % (k * cell):
        raise ValueError("payload is not whole stripes")
    data = payload.reshape(-1, k, cell)
    if unit < k:
        return data[:, unit]
    row = reference.parity_rows(k, p)[unit - k]
    return reference.apply_rows([row], data)[:, 0]


def check_unit(dn, block_id, group_length: int, unit_bytes: np.ndarray,
               scheme: dict, tally: Tally, where: str) -> None:
    """Compare one stored unit with `unit_bytes` (uint8 [stripes, cell],
    the reference's). CRC slices are queued on the tally and compared in
    one pass by finish()."""
    bpc = scheme["bpc"]
    flat = unit_bytes.reshape(-1)
    try:
        blk = dn.get_block(block_id)
    except Exception as e:  # noqa: BLE001 - a missing replica is a finding
        tally.records_wrong += 1
        tally.note(f"{where}: no block record ({e!r})")
        return
    offsets = sorted(info.offset for info in blk.chunks)
    total = sum(info.length for info in blk.chunks)
    if (blk.block_group_length != group_length or total != flat.size
            or len(set(offsets)) != len(offsets)):
        tally.records_wrong += 1
        tally.note(f"{where}: record says group length "
                   f"{blk.block_group_length}, {total} bytes in "
                   f"{len(offsets)} chunks; wanted {group_length}, "
                   f"{flat.size}")
        return
    tally.units_compared += 1
    for info in blk.chunks:
        want = flat[info.offset:info.offset + info.length]
        got = np.asarray(dn.read_chunk(block_id, info, verify=False),
                         dtype=np.uint8).reshape(-1)
        tally.bytes_compared += int(want.size)
        if got.size != want.size or not np.array_equal(got, want):
            tally.stored_bytes_differ += 1
            tally.note(f"{where} chunk at {info.offset}: bytes differ")
        sums = info.checksum
        stored = np.array([int.from_bytes(c, "big") for c in sums.checksums],
                          dtype=np.uint32)
        if sums.type.value != "CRC32C" or sums.bytes_per_checksum != bpc \
                or stored.size * bpc != want.size:
            tally.stored_crcs_differ += max(1, stored.size)
            tally.note(f"{where} chunk at {info.offset}: checksum record "
                       f"{sums.type.value}/{sums.bytes_per_checksum}, "
                       f"{stored.size} entries")
            continue
        tally._pending.append((want, stored, f"{where} chunk at "
                                             f"{info.offset}"))


def finish(tally: Tally, scheme: dict) -> Tally:
    """Compare every queued stored CRC with the reference's CRC32C of
    the reference's bytes, all slices side by side."""
    if tally._pending:
        bpc = scheme["bpc"]
        want = reference.crc32c_slices(
            np.concatenate([w for w, _s, _n in tally._pending]), bpc)
        at = 0
        for _w, stored, name in tally._pending:
            n = stored.size
            bad = int(np.count_nonzero(want[at:at + n] != stored))
            tally.crc_slices_compared += n
            if bad:
                tally.stored_crcs_differ += bad
                tally.note(f"{name}: {bad} stored CRCs differ")
            at += n
        tally._pending.clear()
    return tally


def unit_lengths(group_length: int, scheme: dict) -> list[int]:
    """Bytes every unit of a group of whole stripes holds."""
    k, p, cell = scheme["k"], scheme["p"], scheme["cell"]
    stripes, rem = divmod(group_length, k * cell)
    if rem:
        raise ValueError("group is not whole stripes")
    return [stripes * cell] * (k + p)
