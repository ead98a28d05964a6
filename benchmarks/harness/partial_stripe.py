"""What the plain reference says a block group of ANY length holds, and
the comparison of every unit with it, straight off its datanode.

`storecheck.py` holds groups of whole stripes. A key whose length is no
multiple of a stripe ends in a partial stripe: there the data units hold
their true bytes (a cell past the key's end holds nothing, a cut cell
its head), and each parity unit holds its row of the reference's
product over the stripe zero-padded to whole cells, as long as the
stripe's FIRST cell (the longest: cells fill in order, so past its
length every data cell is zero and so is every parity byte). Every
stored chunk carries one CRC32C per `bpc` bytes of its true bytes, the
last over what is left.

A unit is read as `storecheck.check_unit` reads one: its block record,
its chunks' offsets and lengths, their bytes and stored CRCs, with no
reader and so no decode around a bad replica. Every comparison is
exact, and `units_compared` counts the (unit, stripe) cells held to the
reference, an empty one included (its absence is what is compared).
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import reference
from benchmarks.harness.storecheck import Tally


def data_lengths(group_length: int, scheme: dict) -> list[list[int]]:
    """[stripe][data unit] true lengths of a group of `group_length`
    bytes."""
    k, cell = scheme["k"], scheme["cell"]
    stripe_bytes = k * cell
    out = []
    for at in range(0, group_length, stripe_bytes):
        left = group_length - at
        out.append([max(0, min(cell, left - i * cell)) for i in range(k)])
    return out


def expected_cells(scheme: dict, payload: np.ndarray) -> list[list]:
    """[stripe][unit] the bytes the reference says each of the k+p
    units holds of each stripe of a group written from `payload` (uint8,
    any length): data at their true lengths, parity of the zero-padded
    stripe as long as the stripe's first cell. An empty cell is an empty
    array."""
    k, p, cell = scheme["k"], scheme["p"], scheme["cell"]
    payload = np.asarray(payload, dtype=np.uint8).reshape(-1)
    out = []
    for s, lengths in enumerate(data_lengths(payload.size, scheme)):
        base = s * k * cell
        padded = np.zeros((k, cell), dtype=np.uint8)
        cells = []
        for i, n in enumerate(lengths):
            padded[i, :n] = payload[base + i * cell:base + i * cell + n]
            cells.append(padded[i, :n])
        parity = reference.encode(k, p, padded)
        cells += [parity[j, :lengths[0]] for j in range(p)]
        out.append(cells)
    return out


def check_group(clients, group, payload: np.ndarray, scheme: dict,
                tally: Tally, where: str) -> None:
    """Hold every unit of `group` (a BlockGroup of the program's client:
    block id, pipeline, length), on the datanode its pipeline names, to
    `expected_cells` of `payload` (the group's own bytes). `clients`
    maps a datanode id to a client with get_block / read_chunk. The
    whole CRC slices are queued on the tally: call `storecheck.finish`
    once every group is in."""
    cell, bpc = scheme["cell"], scheme["bpc"]
    cells = expected_cells(scheme, payload)
    want_length = int(np.asarray(payload).size)
    for u, dn_id in enumerate(group.pipeline.nodes):
        name = f"{where} unit {u} on {dn_id}"
        dn = clients.get(dn_id)
        want = {s * cell: stripe[u] for s, stripe in enumerate(cells)
                if stripe[u].size}
        try:
            blk = dn.get_block(group.block_id)
        except Exception as e:  # noqa: BLE001 - a missing replica is a finding
            if not want:
                # a data unit the key never reached holds no block
                tally.units_compared += len(cells)
                continue
            tally.records_wrong += 1
            tally.note(f"{name}: no block record ({e!r})")
            continue
        got = {info.offset: info for info in blk.chunks}
        if (blk.block_group_length != want_length
                or len(got) != len(blk.chunks)
                or {o: i.length for o, i in got.items()}
                != {o: c.size for o, c in want.items()}):
            tally.records_wrong += 1
            tally.note(f"{name}: record says group length "
                       f"{blk.block_group_length}, chunks "
                       f"{sorted((o, i.length) for o, i in got.items())}; "
                       f"wanted {want_length}, "
                       f"{sorted((o, c.size) for o, c in want.items())}")
            continue
        tally.units_compared += len(cells)
        for offset, info in sorted(got.items()):
            expect = want[offset]
            data = np.asarray(dn.read_chunk(group.block_id, info,
                                            verify=False),
                              dtype=np.uint8).reshape(-1)
            tally.bytes_compared += int(expect.size)
            if not np.array_equal(data, expect):
                tally.stored_bytes_differ += 1
                tally.note(f"{name} chunk at {offset}: bytes differ")
            sums = info.checksum
            stored = np.array([int.from_bytes(c, "big")
                               for c in sums.checksums], dtype=np.uint32)
            if sums.type.value != "CRC32C" or sums.bytes_per_checksum != bpc:
                tally.stored_crcs_differ += max(1, stored.size)
                tally.note(f"{name} chunk at {offset}: checksum record "
                           f"{sums.type.value}/{sums.bytes_per_checksum}")
                continue
            # the whole slices are compared side by side by
            # storecheck.finish, the short last one here
            whole = expect.size // bpc
            n_want = -(-expect.size // bpc)
            if stored.size != n_want:
                tally.stored_crcs_differ += max(1, abs(stored.size - n_want))
                tally.note(f"{name} chunk at {offset}: {stored.size} "
                           f"stored CRCs for {n_want} slices")
                continue
            if whole:
                tally._pending.append((expect[:whole * bpc], stored[:whole],
                                       f"{name} chunk at {offset}"))
            if n_want > whole:
                tally.crc_slices_compared += 1
                if reference.crc32c(expect[whole * bpc:]) != int(stored[-1]):
                    tally.stored_crcs_differ += 1
                    tally.note(f"{name} chunk at {offset}: its last, short "
                               f"slice's stored CRC differs")
