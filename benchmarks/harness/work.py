"""The work a codec call has to do, from its shapes and the scheme
alone, and the least time a chip could take for it.

The same numbers whatever implements the call: never read from HLO, a
cost analysis, or the program's own counters of padded slots.

Per stripe of an RS(k, p) scheme with cells of `cell` bytes and one
CRC32C per `bpc` bytes:

- encode: reads k cells, writes p cells and a 4-byte CRC for each slice
  of all k+p units; the GF(2^8) matrix product is p*k*cell multiplies.
- decode of e units: reads k cells, writes e cells and their CRCs;
  e*k*cell multiplies.

A GF(2^8) multiply by a constant is an 8x8 bit-matrix product: 64
multiply-adds, 128 operations, at int8 on the chip's matrix unit. CRC32C
is counted in bytes only: its operation count belongs to an
implementation, not to the work.
"""

from __future__ import annotations

import json
from pathlib import Path

OPS_PER_GF_MULTIPLY = 128

_PEAKS_FILE = Path(__file__).with_name("peaks.json")


class UnknownDevice(Exception):
    """`device_kind` is not in the peaks table: an error, not a default."""


def peaks_for(device_kind: str) -> dict:
    table = json.loads(_PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"device kind {device_kind!r} is not in {_PEAKS_FILE.name} "
            f"(known: {sorted(table)}); add its published peaks with "
            f"their source before measuring on it")
    return table[device_kind]


def encode_work(k: int, p: int, cell: int, bpc: int,
                stripes: float = 1.0) -> dict:
    """{"bytes", "ops"} of `stripes` fused encode+CRC stripes."""
    n = k + p
    return {"bytes": stripes * (n * cell + 4 * n * cell / bpc),
            "ops": stripes * OPS_PER_GF_MULTIPLY * p * k * cell}


def decode_work(k: int, e: float, cell: int, bpc: int,
                stripes: float = 1.0) -> dict:
    """{"bytes", "ops"} of `stripes` fused decode+CRC stripes that each
    recover `e` units from k survivors."""
    return {"bytes": stripes * ((k + e) * cell + 4 * e * cell / bpc),
            "ops": stripes * OPS_PER_GF_MULTIPLY * e * k * cell}


def least_seconds(work: dict, peaks: dict) -> dict:
    """The roof: the larger of bytes over the memory peak and operations
    over the int8 peak, and which of the two binds."""
    mem = work["bytes"] / peaks["hbm_bytes_per_s"]
    alu = work["ops"] / peaks["int8_ops_per_s"]
    return {"seconds": max(mem, alu),
            "binds": "memory" if mem >= alu else "compute",
            "memory_s": mem, "compute_s": alu}
