"""The plain reference: Reed-Solomon over GF(2^8) by table, CRC32C byte
by byte. numpy only; shares no code, table or matrix with `ozone_tpu`.

The scheme is upstream's (Apache Ozone `RSUtil.genCauchyMatrix`, ISA-L
compatible): field polynomial x^8+x^4+x^3+x^2+1 (0x11D), generator 2;
parity row i (k <= i < k+p) has coefficient inv(i ^ j) on data unit j.
CRC32C is Castagnoli (reflected polynomial 0x82F63B78, init and final
xor 0xFFFFFFFF), one per `bpc` bytes of a unit's cell, stored big-endian.
"""

from __future__ import annotations

import numpy as np

_POLY = 0x11D


def _gf_tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= _POLY
    exp[255:510] = exp[:255]
    return exp, log


_EXP, _LOG = _gf_tables()


def gf_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(_EXP[_LOG[a] + _LOG[b]])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(_EXP[255 - _LOG[a]])


def mul_table(c: int) -> np.ndarray:
    """The 256-entry table of c * x."""
    return np.array([gf_mul(c, x) for x in range(256)], dtype=np.uint8)


def parity_rows(k: int, p: int) -> list[list[int]]:
    return [[gf_inv(i ^ j) for j in range(k)] for i in range(k, k + p)]


def encode_rows(k: int, p: int) -> list[list[int]]:
    """All k+p rows: identity on top, the Cauchy parity rows below."""
    ident = [[1 if i == j else 0 for j in range(k)] for i in range(k)]
    return ident + parity_rows(k, p)


def gf_invert(m: list[list[int]]) -> list[list[int]]:
    """Gauss-Jordan inverse of a square matrix over GF(2^8)."""
    n = len(m)
    a = [list(row) + [1 if i == j else 0 for j in range(n)]
         for i, row in enumerate(m)]
    for col in range(n):
        piv = next((r for r in range(col, n) if a[r][col]), None)
        if piv is None:
            raise ValueError("matrix is singular over GF(2^8)")
        a[col], a[piv] = a[piv], a[col]
        inv = gf_inv(a[col][col])
        a[col] = [gf_mul(inv, v) for v in a[col]]
        for r in range(n):
            if r != col and a[r][col]:
                f = a[r][col]
                a[r] = [v ^ gf_mul(f, w) for v, w in zip(a[r], a[col])]
    return [row[n:] for row in a]


def apply_rows(rows: list[list[int]], units: np.ndarray) -> np.ndarray:
    """out[..., r, :] = sum_j rows[r][j] * units[..., j, :] over GF(2^8).
    `units` is uint8 [..., k, C]."""
    units = np.asarray(units, dtype=np.uint8)
    out = np.zeros(units.shape[:-2] + (len(rows), units.shape[-1]),
                   dtype=np.uint8)
    for r, row in enumerate(rows):
        for j, c in enumerate(row):
            if c == 0:
                continue
            src = units[..., j, :]
            out[..., r, :] ^= src if c == 1 else mul_table(c)[src]
    return out


def encode(k: int, p: int, data: np.ndarray) -> np.ndarray:
    """Parity units uint8 [..., p, C] of data units uint8 [..., k, C]."""
    return apply_rows(parity_rows(k, p), data)


def recover(k: int, p: int, valid: list[int], erased: list[int],
            units: np.ndarray) -> np.ndarray:
    """Units `erased` from the k units `valid` (uint8 [..., k, C])."""
    enc = encode_rows(k, p)
    inv = gf_invert([enc[v] for v in valid])
    rows = []
    for e in erased:
        rows.append([
            _xor_sum(gf_mul(enc[e][t], inv[t][j]) for t in range(k))
            for j in range(k)])
    return apply_rows(rows, units)


def _xor_sum(values) -> int:
    acc = 0
    for v in values:
        acc ^= v
    return acc


def _crc32c_table() -> np.ndarray:
    table = np.zeros(256, dtype=np.uint32)
    for n in range(256):
        c = n
        for _ in range(8):
            c = (c >> 1) ^ 0x82F63B78 if c & 1 else c >> 1
        table[n] = c
    return table


_CRC_TABLE = _crc32c_table()


def crc32c(data) -> int:
    """CRC32C of one buffer, byte by byte."""
    crc = 0xFFFFFFFF
    for b in bytes(data):
        crc = int(_CRC_TABLE[(crc ^ b) & 0xFF]) ^ (crc >> 8)
    return crc ^ 0xFFFFFFFF


def crc32c_slices(buf: np.ndarray, bpc: int) -> np.ndarray:
    """CRC32C of every `bpc`-byte slice of `buf` (uint8, size a multiple
    of bpc), uint32 [size // bpc]: the byte-wise table recurrence, run
    over all slices side by side (one numpy step per byte position)."""
    buf = np.ascontiguousarray(buf, dtype=np.uint8).reshape(-1, bpc)
    cols = np.ascontiguousarray(buf.T)  # [bpc, n]: a byte position per row
    crc = np.full(buf.shape[0], 0xFFFFFFFF, dtype=np.uint32)
    for col in cols:
        crc = _CRC_TABLE[(crc ^ col) & 0xFF] ^ (crc >> 8)
    return crc ^ np.uint32(0xFFFFFFFF)
