"""The plain reference for a locally repairable code: Azure's LRC(k, l, r)
(Huang et al., "Erasure Coding in Windows Azure Storage", USENIX ATC
2012, section 3 and Figure 2). numpy only; shares no code, table or
matrix with `ozone_tpu`. The field arithmetic, `apply_rows` and the CRC
are `reference.py`'s, which are as independent.

Geometry, the paper's: k data fragments in l local groups of k / l; one
local parity a group, the XOR of its group; r global parities over all k
data fragments. For LRC(12,2,2): 16 units, groups {0..5} and {6..11}.

    unit u < k           data
    unit k + g           local parity of group g
    unit k + l + i       global parity i

Coefficients, ASSUMED (the configuration lists them so): the paper's
global parities use its alpha / beta sets; this system's use Cauchy rows,
global parity unit u has coefficient inv(u ^ j) on data unit j, the rule
upstream's Reed-Solomon rows follow (`reference.parity_rows`). What the
paper promises of its set holds for these by enumeration (the tests):
every pattern of up to 3 lost units decodes, and 1,557 of the 1,820
patterns of 4.

Which units a repair reads, the paper's rule: a lone lost data unit or
local parity is rebuilt from the k / l other members of its group.
Every other pattern: walk the survivors in unit order and keep each that
no combination of those kept gives (a basis), write each lost unit in
the kept ones (there is one way), and read those with a coefficient: a
set from which nothing can be left out. The lone loss falls out of the
same walk, which the tests hold.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import reference
from benchmarks.harness.reference import gf_inv, gf_mul


def geometry(scheme: dict) -> tuple[int, int, int, int]:
    """(k, l, r, group size) of a scheme {"k", "l", "r", ...}."""
    k, l, r = scheme["k"], scheme["l"], scheme["r"]
    if k % l:
        raise ValueError(f"{k} data units do not split into {l} groups")
    return k, l, r, k // l


def generator(scheme: dict) -> list[list[int]]:
    """All k + l + r rows: row u is unit u written in the k data units."""
    k, l, r, size = geometry(scheme)
    rows = [[1 if i == j else 0 for j in range(k)] for i in range(k)]
    for g in range(l):
        rows.append([1 if j // size == g else 0 for j in range(k)])
    for u in range(k + l, k + l + r):
        rows.append([gf_inv(u ^ j) for j in range(k)])
    return rows


def group_of(scheme: dict, unit: int) -> list[int] | None:
    """The members of `unit`'s local group, its parity among them; None
    for a global parity."""
    k, l, _r, size = geometry(scheme)
    if unit >= k + l:
        return None
    g = unit // size if unit < k else unit - k
    return list(range(g * size, (g + 1) * size)) + [k + g]


def encode(scheme: dict, data: np.ndarray) -> np.ndarray:
    """Parity units uint8 [..., l + r, C] of data uint8 [..., k, C]:
    local parities first, then the global ones."""
    return reference.apply_rows(generator(scheme)[scheme["k"]:], data)


def _express(basis: list[list[int]], target: list[int]) -> list[int] | None:
    """Coefficients x with sum_j x[j] * basis[j] == target over GF(2^8),
    for linearly independent `basis` rows; None where there are none."""
    n, width = len(basis), len(target)
    # columns are the basis rows: eliminate on [basis^T | target]
    a = [[basis[j][i] for j in range(n)] + [target[i]] for i in range(width)]
    row = 0
    for col in range(n):
        piv = next((i for i in range(row, width) if a[i][col]), None)
        if piv is None:
            raise ValueError("basis rows are not independent")
        a[row], a[piv] = a[piv], a[row]
        inv = gf_inv(a[row][col])
        a[row] = [gf_mul(inv, v) for v in a[row]]
        for i in range(width):
            if i != row and a[i][col]:
                f = a[i][col]
                a[i] = [v ^ gf_mul(f, w) for v, w in zip(a[i], a[row])]
        row += 1
    if any(a[i][n] for i in range(row, width)):
        return None
    return [a[j][n] for j in range(n)]


def _independent(kept: list[list[int]], row: list[int]) -> bool:
    """Whether `row` is no combination of the independent rows `kept`."""
    return _express(kept, row) is None if kept else any(row)


def recovery_rows(scheme: dict, valid: list[int],
                  erased: list[int]) -> list[list[int]]:
    """One row of len(valid) coefficients for each erased unit: unit e
    is sum_j rows[e][j] * unit valid[j]. `valid` is any read set, of any
    width; units of it that the others already give get 0. ValueError
    where `valid` does not give an erased unit."""
    gen = generator(scheme)
    kept: list[int] = []  # positions in `valid` of a basis, in order
    for pos, v in enumerate(valid):
        if _independent([gen[valid[p]] for p in kept], gen[v]):
            kept.append(pos)
    rows = []
    for e in erased:
        x = _express([gen[valid[p]] for p in kept], gen[e])
        if x is None:
            raise ValueError(f"units {valid} do not give unit {e}")
        row = [0] * len(valid)
        for p, c in zip(kept, x):
            row[p] = c
        rows.append(row)
    return rows


def read_set(scheme: dict, erased: list[int]) -> list[int]:
    """The units a repair of `erased` reads, every other unit being
    there (ascending). ValueError where the pattern cannot be decoded."""
    k, l, _r, _size = geometry(scheme)
    erased = sorted(erased)
    if len(erased) == 1 and erased[0] < k + l:
        # the paper's rule: the other members of the lost unit's group
        return [u for u in group_of(scheme, erased[0]) if u != erased[0]]
    return general_read_set(scheme, erased)


def general_read_set(scheme: dict, erased: list[int]) -> list[int]:
    """The walk of the module's head, for any pattern."""
    k, l, r, _size = geometry(scheme)
    survivors = [u for u in range(k + l + r) if u not in erased]
    rows = recovery_rows(scheme, survivors, list(erased))
    return [u for j, u in enumerate(survivors) if any(row[j] for row in rows)]


def recover(scheme: dict, valid: list[int], erased: list[int],
            units: np.ndarray) -> np.ndarray:
    """Units `erased` uint8 [..., len(erased), C] from the units `valid`
    (uint8 [..., len(valid), C]), by elimination over that read set."""
    return reference.apply_rows(recovery_rows(scheme, valid, erased), units)


def expected_units(scheme: dict, payload: np.ndarray) -> np.ndarray:
    """The k + l + r units uint8 [stripes, k + l + r, cell] a group
    written from `payload` (whole stripes) holds."""
    k, cell = scheme["k"], scheme["cell"]
    if payload.size % (k * cell):
        raise ValueError("payload is not whole stripes")
    data = payload.reshape(-1, k, cell)
    return np.concatenate([data, encode(scheme, data)], axis=1)


def expected_unit(scheme: dict, payload: np.ndarray, unit: int) -> np.ndarray:
    """One unit uint8 [stripes, cell] of the above."""
    k, cell = scheme["k"], scheme["cell"]
    if payload.size % (k * cell):
        raise ValueError("payload is not whole stripes")
    data = payload.reshape(-1, k, cell)
    if unit < k:
        return data[:, unit]
    return reference.apply_rows([generator(scheme)[unit]], data)[:, 0]
