"""The benchmark's plain reference against the program's own coders, as
a cross-check in the tests only: the benchmark itself never imports
them."""

import numpy as np
import pytest

import bench_minicluster  # noqa: F401  (puts the repo root on sys.path)
from benchmarks.harness import reference


@pytest.mark.parametrize("k,p", [(6, 3), (10, 4)])
def test_reference_agrees_with_the_programs_coders(k, p):
    from ozone_tpu.codec.api import CoderOptions
    from ozone_tpu.codec.numpy_coder import NumpyRSEncoder
    from ozone_tpu.utils.checksum import Checksum, ChecksumType

    cell, bpc = 8192, 1024
    rng = np.random.default_rng([23, k])
    data = rng.integers(0, 256, (3, k, cell), dtype=np.uint8)
    parity = reference.encode(k, p, data)
    want = NumpyRSEncoder(CoderOptions(k, p, "rs", cell_size=cell)).encode(
        data)
    assert np.array_equal(parity, want)

    units = np.concatenate([data, parity], axis=1)
    erased = [1, k - 1][: min(2, p)]
    valid = [u for u in range(k + p) if u not in erased][:k]
    rec = reference.recover(k, p, valid, erased, units[:, valid])
    assert np.array_equal(rec, units[:, erased])

    flat = units.reshape(-1)
    got = reference.crc32c_slices(flat, bpc)
    sums = Checksum(ChecksumType.CRC32C, bpc).compute(flat).checksums
    assert [int.from_bytes(c, "big") for c in sums] == got.tolist()


def test_crc32c_check_value():
    # the CRC catalogue's check value for CRC-32C (Castagnoli)
    assert reference.crc32c(b"123456789") == 0xE3069283
    buf = np.frombuffer(b"123456789" * 2, dtype=np.uint8)
    assert reference.crc32c_slices(buf, 9).tolist() == [0xE3069283] * 2


def test_gf_inverse_and_matrix_inverse():
    for a in range(1, 256):
        assert reference.gf_mul(a, reference.gf_inv(a)) == 1
    rows = reference.encode_rows(6, 3)
    sub = [rows[i] for i in (0, 2, 3, 5, 6, 8)]
    inv = reference.gf_invert(sub)
    for i in range(6):
        for j in range(6):
            acc = 0
            for t in range(6):
                acc ^= reference.gf_mul(sub[i][t], inv[t][j])
            assert acc == (1 if i == j else 0)
