"""Fused EC encode + CRC pass: stripes never round-trip to host.

One jitted program takes a stripe batch [B, k, C], produces parity
[B, p, C] and per-slice CRCs for all k+p units [B, k+p, C/bpc] — the
north-star fusion (BASELINE.json: "ChunkUtils CRC32C checksumming is fused
into the same device pass so stripes never round-trip to host between
encode and verify"). The reference computes these in two separate host
passes (RSUtil.encodeData then Checksum.computeChecksum per chunk).

Also provides the fused decode+verify used by degraded read and offline
reconstruction: recover erased units and checksum them in one dispatch.
"""

from __future__ import annotations

import functools
import os
import threading
from dataclasses import dataclass
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from ozone_tpu.codec import crc_device, rs_math
from ozone_tpu.codec.api import CoderOptions
from ozone_tpu.codec.bitlin import expand_coding_matrix
from ozone_tpu.codec.jax_coder import gf_apply
from ozone_tpu.utils import checksum as hostsum
from ozone_tpu.utils.checksum import ChecksumType

_POLY = {
    ChecksumType.CRC32: hostsum.CRC32_POLY,
    ChecksumType.CRC32C: hostsum.CRC32C_POLY,
}


def effective_bpc(cell_size: int, bytes_per_checksum: int) -> int:
    """Clamp bytes-per-checksum so cells divide into whole slices: the
    device CRC kernel computes fixed-size slices, so a bpc larger than the
    cell (or not dividing it) degrades to one checksum per cell."""
    if bytes_per_checksum <= 0:
        return cell_size
    if bytes_per_checksum <= cell_size and cell_size % bytes_per_checksum == 0:
        return bytes_per_checksum
    return cell_size


@dataclass(frozen=True)
class FusedSpec:
    options: CoderOptions
    checksum: ChecksumType = ChecksumType.CRC32C
    bytes_per_checksum: int = 16 * 1024

    def __post_init__(self):
        object.__setattr__(
            self,
            "bytes_per_checksum",
            effective_bpc(self.options.cell_size, self.bytes_per_checksum),
        )


def _parity_matrix(options: CoderOptions) -> np.ndarray:
    """p x k GF(2^8) parity generator for the option's codec: Cauchy for
    RS, the all-ones row for XOR single parity (XORRawEncoder semantics —
    parity = XOR of the k data units, coefficient 1 each).  LRC stacks
    its local XOR rows and global Cauchy rows into one generator
    (lrc_math.parity_matrix) so all l+r parities still cost ONE fused
    matmul dispatch."""
    if options.codec == "xor":
        if options.parity_units != 1:
            raise ValueError("xor codec has exactly one parity unit")
        return np.ones((1, options.data_units), dtype=np.uint8)
    if options.codec == "lrc":
        from ozone_tpu.codec import lrc_math

        return lrc_math.parity_matrix(options)
    return rs_math.parity_matrix(options.data_units, options.parity_units)


def _decode_matrix(options: CoderOptions, valid: list[int],
                   erased: list[int]) -> np.ndarray:
    """e x len(valid) GF(2^8) recovery matrix. RS inverts the surviving
    k x k submatrix (RSRawDecoder.java:133-157); XOR recovers its single
    erasable unit as the XOR of everything else (XORRawDecoder).  LRC
    solves over an ARBITRARY read set (len(valid) may be the local group
    size instead of k — lrc_math.recovery_rows), which downstream is
    just a different traced-matrix shape, not a new program per
    pattern."""
    if options.codec == "lrc":
        from ozone_tpu.codec import lrc_math

        return lrc_math.recovery_rows(options, list(valid), list(erased))
    if options.codec == "xor":
        if len(erased) != 1:
            raise ValueError("xor codec recovers at most one erasure")
        if len(valid) != options.data_units:
            raise ValueError("xor decode needs all other units")
        if erased[0] == options.data_units:
            # the parity itself: re-encode from the k data units
            return np.ones((1, options.data_units), dtype=np.uint8)
        return np.ones((1, len(valid)), dtype=np.uint8)
    return rs_math.decode_matrix(
        options.data_units, options.parity_units, list(erased), list(valid))


@lru_cache(maxsize=16)
def _fused_encode_cached(options: CoderOptions, checksum: ChecksumType, bpc: int):
    a_np = expand_coding_matrix(_parity_matrix(options))
    a = jnp.asarray(a_np, dtype=jnp.int8)
    if checksum in _POLY:
        k_np, zeros_crc = crc_device.crc_constants_planemajor(bpc, _POLY[checksum])
        k_dev = jnp.asarray(k_np)
    else:
        k_dev, zeros_crc = None, 0

    @jax.jit
    def fn(data: jax.Array):
        parity = gf_apply(data, a)
        if k_dev is None:
            return parity, jnp.zeros(
                (data.shape[0], data.shape[1] + parity.shape[1], 0), jnp.uint32
            )
        # CRC data and parity units separately (concatenating the byte
        # buffers first would copy 1.5x the batch through HBM)
        crcs = jnp.concatenate(
            [
                crc_device.crc_slices(data, k_dev, zeros_crc),
                crc_device.crc_slices(parity, k_dev, zeros_crc),
            ],
            axis=1,
        )
        return parity, crcs

    return fn


def _prefer_host_coder() -> bool:
    """True when the fused pass should run on the host. The rule reads
    off the platform: the jax backend is CPU (tests, hosts with no chip
    — XLA's GF(2) bit-matmul formulation is an MXU shape; on plain CPUs
    the native AVX2 nibble-shuffle coder + SSE4.2 CRC is an order of
    magnitude faster) -> native twin; any other platform -> the jitted
    program. A backend that fails to initialise raises here: a process
    that cannot reach its chip must not finish on the host.
    OZONE_TPU_FUSED_BACKEND=jax|native overrides (tests)."""
    forced = os.environ.get("OZONE_TPU_FUSED_BACKEND", "")
    if forced == "jax":
        return False
    if forced == "native":
        return True
    return jax.default_backend() == "cpu"


_REPORT_LOCK = threading.Lock()
#: which fused paths the factories handed out in this process
_CHOSEN: set[str] = set()


def _chose(backend: str) -> None:
    with _REPORT_LOCK:
        _CHOSEN.add(backend)


def backend_report() -> dict:
    """What this process's fused passes ran on: the device as JAX
    reports it and which path the factories chose (`jax`, `native`,
    `jax+native` when both were handed out, `none` before any factory
    ran). Load generators print it beside their numbers so no result is
    read without its device."""
    devs = jax.devices()
    with _REPORT_LOCK:
        return {
            "platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs),
            "fused_backend": "+".join(sorted(_CHOSEN)) or "none",
        }


def _native_crc_slices(units: np.ndarray, bpc: int) -> np.ndarray:
    """[B, U, C] uint8 -> [B, U, C // bpc] uint32 via the native
    hardware-CRC slicer; C divides by bpc (FusedSpec contract), so one
    flat pass never crosses a unit boundary."""
    from ozone_tpu.codec.cpp_coder import _require_lib

    lib = _require_lib()
    flat = np.ascontiguousarray(units).reshape(-1)
    out = np.empty(flat.size // bpc, dtype=np.uint32)
    lib.crc32c_slices(flat.ctypes.data, flat.size, bpc, out.ctypes.data)
    return out.reshape(units.shape[0], units.shape[1], -1)


@lru_cache(maxsize=16)
def _native_fused_encoder(options: CoderOptions, checksum: ChecksumType,
                          bpc: int):
    """Host twin of the fused device pass: AVX2 GF multiply + hardware
    CRC32C, same (parity, crcs) contract, numpy in/out. Returns None
    when the native library or checksum type can't serve it."""
    if checksum is not ChecksumType.CRC32C:
        return None
    try:
        from ozone_tpu.codec.cpp_coder import _nibble_tables, _apply, \
            _require_lib

        lib = _require_lib()
    except Exception:  # noqa: BLE001 - no native lib: jax path
        return None
    tables = _nibble_tables(_parity_matrix(options))
    p, k = options.parity_units, options.data_units

    def fn(data: np.ndarray):
        data = np.ascontiguousarray(data, dtype=np.uint8)
        parity = _apply(lib, tables, p, k, data)
        crcs = np.concatenate(
            [_native_crc_slices(data, bpc),
             _native_crc_slices(parity, bpc)], axis=1)
        return parity, crcs

    return fn


def make_fused_encoder(spec: FusedSpec):
    """fn(data uint8 [B, k, C]) -> (parity [B, p, C],
    crcs uint32 [B, k+p, C // bpc]). C must divide by bytes_per_checksum.
    Jitted on accelerator backends; the native AVX2+CRC twin where the
    platform is CPU (_prefer_host_coder) and the twin can serve the
    spec (CRC32C, library built)."""
    if _prefer_host_coder():
        fn = _native_fused_encoder(spec.options, spec.checksum,
                                   spec.bytes_per_checksum)
        if fn is not None:
            _chose("native")
            return fn
    _chose("jax")
    return _fused_encode_cached(spec.options, spec.checksum,
                                spec.bytes_per_checksum)


@functools.partial(jax.jit, static_argnames=("zeros_crc",))
def _decode_apply_jit(valid_units: jax.Array, a_bits: jax.Array,
                      k_planes: jax.Array, zeros_crc: int):
    """One decode+CRC executable for EVERY erasure pattern: the recovery
    matrix arrives as a traced argument (the jax_coder._gf_apply_jit
    treatment applied to the fused pass), so jit caches per SHAPE
    (batch, erasure count, cell, bpc) — pattern churn during multi-unit
    failures swaps the tiny device matrix, never the compiled program.
    The old per-(valid, erased) lru_cache of jitted closures evicted
    whole executables under churn and recompiled mid-read."""
    rec = gf_apply(valid_units, a_bits)  # [B, e, C]
    crcs = crc_device.crc_slices(rec, k_planes, zeros_crc)
    return rec, crcs


@jax.jit
def _decode_apply_nocrc_jit(valid_units: jax.Array, a_bits: jax.Array):
    rec = gf_apply(valid_units, a_bits)  # [B, e, C]
    return rec, jnp.zeros(rec.shape[:2] + (0,), jnp.uint32)


def decode_jit_cache_size() -> int:
    """Compiled fused-decode executables currently cached. The
    pattern-churn tests probe this to assert that a NEW erasure
    pattern of an already-seen shape costs zero recompiles."""
    return int(_decode_apply_jit._cache_size()
               + _decode_apply_nocrc_jit._cache_size())


@lru_cache(maxsize=8)
def crc_plan_cached(checksum: ChecksumType, bpc: int):
    """(device CRC constant table | None, initial CRC) for one
    (checksum, bpc) — pattern-INDEPENDENT, so every decode plan of a
    config shares ONE device copy instead of re-deriving and re-storing
    the table per erasure pattern."""
    if checksum in _POLY:
        k_np, zeros_crc = crc_device.crc_constants_planemajor(
            bpc, _POLY[checksum])
        return jnp.asarray(k_np), zeros_crc
    return None, 0


@lru_cache(maxsize=512)
def _decode_plan_cached(options: CoderOptions, valid: tuple, erased: tuple):
    """Persistent decode plan for one (valid, erased) pattern: the
    device-resident bit-expanded recovery matrix. Cheap to build (a
    k x k GF inversion and one small device_put), so the cache can be
    generously sized — the expensive jitted executable lives in
    _decode_apply_jit and is shared across all patterns."""
    dm = _decode_matrix(options, list(valid), list(erased))
    return jnp.asarray(expand_coding_matrix(dm), dtype=jnp.int8)


def _fused_decode_plan(options: CoderOptions, checksum: ChecksumType,
                       bpc: int, valid: tuple, erased: tuple):
    a = _decode_plan_cached(options, valid, erased)
    k_dev, zeros_crc = crc_plan_cached(checksum, bpc)
    if k_dev is None:
        return lambda valid_units: _decode_apply_nocrc_jit(valid_units, a)
    return lambda valid_units: _decode_apply_jit(
        valid_units, a, k_dev, zeros_crc)


@lru_cache(maxsize=512)
def _native_fused_decoder(options: CoderOptions, checksum: ChecksumType,
                          bpc: int, valid: tuple, erased: tuple):
    if checksum is not ChecksumType.CRC32C:
        return None
    try:
        from ozone_tpu.codec.cpp_coder import _nibble_tables, _apply, \
            _require_lib

        lib = _require_lib()
    except Exception:  # noqa: BLE001
        return None
    dm = _decode_matrix(options, list(valid), list(erased))
    tables = _nibble_tables(dm)
    e, kk = len(erased), len(valid)

    def fn(valid_units: np.ndarray):
        valid_units = np.ascontiguousarray(valid_units, dtype=np.uint8)
        rec = _apply(lib, tables, e, kk, valid_units)
        return rec, _native_crc_slices(rec, bpc)

    return fn


def make_fused_decoder(spec: FusedSpec, valid: list[int], erased: list[int]):
    """fn(valid_units uint8 [B, k, C]) -> (recovered [B, e, C],
    crcs uint32 [B, e, C // bpc]). valid lists the unit indexes of the rows
    supplied, erased the unit indexes to reconstruct. Jitted on
    accelerator backends; native AVX2+CRC twin where the platform is
    CPU. Device plans come from the persistent decode-plan cache: one
    compiled program per SHAPE serves every erasure pattern (see
    _decode_apply_jit)."""
    if _prefer_host_coder():
        fn = _native_fused_decoder(
            spec.options, spec.checksum, spec.bytes_per_checksum,
            tuple(valid), tuple(erased))
        if fn is not None:
            _chose("native")
            return fn
    _chose("jax")
    return _fused_decode_plan(
        spec.options, spec.checksum, spec.bytes_per_checksum,
        tuple(valid), tuple(erased),
    )


@lru_cache(maxsize=16)
# ozlint: allow[dispatch-shape-stability] -- `lost` is bounded by data_units (<= a handful of programs, all cache-resident); folding it into the matrix as a traced arg would forfeit the single fused dispatch
def _fused_reencode_cached(options: CoderOptions, checksum: ChecksumType,
                           bpc: int, lost: int):
    """XOR(1)-decode -> RS(k,p)-encode as ONE bit-linear matrix.

    The XOR decode (recover unit `lost` from the k-1 survivors plus the
    XOR parity) and the RS parity generation are both GF(2^8)-linear, so
    their composition is a single matrix: M = [D[lost] ; P @ D], where D
    is the k x k XOR-decode matrix (identity rows for survivors, the
    all-ones row for the lost unit) and P the Cauchy parity matrix.
    Precomputing M host-side (gf_matmul) collapses what the reference
    runs as XORRawDecoder.decode followed by RSRawEncoder.encode — and
    what round 1 ran as two device dispatches with an HBM round trip —
    into one gf_apply + fused CRC pass."""
    from ozone_tpu.codec.gf256 import gf_matmul

    k, p = options.data_units, options.parity_units
    d = np.eye(k, dtype=np.uint8)
    # input slot `lost` holds the XOR parity; over GF(2) the lost unit is
    # the XOR of ALL k input slots (survivors + parity)
    d[lost, :] = 1
    pm = rs_math.parity_matrix(k, p)
    m = np.vstack([d[lost:lost + 1], gf_matmul(pm, d)])
    a = jnp.asarray(expand_coding_matrix(m), dtype=jnp.int8)
    if checksum in _POLY:
        k_np, zeros_crc = crc_device.crc_constants_planemajor(
            bpc, _POLY[checksum])
        k_dev = jnp.asarray(k_np)
    else:
        k_dev, zeros_crc = None, 0

    @jax.jit
    def fn(units: jax.Array):
        out = gf_apply(units, a)  # [B, 1+p, C]: recovered unit, parity
        if k_dev is None:
            empty = jnp.zeros((units.shape[0], 0, 0), jnp.uint32)
            return out, empty, empty
        # CRCs stay in producer order — slicing/interleaving the big
        # byte tensors on device would re-write the whole output through
        # HBM (measured ~35% of the dispatch); the CRC arrays are tiny
        # and the host assembles the k+p layout order for free
        return (out,
                crc_device.crc_slices(units, k_dev, zeros_crc),
                crc_device.crc_slices(out, k_dev, zeros_crc))

    return fn


def make_fused_reencoder(spec: FusedSpec, lost: int = 0):
    """jitted fn(units uint8 [B, k, C]) -> (out [B, 1+p, C],
    units_crcs uint32 [B, k, S], out_crcs uint32 [B, 1+p, S]).

    `units` carries the XOR(1) group with data unit `lost` replaced by
    the XOR parity in its slot; the single dispatch recovers the lost
    unit (out[:, 0]), produces the RS parity of the full group
    (out[:, 1:]), and checksums every unit (BASELINE config #4 without
    the lost unit ever round-tripping through HBM between decode and
    encode). `reencode_layout_crcs` assembles the k+p EC-layout CRC
    order host-side; units_crcs[:, lost] checksums the XOR parity slot
    and is simply unused."""
    _chose("jax")
    return _fused_reencode_cached(
        spec.options, spec.checksum, spec.bytes_per_checksum, int(lost))


def reencode_layout_crcs(units_crcs: np.ndarray, out_crcs: np.ndarray,
                         lost: int) -> np.ndarray:
    """Assemble re-encode CRCs into EC layout order [B, k+p, S]: data
    units 0..k-1 (the recovered unit in slot `lost`), then parity."""
    return np.concatenate(
        [units_crcs[:, :lost], out_crcs[:, :1],
         units_crcs[:, lost + 1:], out_crcs[:, 1:]],
        axis=1,
    )
