"""Pallas TPU kernel: fully fused EC encode + CRC in VMEM.

The XLA-composed pipeline (fused.py) materializes the 8x bit expansion and
the matmul accumulator in HBM; this kernel keeps everything in VMEM per
tile and writes only packed parity bytes + CRC words back, cutting HBM
traffic from ~17 bytes per input byte to ~1.6.

Per grid step (batch-block i, slice s) the kernel:
  1. loads data [S_b, k, T] uint8 (T == bytes_per_checksum),
  2. unpacks to {0,1} bits (int32 arithmetic — Mosaic on this platform
     rejects 8-bit elementwise ops; int8 only as MXU operands),
  3. parity bits = A^T (int8 [p8, k8]) @ bits (int8 [S_b, k8, T]) mod 2,
  4. packs parity bytes [p, S_b, T],
  5. CRCs data bits and (re-unpacked) parity via one [rows, 8T] @ [8T, 32]
     int8 MXU dot against the plane-major CRC contribution matrix,
  6. stores parity in [p, B, C] layout (avoids any in-kernel transpose;
     the wrapper moves the axis outside) and CRC words.

Design notes: no in-kernel transposes at all — parity bits for the CRC are
re-derived from the packed parity bytes instead of relayouting the matmul
output, which costs a little VPU work but avoids Mosaic relayouts.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ozone_tpu.codec import crc_device, rs_math
from ozone_tpu.codec.api import CoderOptions
from ozone_tpu.codec.bitlin import expand_coding_matrix
from ozone_tpu.codec.fused import FusedSpec, _POLY
from ozone_tpu.utils.checksum import ChecksumType


def _unpack_bits_i32(x_u8: jax.Array) -> jax.Array:
    """uint8 [..., T] -> int32 {0,1} [..., 8, T] (LSB-first planes)."""
    x = x_u8.astype(jnp.int32)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0)  # [8, 1]
    return (x[..., None, :] >> shifts) & 1


def _make_kernel(k: int, p: int, sb: int, t: int, zeros_crc: int):
    k8, p8 = 8 * k, 8 * p

    def kernel(data_ref, a_ref, kmat_ref, par_ref, crcd_ref, crcp_ref):
        # ---- unpack data bits
        d_bits = _unpack_bits_i32(data_ref[...])  # [sb, k, 8, t] int32
        bits8 = d_bits.astype(jnp.int8).reshape(sb, k8, t)

        # ---- encode: parity bits
        acc = jax.lax.dot_general(
            a_ref[...],  # [p8, k8] int8
            bits8,  # [sb, k8, t] int8
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # [p8, sb, t]
        pbits = acc & 1  # int32

        # ---- pack parity bytes: [p, 8, sb, t] -> weighted sum over bit axis
        w8 = jax.lax.broadcasted_iota(jnp.int32, (1, 8, 1, 1), 1)
        packed = jnp.sum(
            pbits.reshape(p, 8, sb, t) << w8, axis=1
        )  # [p, sb, t] int32
        packed_u8 = packed.astype(jnp.uint8)
        par_ref[...] = jnp.swapaxes(packed_u8, 0, 1)  # [sb, p, t]

        # ---- CRC of data units: rows (sb*k), cols plane-major (8*t)
        dcrc_acc = jax.lax.dot_general(
            bits8.reshape(sb * k, 8 * t),
            kmat_ref[...],  # [8t, 32] int8
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # [sb*k, 32]
        # ---- CRC of parity units: re-unpack packed bytes (no relayout)
        p_bits = _unpack_bits_i32(packed_u8)  # [p, sb, 8, t]
        pcrc_acc = jax.lax.dot_general(
            p_bits.astype(jnp.int8).reshape(p * sb, 8 * t),
            kmat_ref[...],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # [p*sb, 32]

        # int32 packing: Mosaic lacks unsigned reductions; summing distinct
        # powers of two wraps mod 2^32 with the exact same bit pattern, and
        # the wrapper bitcasts to uint32 outside the kernel
        w32 = jax.lax.broadcasted_iota(jnp.int32, (1, 32), 1)
        zc = jnp.int32(np.uint32(zeros_crc).view(np.int32))

        dwords = jnp.sum((dcrc_acc & 1) << w32, axis=-1) ^ zc  # [sb*k]
        pwords = jnp.sum((pcrc_acc & 1) << w32, axis=-1) ^ zc  # [p*sb]

        # CRC words are written broadcast over a 128-lane block per slice
        # (Mosaic rejects single-lane dynamic vector stores); the wrapper
        # reads lane 0 of each block
        crcd_ref[...] = jnp.broadcast_to(
            dwords.reshape(sb, k, 1), (sb, k, 128)
        )
        crcp_ref[...] = jnp.broadcast_to(
            jnp.swapaxes(pwords.reshape(p, sb), 0, 1)[:, :, None],
            (sb, p, 128),
        )

    return kernel


@lru_cache(maxsize=16)
def _pallas_fused_cached(
    options: CoderOptions,
    checksum: ChecksumType,
    bpc: int,
    sb: int,
    interpret: bool,
):
    k, p = options.data_units, options.parity_units
    t = bpc
    a_np = expand_coding_matrix(rs_math.parity_matrix(k, p))  # [k8, p8]
    a = jnp.asarray(a_np.T, dtype=jnp.int8)  # [p8, k8]
    k_np, zeros_crc = crc_device.crc_constants_planemajor(bpc, _POLY[checksum])
    # [8, bpc, 32] -> [8*bpc, 32] plane-major rows
    kmat = jnp.asarray(k_np.reshape(8 * bpc, 32))

    def call(data):  # [B, k, C] uint8
        b, _, c = data.shape
        assert b % sb == 0, (b, sb)
        assert c % t == 0, (c, t)
        s = c // t
        grid = (b // sb, s)
        par, crcd, crcp = pl.pallas_call(
            _make_kernel(k, p, sb, t, zeros_crc),
            grid=grid,
            in_specs=[
                pl.BlockSpec((sb, k, t), lambda i, j: (i, 0, j)),
                pl.BlockSpec((8 * p, 8 * k), lambda i, j: (0, 0)),
                pl.BlockSpec((8 * t, 32), lambda i, j: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((sb, p, t), lambda i, j: (i, 0, j)),
                pl.BlockSpec((sb, k, 128), lambda i, j: (i, 0, j)),
                pl.BlockSpec((sb, p, 128), lambda i, j: (i, 0, j)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, p, c), jnp.uint8),
                jax.ShapeDtypeStruct((b, k, s * 128), jnp.int32),
                jax.ShapeDtypeStruct((b, p, s * 128), jnp.int32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
                vmem_limit_bytes=100 * 1024 * 1024,
            ),
            interpret=interpret,
        )(data, a, kmat)
        crcd = crcd.reshape(b, k, s, 128)[..., 0]
        crcp = crcp.reshape(b, p, s, 128)[..., 0]
        crcs = jnp.concatenate([crcd, crcp], axis=1).view(jnp.uint32)
        return par, crcs

    return jax.jit(call)


def make_pallas_fused_encoder(
    spec: FusedSpec, stripes_per_block: int = 2, interpret: bool = False
):
    """Same contract as fused.make_fused_encoder: fn(data [B, k, C]) ->
    (parity [B, p, C], crcs [B, k+p, C//bpc]). B must divide by
    stripes_per_block; C by bytes_per_checksum. interpret=True runs the
    kernel in the pallas interpreter (CPU tests)."""
    if spec.checksum not in _POLY:
        raise ValueError(f"pallas path requires CRC checksums, got {spec.checksum}")
    return _pallas_fused_cached(
        spec.options,
        spec.checksum,
        spec.bytes_per_checksum,
        stripes_per_block,
        interpret,
    )
