"""Multi-chip sharded EC codec: jit + shard_map over a device mesh.

The distribution story of the TPU build (SURVEY.md section 2 "distribution
strategies" and BASELINE config #5 — multi-datanode reconstruction with
parity work sharded over v5e-8 ICI):

- **Stripe parallelism (DP)**: the stripe batch axis is sharded over the
  mesh; encode/decode+CRC run with zero cross-chip traffic. This is the
  production path for bulk encode and multi-block reconstruction — the
  structural analog of the reference running one reconstruction task per
  datanode (ECReconstructionCoordinator) but with the batch spread over
  chips instead of threads.

- **Unit parallelism (TP)**: the k data units are sharded over the mesh;
  each chip computes a partial GF(2) sum against its slice of the coding
  matrix and an int32 psum over ICI accumulates before the mod-2. XOR-
  accumulate distributes over psum because parity bits are sums mod 2 and
  integer addition commutes with the final &1. Used when single stripes
  are huge (cell >> HBM/chip) — the analog of splitting one stripe's
  coding work across nodes.

- **Ring reconstruction (SP)**: the k surviving units are sharded one
  group per chip — the natural layout when each chip fronts one datanode
  of the reconstruction read fan-in (ECReconstructionCoordinator reads k
  survivors in parallel; here each survivor's bytes land on a different
  chip). Each chip computes its packed-byte partial parity and the
  partials ride an explicit ppermute ring, XOR-combining at every hop
  (the ring-attention pattern applied to GF(2) coding: XOR is the
  mod-2 reduction, so packed uint8 partials — not bit-planes, not int32
  sums — are the ring payload, 32x less ICI traffic than a naive int32
  psum of bit-planes).

All collectives are XLA collectives over the mesh (psum / ppermute); no
host-side communication is involved.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from ozone_tpu.codec import crc_device
from ozone_tpu.codec.api import CoderOptions
from ozone_tpu.codec.bitlin import expand_coding_matrix
from ozone_tpu.codec.fused import (
    FusedSpec,
    _POLY,
    _decode_matrix,
    _parity_matrix,
    crc_plan_cached,
)
from ozone_tpu.codec.jax_coder import (
    _gf_dot,
    bits_to_bytes,
    bytes_to_bits,
    gf_apply,
    pack_bit_rows,
)
from ozone_tpu.utils.checksum import ChecksumType


def make_mesh(
    n_devices: Optional[int] = None, axis: str = "dn"
) -> Mesh:
    """1-D mesh over the first n devices (default: all)."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


def default_codec_mesh(axis: str = "dn") -> Optional[Mesh]:
    """Production mesh policy: all local devices when more than one is
    attached, None (single-chip fused path) otherwise. Datanode daemons
    and the minicluster hand this to the reconstruction coordinator and
    scrubber so multi-chip hosts repair/scrub across every chip without
    configuration. A backend that fails to initialise raises: a daemon
    that cannot reach its chip refuses to start instead of serving on
    the host."""
    return make_mesh(axis=axis) if jax.device_count() > 1 else None


def pad_batch(batch: np.ndarray, n: int) -> tuple[np.ndarray, int]:
    """Pad the leading axis to a multiple of n; returns (padded, original)."""
    b = batch.shape[0]
    rem = (-b) % n
    if rem:
        pad = np.zeros((rem,) + batch.shape[1:], dtype=batch.dtype)
        batch = np.concatenate([batch, pad], axis=0)
    return batch, b


# --------------------------------------------------------------------- DP
@lru_cache(maxsize=16)
def _sharded_fused_encoder_cached(
    options: CoderOptions,
    checksum: ChecksumType,
    bpc: int,
    mesh: Mesh,
    axis: str,
):
    a = jnp.asarray(
        expand_coding_matrix(_parity_matrix(options)),
        dtype=jnp.int8,
    )
    if checksum in _POLY:
        k_np, zeros_crc = crc_device.crc_constants_planemajor(
            bpc, _POLY[checksum]
        )
        k_dev = jnp.asarray(k_np)
    else:
        k_dev, zeros_crc = None, 0

    batch_sharding = NamedSharding(mesh, P(axis))

    # a name of its own: the device trace's module line then reads
    # `jit_sharded_fused_encode(`, apart from the single-chip `jit_fn(`
    def sharded_fused_encode(data):
        parity = gf_apply(data, a)
        if k_dev is None:
            crcs = jnp.zeros(
                (data.shape[0], data.shape[1] + parity.shape[1], 0), jnp.uint32
            )
        else:
            crcs = jnp.concatenate(
                [
                    crc_device.crc_slices(data, k_dev, zeros_crc),
                    crc_device.crc_slices(parity, k_dev, zeros_crc),
                ],
                axis=1,
            )
        return parity, crcs

    return jax.jit(
        sharded_fused_encode,
        in_shardings=batch_sharding,
        out_shardings=(batch_sharding, batch_sharding),
    )


def make_sharded_fused_encoder(spec: FusedSpec, mesh: Mesh, axis: str = "dn"):
    """Stripe-parallel fused encode+CRC: fn(data [B, k, C]) with B sharded
    over the mesh; B must divide by mesh size (see pad_batch)."""
    return _sharded_fused_encoder_cached(
        spec.options, spec.checksum, spec.bytes_per_checksum, mesh, axis
    )


@lru_cache(maxsize=16)
def _sharded_decode_apply_cached(mesh: Mesh, axis: str, with_crc: bool,
                                 zeros_crc: int):
    """One sharded decode+CRC executable per (mesh, shape): the recovery
    matrix and CRC constants arrive as traced, mesh-replicated arguments
    (the fused._decode_apply_jit treatment with explicit shardings), so
    erasure-pattern churn during multi-unit failures never recompiles
    the SPMD program — only the tiny replicated matrix changes."""
    batch_sharding = NamedSharding(mesh, P(axis))
    replicated = NamedSharding(mesh, P())

    if not with_crc:
        def sharded_decode_apply_nocrc(valid_units, a):
            rec = gf_apply(valid_units, a)
            return rec, jnp.zeros(rec.shape[:2] + (0,), jnp.uint32)

        return jax.jit(
            sharded_decode_apply_nocrc,
            in_shardings=(batch_sharding, replicated),
            out_shardings=(batch_sharding, batch_sharding),
        )

    def sharded_decode_apply(valid_units, a, k_dev):
        rec = gf_apply(valid_units, a)
        crcs = crc_device.crc_slices(rec, k_dev, zeros_crc)
        return rec, crcs

    return jax.jit(
        sharded_decode_apply,
        in_shardings=(batch_sharding, replicated, replicated),
        out_shardings=(batch_sharding, batch_sharding),
    )


@lru_cache(maxsize=512)
def _sharded_decode_plan_cached(
    options: CoderOptions, valid: tuple, erased: tuple,
):
    """Per-pattern decode matrix for the sharded path; cheap host work,
    shared executable above, CRC constants shared via
    fused.crc_plan_cached."""
    dm = _decode_matrix(options, list(valid), list(erased))
    return jnp.asarray(expand_coding_matrix(dm), dtype=jnp.int8)


def make_sharded_decoder(
    spec: FusedSpec, valid: list[int], erased: list[int], mesh: Mesh,
    axis: str = "dn",
):
    """Stripe-parallel fused decode+CRC (multi-chip reconstruction path).
    Pattern-count-proof like the single-chip path: one compiled SPMD
    program per shape serves every (valid, erased) pattern."""
    a = _sharded_decode_plan_cached(
        spec.options, tuple(valid), tuple(erased))
    k_dev, zeros_crc = crc_plan_cached(spec.checksum,
                                       spec.bytes_per_checksum)
    apply_fn = _sharded_decode_apply_cached(
        mesh, axis, k_dev is not None, zeros_crc)
    if k_dev is None:
        return lambda valid_units: apply_fn(valid_units, a)
    return lambda valid_units: apply_fn(valid_units, a, k_dev)


# --------------------------------------------------------------------- TP
@lru_cache(maxsize=16)
def _tp_encoder_cached(options: CoderOptions, mesh: Mesh, axis: str):
    k, p = options.data_units, options.parity_units
    n = mesh.devices.size
    if k % n:
        raise ValueError(f"TP encode requires k % mesh == 0, got {k} % {n}")
    a_np = expand_coding_matrix(_parity_matrix(options))  # [k*8, p*8]
    a = jnp.asarray(a_np, dtype=jnp.int8)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(None, axis, None), P(axis, None)),
        out_specs=P(None, None, None),
    )
    def tp_encode(data_local, a_local):
        # data_local [B, k/n, C]; a_local [k*8/n, p*8]
        bits = bytes_to_bits(data_local)  # [B, (k/n)*8, C]
        partial_acc = jax.lax.dot_general(
            a_local.T,
            bits,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # [p*8, B, C] partial integer sums
        total = jax.lax.psum(partial_acc, axis)  # ICI collective
        pbits = jnp.moveaxis(jnp.bitwise_and(total, 1), 0, -2).astype(jnp.int8)
        return bits_to_bytes(pbits)  # [B, p, C] replicated

    return jax.jit(lambda d: tp_encode(d, a))


def make_tp_encoder(options: CoderOptions, mesh: Mesh, axis: str = "dn"):
    """Unit-parallel encode: data units sharded over the mesh, parity
    accumulated with psum over ICI. fn(data [B, k, C]) -> parity [B, p, C]."""
    return _tp_encoder_cached(options, mesh, axis)


# ------------------------------------------------------------------- ring
@lru_cache(maxsize=512)
def _ring_decode_plan_cached(
    options: CoderOptions, valid: tuple, erased: tuple, n: int,
):
    """Per-pattern ring plan: the decode matrix zero-padded to the
    mesh's survivor slots. Cheap host work; the compiled SPMD program
    lives in _ring_apply_cached and serves every pattern of a shape."""
    k = len(valid)
    e = len(erased)
    upc = -(-k // n)  # units per chip, survivors zero-padded to upc * n
    dm = _decode_matrix(options, list(valid), list(erased))  # GF [e, k]
    a_np = expand_coding_matrix(dm)  # [k*8, e*8]
    if upc * n != k:
        # zero matrix rows for the padded survivor slots: a zero unit
        # contributes a zero partial, keeping the ring XOR exact
        a_np = np.concatenate(
            [a_np, np.zeros(((upc * n - k) * 8, e * 8), dtype=a_np.dtype)]
        )
    return jnp.asarray(a_np, dtype=jnp.int8), upc


@lru_cache(maxsize=16)
def _ring_apply_cached(mesh: Mesh, axis: str, with_crc: bool,
                       zeros_crc: int):
    """One ring-decode executable per (mesh, shape): like the DP path,
    the padded recovery matrix arrives as a traced argument (sharded
    over survivors), so erasure-pattern churn never recompiles the
    SPMD ring program."""
    n = mesh.devices.size
    perm = [(i, (i + 1) % n) for i in range(n)]

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(None, axis, None), P(axis, None)),
        out_specs=P(None, None, None),
        # replication checker off: the output IS
        # replicated, but only by a dynamic argument — after n-1
        # ppermute hops every chip has XOR-accumulated all n partials
        # (each hop k adds the partial that originated k chips
        # upstream), so all chips hold the same XOR-of-all-partials.
        # The static replication checker cannot prove properties that
        # depend on the permutation completing a cycle; the dryrun
        # asserts cross-device equality of this output at runtime
        # (__graft_entry__.dryrun_multichip).
        check_vma=False,
    )
    def ring_decode(units_local, a_local):
        # units_local [B, upc, C] uint8; a_local [upc*8, e*8] int8
        pbits = _gf_dot(bytes_to_bits(units_local), a_local)  # [e*8, B, C]
        # pack the PARTIAL parity to bytes before touching the ring: XOR
        # of packed bytes == packed XOR of bits, so the ring payload is
        # [e, B, C] uint8 — 8x smaller than bit-planes
        local = pack_bit_rows(pbits)  # [e, B, C]
        acc_ring = local
        for _ in range(n - 1):
            acc_ring = (
                jax.lax.ppermute(acc_ring, axis, perm) ^ local
            )
        return jnp.moveaxis(acc_ring, 0, 1)  # [B, e, C] replicated

    batch_sharding = NamedSharding(mesh, P(axis))

    if not with_crc:
        def inner_nocrc(valid_units, a):
            rec = ring_decode(valid_units, a)
            return rec, jnp.zeros(rec.shape[:2] + (0,), jnp.uint32)

        return jax.jit(inner_nocrc)

    def inner(valid_units, a, k_dev):
        rec = ring_decode(valid_units, a)
        # the ring output is replicated; shard the CRC pass over the
        # stripe batch so the checksum work spreads over the mesh
        # instead of running n-fold redundantly
        rec_sh = jax.lax.with_sharding_constraint(rec, batch_sharding)
        crcs = crc_device.crc_slices(rec_sh, k_dev, zeros_crc)
        return rec, crcs

    return jax.jit(inner)


def make_ring_decoder(
    spec: FusedSpec, valid: list[int], erased: list[int], mesh: Mesh,
    axis: str = "dn",
):
    """Survivor-sharded ring reconstruction: fn(valid_units [B, k, C]) ->
    (recovered [B, e, C], crcs). The k survivor units are sharded over the
    mesh (zero-padded to a multiple of its size); packed-byte partial
    parities XOR-combine around a ppermute ring. The multi-datanode
    reconstruction layout of BASELINE config #5: each chip ingests one
    survivor datanode's bytes, no chip ever holds the whole stripe.
    Pattern-count-proof like the DP path: the padded decode matrix is a
    per-pattern plan fed to ONE compiled ring program per shape."""
    n = mesh.devices.size
    a, upc = _ring_decode_plan_cached(
        spec.options, tuple(valid), tuple(erased), n)
    k_dev, zeros_crc = crc_plan_cached(spec.checksum,
                                       spec.bytes_per_checksum)
    apply_fn = _ring_apply_cached(mesh, axis, k_dev is not None, zeros_crc)

    def fn(valid_units):
        b, kk, c = valid_units.shape
        if kk != upc * n:
            # pad OUTSIDE the jitted program: inside it, the zeros pad
            # is a broadcast whose unit axis (size upc*n-kk < n) cannot
            # take the survivor sharding, forcing XLA's SPMD partitioner
            # into an involuntary full rematerialization
            # (replicate-then-repartition) — the round-1 dryrun warning.
            # jnp (not np) keeps the wrapper traceable and device arrays
            # on device; the jit call boundary below shards the result.
            pad = jnp.zeros((b, upc * n - kk, c), dtype=valid_units.dtype)
            valid_units = jnp.concatenate(
                [jnp.asarray(valid_units), pad], axis=1)
        return (apply_fn(valid_units, a) if k_dev is None
                else apply_fn(valid_units, a, k_dev))

    return fn
