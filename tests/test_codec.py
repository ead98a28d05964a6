"""Raw coder SPI tests: encode -> erase -> decode -> compare.

Mirrors the strategy of the reference's TestRawCoderBase (erasurecode
src/test .../rawcoder/TestRawCoderBase.java): randomized data, randomized
erasure sets across data+parity units, multiple chunk sizes, and
cross-backend bit-compatibility (numpy vs jax, the analog of the reference's
Java vs ISA-L interop guarantee, RSRawEncoder.java:25-28).
"""

import numpy as np
import pytest

from ozone_tpu.codec import CoderOptions, create_decoder, create_encoder
from ozone_tpu.codec.registry import CodecRegistry
from ozone_tpu.utils.checksum import ChecksumType

SCHEMAS = [("rs", 3, 2), ("rs", 6, 3), ("rs", 10, 4), ("xor", 4, 1)]
BACKENDS = ["numpy", "jax"]


def _roundtrip(codec, k, p, backend, batch, cell, rng, n_erase=None):
    opts = CoderOptions(k, p, codec, cell_size=cell)
    enc = create_encoder(opts, backend)
    dec = create_decoder(opts, backend)
    shape = (batch, k, cell) if batch else (k, cell)
    data = rng.integers(0, 256, shape, dtype=np.uint8)
    parity = enc.encode(data)
    units = np.concatenate([data, parity], axis=-2)

    max_erase = 1 if codec == "xor" else p
    n_erase = n_erase or max_erase
    erased = sorted(rng.choice(k + p, size=n_erase, replace=False).tolist())
    inputs = [None if i in erased else units[..., i, :] for i in range(k + p)]
    rec = dec.decode(inputs, erased)
    assert np.array_equal(rec, units[..., erased, :]), (codec, k, p, erased)


@pytest.mark.parametrize("codec,k,p", SCHEMAS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_roundtrip_unbatched(codec, k, p, backend):
    _roundtrip(codec, k, p, backend, batch=0, cell=257, rng=np.random.default_rng(7))


@pytest.mark.parametrize("codec,k,p", SCHEMAS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_roundtrip_batched(codec, k, p, backend):
    _roundtrip(codec, k, p, backend, batch=5, cell=128, rng=np.random.default_rng(8))


@pytest.mark.parametrize("codec,k,p", SCHEMAS)
def test_backends_bit_identical(codec, k, p):
    rng = np.random.default_rng(9)
    opts = CoderOptions(k, p, codec, cell_size=512)
    data = rng.integers(0, 256, (3, k, 512), dtype=np.uint8)
    outs = [create_encoder(opts, b).encode(data) for b in BACKENDS]
    assert np.array_equal(outs[0], outs[1])


@pytest.mark.parametrize("backend", BACKENDS)
def test_rs_all_erasure_patterns_small(backend):
    """Exhaustive erasure patterns for RS(3,2)."""
    import itertools

    rng = np.random.default_rng(10)
    opts = CoderOptions(3, 2, "rs", cell_size=64)
    enc = create_encoder(opts, backend)
    dec = create_decoder(opts, backend)
    data = rng.integers(0, 256, (3, 64), dtype=np.uint8)
    parity = enc.encode(data)
    units = np.concatenate([data, parity], axis=0)
    for n in (1, 2):
        for erased in itertools.combinations(range(5), n):
            inputs = [None if i in erased else units[i] for i in range(5)]
            rec = dec.decode(inputs, list(erased))
            assert np.array_equal(rec, units[list(erased)]), erased


def test_known_vector_rs_3_2():
    """Pin parity bytes for a fixed input so any coder regression or
    incompatibility with the ISA-L matrix layout shows up as a diff."""
    opts = CoderOptions(3, 2, "rs", cell_size=8)
    enc = create_encoder(opts, "numpy")
    data = np.arange(24, dtype=np.uint8).reshape(3, 8)
    parity = enc.encode(data)
    # recompute from first principles: P = enc_matrix rows k..k+p
    from ozone_tpu.codec import gf256, rs_math

    expected = gf256.gf_matmul(rs_math.parity_matrix(3, 2), data)
    assert np.array_equal(parity, expected)


def test_dummy_coder():
    opts = CoderOptions(3, 2, "dummy")
    enc = create_encoder(opts)
    data = np.ones((3, 16), dtype=np.uint8)
    assert np.array_equal(enc.encode(data), np.zeros((2, 16), np.uint8))


def test_registry_priority_and_fallback():
    reg = CodecRegistry.instance()
    assert "numpy" in reg.backends("rs")
    # jax should be present in this environment and preferred
    assert reg.backends("rs")[0] == "jax"
    with pytest.raises(ValueError):
        create_encoder(CoderOptions(3, 2, "nosuch"))


def test_options_parse_roundtrip():
    o = CoderOptions.parse("rs-6-3-1024k")
    assert (o.data_units, o.parity_units, o.cell_size) == (6, 3, 1024 * 1024)
    assert str(o) == "rs-6-3-1m"
    o2 = CoderOptions.parse("xor-4-1-4096")
    assert o2.cell_size == 4096


def test_decoder_input_validation():
    opts = CoderOptions(3, 2, "rs")
    dec = create_decoder(opts, "numpy")
    units = [np.zeros(8, np.uint8)] * 5
    with pytest.raises(ValueError):
        dec.decode(units[:4], [0])  # wrong length
    with pytest.raises(ValueError):
        dec.decode(units, [0])  # erased index not None
    inputs = [None, None, None, units[3], units[4]]
    with pytest.raises(ValueError):
        dec.decode(inputs, [0, 1, 2])  # only 2 available


def test_backend_selection_rule(monkeypatch):
    """The fused backend is a rule anyone can read off the platform —
    cpu (the platform was NAMED) -> native twin, anything else -> the
    jitted program, OZONE_TPU_FUSED_BACKEND overrides — and a backend
    that fails to initialise propagates instead of finishing on the
    host: from the fused factories, the default mesh, the mesh executor
    and the codec registry."""
    from ozone_tpu.codec import fused
    from ozone_tpu.codec.numpy_coder import NumpyRSDecoder, NumpyRSEncoder
    from ozone_tpu.parallel import mesh_executor, sharded

    opts = CoderOptions(6, 3, "rs", cell_size=4096)
    spec = fused.FusedSpec(opts, ChecksumType.CRC32C, 1024)
    jitted = fused._fused_encode_cached(opts, ChecksumType.CRC32C, 1024)
    native = fused._native_fused_encoder(opts, ChecksumType.CRC32C, 1024)
    monkeypatch.delenv("OZONE_TPU_FUSED_BACKEND", raising=False)

    assert fused._prefer_host_coder() is True  # tests run on cpu
    if native is not None:  # toolchain present
        assert fused.make_fused_encoder(spec) is native
    monkeypatch.setenv("OZONE_TPU_FUSED_BACKEND", "jax")
    assert fused.make_fused_encoder(spec) is jitted
    monkeypatch.setenv("OZONE_TPU_FUSED_BACKEND", "native")
    assert fused._prefer_host_coder() is True
    monkeypatch.delenv("OZONE_TPU_FUSED_BACKEND")
    monkeypatch.setattr(fused.jax, "default_backend", lambda: "tpu")
    assert fused._prefer_host_coder() is False
    assert fused.make_fused_encoder(spec) is jitted

    # a registry whose device coder cannot be built
    def no_device(_options):
        raise RuntimeError("coder needs the chip")

    reg = CodecRegistry()
    reg.register("rs", "numpy", 10, NumpyRSEncoder, NumpyRSDecoder)
    reg.register("rs", "jax", 100, no_device, no_device)
    with pytest.raises(RuntimeError, match="needs the chip"):
        reg.create_encoder(opts)  # platform is not cpu: no host coder

    def held(*_a, **_k):
        raise RuntimeError("Unable to initialize backend 'tpu': in use")

    monkeypatch.setattr(fused.jax, "default_backend", held)
    monkeypatch.setattr(fused.jax, "device_count", held)
    for call in (lambda: fused.make_fused_encoder(spec),
                 lambda: fused.make_fused_decoder(
                     spec, [0, 1, 2, 3, 4, 5], [6]),
                 sharded.default_codec_mesh,
                 mesh_executor.maybe_executor,
                 lambda: reg.create_decoder(opts)):
        with pytest.raises(RuntimeError, match="in use"):
            call()

    # on a platform named cpu the registry still falls through
    monkeypatch.setattr(fused.jax, "default_backend", lambda: "cpu")
    assert isinstance(reg.create_encoder(opts), NumpyRSEncoder)


