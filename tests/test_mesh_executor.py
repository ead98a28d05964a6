"""Persistent mesh executor tests: program persistence, cross-operation
coalescing into full-width dispatches, depth-N in-flight buffering,
staging reuse, and the `pad_batch` / plan-cache-key edges the executor
leans on."""

import sys
import threading
import time

import jax
import numpy as np
import pytest

from ozone_tpu.codec import create_encoder
from ozone_tpu.codec import service as codec_service
from ozone_tpu.codec.api import CoderOptions
from ozone_tpu.codec.fused import FusedSpec, make_fused_encoder
from ozone_tpu.parallel import dispatch, mesh_executor, sharded
from ozone_tpu.parallel.mesh_executor import (
    MeshExecutor,
    _MeshProgram,
)
from ozone_tpu.parallel.sharded import (
    _sharded_fused_encoder_cached,
    make_mesh,
    pad_batch,
)
from ozone_tpu.utils.checksum import ChecksumType

OPTS = CoderOptions(6, 3, "rs", cell_size=1024)
SPEC = FusedSpec(OPTS, ChecksumType.CRC32C, bytes_per_checksum=256)


@pytest.fixture
def executor():
    assert jax.device_count() == 8, "conftest must provide 8 CPU devices"
    ex = MeshExecutor(depth=2)
    yield ex
    ex.close()


# ------------------------------------------------------- pad_batch edges
def test_pad_batch_zero_rows():
    batch = np.empty((0, 6, 1024), dtype=np.uint8)
    padded, orig = pad_batch(batch, 8)
    assert orig == 0
    assert padded.shape == (0, 6, 1024)


def test_pad_batch_already_aligned():
    batch = np.arange(8 * 6 * 4, dtype=np.uint8).reshape(8, 6, 4)
    padded, orig = pad_batch(batch, 8)
    assert orig == 8
    assert padded is batch  # aligned input must not be copied


def test_pad_batch_pads_with_zeros():
    batch = np.ones((5, 2, 4), dtype=np.uint8)
    padded, orig = pad_batch(batch, 4)
    assert orig == 5 and padded.shape[0] == 8
    assert np.array_equal(padded[:5], batch)
    assert not padded[5:].any()


# ------------------------------------------------- plan cache key edges
def test_sharded_encoder_cache_isolated_across_meshes():
    """The lru_cache key includes the MESH: two meshes of different
    sizes must never share a compiled encoder (a 4-wide program fed an
    8-wide shard layout would mis-shard silently)."""
    mesh8 = make_mesh(8)
    mesh4 = make_mesh(4)
    fn8 = _sharded_fused_encoder_cached(
        OPTS, SPEC.checksum, SPEC.bytes_per_checksum, mesh8, "dn")
    fn4 = _sharded_fused_encoder_cached(
        OPTS, SPEC.checksum, SPEC.bytes_per_checksum, mesh4, "dn")
    assert fn8 is not fn4
    # same mesh object -> cache hit, the SAME long-lived program
    again = _sharded_fused_encoder_cached(
        OPTS, SPEC.checksum, SPEC.bytes_per_checksum, mesh8, "dn")
    assert again is fn8


def test_decode_program_isolated_across_patterns(executor):
    """Two erasure patterns of the same spec get distinct programs
    (pattern is part of the semantic key) and both stay resolved."""
    k1 = codec_service.decode_key(SPEC, [0, 1, 2, 3, 4, 5], [6])
    k2 = codec_service.decode_key(SPEC, [1, 2, 3, 4, 5, 6], [0])
    assert executor.accepts(k1) and executor.accepts(k2)
    assert executor._programs[k1] is not executor._programs[k2]


# --------------------------------------------------------- correctness
def test_executor_encode_matches_reference(executor):
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, (16, 6, 1024), dtype=np.uint8)
    fut = executor.submit(codec_service.encode_key(SPEC), data, width=2)
    parity, crcs = fut.result(timeout=60)
    expect = create_encoder(OPTS, "numpy").encode(data)
    assert np.array_equal(np.asarray(parity), expect)
    assert crcs.shape[0] == 16


def test_executor_decode_matches_reference(executor):
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, (8, 6, 1024), dtype=np.uint8)
    enc = create_encoder(OPTS, "numpy")
    units = np.concatenate([data, enc.encode(data)], axis=1)
    erased = [1, 7]
    valid = [i for i in range(9) if i not in erased][:6]
    key = codec_service.decode_key(SPEC, valid, erased)
    fut = executor.submit(key, units[:, valid], width=2)
    rec, crcs = fut.result(timeout=60)
    assert np.array_equal(np.asarray(rec), units[:, erased])


def test_executor_unknown_key_raises(executor):
    with pytest.raises(KeyError):
        executor.submit(codec_service.reencode_key(SPEC, 2),
                        np.zeros((1, 6, 1024), dtype=np.uint8), width=1)
    with pytest.raises(KeyError):
        executor.pipeline(codec_service.reencode_key(SPEC, 2), width=1)


def test_warm_programs_no_new_compiles(executor, monkeypatch):
    """The zero-new-compile proof on the jitted SPMD path: steady-state
    rounds after the first dispatch must not grow the compiled-
    executable census (erasure-pattern churn included — each pattern
    compiles once, then stays warm)."""
    monkeypatch.setenv("OZONE_TPU_FUSED_BACKEND", "jax")
    rng = np.random.default_rng(2)
    enc_key = codec_service.encode_key(SPEC)
    data = rng.integers(0, 256, (8, 6, 1024), dtype=np.uint8)
    executor.submit(enc_key, data, width=1).result(timeout=120)
    assert not executor._programs[enc_key].host_twin
    warm = executor.compile_counts()
    assert warm >= 1
    for _ in range(3):
        executor.submit(enc_key, data, width=1).result(timeout=120)
    assert executor.compile_counts() == warm, \
        "steady-state dispatches recompiled the mesh program"


def test_host_twin_on_cpu(executor):
    """On CPU backends the lane resolves to the native host twin (no
    XLA program at all): same contract, zero compiles."""
    key = codec_service.encode_key(SPEC)
    assert executor.accepts(key)
    prog = executor._programs[key]
    assert prog.host_twin and prog.compile_count() == 0


# ------------------------------------------------ coalescing + depth-N
def _identity_program(delay_s: float = 0.0):
    """A synthetic lane program: returns its batch, optionally slowly —
    deterministic dispatcher-backpressure for the scheduling tests."""
    def fn(batch):
        if delay_s:
            time.sleep(delay_s)
        return (batch.copy(),)
    return _MeshProgram(fn, (), True)


def test_cross_operation_coalescing_single_dispatch(executor):
    """Submissions from many concurrent operations sharing one lane
    coalesce into ONE multi-op dispatch while the dispatcher is busy —
    the storm-shaped win over per-operation dribbles."""
    key = ("encode", "synthetic-coalesce")
    executor._programs[key] = _identity_program(delay_s=0.1)
    snap0 = mesh_executor.METRICS.snapshot()
    # occupy the dispatcher: one full-width submission dispatches
    # immediately and sleeps inside the program fn
    plug = executor.submit(key, np.zeros((8, 4), dtype=np.uint8), width=1)
    time.sleep(0.02)  # let the dispatcher pick it up
    subs = [
        executor.submit(
            key, np.full((2, 4), i, dtype=np.uint8), width=1)
        for i in range(4)
    ]
    outs = [f.result(timeout=30) for f in subs]
    plug.result(timeout=30)
    executor.quiesce()
    for i, out in enumerate(outs):
        assert np.array_equal(out[0], np.full((2, 4), i, dtype=np.uint8))
    snap1 = mesh_executor.METRICS.snapshot()
    dispatches = snap1["dispatches"] - snap0.get("dispatches", 0)
    multi = (snap1.get("multi_op_dispatches", 0)
             - snap0.get("multi_op_dispatches", 0))
    # 5 operations, 2 dispatches: the plug, then all 4 queued ops in one
    assert dispatches == 2, f"expected 2 dispatches, saw {dispatches}"
    assert multi == 1


def test_inflight_depth_reaches_window(executor):
    """Depth-N buffering: with a backlog of full batches the dispatcher
    keeps `depth` dispatches launched behind the one the completer is
    pulling, and never more."""
    key = ("encode", "synthetic-depth")
    executor._programs[key] = _identity_program(delay_s=0.005)
    base = executor._max_inflight
    futs = [
        executor.submit(key, np.zeros((8, 4), dtype=np.uint8), width=1)
        for _ in range(8)
    ]
    for f in futs:
        f.result(timeout=30)
    executor.quiesce()
    assert executor._max_inflight >= executor.depth, \
        f"in-flight window never filled: {executor._max_inflight}"
    assert executor._max_inflight <= executor.depth + 1
    assert executor._max_inflight >= base


def test_staging_buffers_reused(executor):
    """Partial-batch dispatches pack into pooled staging buffers; the
    steady state recycles instead of allocating."""
    key = ("encode", "synthetic-staging")
    executor._programs[key] = _identity_program()
    snap0 = mesh_executor.METRICS.snapshot()
    for i in range(6):
        out = executor.submit(
            key, np.full((3, 4), i, dtype=np.uint8), width=1
        ).result(timeout=30)
        assert np.array_equal(out[0], np.full((3, 4), i, dtype=np.uint8))
    snap1 = mesh_executor.METRICS.snapshot()
    reuses = (snap1.get("staging_reuses", 0)
              - snap0.get("staging_reuses", 0))
    assert reuses >= 4, f"staging pool not recycling: {reuses} reuses"


def test_multi_dispatch_submission_reassembles(executor):
    """A submission wider than the lane splits across dispatches and
    reassembles in offset order."""
    key = ("encode", "synthetic-wide")
    executor._programs[key] = _identity_program()
    big = np.arange(20 * 4, dtype=np.uint8).reshape(20, 4)
    out = executor.submit(key, big, width=1).result(timeout=30)
    assert np.array_equal(out[0], big)


def test_split_submission_resolves_once_under_a_racing_completer(
        executor, monkeypatch):
    """A submission split over two dispatches: the completer resolves
    part one while the dispatcher packs part two. 200 rounds, two
    submitters: each future resolves exactly once, only when all its
    parts are host arrays, byte-exact and in offset order."""
    monkeypatch.setattr(executor, "linger_s", 0.0)
    key = ("encode", "synthetic-split-race")
    executor._programs[key] = _identity_program()
    joined: list = []  # the futures themselves: an id can come again
    resolve = mesh_executor._resolve_sub

    def counting(sub):
        assert sub.taken == sub.n and sub.pending_parts == 0
        assert len(sub.parts) == 2  # 12 rows over a lane of 8
        joined.append(sub.future)
        resolve(sub)

    monkeypatch.setattr(mesh_executor, "_resolve_sub", counting)
    wrong: list[int] = []

    def submitter(n: int):
        rng = np.random.default_rng(5 + n)
        for r in range(100):
            rows = rng.integers(0, 256, (12, 16), dtype=np.uint8)
            fut = executor.submit(key, rows.copy(), width=1)
            if not np.array_equal(fut.result(timeout=30)[0], rows):
                wrong.append(100 * n + r)

    threads = [threading.Thread(target=submitter, args=(n,))
               for n in range(2)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # hand the GIL over mid-bookkeeping
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    executor.quiesce()
    assert not wrong
    assert len(joined) == len({id(f) for f in joined}) == 200
    assert executor.stats()["inflight"] == 0


def test_program_error_fails_future(executor):
    key = ("encode", "synthetic-broken")

    def boom(batch):
        raise RuntimeError("kaboom")

    executor._programs[key] = _MeshProgram(boom, (), True)
    fut = executor.submit(key, np.zeros((2, 4), dtype=np.uint8), width=1)
    with pytest.raises(RuntimeError, match="kaboom"):
        fut.result(timeout=30)


@pytest.mark.parametrize("qos,registry", [
    ("bulk", mesh_executor.METRICS),
    ("interactive", codec_service.METRICS),
], ids=["mesh", "service"])
def test_pipeline_contract(executor, qos, registry):
    """The one depth-1 adaptor over either scheduler's futures: submit
    returns the PREVIOUS submission's (ctx, outs); drain flushes the
    last."""
    before = registry.counter("submissions").value
    pipe = dispatch.pipeline(
        codec_service.encode_key(SPEC), make_fused_encoder(SPEC),
        width=2, qos=qos, executor=executor)
    rng = np.random.default_rng(3)
    batches = [rng.integers(0, 256, (4, 6, 1024), dtype=np.uint8)
               for _ in range(3)]
    enc = create_encoder(OPTS, "numpy")
    got = []
    for i, b in enumerate(batches):
        out = pipe.submit(b, ctx=i)
        if out is not None:
            got.append(out)
    out = pipe.drain()
    if out is not None:
        got.append(out)
    assert [ctx for ctx, _ in got] == [0, 1, 2]
    for ctx, (parity, _crcs) in got:
        assert np.array_equal(np.asarray(parity),
                              enc.encode(batches[ctx]))
    assert pipe.drain() is None
    assert registry.counter("submissions").value == before + 3


def test_close_fails_pending_and_rejects_submits():
    ex = MeshExecutor(depth=1)
    key = ("encode", "synthetic-close")
    ex._programs[key] = _identity_program()
    ex.close()
    with pytest.raises(RuntimeError):
        ex.submit(key, np.zeros((1, 4), dtype=np.uint8), width=1)
