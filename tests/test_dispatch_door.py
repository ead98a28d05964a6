"""The one door to the device (`parallel/dispatch.py`): its route table,
and that no other module of the package chooses between executors."""

import ast
from pathlib import Path

import jax
import numpy as np
import pytest

import ozone_tpu
from ozone_tpu.codec import service as codec_service
from ozone_tpu.codec.api import CoderOptions
from ozone_tpu.codec.fused import (
    FusedSpec,
    make_fused_decoder,
    make_fused_encoder,
    make_fused_reencoder,
)
from ozone_tpu.parallel import dispatch, mesh_executor
from ozone_tpu.parallel.sharded import make_mesh
from ozone_tpu.utils.checksum import ChecksumType

OPTS = CoderOptions(6, 3, "rs", cell_size=1024)
SPEC = FusedSpec(OPTS, ChecksumType.CRC32C, bytes_per_checksum=256)
VALID, ERASED = [0, 1, 2, 3, 4, 5], [6]


@pytest.fixture
def injected():
    ex = mesh_executor.MeshExecutor(mesh=make_mesh(4), depth=2)
    yield ex
    ex.close()


@pytest.fixture
def routes(monkeypatch):
    """Every submission either scheduler takes, as (scheduler, object)."""
    codec_service.reset_for_tests()
    mesh_executor.reset_for_tests()
    taken: list[tuple] = []
    for name, cls in (("mesh", mesh_executor.MeshExecutor),
                      ("service", codec_service.CodecService)):
        real = cls.submit

        def spy(self, *a, _name=name, _real=real, **kw):
            taken.append((_name, self))
            return _real(self, *a, **kw)

        monkeypatch.setattr(cls, "submit", spy)
    yield taken
    codec_service.reset_for_tests()
    mesh_executor.reset_for_tests()


def _work(kind: str):
    if kind == "decode":
        return (codec_service.decode_key(SPEC, VALID, ERASED),
                make_fused_decoder(SPEC, VALID, ERASED))
    if kind == "encode":
        return codec_service.encode_key(SPEC), make_fused_encoder(SPEC)
    return (codec_service.reencode_key(SPEC, 2),
            make_fused_reencoder(SPEC, lost=2))


@pytest.mark.parametrize("kind,qos,hand_in,one_device,want", [
    ("decode", "bulk", True, False, "injected"),
    ("encode", "bulk", False, False, "process_wide"),
    ("reencode", "bulk", True, False, "service"),
    ("decode", "interactive", True, False, "service"),
    ("encode", "bulk", False, True, "service"),
    ("encode", "bulk", True, False, "injected"),
    ("decode", "bulk", False, False, "service"),
    ("reencode", "bulk", False, False, "service"),
], ids=["bulk_decode_with_an_executor", "bulk_encode_process_wide",
        "bulk_reencode_has_no_mesh_program", "interactive_stays_on_one_chip",
        "bulk_on_a_one_device_host", "the_injected_executor_wins",
        "bulk_decode_handed_no_executor", "bulk_reencode_handed_no_executor"])
def test_route_table(routes, injected, monkeypatch, kind, qos, hand_in,
                     one_device, want):
    """A bulk stream joins the mesh executor its caller was handed, or,
    an encode sweep, the process-wide one, where that has a program for
    the key; every other stream and every single batch joins the codec
    service. Byte-exact with the single-chip callable either way."""
    assert jax.device_count() == 8, "conftest must provide 8 CPU devices"
    if one_device:
        monkeypatch.setattr(jax, "device_count", lambda *a, **kw: 1)
    if want == "injected":
        # a process-wide executor exists too, and is not asked
        assert mesh_executor.maybe_executor() is not injected
    key, fn = _work(kind)
    rng = np.random.default_rng(7)
    stripes = rng.integers(0, 256, (4, 6, 1024), dtype=np.uint8)
    expect = fn(stripes)

    pipe = dispatch.pipeline(key, fn, width=2, qos=qos,
                             executor=injected if hand_in else None)
    assert pipe.submit(stripes, ctx="a") is None
    ctx, piped = pipe.drain()
    got = codec_service.wait_result(dispatch.submit(
        key, fn, stripes, width=2, qos=qos))

    assert ctx == "a" and len(routes) == 2
    (name, scheduler), single = routes
    if want == "service":
        assert name == "service"
    elif want == "injected":
        assert scheduler is injected
    else:
        assert name == "mesh" and scheduler is not injected
        assert scheduler is mesh_executor.maybe_executor()
    # one batch (a PUT's flush, a hedge decode) never leaves the chip
    assert single[0] == "service"
    for outs in (got, piped):
        assert len(outs) == len(expect)
        for a, b in zip(outs, expect):
            assert np.array_equal(np.asarray(a), np.asarray(b))


def test_a_key_without_a_mesh_program_is_logged_once(
        routes, injected, caplog):
    """The executor's KeyError is answered by the service, and said:
    a missing program and a fault while building one look the same
    from outside, and both put bulk work on one chip."""
    key, fn = _work("reencode")
    dispatch._no_mesh_program.discard(key)
    with caplog.at_level("WARNING", logger=dispatch.__name__):
        for _ in range(2):
            dispatch.pipeline(key, fn, width=2, qos="bulk",
                              executor=injected)
    said = [r for r in caplog.records if "no mesh program" in r.message]
    assert len(said) == 1 and "reencode" in said[0].getMessage()
    assert not routes  # a pipeline that was handed no batch queues none


# Who may name a scheduler's entry points, and why. Everyone else makes
# one call to the door and knows neither scheduler.
CHOOSERS = {"get_service", "get_executor", "maybe_executor",
            "DeviceBatchPipeline"}
ALLOWED = {
    "parallel/dispatch.py": CHOOSERS,       # the door
    "codec/service.py": CHOOSERS,           # a scheduler
    "parallel/mesh_executor.py": CHOOSERS,  # a scheduler
    # defines the raw `mesh=` route's wrapper
    "codec/pipeline.py": {"DeviceBatchPipeline"},
    # the raw `mesh=` route of the datanode daemons (ROADMAP D2b)
    "client/ec_reader.py": {"DeviceBatchPipeline"},
    # the storm REPORTS on the mesh executor (quiesce, `mesh_*` deltas)
    # and benchmarks/generators/repair_storm.py reads `storm.executor`;
    # it hands the executor to the door and chooses nothing
    "client/reconstruction.py": {"maybe_executor"},
    # the repair drill hands the host's executor to its coordinator, as
    # benchmarks/generators/repair_drill.py does; it chooses nothing
    "tools/freon.py": {"maybe_executor"},
}
ALLOWED_DIRS = ("testing/", "recon/")  # stats and fixtures


def test_no_module_but_the_door_chooses_between_executors():
    root = Path(ozone_tpu.__file__).parent
    found = []
    for path in sorted(root.rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        if rel.startswith(ALLOWED_DIRS):
            continue
        for node in ast.walk(ast.parse(path.read_text(), str(path))):
            name = (node.id if isinstance(node, ast.Name)
                    else node.attr if isinstance(node, ast.Attribute)
                    else node.name.rsplit(".", 1)[-1]
                    if isinstance(node, ast.alias) else None)
            if name in CHOOSERS and name not in ALLOWED.get(rel, ()):
                found.append(f"{rel}:{node.lineno} names {name}")
    assert not found, "\n".join(found)
    # and the arrows point one way: the lower scheduler knows no higher
    service = ast.parse((root / "codec/service.py").read_text())
    for node in ast.walk(service):
        if isinstance(node, ast.ImportFrom):
            assert "ozone_tpu.parallel" not in (node.module or "")
            assert not any(a.name == "parallel" for a in node.names
                           if node.module == "ozone_tpu")
        elif isinstance(node, ast.Import):
            assert not any("ozone_tpu.parallel" in a.name
                           for a in node.names)
