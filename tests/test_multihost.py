"""Multi-host mesh path: two OS processes × four virtual CPU devices
form ONE 8-device global mesh through `jax.distributed` (the comm-
backend bootstrap the reference does with Ratis/gRPC fan-out and HPC
stacks do with NCCL/MPI init), run the SAME sharded fused encoder the
single-host tests use, and prove a cross-process collective executes.

This is the proof that parallel/sharded.py is topology-agnostic: on a
real multi-host TPU slice only `multihost.initialize` changes.
"""

import os
import socket
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

_WORKER = r"""
import os, sys
port, pid = sys.argv[1], int(sys.argv[2])
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, sys.argv[3])
from ozone_tpu.parallel import multihost
multihost.initialize(f"127.0.0.1:{port}", 2, pid, local_device_count=4)
import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

assert len(jax.devices()) == 8, jax.devices()
assert jax.process_count() == 2
assert len(jax.local_devices()) == 4

from ozone_tpu.codec import create_encoder
from ozone_tpu.codec.api import CoderOptions
from ozone_tpu.codec.fused import FusedSpec
from ozone_tpu.parallel import sharded
from ozone_tpu.utils.checksum import ChecksumType

opts = CoderOptions(3, 2, "rs", cell_size=1024)
spec = FusedSpec(opts, ChecksumType.CRC32C, 1024)
mesh = multihost.global_codec_mesh()
fn = sharded.make_sharded_fused_encoder(spec, mesh)

rng = np.random.default_rng(0)  # same seed both processes: shared view
batch = rng.integers(0, 256, (8, 3, 1024), dtype=np.uint8)
sh = NamedSharding(mesh, P("dn"))
local = batch[pid * 4:(pid + 1) * 4]
garr = jax.make_array_from_process_local_data(
    sh, local, global_shape=batch.shape)
parity, crcs = fn(garr)

# every process checks ITS addressable output shards bit-exactly
# against the single-host numpy coder
ref = create_encoder(opts, "numpy").encode(batch)
checked = 0
for shard in parity.addressable_shards:
    i0 = shard.index[0].start or 0
    got = np.asarray(shard.data)
    assert np.array_equal(got, ref[i0:i0 + got.shape[0]]), \
        f"proc {pid}: parity shard at {i0} mismatches host coder"
    checked += got.shape[0]
assert checked == 4, checked

# a collective that MUST cross the process boundary: psum over the
# hybrid (dcn, dn) mesh's both axes
h = multihost.hybrid_codec_mesh()
assert h.devices.shape == (2, 4)
hs = NamedSharding(h, P(("dcn", "dn")))
ones = jax.make_array_from_process_local_data(
    hs, np.full(4, pid + 1, np.float32), global_shape=(8,))
summed = jax.shard_map(
    lambda x: jax.lax.psum(x, ("dcn", "dn")),
    mesh=h, in_specs=P(("dcn", "dn")), out_specs=P())(ones)
# proc0 contributes 4x1, proc1 4x2 -> 12; replicated everywhere
got = float(np.asarray(summed.addressable_shards[0].data).ravel()[0])
assert got == 12.0, got
print(f"WORKER_OK {pid}")
"""


#: the exact jaxlib error marking the known capability gap (the CPU
#: client rejects cross-process computations); anything else is a real
#: failure and must stay red
_CPU_MULTIPROC_UNSUPPORTED = (
    "Multiprocess computations aren't implemented on the CPU backend")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_global_mesh(tmp_path):
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER)
    port = _free_port()
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)  # the worker sets its own device count
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(port), str(i), str(REPO)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env, cwd=str(REPO),
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=180)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for i, (p, out) in enumerate(zip(procs, outs)):
        if p.returncode != 0 and _CPU_MULTIPROC_UNSUPPORTED in out:
            # known jaxlib capability gap, not a mesh-code regression:
            # this jaxlib's CPU client refuses cross-process XLA
            # computations outright (see KNOWN_ISSUES.md). The sharding
            # semantics stay covered by the single-process 8-device
            # suite; only the cross-process transport leg skips.
            pytest.skip(
                "jaxlib cannot run multiprocess computations on the "
                "CPU backend — cross-process leg requires a real "
                "accelerator runtime")
        assert p.returncode == 0, f"worker {i} failed:\n{out}"
        assert f"WORKER_OK {i}" in out, out
