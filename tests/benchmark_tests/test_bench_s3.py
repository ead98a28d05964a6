"""The cell `s3-mixed.rs-6-3`: MinIO warp's `mixed` benchmark through the
S3 gateway (deployment `s3g-rs-6-3-1024k`, traffic `s3-mixed`). Its
entries in the manifest; the plain bucket model and the reference for
partial stripes, each on its own and against the gateway on the
in-process mini-cluster; the stage groups of its two roots; the padded
roofline on planted numbers; and CPU passes of the whole cell, clean
through `run.py --rehearse` and with each control through `measure()`."""

import argparse
import copy
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import bench_minicluster as bm
import test_bench_spans as spans_tests
from benchmarks.harness import manifest as mf
from benchmarks.harness import partial_stripe, reference, s3_clients
from benchmarks.harness import spans, storecheck, warm
from benchmarks.harness.bucket_model import NOT_FOUND, BucketModel
from benchmarks.harness.record import Run

CELL = "s3-mixed.rs-6-3"
CONFIG = "s3g-rs-6-3-1024k"
MANIFEST = mf.load()
K, P, CELL_BYTES = 6, 3, 4096
TINY_SCHEME = {"k": K, "p": P, "cell": CELL_BYTES, "bpc": CELL_BYTES}
EC = "rs-6-3-4096"
#: object sizes the tests write, each ending in a partial stripe
SIZES = {"4_of_6_cells": K * CELL_BYTES + 4 * CELL_BYTES,
         "a_short_cell": K * CELL_BYTES + 2 * CELL_BYTES + 1000}
#: the stage groups: {metric: (root, layer, moves)}. The gateway's layer
#: is `s3 gateway`: `test_bench_room.py` holds that the benchmark names no
#: layer `gateway` (the name its grown copy shows to be new)
GROUPS = {
    "s3_get_gateway_ms": ("s3:get", "s3 gateway", "get_mib_s"),
    "s3_get_client_ms": ("s3:get", "client", "get_mib_s"),
    "s3_get_dn_read_ms": ("s3:get", "datanode wire + disk", "get_mib_s"),
    "s3_get_om_ms": ("s3:get", "metadata", "get_mib_s"),
    "s3_put_gateway_ms": ("s3:put", "s3 gateway", "put_mib_s"),
    "s3_put_client_ms": ("s3:put", "client", "put_mib_s"),
    "s3_put_codec_ms": ("s3:put", "codec queue", "put_mib_s"),
    "s3_put_dn_write_ms": ("s3:put", "datanode wire + disk", "put_mib_s"),
    "s3_put_om_ms": ("s3:put", "metadata", "put_mib_s"),
}
#: the metrics other cells report that the cell is appended to
SHARED = ("codec_fill_pct.put", "codec_queue_wait_ms.put",
          "codec_submit_pack_ms.put", "codec_dispatch_ms.put",
          "codec_idle_pct.put", "device_idle_pct.put",
          "device_idle_unfed_pct.put", "client_cpu_cores.put",
          "interp_wait_ms.put", "host_busy_pct.put", "put_p95_ms",
          "codec_pack_ms.put", "codec_launch_ms.put", "codec_d2h_ms.put",
          "codec_complete_ms.put", "codec_submit_packed_pct.put",
          "codec_staging_reuse_pct.put")
#: the traffic at a size a test can hold (4 KiB cells)
TINY = {"clients": 4, "clients_per_process": 2,
        "object_bytes": SIZES["a_short_cell"], "preload_per_client": 3,
        "verify_puts": 3}


# ------------------------------------------------------- the manifest
def test_the_cell_and_its_deployment_are_appended_entries():
    assert mf.problems(MANIFEST) == []
    assert MANIFEST["workloads"][-1]["name"] == CELL
    assert MANIFEST["configs"][-1]["name"] == CONFIG
    w = mf.cell(MANIFEST, CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, "s3-mixed", 1)
    assert len(w["why"]) <= 200
    entry = MANIFEST["configs"][-1]
    cfg = mf.config_of(MANIFEST, w)
    base = json.loads((mf.BENCH_DIR / "configs" / "rs-6-3-1024k.json")
                      .read_text())
    assert cfg["source"] == entry["source"]
    assert "github.com/minio/warp" in cfg["source"]
    assert cfg["scheme"] == base["scheme"]
    assert cfg["cluster"] == base["cluster"]
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {
        "metadata_replicas", "hosts", "data_scale", "gateways"}
    assert len(cfg["guarantees"]) == 5
    traffic = mf.traffic_of(w)
    assert traffic["mix"] == {"get": 9, "head": 6, "put": 3, "delete": 2}
    assert (traffic["clients"], traffic["object_bytes"],
            traffic["clients"] * traffic["preload_per_client"]) \
        == (20, 10 * 2 ** 20, 200)
    # every object ends in a partial stripe: one whole, 4 of 6 cells
    stripe = cfg["scheme"]["k"] * cfg["scheme"]["cell"]
    assert traffic["object_bytes"] % stripe == 4 * cfg["scheme"]["cell"]


def test_the_cell_reports_both_rates_its_groups_and_the_shared_metrics():
    by_name = {m["name"]: m for m in MANIFEST["end_to_end"]
               + MANIFEST["per_layer"]}
    assert {m["name"] for m in mf.metrics_for(MANIFEST, "end_to_end", CELL)} \
        == {"get_mib_s", "put_mib_s", "setup_s"}
    for name in ("get_mib_s", "put_mib_s", *SHARED):
        assert by_name[name]["workloads"][-1] == CELL, name
    for name, (root, layer, moves) in GROUPS.items():
        assert by_name[name] == {
            "name": name, "unit": "ms", "better": "lower",
            "source": "program_span", "layer": layer, "moves": moves,
            "workloads": [CELL]}
        params = mf.metric_params(name)
        assert (params["reader"], params["root"]) == ("op_stage_ms", root)
    roof = by_name["fused_encode_roofline.s3"]
    assert (roof["unit"], roof["source"], roof["layer"], roof["moves"],
            roof["workloads"]) == ("%", "device_trace", "kernels",
                                   "put_mib_s", [CELL])
    assert mf.metric_params(roof["name"])["reader"] == "padded_kernel_roofline"
    # the whole stripe's roofline and the client-rooted groups are not
    # the cell's: a padded stripe is no whole work, its roots are s3:*
    assert CELL not in by_name["fused_encode_roofline.put"]["workloads"]
    for m in MANIFEST["per_layer"]:
        if CELL in m["workloads"]:
            params = mf.metric_params(m["name"])
            assert params.get("root", "s3:") .startswith("s3:"), m["name"]
    assert len(MANIFEST["per_layer"]) <= 123


# ------------------------------------------------------ the references
def test_the_bucket_model_answers_as_s3_does():
    m = BucketModel()
    a, b = np.arange(10, dtype=np.uint8), np.arange(7, dtype=np.uint8)
    assert m.get("x").status == m.head("x").status == NOT_FOUND
    m.put("x", a)
    assert m.get("x").body is a and m.head("x").size == 10
    m.put("x", b)  # the last acknowledged PUT wins
    assert m.get("x").body is b and m.head("x").size == 7
    m.put("y", a)
    m.delete("x")
    m.delete("never")  # S3 answers 204; nothing changes
    assert m.get("x").status == m.head("x").status == NOT_FOUND
    assert m.live() == ["y"] and m.deleted() == ["x"]
    m.put("x", a)
    assert m.deleted() == [] and m.live() == ["y", "x"]


@pytest.mark.parametrize("size", [
    K * CELL_BYTES + 4 * CELL_BYTES, K * CELL_BYTES + 2 * CELL_BYTES + 1000,
    2 * K * CELL_BYTES, CELL_BYTES + 7, 100, 3 * CELL_BYTES])
def test_the_references_partial_stripe_is_encode_of_the_zero_padded_stripe(
        size):
    payload = np.random.default_rng(size).integers(0, 256, size,
                                                    dtype=np.uint8)
    cells = partial_stripe.expected_cells(TINY_SCHEME, payload)
    stripe = K * CELL_BYTES
    assert len(cells) == -(-size // stripe)
    for s, units in enumerate(cells):
        chunk = payload[s * stripe:(s + 1) * stripe]
        padded = np.zeros(stripe, dtype=np.uint8)
        padded[:chunk.size] = chunk
        padded = padded.reshape(K, CELL_BYTES)
        lengths = [u.size for u in units[:K]]
        assert sum(lengths) == chunk.size
        assert np.array_equal(np.concatenate(units[:K]), chunk)
        parity = reference.encode(K, P, padded)
        for j in range(P):
            # as long as the stripe's first cell, the longest
            assert np.array_equal(units[K + j], parity[j, :lengths[0]])
            assert not parity[j, lengths[0]:].any()
    if size % stripe == 0:  # whole stripes: what storecheck says
        whole = storecheck.expected_units(TINY_SCHEME, payload)
        for s, units in enumerate(cells):
            for u in range(K + P):
                assert np.array_equal(units[u], whole[s, u])


# ------------------------------------- against the gateway, in-process
@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """(connection factory, client): a 9-datanode mini-cluster, an S3
    gateway requiring SigV4 in front of it, bucket `warp`."""
    from ozone_tpu.gateway.s3 import S3_VOLUME, S3Gateway
    from ozone_tpu.testing.minicluster import MiniOzoneCluster

    mini = MiniOzoneCluster(tmp_path_factory.mktemp("s3cell"),
                            num_datanodes=9, stale_after_s=1000.0,
                            dead_after_s=2000.0)
    client = mini.client()
    gw = S3Gateway(client, replication=EC, require_auth=True)
    client.om.create_bucket(S3_VOLUME, "warp", EC)
    secret = client.om.get_s3_secret("warp-test", create=True)
    gw.start()

    def connect():
        return s3_clients.Connection(gw.address, "warp", s3_clients.Signer(
            "warp-test", secret, gw.address))

    yield connect, client
    gw.stop()
    mini.close()


@pytest.mark.parametrize("size_name", sorted(SIZES))
def test_a_seeded_sequence_through_the_gateway_equals_the_model(
        served, size_name):
    connect, client = served
    size = SIZES[size_name]
    rng = np.random.default_rng(41)
    pool = s3_clients.payload_pool(41, 0, size)
    model, conn = BucketModel(), connect()
    names = [f"{size_name}-{i}" for i in range(6)]
    compared = {"GET": 0, "HEAD": 0, "PUT": 0, "DELETE": 0}
    for step in range(80):
        method = ("PUT", "GET", "HEAD", "DELETE")[int(rng.integers(4))]
        name = names[int(rng.integers(len(names)))]
        if method == "PUT":
            data = pool.payload(step)
            assert conn.request("PUT", name, data)[0] == 200
            model.put(name, data)
        elif method == "DELETE":
            assert conn.request("DELETE", name)[0] == 204
            model.delete(name)
        else:
            want = model.get(name) if method == "GET" else model.head(name)
            status, headers, body = conn.request(method, name)
            assert status == want.status, (step, method, name)
            if status == 200 and method == "GET":
                assert body == want.body.tobytes(), (step, name)
            elif status == 200:
                assert int(headers["content-length"]) == want.size
        compared[method] += 1
    conn.close()
    assert min(compared.values()) >= 10
    # the names the model holds are the OM's, at their sizes
    from ozone_tpu.gateway.s3 import S3_VOLUME

    held = {k["name"]: int(k["size"]) for k in client.om.list_keys(
        S3_VOLUME, "warp", size_name)}
    assert held == {n: size for n in model.live()}


@pytest.mark.parametrize("size_name", sorted(SIZES) + ["a_short_stripe"])
def test_every_stored_unit_equals_the_partial_stripe_reference(
        served, size_name):
    from ozone_tpu.gateway.s3 import S3_VOLUME

    connect, client = served
    # a short stripe: its first cell whole, its second 7 bytes, and four
    # data units that the key never reaches and that hold no block
    size = SIZES.get(size_name, CELL_BYTES + 7)
    stripes = -(-size // (K * CELL_BYTES))
    pool = s3_clients.payload_pool(7, 3, size)
    conn = connect()
    names = [f"stored-{size_name}-{j}" for j in range(3)]
    for j, name in enumerate(names):
        assert conn.request("PUT", name, pool.payload(j))[0] == 200
    conn.close()
    tally = storecheck.Tally()
    for j, name in enumerate(names):
        info = client.om.lookup_key(S3_VOLUME, "warp", name)
        (group,) = client.om.key_block_groups(info)
        partial_stripe.check_group(client.clients, group, pool.payload(j),
                                   TINY_SCHEME, tally, name)
    storecheck.finish(tally, TINY_SCHEME)
    assert tally.first_error == ""
    assert (tally.records_wrong, tally.stored_bytes_differ,
            tally.stored_crcs_differ) == (0, 0, 0)
    assert tally.units_compared == len(names) * (K + P) * stripes
    assert tally.bytes_compared == len(names) * (
        size + P * stripes * CELL_BYTES)
    # and a byte flipped in a stored unit is found
    bad = storecheck.Tally()
    payload = pool.payload(0).copy()
    payload[size - 1] ^= 1  # the partial stripe's last cell
    info = client.om.lookup_key(S3_VOLUME, "warp", names[0])
    (group,) = client.om.key_block_groups(info)
    partial_stripe.check_group(client.clients, group, payload, TINY_SCHEME,
                               bad, "flipped")
    storecheck.finish(bad, TINY_SCHEME)
    # the data cell, each parity cell, and the data cell's CRC
    assert bad.stored_bytes_differ == 1 + P and bad.stored_crcs_differ >= 1


def test_a_get_and_a_put_leave_roots_whose_groups_partition_and_sum(
        served):
    from ozone_tpu.utils.tracing import Tracer

    connect, _client = served
    Tracer._instance = None
    try:
        conn = connect()
        pool = s3_clients.payload_pool(5, 1, SIZES["a_short_cell"])
        t0 = time.monotonic()
        for j in range(3):
            assert conn.request("PUT", f"groups-{j}", pool.payload(j))[0] \
                == 200
            assert conn.request("GET", f"groups-{j}")[0] == 200
        conn.close()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not all(
                len(spans.operations(r, t0, float("inf"))) == 3
                for r in ("s3:put", "s3:get")):
            time.sleep(0.01)
        run = Run(cell={}, config={}, traffic={}, setup_s=1.0, ops=[],
                  t0=t0, t1=time.monotonic() + 1, counters0={},
                  counters1={})
        groups = spans_tests._groups(CELL)
        assert set(groups) == {"s3:get", "s3:put"}
        for root, metrics in groups.items():
            ops = spans.operations(root, run.t0, run.t1)
            assert len(ops) == 3, root
            for stage in {s for o in ops for s in o["stages"]}:
                owners = [m for m, pats in metrics.items()
                          if any(p.match(stage) for p in pats)]
                assert len(owners) == 1, (root, stage, owners)
            got = {}
            for name in metrics:
                params = mf.metric_params(name)
                got[name] = mf.reader_of(params)(params, run)
            mean_ms = sum(o["durationUs"] for o in ops) / len(ops) / 1e3
            assert sum(v or 0.0 for v in got.values()) \
                == pytest.approx(mean_ms, rel=0.01)
            assert got[f"s3_{root[3:]}_gateway_ms"] > 0
            assert got[f"s3_{root[3:]}_client_ms"] > 0
    finally:
        Tracer._instance = None


# --------------------------------------------------- the set-up's warm
def test_set_up_loads_every_decode_shape_a_straggler_can_ask_for(
        monkeypatch):
    """The hedge's one cell at width 1, and a replan around 1 to p
    stragglers at the reader's decode width: after the warm, another
    erasure pattern of each compiles nothing (a program is per shape)."""
    from ozone_tpu.codec import fused
    from ozone_tpu.codec.api import CoderOptions
    from ozone_tpu.codec.pipeline import decode_batch_size
    from ozone_tpu.utils.checksum import ChecksumType
    from ozone_tpu.utils.compile_cache import compile_counts, count_compiles

    monkeypatch.setenv("OZONE_TPU_FUSED_BACKEND", "jax")
    count_compiles()
    _manifest, _cell, config, _traffic = _tiny_cell()
    scheme = config["scheme"]
    warm.decoders(scheme, warm.reader_decode_shapes(scheme))
    spec = fused.FusedSpec(CoderOptions(K, P, "rs", cell_size=CELL_BYTES),
                           ChecksumType.CRC32C, CELL_BYTES)
    before = compile_counts()["compiles"]
    asked = [(1, [0, 2, 3, 4, 5, 6], [1])] + [
        (decode_batch_size(), [u for u in range(K + P) if u not in lost][:K],
         lost) for lost in ([3], [2, 5], [0, 1, 4])]
    for width, valid, erased in asked:
        out = fused.make_fused_decoder(spec, valid, erased)(
            np.zeros((width, K, CELL_BYTES), dtype=np.uint8))
        np.asarray(out[0])
    assert compile_counts()["compiles"] == before


# ------------------------------------------------- the padded roofline
@pytest.mark.parametrize("pad,expect_share", [(0, 1.0), (24, 0.5)])
def test_pad_cells_are_waste_in_the_padded_roofline(
        monkeypatch, pad, expect_share):
    """48 stripes dispatched in 6 dispatches, 24 pad cells: 4 whole
    stripes' worth of zeros, so 44 of 48 stripes are work. At 0 pad the
    share is kernel_roofline's to the last digit."""
    from benchmarks.harness import trace as tr

    monkeypatch.setattr(tr, "program_seconds", lambda trace, program:
                        (0.012, 6))
    peaks = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}
    counters0 = {"codec.service/stripes_dispatched": 0.0,
                 "codec.service/dispatches": 0.0,
                 "client.ops/pad_cells": 100.0}
    counters1 = {"codec.service/stripes_dispatched": 48.0,
                 "codec.service/dispatches": 6.0,
                 "client.ops/pad_cells": 100.0 + pad}
    config = mf.config_of(MANIFEST, mf.cell(MANIFEST, CELL))
    run = Run(cell={}, config=config, traffic={}, setup_s=1.0, ops=[],
              t0=0.0, t1=10.0, counters0={}, counters1={}, peaks=peaks,
              trace={}, slice_counters0=counters0,
              slice_counters1=counters1)
    params = mf.metric_params("fused_encode_roofline.s3")
    got = mf.reader_of(params)(params, run)
    whole = mf.metric_params("fused_encode_roofline.put")
    base = mf.reader_of(whole)(whole, run)
    useful = 48 - pad / 6
    assert got == pytest.approx(base * useful / 48)
    if not pad:
        assert got == base
    # a program that counts no padding (the parent) reads as nothing
    for c in (counters0, counters1):
        del c["client.ops/pad_cells"]
    assert mf.reader_of(params)(params, run) is None


# -------------------------------------------------- CPU passes of the cell
def _tiny_cell():
    manifest = mf.load()
    cell = mf.cell(manifest, CELL)
    config = copy.deepcopy(mf.config_of(manifest, cell))
    s = config["scheme"]
    s["cell"], s["bpc"] = CELL_BYTES, CELL_BYTES
    config["replication"] = EC
    return manifest, cell, config, {**mf.traffic_of(cell), **TINY}


@pytest.mark.parametrize("control", ["byte_flip", "undelete"])
def test_each_control_ends_the_cell_incorrect(tmp_path, control):
    import benchmarks.run as bench_run

    manifest, cell, config, traffic = _tiny_cell()
    cluster = bm.MiniCluster(tmp_path, config["cluster"]["datanodes"])
    args = argparse.Namespace(workload=CELL, seed=2147483999, seconds=1.5,
                              trace=0, rehearse=True, control=control,
                              dump_trace="")
    try:
        out = json.loads(json.dumps(bench_run.measure(
            args, manifest, cluster, cell, config, traffic)))
    finally:
        cluster.close()
    assert out["correct"] is False and out["control"] == control
    wrong = {k for k, c in out["compared"].items()
             if c["value"] > c["limit"] and k != "units_compared"}
    assert wrong == ({"stored_bytes_differ"} if control == "byte_flip"
                     else {"deleted_keys_present"})
    assert out["compared"]["units_compared"]["value"] \
        >= out["compared"]["units_compared"]["limit"]


def test_a_rehearsal_through_run_py_ends_correct(tmp_path):
    """The real launcher, nine datanode processes and the clients'
    worker processes; objects of one stripe and 2.5 of its 1 MiB cells."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "2147485041", "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=mf.ROOT, env=env, capture_output=True, text=True, timeout=400)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["rehearsal"] is True, out
    assert out["failed"] == 0
    kinds = out["notes"]["ops_in_window"]
    assert min(kinds.values()) >= 1, kinds
    got = out["metrics"]
    # the span and counter metrics read on the CPU; no device metric
    assert set(GROUPS) <= set(got)
    assert {name for name in SHARED
            if not name.startswith("device_")} <= set(got)
    assert not any(k.startswith("device_") or k.endswith("roofline.s3")
                   for k in got)
    assert not bm.processes_mentioning(str(tmp_path))


def test_an_operation_ends_when_its_reply_is_read_not_after_its_check():
    """The worker's own check of a GET's bytes against its model is the
    load generator's work: the op log's end is when the reply was read."""

    class Stub:
        replied_at = 0.0

        def request(self, method, name, body=None):
            time.sleep(0.01)
            self.replied_at = time.monotonic()
            return 200, {"content-length": str(data.size)}, data.tobytes()

    data = np.arange(4096, dtype=np.uint8)
    plan = s3_clients.plan("127.0.0.1:1", "b", "id", "secret", 7, [0],
                           data.size, 0, {"get": 1})
    c = s3_clients.Client(plan, 0)
    c.conn = Stub()
    c.model.put("x", data)
    checked = []
    real = np.array_equal

    def slow_equal(a, b):
        time.sleep(0.05)
        checked.append(time.monotonic())
        return real(a, b)

    import unittest.mock as mock
    with mock.patch.object(s3_clients.np, "array_equal", slow_equal):
        c.run(time.monotonic() + 0.001)
    (kind, start, end, nbytes, ok, _e, _c, name), = c.ops
    assert (kind, ok, nbytes, name) == ("get", True, data.size, "x")
    assert start < end == c.conn.replied_at < checked[0]
