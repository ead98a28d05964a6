"""The reduction from a device trace to busy/idle, program time and
roofline shares, on a small recorded trace kept with the benchmark and
against hand-worked numbers."""

import json
from pathlib import Path

import pytest

import bench_minicluster  # noqa: F401  (puts the repo root on sys.path)
from benchmarks.harness import manifest as mf
from benchmarks.harness import trace as tr
from benchmarks.harness import work
from benchmarks.harness.record import Run
from benchmarks.harness.stats import Op

FIXTURES = mf.BENCH_DIR / "fixtures"
MIB = 2 ** 20


def _plane(ops, modules=(), name="/device:TPU:0"):
    return {"name": name, "lines": [
        {"name": tr.OPS_LINE, "events": [list(e) for e in ops]},
        {"name": tr.MODULES_LINE, "events": [list(e) for e in modules]}]}


def test_union_merges_overlaps_and_touching_intervals():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 11)]) == [
        (0, 4), (5, 7), (10, 11)]
    assert tr.union([(0, 10), (2, 3)]) == [(0, 10)]
    assert tr.union([]) == []


def test_busy_is_the_union_of_op_intervals_averaged_over_chips():
    # chip 0: [0,4ms) and [3ms,5ms) overlap -> 5 ms; chip 1: 1 ms
    t = {"planes": [
        _plane([("a", 0, 4_000_000), ("b", 3_000_000, 2_000_000)]),
        _plane([("a", 0, 1_000_000)], name="/device:TPU:1"),
        # not a TensorCore plane, and a host plane: neither counts
        _plane([("x", 0, 9_000_000)], name="/device:TPU:0 SparseCore 0"),
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [["h", 0, 9_000_000]]}]}]}
    assert tr.busy_seconds(t) == pytest.approx((0.005 + 0.001) / 2)
    with pytest.raises(tr.TraceError):
        tr.busy_seconds({"planes": [t["planes"][3]]})


def test_idle_gaps_are_named_after_the_host_event_that_covers_them():
    t = {"planes": [
        _plane([("a", 1_000_000, 1_000_000), ("a", 8_000_000, 1_000_000)]),
        {"name": "/host:CPU", "lines": [{"name": "python/123", "events": [
            ["PjitFunction(fn)", 500_000, 400_000],
            ["TransferFromDevice", 2_500_000, 5_000_000]]}]}]}
    gaps = tr.idle_gaps(t, (0, 10_000_000))
    assert gaps[0] == ["python: TransferFromDevice", pytest.approx(0.006)]
    assert sorted(g[1] for g in gaps) == pytest.approx([0.001, 0.001, 0.006])


def test_recorded_trace_reduces_to_its_hand_counted_numbers():
    """fixtures/ockg.events.json: cut from a --trace 1 run of
    ockg.rs-6-3 on the chip; expected.json holds what was counted by
    hand from it."""
    t = json.loads((FIXTURES / "ockg.events.json").read_text())
    want = json.loads((FIXTURES / "ockg.expected.json").read_text())
    assert tr.busy_seconds(t) == pytest.approx(want["busy_s"], rel=1e-9)
    params = mf.metric_params("fused_encode_roofline.put")
    seconds, n = tr.program_seconds(t, params["program"])
    assert n == want["encode_executions"]
    assert seconds == pytest.approx(want["encode_s"], rel=1e-9)
    # the decode metrics' pattern finds nothing in a write cell's trace
    assert tr.program_seconds(
        t, mf.metric_params("fused_decode_roofline.get")["program"]) \
        == (0.0, 0)
    assert tr.top_device_ops(t, 3)[0][0] == want["top_op"]


@pytest.mark.parametrize("metric,names,other", [
    ("fused_encode_roofline.put", ["jit_fn(12345)", "jit_fn"],
     ["jit__decode_apply_jit(7)", "jit_fnord(1)", "pjit_fn(1)"]),
    ("fused_decode_roofline.get", ["jit__decode_apply_jit(99)"],
     ["jit_fn(1)", "jit__decode_apply_nocrc_jit(1)"]),
    ("fused_decode_roofline.repair", ["jit__decode_apply_jit"],
     ["jit_fn(1)"]),
])
def test_programs_are_matched_by_the_metric_files_pattern(
        metric, names, other):
    pattern = mf.metric_params(metric)["program"]
    t = {"planes": [_plane([], [(n, 0, 1000) for n in names + other])]}
    assert tr.program_seconds(t, pattern) == (
        pytest.approx(len(names) * 1e-6), len(names))


def test_work_from_shapes_against_hand_worked_numbers():
    # rs-6-3-1024k encode, one stripe: 9 MiB of units moved once, one
    # 4-byte CRC per 16 KiB of them; 3 x 6 x 1 MiB GF multiplies x 128
    enc = work.encode_work(6, 3, MIB, 16384)
    assert enc["bytes"] == 9 * MIB + 4 * 9 * 64 == 9_439_488
    assert enc["ops"] == 128 * 18 * MIB == 2_415_919_104
    peaks = work.peaks_for("TPU v5 lite")
    roof = work.least_seconds(enc, peaks)
    assert roof["binds"] == "memory"
    assert roof["seconds"] == pytest.approx(9_439_488 / 819e9)
    assert roof["compute_s"] == pytest.approx(2_415_919_104 / 393e12)
    # user bytes at the roof: 6 MiB per stripe -> 508 GiB/s
    assert 6 * MIB / roof["seconds"] / 2 ** 30 == pytest.approx(508.4, abs=0.1)
    # rs-10-4-1024k decode of e = 2: reads 10 cells, writes 2 and their CRCs
    dec = work.decode_work(10, 2, MIB, 16384)
    assert dec["bytes"] == 12 * MIB + 4 * 2 * 64 == 12_583_424
    assert dec["ops"] == 128 * 20 * MIB
    assert work.least_seconds(dec, peaks)["seconds"] == pytest.approx(
        12_583_424 / 819e9)
    # stripes scale both
    assert work.encode_work(6, 3, MIB, 16384, stripes=7)["ops"] == 7 * enc["ops"]


def test_a_device_missing_from_the_peaks_table_is_an_error():
    with pytest.raises(work.UnknownDevice, match="TPU v9"):
        work.peaks_for("TPU v9")


def _run(trace, slice_counters, ops=(), scheme=None):
    cfg = {"scheme": scheme or {"k": 6, "p": 3, "cell": MIB, "bpc": 16384}}
    return Run(cell={}, config=cfg, traffic={}, setup_s=1.0, ops=list(ops),
               t0=0.0, t1=30.0, counters0={}, counters1={},
               peaks=work.peaks_for("TPU v5 lite"), trace=trace,
               slice0=10.0, slice1=15.0, slice_counters0={},
               slice_counters1=slice_counters)


def test_roofline_reader_counts_useful_stripes_and_scales_by_dispatches():
    read = mf.reader_of(mf.metric_params("fused_encode_roofline.put"))
    params = mf.metric_params("fused_encode_roofline.put")
    # 5 executions of 2 ms traced, 4 dispatches counted in the slice,
    # 24 useful stripes in them (of 32 slots: padding lowers the share)
    t = {"planes": [_plane([], [("jit_fn(1)", i * 10_000_000, 2_000_000)
                                for i in range(5)])]}
    counters = {"codec.service/stripes_dispatched": 24.0,
                "codec.service/dispatches": 4.0}
    least = 24 * 9_439_488 / 819e9
    assert read(params, _run(t, counters)) == pytest.approx(
        100 * least / (0.010 * 4 / 5))
    # nothing to read: no program in the trace, or no dispatch counted
    assert read(params, _run({"planes": [_plane([], [])]}, counters)) is None
    assert read(params, _run(t, {})) is None
    assert read(params, _run(None, counters)) is None


def test_decode_roofline_takes_erased_units_from_the_overlapping_gets():
    params = mf.metric_params("fused_decode_roofline.get")
    read = mf.reader_of(params)
    scheme = {"k": 10, "p": 4, "cell": MIB, "bpc": 16384}
    t = {"planes": [_plane([], [("jit__decode_apply_jit(3)", 0, 1_000_000)])]}
    counters = {"codec.service/stripes_dispatched": 4.0,
                "codec.service/dispatches": 1.0}
    ops = [Op("get", 9.0, 11.0, 1, True, tag=(0, 2)),    # half inside
           Op("get", 12.0, 13.0, 1, True, tag=(1, 1)),   # inside
           Op("get", 12.0, 13.0, 1, True, tag=(2, 0)),   # decoded nothing
           Op("get", 20.0, 21.0, 1, True, tag=(3, 2))]   # outside
    e = (0.5 * 2 + 1.0 * 1) / 1.5
    least = work.least_seconds(
        work.decode_work(10, e, MIB, 16384, 4),
        work.peaks_for("TPU v5 lite"))["seconds"]
    assert read(params, _run(t, counters, ops, scheme)) == pytest.approx(
        100 * least / 0.001)


def test_idle_reader_and_counter_readers():
    t = {"planes": [
        _plane([("a", 0, 1_000_000_000)]),
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["x", 0, 100_000], ["y", 3_900_000_000, 100_000_000]]}]}]}
    run = _run(t, {})
    idle = mf.reader_of(mf.metric_params("device_idle_pct.put"))
    # the slice is 5 s by the host's clock, the trace spans 4 s: 1 s busy
    assert idle({}, run) == pytest.approx(80.0)
    run.counters0 = {"codec.service/stripes_dispatched": 10.0,
                     "codec.service/slots_dispatched": 16.0,
                     "codec.service/dispatch_seconds.sum": 1.0,
                     "codec.service/dispatch_seconds.count": 2.0}
    run.counters1 = {"codec.service/stripes_dispatched": 31.0,
                     "codec.service/slots_dispatched": 40.0,
                     "codec.service/dispatch_seconds.sum": 1.3,
                     "codec.service/dispatch_seconds.count": 8.0}
    p = mf.metric_params("codec_fill_pct.put")
    assert mf.reader_of(p)(p, run) == pytest.approx(100 * 21 / 24)
    p = mf.metric_params("codec_dispatch_ms.put")
    assert mf.reader_of(p)(p, run) == pytest.approx(50.0)
    p = mf.metric_params("codec_queue_wait_ms.put")
    assert mf.reader_of(p)(p, run) is None  # observed nothing
