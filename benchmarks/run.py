#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s>
                              --trace <0|1>

This process IS the chip-owning client, the load generator and the
profiler. Before it touches JAX it starts the cell's deployment (one
scm-om and N datanode processes, CPU-pinned by the program's own
launcher); then it requires a TPU that is in the peaks table, warms the
cell's shapes through the served path, drives the cell's traffic for
exactly `--seconds`, holds what the window left on the datanodes to the
plain reference, and prints one JSON line. Nothing falls back: no TPU,
a device missing from the peaks table, a fused backend other than the
jitted one, a program compiled inside the window, or a window without a
codec dispatch each end the run with a non-zero exit and no result.

Cells, configurations, traffic mixes, generators, metrics and readers
are files found by name (harness/manifest.py); this file names none.

`--rehearse` is for a sandbox without a chip: tiny traffic on the CPU,
`"platform": "cpu"` and `"rehearsal": true` in the line, no device
metric. It can never be mistaken for a cell.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import tempfile
import threading
import time
from pathlib import Path

T_START = time.monotonic()

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks.harness import manifest as mf  # noqa: E402
from benchmarks.harness.cluster import Cluster, ClusterFailure  # noqa: E402
from benchmarks.harness.work import UnknownDevice  # noqa: E402

#: the traced slice of a `--trace 1` run: starts this long into the
#: window and lasts this long (both cut down for very short windows)
TRACE_DELAY_S = 5.0
TRACE_SLICE_S = 5.0


class RunFailure(Exception):
    """The run is no measurement; the message says why."""


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def parse(argv: list[str]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny traffic on the CPU; never a measurement")
    ap.add_argument("--control", default="",
                    help="plant a named fault (harness/faults.py): the run "
                         "must then report correct=false")
    ap.add_argument("--dump-trace", default="",
                    help="directory to keep the traced run's .xplane.pb "
                         "and its extracted events in, to read by hand")
    return ap.parse_args(argv)


def require_device(chips: int, rehearse: bool):
    """(device dict for the result line, peaks row or None)."""
    import jax

    from benchmarks.harness import work

    devs = jax.devices()
    d = devs[0]
    if rehearse:
        return {"platform": d.platform, "kind": d.device_kind,
                "count": len(devs)}, None
    if d.platform != "tpu":
        raise RunFailure(
            f"no TPU found: JAX reports platform {d.platform!r} "
            f"({d.device_kind}); a cell has no CPU mode")
    if len(devs) < chips:
        raise RunFailure(f"the cell asks for {chips} chips, JAX finds "
                         f"{len(devs)}")
    peaks = work.peaks_for(d.device_kind)  # UnknownDevice: an error
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}, peaks


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class Tracer:
    """Traces a short steady slice of the window on a thread of its own,
    with the program's counters snapshotted at both ends."""

    def __init__(self, seconds: float, out_dir: str):
        self.delay = min(TRACE_DELAY_S, seconds / 4)
        self.length = min(TRACE_SLICE_S, seconds / 2)
        self.out_dir = out_dir
        self.error: BaseException | None = None
        self.slice = (0.0, 0.0)
        self.counters = ({}, {})
        self._thread = threading.Thread(target=self._run, name="tracer",
                                        daemon=True)

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        import jax

        from benchmarks.harness import program

        try:
            time.sleep(self.delay)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # the op events, not every frame
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            try:
                s0, c0 = time.monotonic(), program.snapshot()
                time.sleep(self.length)
                c1, s1 = program.snapshot(), time.monotonic()
            finally:
                jax.profiler.stop_trace()
            self.slice, self.counters = (s0, s1), (c0, c1)
        except BaseException as e:  # noqa: BLE001 - reported by finish()
            self.error = e

    def finish(self, dump_dir: str = "") -> dict:
        from benchmarks.harness import trace as tr

        self._thread.join()
        if self.error is not None:
            raise RunFailure(f"tracing failed: {self.error!r}")
        path = tr.find_xplane(self.out_dir)
        trace = tr.extract(path)
        if dump_dir:
            import shutil

            os.makedirs(dump_dir, exist_ok=True)
            shutil.copy(path, os.path.join(dump_dir, "trace.xplane.pb"))
            with open(os.path.join(dump_dir, "trace.events.json"), "w") as f:
                json.dump(trace, f)
        return trace


def every_2s(ops, t0: float, t1: float) -> list[list]:
    """[operations ended, MiB, median ms, slowest ms] for each 2 s of the
    window: where inside it a stall sat (not a metric; for PERF.md)."""
    out = []
    at = t0
    while at < t1:
        lat = sorted(1e3 * (o.end - o.start) for o in ops
                     if o.ok and at <= o.end < at + 2.0)
        mib = sum(o.nbytes for o in ops
                  if o.ok and at <= o.end < at + 2.0) / 2 ** 20
        out.append([len(lat), round(mib), round(lat[len(lat) // 2]) if lat
                    else None, round(lat[-1]) if lat else None])
        at += 2.0
    return out


def measure(args, manifest: dict, cluster, cell: dict, config: dict,
            traffic: dict, bench_dir: Path = mf.BENCH_DIR) -> dict:
    """Everything of a run after the launcher was started: `cluster` is
    a harness.cluster.Cluster, or what stands in for one in a test."""
    # the program's rule for the compile cache (the directory the
    # environment gives, else <checkout>/.jax_cache), before JAX loads
    from benchmarks.harness import program

    cache_dir = program.compile_cache_dir()
    device, peaks = require_device(cell["chips"], args.rehearse)
    program.start_counting_compiles()
    cluster.wait_up()
    say(f"cluster up at {cluster.om} after "
        f"{time.monotonic() - T_START:.1f}s; device {device}; compile "
        f"cache {cache_dir}")

    from benchmarks.harness.context import Context
    from benchmarks.harness.record import Run

    client, scm = cluster.connect()
    ctx = Context(cell=cell, config=config, traffic=traffic, seed=args.seed,
                  client=client, scm=scm, cluster=cluster,
                  control=args.control)
    gen = mf.generator_of(traffic, bench_dir)(ctx)
    gen.prepare()
    cluster.check_alive()

    tracer = None
    trace_dir = None
    if args.trace and not args.rehearse:
        trace_dir = tempfile.mkdtemp(prefix="ozbench_trace_")
        tracer = Tracer(args.seconds, trace_dir)
    at_close: list[dict] = []
    closer = threading.Timer(args.seconds,
                             lambda: at_close.append(program.snapshot()))
    setup_s = time.monotonic() - T_START
    counters0 = program.snapshot()
    closer.start()
    if tracer:
        tracer.start()
    ops, t0, t1 = gen.window(args.seconds)
    closer.join()
    counters1 = at_close[0]
    peak = memory_peak_bytes()
    say(f"window closed: {len(ops)} operations, "
        f"{sum(not o.ok for o in ops)} failed; set-up took {setup_s:.1f}s")

    run = Run(cell=cell, config=config, traffic=traffic, setup_s=setup_s,
              ops=ops, t0=t0, t1=t1, counters0=counters0,
              counters1=counters1, notes=ctx.notes, peaks=peaks)
    if tracer:
        try:
            run.trace = tracer.finish(args.dump_trace)
        finally:
            import shutil

            shutil.rmtree(trace_dir, ignore_errors=True)
        run.slice0, run.slice1 = tracer.slice
        run.slice_counters0, run.slice_counters1 = tracer.counters

    # a window that is no measurement, whatever the comparison says
    compiled = program.delta(counters1, counters0, "compile/compiles")
    dispatched = program.delta(counters1, counters0,
                               "codec.service/dispatches") \
        + program.delta(counters1, counters0, "mesh/dispatches")
    backend = program.backend_report()["fused_backend"]
    if compiled:
        raise RunFailure(f"{compiled:.0f} programs compiled inside the "
                         f"window: a shape was not warmed in set-up")
    if dispatched <= 0:
        raise RunFailure("the window launched no codec dispatch")
    if backend != "jax" and not args.rehearse:
        raise RunFailure(f"fused backend is {backend!r}, not the jitted "
                         f"programs")
    cluster.check_alive()

    t_verify = time.monotonic()
    compared = gen.verify(ops, t0, t1)
    say(f"comparison took {time.monotonic() - t_verify:.1f}s")
    correct = all(c["ok"] for c in compared.values())
    failed = sum(not o.ok for o in ops)
    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in mf.metrics_for(manifest, section, cell["name"]):
        params = mf.metric_params(m["name"], bench_dir)
        value = mf.reader_of(params, bench_dir)(params, run)
        if value is None:
            if section == "per_layer":
                continue  # a reader that finds nothing returns nothing
            if correct and not failed:
                raise RunFailure(f"nothing to read for {m['name']}: no "
                                 f"operation completed inside the window")
            value = 0.0  # every operation failed: the line says so
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device["memory_peak_bytes"] = peak
    result = {
        "correct": correct,
        "attempted": len(ops),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if run.trace is not None:
        from benchmarks.harness import trace as tr

        device["busy_s"] = tr.busy_seconds(run.trace)
        device["window_s"] = tr.traced_seconds(run.trace,
                                               run.slice1 - run.slice0)
        first, last = tr.span_ns(run.trace)
        result["breakdown"] = {
            "device_ops": tr.top_device_ops(run.trace),
            "idle_gaps": tr.idle_gaps(run.trace, (first, last)),
        }
    if args.rehearse:
        result["rehearsal"] = True
    if args.control:
        result["control"] = args.control
    errors = [o.error for o in ops if not o.ok][:1]
    if ctx.notes.get("first_error"):
        errors.append(ctx.notes["first_error"])
    result["notes"] = {k: v for k, v in ctx.notes.items()
                       if k != "first_error"}
    if errors:
        result["notes"]["first_errors"] = errors
    result["notes"]["every_2s"] = every_2s(ops, t0, t1)
    result["notes"]["background"] = {
        needle: cluster.grep_logs(needle)
        for needle in ("reconstruct", "stale", "dead")}
    # each number compared beside its limit: the last key of the line
    result["compared"] = {k: {"value": c["value"], "limit": c["limit"]}
                          for k, c in compared.items()}
    return result


def main(argv: list[str]) -> int:
    args = parse(argv)
    if not (ROOT / "ozone_tpu").is_dir():
        say(f"no program beside the benchmark: {ROOT}/ozone_tpu is missing")
        return 2
    try:
        manifest = mf.load()
        cell = mf.cell(manifest, args.workload)
        config = mf.config_of(manifest, cell)
        traffic = mf.traffic_of(cell)
        mf.generator_of(traffic)  # fail before booting anything
    except mf.ManifestError as e:
        say(str(e))
        return 2
    if args.rehearse:
        traffic = {**traffic, **traffic.get("rehearsal", {})}
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    def stop(signum, _frame):
        raise SystemExit(128 + signum)

    signal.signal(signal.SIGTERM, stop)
    cluster = None
    try:
        cluster = Cluster(config["cluster"]["datanodes"],
                          traffic["need_free_gib"])
        cluster.start()
        result = measure(args, manifest, cluster, cell, config, traffic)
    except (RunFailure, ClusterFailure, UnknownDevice) as e:
        say(f"FAILED: {e}")
        return 3
    finally:
        if cluster is not None:
            t_down = time.monotonic()
            cluster.teardown()
            say(f"teardown took {time.monotonic() - t_down:.1f}s")
    for name, c in result["compared"].items():
        say(f"compared {name}: {c['value']} (limit {c['limit']})")
    say(f"correct: {result['correct']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
