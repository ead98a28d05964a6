"""MinIO warp's `mixed` benchmark through the S3 gateway: clients in
worker processes (`harness/s3_clients.py`), each a closed loop over
seeded, shuffled blocks of GET / HEAD / PUT / DELETE, against
`gateway/s3.py` `S3Gateway` served from this, the chip-owning, process
with SigV4 required; every object ends in a partial stripe. Then the
comparison: the workers' models against the OM and the gateway, and a
sample of the window's objects, every unit of every stripe, against the
plain reference (`harness/partial_stripe.py`).

Traffic parameters: clients, clients_per_process, object_bytes,
preload_per_client, mix, verify_puts, verify_deleted_404.
"""

from __future__ import annotations

import json
import os
import queue
import shutil
import subprocess
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

from benchmarks.harness import partial_stripe, s3_clients, storecheck, warm
from benchmarks.harness.context import Context, check, seeded_sample
from benchmarks.harness.stats import MIB, Op, in_window

ROOT = Path(__file__).resolve().parents[2]
BUCKET = "warp"
ACCESS_ID = "warp-bench"
#: a worker's set-up (its preload) and its report may take this long
READY_TIMEOUT_S = 600.0
REPORT_SLACK_S = 180.0


class _Worker:
    """One worker process of S3 clients, spoken to in JSON lines."""

    def __init__(self, plan: dict, log_path: Path):
        self._log = open(log_path, "w")
        env = dict(os.environ, PYTHONPATH=str(ROOT), JAX_PLATFORMS="cpu")
        env.pop("BENCH_RUN", None)
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "benchmarks.harness.s3_clients"],
            cwd=ROOT, env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self._log, text=True)
        self.log_path = log_path
        self._lines: queue.Queue = queue.Queue()
        threading.Thread(target=self._read, daemon=True,
                         name="s3-worker-reader").start()
        self.send(plan)

    def _read(self) -> None:
        for line in self.proc.stdout:
            self._lines.put(line)
        self._lines.put(None)

    def send(self, obj: dict) -> None:
        self.proc.stdin.write(json.dumps(obj) + "\n")
        self.proc.stdin.flush()

    def receive(self, timeout: float) -> dict:
        try:
            line = self._lines.get(timeout=timeout)
        except queue.Empty:
            line = None
        if line is None:
            self.stop()
            raise RuntimeError(
                f"S3 client worker gave no answer (exit "
                f"{self.proc.poll()}):\n"
                + self.log_path.read_text()[-2000:])
        return json.loads(line)

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._log.close()


class Generator:
    def __init__(self, ctx: Context):
        self.ctx = ctx
        t = ctx.traffic
        self.object_bytes = int(t["object_bytes"])
        stripes = -(-self.object_bytes // ctx.stripe_bytes)
        if self.object_bytes % ctx.stripe_bytes == 0:
            raise ValueError("s3-mixed objects must end in a partial stripe")
        self.stripes_per_object = stripes
        self.gateway = None
        self.secret = ""
        self.workers: list[_Worker] = []
        self._pools: dict[int, object] = {}
        self._dir = Path(tempfile.mkdtemp(prefix="ozbench_s3_"))
        self.report: dict = {}

    # ---------------------------------------------------------- set-up
    def prepare(self) -> None:
        from ozone_tpu.gateway.s3 import S3_VOLUME, S3Gateway

        ctx, t = self.ctx, self.ctx.traffic
        self.gateway = S3Gateway(ctx.client, replication=ctx.config[
            "replication"], require_auth=True)
        try:
            ctx.client.om.create_bucket(S3_VOLUME, BUCKET,
                                        ctx.config["replication"])
        except Exception as e:  # noqa: BLE001 - only "exists" is fine
            if "EXISTS" not in repr(e).upper():
                raise
        # the secret the OM issues: the gateway fetches it for every
        # signed request
        self.secret = ctx.client.om.get_s3_secret(ACCESS_ID, create=True)
        # no datanode is down, yet under twenty clients a survivor can
        # straggle past its hedge delay: load every decode the reader
        # can then ask for
        warm.decoders(ctx.scheme, warm.reader_decode_shapes(ctx.scheme))
        self.gateway.start()
        per = int(t["clients_per_process"])
        numbers = list(range(int(t["clients"])))
        t0 = time.monotonic()
        for w, at in enumerate(range(0, len(numbers), per)):
            self.workers.append(_Worker(self._plan(numbers[at:at + per]),
                                        self._dir / f"worker{w}.log"))
        try:
            ready = [w.receive(READY_TIMEOUT_S) for w in self.workers]
        except BaseException:
            self._stop_workers()
            raise
        ctx.notes["preload_s"] = round(time.monotonic() - t0, 3)
        ctx.notes["worker_set_up_s"] = [round(r["set_up_s"], 3)
                                        for r in ready]

    def _plan(self, clients: list[int]) -> dict:
        t = self.ctx.traffic
        return s3_clients.plan(
            self.gateway.address, BUCKET, ACCESS_ID, self.secret,
            self.ctx.seed, clients, self.object_bytes,
            int(t["preload_per_client"]), t["mix"])

    def _stop_workers(self) -> None:
        for w in self.workers:
            w.stop()

    # ---------------------------------------------------------- window
    def window(self, seconds: float):
        t0 = time.monotonic()
        t1 = t0 + seconds
        for w in self.workers:
            w.send({"t1": t1})
        reports = []
        try:
            for w in self.workers:
                reports.append(w.receive(seconds + REPORT_SLACK_S))
        finally:
            self._stop_workers()
        ops = [Op(kind, start, end, nbytes, ok, error=error,
                  tag=(client, name))
               for r in reports
               for kind, start, end, nbytes, ok, error, client, name
               in r["ops"]]
        self.report = {
            "models": {int(c): m for r in reports
                       for c, m in r["models"].items()},
            "gets_differ": sum(r["gets_differ"] for r in reports),
            "heads_wrong": sum(r["heads_wrong"] for r in reports)}
        return ops, t0, t1

    # ---------------------------------------------------------- verify
    def _payload(self, name: str) -> np.ndarray:
        client, j = s3_clients.parse_name(name)
        if client not in self._pools:
            self._pools[client] = s3_clients.payload_pool(
                self.ctx.seed, client, self.object_bytes)
        return self._pools[client].payload(j)

    def _connection(self) -> s3_clients.Connection:
        return s3_clients.Connection(
            self.gateway.address, BUCKET, s3_clients.Signer(
                ACCESS_ID, self.secret, self.gateway.address))

    def verify(self, ops, t0: float, t1: float) -> dict:
        try:
            return self._verify(ops, t0, t1)
        finally:
            self.gateway.stop()
            shutil.rmtree(self._dir, ignore_errors=True)

    def _verify(self, ops, t0: float, t1: float) -> dict:
        from ozone_tpu.gateway.s3 import S3_VOLUME

        ctx, scheme, om = self.ctx, self.ctx.scheme, self.ctx.client.om
        models = self.report["models"]
        live = [n for m in models.values() for n in m["live"]]
        deleted = [n for m in models.values() for n in m["deleted"]]
        self._notes(ops, t0, t1)
        conn = self._connection()
        if ctx.control == "undelete" and deleted:
            # a deleted name written again after the window: the
            # comparison must find it present
            status, _h, _b = conn.request("PUT", deleted[0],
                                          self._payload(deleted[0]))
            ctx.notes["undeleted"] = [deleted[0], status]

        def size_of(name: str):
            try:
                return int(om.lookup_key(S3_VOLUME, BUCKET, name)["size"])
            except Exception as e:  # noqa: BLE001 - only "not found" is absent
                if "NOT_FOUND" not in repr(e).upper():
                    raise
                return None

        with ThreadPoolExecutor(max_workers=8) as tp:
            live_sizes = list(tp.map(size_of, live))
            deleted_sizes = list(tp.map(size_of, deleted))
        missing = sum(s != self.object_bytes for s in live_sizes)
        present = sum(s is not None for s in deleted_sizes)
        for j in seeded_sample(ctx.rng(3), len(deleted),
                               int(ctx.traffic["verify_deleted_404"]), set()):
            status, _h, _b = conn.request("GET", deleted[j])
            present += status != 404
        conn.close()

        # the window's acknowledged PUTs that are still live: a seeded
        # sample with the first and the last, every unit of every stripe
        # straight off its datanode
        still = set(live)
        puts = sorted((o for o in in_window(ops, "put", t0, t1)
                       if o.tag[1] in still), key=lambda o: o.end)
        sample = [puts[j].tag[1] for j in seeded_sample(
            ctx.rng(2), len(puts), int(ctx.traffic["verify_puts"]),
            {0, len(puts) - 1})]
        groups = {}
        for name in sample:
            info = om.lookup_key(S3_VOLUME, BUCKET, name)
            groups[name] = om.key_block_groups(info)
        if ctx.control and ctx.control != "undelete" and sample:
            from benchmarks.harness import faults

            # the default unit is the first parity: its second cell is
            # the partial stripe's
            faults.plant(ctx.control, ctx, groups[sample[-1]][-1])

        def one(name: str) -> storecheck.Tally:
            tally = storecheck.Tally()  # one per thread, merged below
            payload = self._payload(name)
            at = 0
            for g in groups[name]:
                partial_stripe.check_group(
                    ctx.client.clients, g, payload[at:at + g.length],
                    scheme, tally, name)
                at += g.length
            return tally

        tally = storecheck.Tally()
        with ThreadPoolExecutor(max_workers=4) as tp:
            for part in tp.map(one, sample):
                tally.merge(part)
        storecheck.finish(tally, scheme)
        ctx.notes["first_error"] = tally.first_error
        n = scheme["k"] + scheme["p"]
        return {
            "gets_differ": check(self.report["gets_differ"], 0),
            "heads_wrong": check(self.report["heads_wrong"], 0),
            "acked_keys_missing": check(missing, 0),
            "deleted_keys_present": check(present, 0),
            "stored_records_wrong": check(tally.records_wrong, 0),
            "stored_bytes_differ": check(tally.stored_bytes_differ, 0),
            "stored_crcs_differ": check(tally.stored_crcs_differ, 0),
            "units_compared": check(
                tally.units_compared, int(ctx.traffic["verify_puts"])
                * n * self.stripes_per_object, ">="),
        }

    def _notes(self, ops, t0: float, t1: float) -> None:
        """What the run's line says beside its metrics: operations by
        kind, the median HEAD and DELETE, the stage records kept, the
        span ring."""
        from benchmarks.harness import program, spans

        notes = self.ctx.notes
        notes["ops_in_window"] = {
            k: len(in_window(ops, k, t0, t1))
            for k in ("get", "head", "put", "delete")}
        for kind in ("head", "delete"):
            done = sorted(o.end - o.start for o in in_window(ops, kind,
                                                             t0, t1))
            if done:
                notes[f"{kind}_p50_ms"] = round(
                    1e3 * done[len(done) // 2], 3)
        notes["put_gib_in_window"] = round(sum(
            o.nbytes for o in in_window(ops, "put", t0, t1)) / MIB / 1024, 3)
        notes["stage_records_in_window"] = {
            k: len(spans.operations(f"s3:{k}", t0, t1))
            for k in ("get", "head", "put", "delete")}
        snap = program.snapshot()
        notes["spans_evicted"] = snap.get("tracing/spans_evicted", 0.0)
