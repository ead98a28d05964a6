"""Time-bounded cold-data tiering sweep (the lifecycle sweeper's shape):
ONE sweeper converts a bucket of cold `RATIS/THREE` keys to the
configuration's EC scheme, `batch_keys` keys a `transition_keys` call,
one call after another, unthrottled, as `LifecycleService.run_once`
pages its executor. The executor is the program's own
(`lifecycle/executor.py` `TieringExecutor`), built in this chip-owning
process with the cluster's OM behind RPC; on a host with two or more
devices the door sends its encode windows to the mesh executor.

Traffic parameters: stripes_per_key (a list, taken in turn: key i has
stripes_per_key[i % len] whole stripes), source_keys (the bucket's cold
keys, preloaded in set-up by preload_threads writers), warm_keys (swept
before the window, so that no program compiles inside it), batch_keys,
verify_converted, verify_unconverted.

The op log holds one `put` per key whose fenced `CommitKey` was
acknowledged: from its `OpenKey` to that acknowledgement, its bytes the
key's user bytes. The generator learns of both from the OM client it
hands the executor, which passes every call through; the sweep itself
is one continuous stream. The window closes as a budgeted sweep of the
service closes: the call runs under a deadline that ends with the
window, the executor stops packing, and what was in flight on the
device is not written out (those keys stay replicated and count
nowhere). A sweep that runs out of source keys inside the window is no
measurement and ends the run.

Controls: `byte_flip` (harness/faults.py, on a converted key's first
parity unit) and `fence_dropped` (the raced conversion commits without
its rewrite fence, so it clobbers the user's overwrite).
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.harness import program, storecheck
from benchmarks.harness.context import (
    Context,
    PayloadPool,
    check,
    seeded_sample,
)
from benchmarks.harness.stats import Op, in_window

VOLUME, BUCKET = "bench", "tier"


class _Om:
    """The OM client the executor is handed: every call goes through to
    the real one; an `OpenKey` is timed and an acknowledged `CommitKey`
    is logged as one operation."""

    def __init__(self, om, ops: list):
        self._om = om
        self._ops = ops
        self._opened: dict[str, float] = {}
        self.reached: set[str] = set()  # every key the sweep has opened
        self.drop_fence = False  # the control, for the raced key only

    def __getattr__(self, name):
        return getattr(self._om, name)

    def open_key(self, volume, bucket, key, **kw):
        self._opened[key] = time.monotonic()
        self.reached.add(key)
        return self._om.open_key(volume, bucket, key, **kw)

    def commit_key(self, session, groups, size, hsync=False):
        if self.drop_fence:
            session.expect_object_id, session.expect_generation = "", -1
        self._om.commit_key(session, groups, size, hsync=hsync)
        self._ops.append(Op("put", self._opened.pop(session.key),
                            time.monotonic(), int(size), True,
                            tag=session.key))


class Generator:
    def __init__(self, ctx: Context):
        from ozone_tpu.lifecycle.executor import TieringExecutor

        self.ctx = ctx
        t = ctx.traffic
        self.sizes = [n * ctx.stripe_bytes for n in t["stripes_per_key"]]
        self.pool = PayloadPool(ctx.rng(1), max(self.sizes))
        self.n_keys = t["source_keys"]
        self.target = ctx.config["replication"]
        self.ops: list[Op] = []
        self.om = _Om(ctx.client.om, self.ops)
        self.executor = TieringExecutor(self.om, ctx.client.clients)
        if not hasattr(self.executor, "last_window"):
            raise RuntimeError(
                "this program's TieringExecutor packs windows of a width "
                "of its own, not of the lane the door routes them to, "
                "and lays a converted key out by its source's blocks: "
                "the cell cannot run on it")
        self.bucket = None
        self.order: list[int] = []   # the window's keys, in sweep order
        self.at = 0                  # the first of them not yet handed out
        self._counters = ({}, {})

    # ------------------------------------------------------------ set-up
    def _name(self, i: int) -> str:
        return f"cold-{i:05d}"

    def _payload(self, i: int) -> np.ndarray:
        return self.pool.payload(i)[:self.sizes[i % len(self.sizes)]]

    def _preload(self, i: int) -> int:
        """PUT source key i; the tries beyond the first. A replicated
        PUT can fail while eight writers race the SCM's one open RATIS
        container through its closes and pipeline hand-overs
        (KNOWN_ISSUES.md): set-up takes the key again, as a client
        would, and the run's notes say how often."""
        from ozone_tpu.storage.ids import StorageError

        for attempt in range(3):
            try:
                self.bucket.write_key(self._name(i), self._payload(i))
                return attempt
            except StorageError:
                if attempt == 2:
                    raise
        return 0

    def _work(self, keys) -> list[tuple]:
        return [(VOLUME, BUCKET, self._name(i), self.target) for i in keys]

    def prepare(self) -> None:
        from ozone_tpu.parallel import mesh_executor

        ctx, t = self.ctx, self.ctx.traffic
        if mesh_executor.maybe_executor() is None:
            raise RuntimeError(
                "no mesh executor on this host (one device): the sweep "
                "would run on the single-chip service, which the cell "
                "does not measure")
        om = ctx.client.om
        for make in (lambda: om.create_volume(VOLUME),
                     lambda: om.create_bucket(
                         VOLUME, BUCKET, ctx.config["source_replication"])):
            try:
                make()
            except Exception as e:  # noqa: BLE001 - only "exists" is fine
                if "EXISTS" not in repr(e).upper():
                    raise
        self.bucket = ctx.client.get_volume(VOLUME).get_bucket(BUCKET)
        t_load = time.monotonic()
        with ThreadPoolExecutor(max_workers=t["preload_threads"]) as tp:
            retried = sum(tp.map(self._preload, range(self.n_keys)))
        ctx.notes["preload_s"] = round(time.monotonic() - t_load, 2)
        ctx.notes["preload_puts_retried"] = retried
        ctx.notes["source_mib"] = sum(
            self.sizes[i % len(self.sizes)]
            for i in range(self.n_keys)) // 2 ** 20
        # the warm-up sweep: the first keys, of every size, through the
        # whole path; then the window's order over the rest
        warm = list(range(t["warm_keys"]))
        t_warm = time.monotonic()
        stats = self.executor.transition_keys(self._work(warm))
        ctx.notes["warm_s"] = round(time.monotonic() - t_warm, 2)
        if stats["transitioned"] != len(warm):
            raise RuntimeError(f"the warm-up sweep converted "
                               f"{stats['transitioned']} of {len(warm)} "
                               f"keys: {stats}")
        rest = np.arange(len(warm), self.n_keys)
        self.order = [int(i) for i in ctx.rng(5).permutation(rest)]
        del self.ops[:]

    # ------------------------------------------------------------ window
    def _lifecycle_counters(self) -> dict:
        """Registry `lifecycle` (harness/program.snapshot() does not
        list it): the packer's counters and the seconds of the sweeper
        thread's four stages, for the run's notes."""
        from ozone_tpu.lifecycle.executor import METRICS

        out = {name: float(c.value)
               for name, c in list(METRICS._counters.items())}
        out.update({name: float(h.total)
                    for name, h in list(METRICS._histograms.items())})
        return out

    def window(self, seconds: float):
        from ozone_tpu.client import resilience
        from ozone_tpu.storage.ids import StorageError

        batch = self.ctx.traffic["batch_keys"]
        totals = {"transitioned": 0, "conflicts": 0, "failed": 0,
                  "skipped": 0, "dispatches": 0, "calls": 0}
        before = (program.snapshot(), self._lifecycle_counters())
        t0 = time.monotonic()
        t1 = t0 + seconds
        while time.monotonic() < t1:
            if self.at >= len(self.order):
                raise RuntimeError(
                    f"the sweep ran dry {time.monotonic() - t0:.1f}s into "
                    f"the window: {len(self.order)} source keys are too "
                    f"few (source_keys in the traffic file)")
            keys = self.order[self.at:self.at + batch]
            try:
                # a budgeted sweep, as the service runs one: the budget
                # ends with the window
                with resilience.start("lifecycle_sweep",
                                      seconds=t1 - time.monotonic()):
                    stats = self.executor.transition_keys(self._work(keys))
            except StorageError as e:
                if e.code != resilience.DEADLINE_EXCEEDED:
                    raise
                stats = getattr(e, "stats", None) or {}
            # the sweep takes its keys in order: the next call starts at
            # the first one this call did not reach
            while self.at < len(self.order) and \
                    self._name(self.order[self.at]) in self.om.reached:
                self.at += 1
            totals["calls"] += 1
            for k in totals:
                totals[k] += stats.get(k, 0)
            now = time.monotonic()
            self.ops.extend(
                Op("failed", now, now, 0, False,
                   error="a key's transition failed (the sweeper's log "
                         "names it)")
                for _ in range(stats.get("failed", 0)))
        after = (program.snapshot(), self._lifecycle_counters())
        self._counters = (before[0], after[0])
        self.ctx.notes["sweep"] = totals
        self.ctx.notes["lifecycle"] = {
            k: after[1].get(k, 0.0) - before[1].get(k, 0.0)
            for k in ("stripes_packed", "pad_stripes", "windows_submitted",
                      "keys_split", "transitions", "transition_conflicts",
                      "transition_failures", "closed_container_retries",
                      "read_seconds", "pack_seconds", "write_seconds",
                      "finalize_seconds")}
        self.ctx.notes["source_keys_left"] = len(self.order) - self.at
        return list(self.ops), t0, t1

    # ------------------------------------------------------- comparison
    def _reads_back(self, i: int, payload: np.ndarray) -> bool:
        got = np.asarray(self.bucket.read_key(self._name(i))).reshape(-1)
        return got.size == payload.size and np.array_equal(got, payload)

    def _race(self, i: int) -> tuple[int, int]:
        """One conversion of key i with a user's overwrite landing just
        before its commit: (conflicts counted, 1 if the user's bytes do
        not read back as the key)."""
        newer = self.pool.payload(self.n_keys + 1)[:self.sizes[0]]

        def overwrite(ks) -> None:
            self.bucket.write_key(ks.key, newer)

        self.executor.pre_commit_hook = overwrite
        self.om.drop_fence = self.ctx.control == "fence_dropped"
        try:
            stats = self.executor.transition_keys(self._work([i]))
        finally:
            self.executor.pre_commit_hook = None
            self.om.drop_fence = False
        return stats["conflicts"], int(not self._reads_back(i, newer))

    def verify(self, ops, t0: float, t1: float) -> dict:
        import jax

        from ozone_tpu.lifecycle.executor import tier_batch_size
        from ozone_tpu.parallel import mesh_executor

        ctx, scheme, t = self.ctx, self.ctx.scheme, self.ctx.traffic
        om = ctx.client.om
        index = {self._name(i): i for i in range(self.n_keys)}
        done = in_window(ops, "put", t0, t1)
        acked = [o for o in ops if o.ok]
        by_end = sorted(done or acked, key=lambda o: o.end)
        sample = [index[by_end[j].tag] for j in seeded_sample(
            ctx.rng(2), len(by_end), t["verify_converted"],
            {0, len(by_end) - 1})]
        # the raced key and the keys held to RATIS/THREE: the sweep's
        # order from its END, which the window may never reach
        untouched = self.order[self.at:][::-1]
        raced, unconverted = untouched[:1], \
            untouched[1:1 + t["verify_unconverted"]]
        if ctx.control == "byte_flip" and sample:
            from benchmarks.harness import faults

            info = om.lookup_key(VOLUME, BUCKET, self._name(sample[-1]))
            faults.plant(ctx.control, ctx, om.key_block_groups(info)[0])
        elif ctx.control not in ("", "fence_dropped"):
            raise ValueError(f"unknown control {ctx.control!r} for this "
                             f"cell; known: byte_flip, fence_dropped")

        def converted(i: int) -> tuple[storecheck.Tally, int, int]:
            tally = storecheck.Tally()  # one per thread, merged below
            payload = self._payload(i)
            differs = int(not self._reads_back(i, payload))
            info = om.lookup_key(VOLUME, BUCKET, self._name(i))
            at = 0
            for g in om.key_block_groups(info):
                units = storecheck.expected_units(
                    scheme, payload[at:at + g.length])
                at += g.length
                for u, dn_id in enumerate(g.pipeline.nodes):
                    storecheck.check_unit(
                        ctx.client.clients.get(dn_id), g.block_id,
                        g.length, units[:, u], scheme, tally,
                        f"{self._name(i)} unit {u} on {dn_id}")
            return tally, differs, int(info["replication"] != self.target)

        tally = storecheck.Tally()
        readback_differ = not_ec = 0
        with ThreadPoolExecutor(max_workers=4) as tp:
            for part, differs, wrong in tp.map(converted, sample):
                tally.merge(part)
                readback_differ += differs
                not_ec += wrong
        storecheck.finish(tally, scheme)
        source = ctx.config["source_replication"]
        stale = 0
        for i in unconverted:
            info = om.lookup_key(VOLUME, BUCKET, self._name(i))
            stale += int(info["replication"] != source
                         or not self._reads_back(i, self._payload(i)))
        conflicts, user_lost = self._race(raced[0]) if raced else (0, 1)
        missing = sum(
            int(om.lookup_key(VOLUME, BUCKET, o.tag)["size"]) != o.nbytes
            for o in acked)
        n = scheme["k"] + scheme["p"]
        c0, c1 = self._counters
        dispatches = program.delta(c1, c0, "mesh/dispatches")
        ctx.notes["first_error"] = tally.first_error
        ctx.notes["keys_converted_in_window"] = len(done)
        ctx.notes["mesh_dispatches_to_window_end"] = dispatches
        ctx.notes["packer_window_stripes"] = self.executor.last_window
        compared = {
            "acked_keys_missing": check(missing, 0),
            "readback_keys_differ": check(readback_differ, 0),
            "converted_keys_not_ec": check(not_ec, 0),
            "stored_records_wrong": check(tally.records_wrong, 0),
            "stored_bytes_differ": check(tally.stored_bytes_differ, 0),
            "stored_crcs_differ": check(tally.stored_crcs_differ, 0),
            "units_compared": check(tally.units_compared,
                                    len(sample) * n, ">="),
            "keys_compared": check(len(sample), min(1, len(acked)), ">="),
            "unconverted_keys_wrong": check(stale, 0),
            "unconverted_keys_compared": check(
                len(unconverted), t["verify_unconverted"], ">="),
            "raced_conversion_conflicts": check(conflicts, 1, ">="),
            "raced_user_bytes_lost": check(user_lost, 0),
            "packer_window_stripes": check(
                self.executor.last_window,
                mesh_executor.get_executor().dispatch_width(
                    tier_batch_size()), ">="),
            "single_chip_encode_stripes": check(program.delta(
                c1, c0, "codec.service/stripes_dispatched"), 0),
            "mesh_encode_stripes": check(program.delta(
                c1, c0, "mesh/stripes_dispatched"), 1, ">="),
        }
        if jax.devices()[0].platform != "tpu":
            # a rehearsal: on the CPU the program picks the host twin by
            # its own rule, and its outputs are numpy arrays
            return compared
        compared["mesh_output_shards"] = check(
            program.delta(c1, c0, "mesh/output_shards_dispatched")
            / max(dispatches, 1.0), ctx.config["cluster"]["chips"], ">=")
        compared["mesh_host_twin_programs"] = check(
            mesh_executor.get_executor().stats()["programs_host_twin"], 0)
        return compared
