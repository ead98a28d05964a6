"""Time-bounded repair drill (freon ecrd's shape): one coordinator, one
repair at a time. Each operation wipes one replica of a closed EC
container and has `ECReconstructionCoordinator` rebuild it onto the
wiped node, built as `freon ecrd` builds it (the executor
`mesh_executor.maybe_executor()` hands out). The comparison reads the
rebuilt replicas themselves off their datanodes.

Traffic parameters: stripes_per_key, keys_per_container (a list: how
many keys each container gets, so the replicas differ in size),
verify_replicas, settle_s.

One coordinator finishes a whole number of repairs in a window, so the
rate moves in steps of one repair. Replicas of several sizes, repaired in
an order drawn from the seed (every seed the same repairs, in another
order), keep those steps from reading as "no spread at all" in one set of
runs and as a jump in the next.
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

from benchmarks.harness import storecheck
from benchmarks.harness.context import (
    Context,
    PayloadPool,
    check,
    seeded_sample,
)
from benchmarks.harness.loop import closed_loop
from benchmarks.harness.stats import in_window


class Generator:
    def __init__(self, ctx: Context):
        self.ctx = ctx
        self.key_bytes = ctx.traffic["stripes_per_key"] * ctx.stripe_bytes
        self.pool = PayloadPool(ctx.rng(1), self.key_bytes)
        self.bucket = None
        self.coord = None
        self.opts = None
        #: container id -> {"nodes": [...], "groups": [(key index, group)]}
        self.containers: dict[int, dict] = {}
        self.order: list[tuple[int, int]] = []  # (container, lost unit)

    # ------------------------------------------------------------ set-up
    def prepare(self) -> None:
        from ozone_tpu.codec.api import CoderOptions
        from ozone_tpu.parallel import mesh_executor
        from ozone_tpu.storage.reconstruction import (
            ECReconstructionCoordinator,
        )

        ctx, t = self.ctx, self.ctx.traffic
        self.opts = CoderOptions.parse(ctx.config["replication"])
        self.bucket = ctx.bucket("ecrd")
        first = 0
        for per in t["keys_per_container"]:
            keys = range(first, first + per)
            first += per
            with ThreadPoolExecutor(max_workers=len(keys)) as tp:
                list(tp.map(lambda i: self.bucket.write_key(
                    f"k-{i}", self.pool.payload(i)), keys))
            touched = set()
            for i in keys:
                info = ctx.client.om.lookup_key("bench", "ecrd", f"k-{i}")
                for g in ctx.client.om.key_block_groups(info):
                    c = self.containers.setdefault(
                        g.container_id,
                        {"nodes": list(g.pipeline.nodes), "groups": []})
                    c["groups"].append((i, g))
                    touched.add(g.container_id)
            # the allocator fills one open container per scheme: closing
            # it moves the next keys to a new one
            for cid in touched:
                ctx.scm.admin("close-container", str(cid))
        # close the replicas DIRECTLY on the datanodes, as freon ecrd
        # does: the SCM's own close commands arrive over later
        # heartbeats and would race the drill's RECOVERING containers
        for cid, c in self.containers.items():
            for dn_id in set(c["nodes"]):
                ctx.client.clients.get(dn_id).close_container(cid)
        self._wait_closed()
        # rounds over all containers, each in an order of its own drawn
        # from the seed; the lost unit rotates over data and parity
        rng = ctx.rng(5)
        cids = sorted(self.containers)
        n_u = ctx.scheme["k"] + ctx.scheme["p"]
        self.order = [(cids[j], int(r + j) % n_u) for r in range(4 * n_u)
                      for j in rng.permutation(len(cids))]
        ctx.notes["containers"] = {
            str(cid): {"block_groups": len(c["groups"]),
                       "replica_mib": self._replica_bytes(cid) / 2 ** 20}
            for cid, c in self.containers.items()}
        self.coord = ECReconstructionCoordinator(
            ctx.client.clients, executor=mesh_executor.maybe_executor())
        # one repair of the cell's own shape, outside the window
        self._repair(*self.order[-1])

    def _wait_closed(self, timeout: float = 30.0) -> None:
        """Until the SCM has every drill container CLOSED (its close
        commands have then been delivered, before any wipe)."""
        deadline = time.monotonic() + timeout
        while True:
            states = {c["id"]: c["state"]
                      for c in self.ctx.scm.list_containers()}
            open_ = [cid for cid in self.containers
                     if states.get(cid) != "CLOSED"]
            if not open_:
                # the SCM hands each datanode its close command in the
                # answer to a heartbeat (1 s apart), and the last may
                # arrive just after the SCM reads CLOSED: one that lands
                # on a RECOVERING container the drill has just created
                # closes it under the coordinator (seen once in ~75 chip
                # runs). `settle_s`, two heartbeats, flushes them all.
                time.sleep(self.ctx.traffic["settle_s"])
                return
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"containers {open_} not CLOSED at the SCM after "
                    f"{timeout:.0f}s: {[states.get(c) for c in open_]}")
            time.sleep(0.2)

    def _replica_bytes(self, cid: int) -> int:
        """Bytes one replica of the container holds: the same for every
        unit index, since every group is whole stripes."""
        return sum(storecheck.unit_lengths(g.length, self.ctx.scheme)[0]
                   for _i, g in self.containers[cid]["groups"])

    # ------------------------------------------------------------ window
    def _repair(self, cid: int, unit: int) -> None:
        from ozone_tpu.storage.reconstruction import ReconstructionCommand

        nodes = self.containers[cid]["nodes"]
        self.ctx.client.clients.get(nodes[unit]).delete_container(
            cid, force=True)
        # with k+p nodes the pipeline spans them all: the spare is the
        # wiped node itself, as in the placement policy's candidate set
        self.coord.reconstruct_container_group(ReconstructionCommand(
            cid, self.opts,
            sources={u + 1: nodes[u] for u in range(len(nodes))
                     if u != unit},
            targets={unit + 1: nodes[unit]}))

    def window(self, seconds: float):
        def op(i: int):
            cid, unit = self.order[i % len(self.order)]
            self._repair(cid, unit)
            return "repair", self._replica_bytes(cid), (cid, unit)

        return closed_loop(1, seconds, op)

    # ------------------------------------------------------- comparison
    def verify(self, ops, t0: float, t1: float) -> dict:
        ctx, scheme = self.ctx, self.ctx.scheme
        done = in_window(ops, "repair", t0, t1)
        rebuilt = [o for o in ops if o.ok]
        # every (container, unit) holds what its LAST repair left: a
        # sample of those drawn from the seed, the last repair in it
        last: dict[tuple, int] = {}
        for n, o in enumerate(rebuilt):
            last[o.tag] = n
        pairs = sorted(last, key=last.get)
        sample = [pairs[j] for j in seeded_sample(
            ctx.rng(2), len(pairs), ctx.traffic["verify_replicas"],
            {len(pairs) - 1})]
        if ctx.control and sample:
            from benchmarks.harness import faults

            cid, unit = sample[-1]
            faults.plant(ctx.control, ctx,
                         self.containers[cid]["groups"][0][1], unit=unit)
        tally = storecheck.Tally()
        for cid, unit in sample:
            dn_id = self.containers[cid]["nodes"][unit]
            dn = ctx.client.clients.get(dn_id)
            for i, g in self.containers[cid]["groups"]:
                storecheck.check_unit(
                    dn, g.block_id, g.length, storecheck.expected_unit(
                        scheme, self.pool.payload(i), unit),
                    scheme, tally,
                    f"container {cid} unit {unit} on {dn_id} (k-{i})")
        storecheck.finish(tally, scheme)
        ctx.notes["first_error"] = tally.first_error
        ctx.notes["repairs_in_window"] = len(done)
        groups = sum(len(self.containers[cid]["groups"])
                     for cid, _u in sample)
        return {
            "rebuilt_records_wrong": check(tally.records_wrong, 0),
            "rebuilt_bytes_differ": check(tally.stored_bytes_differ, 0),
            "rebuilt_crcs_differ": check(tally.stored_crcs_differ, 0),
            "rebuilt_units_compared": check(tally.units_compared, groups,
                                            ">="),
            "replicas_compared": check(len(sample),
                                       min(1, len(rebuilt)), ">="),
        }
