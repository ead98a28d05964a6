"""Closed-loop whole-key GETs with datanodes down (freon ockv's shape
during an outage), every GET's bytes held to the seeded payload.

Traffic parameters: threads, stripes_per_key, preload_keys,
kill_datanodes.
"""

from __future__ import annotations

import itertools
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.harness.context import Context, PayloadPool, check
from benchmarks.harness.loop import closed_loop
from benchmarks.harness.stats import in_window


class Generator:
    def __init__(self, ctx: Context):
        self.ctx = ctx
        self.key_bytes = ctx.traffic["stripes_per_key"] * ctx.stripe_bytes
        self.pool = PayloadPool(ctx.rng(1), self.key_bytes)
        self.n_keys = ctx.traffic["preload_keys"]
        self.bucket = None
        self.lost: list[int] = []   # lost DATA units per key (e)
        self.order: np.ndarray | None = None
        self.differ = 0
        self._lock = threading.Lock()

    def prepare(self) -> None:
        ctx, t = self.ctx, self.ctx.traffic
        self.bucket = ctx.bucket("ockv")
        with ThreadPoolExecutor(max_workers=t["threads"]) as tp:
            list(tp.map(lambda i: self.bucket.write_key(
                f"k-{i}", self.pool.payload(i)), range(self.n_keys)))
        # the outage: SIGKILLed at the same point of every run. The
        # victims are drawn from the seed among the sets of datanodes
        # that hold a DATA unit of every key, so that every seed does
        # the same work: each GET recovers `kill_datanodes` data units
        # of every stripe (BASELINE config 3: two missing data chunks).
        # Which sets qualify depends on the pipelines the SCM placed.
        k = ctx.scheme["k"]
        pipelines = []
        for i in range(self.n_keys):
            info = ctx.client.om.lookup_key("bench", "ockv", f"k-{i}")
            pipelines.append([g.pipeline.nodes for g in
                              ctx.client.om.key_block_groups(info)])
        nodes = sorted(ctx.scm.node_addresses())

        def lost_data(dead, groups) -> int:
            return max(sum(d in g[:k] for d in dead) for g in groups)

        sets = list(itertools.combinations(nodes, t["kill_datanodes"]))
        score = [sum(lost_data(c, groups) for groups in pipelines)
                 for c in sets]
        best = [c for c, sc in zip(sets, score) if sc == max(score)]
        dead = list(best[int(ctx.rng(3).integers(len(best)))])
        self.lost = [lost_data(dead, groups) for groups in pipelines]
        if ctx.control:
            from benchmarks.harness import faults

            # the newest key: its container is still open, so its block
            # record can be rewritten
            victim = self.n_keys - 1
            info = ctx.client.om.lookup_key("bench", "ockv", f"k-{victim}")
            g = ctx.client.om.key_block_groups(info)[0]
            unit = next(u for u in range(k)
                        if g.pipeline.nodes[u] not in dead)
            faults.plant(ctx.control, ctx, g, unit=unit)
        for d in dead:
            ctx.cluster.kill_datanode(d)
        ctx.notes["killed"] = dead
        ctx.notes["keys_by_lost_data_units"] = {
            str(e): self.lost.count(e) for e in sorted(set(self.lost))}
        # one GET for every decode shape the window will use (e = 1 and
        # e = 2 recovered units compile apart), through the served path
        for e in sorted(set(self.lost) - {0}):
            self.bucket.read_key(f"k-{self.lost.index(e)}")
        self._warm_hedge_shape()
        self.order = ctx.rng(4).permutation(self.n_keys)

    def _warm_hedge_shape(self) -> None:
        """The reader's straggler hedge decodes ONE cell at batch width 1
        (client/ec_reader.py `_decode_cell_traced`): rare, but a shape of
        its own, so it is loaded here and not compiled inside a window."""
        from ozone_tpu.codec import fused
        from ozone_tpu.codec.api import CoderOptions
        from ozone_tpu.utils.checksum import ChecksumType

        s = self.ctx.scheme
        spec = fused.FusedSpec(
            CoderOptions(s["k"], s["p"], s["codec"], cell_size=s["cell"]),
            ChecksumType.CRC32C, s["bpc"])
        valid = list(range(1, s["k"] + 1))
        out = fused.make_fused_decoder(spec, valid, [0])(
            np.zeros((1, s["k"], s["cell"]), dtype=np.uint8))
        np.asarray(out[0])

    def _get(self, i: int) -> None:
        """One whole-key GET, its bytes compared with the payload in the
        reader's own thread (a client that validates what it reads, as
        `ockv` does; a window's GETs are tens of GiB, so only the verdict
        is kept). One that differs was not returned byte-exact: it is a
        failed operation and counts in no rate."""
        got = np.asarray(self.bucket.read_key(f"k-{i}")).reshape(-1)
        want = self.pool.payload(i)
        if got.size != want.size or not np.array_equal(got, want):
            with self._lock:
                self.differ += 1
            raise ValueError(f"GET k-{i}: bytes differ from the payload")

    def window(self, seconds: float):
        def op(j: int):
            i = int(self.order[j % self.n_keys])
            self._get(i)
            return "get", self.key_bytes, (i, self.lost[i])

        return closed_loop(self.ctx.traffic["threads"], seconds, op)

    def verify(self, ops, t0: float, t1: float) -> dict:
        done = in_window(ops, "get", t0, t1)
        decoded = sum(1 for o in done if o.tag[1] > 0)
        self.ctx.notes["decoded_stripes_by_e"] = {
            str(e): sum(self.ctx.traffic["stripes_per_key"]
                        for o in done if o.tag[1] == e)
            for e in sorted(set(self.lost))}
        return {
            "gets_compared": check(sum(1 for o in ops if o.ok),
                                   min(1, len(ops)), ">="),
            "get_bytes_differ": check(self.differ, 0),
            "gets_that_decoded": check(decoded, 1, ">="),
        }
