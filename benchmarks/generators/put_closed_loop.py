"""Closed-loop PUTs of fresh keys (freon ockg's shape), and the
comparison of what they left on the datanodes.

Traffic parameters: threads, stripes_per_key, verify_keys.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

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

    def prepare(self) -> None:
        self.bucket = self.ctx.bucket("ockg")
        # one PUT of the cell's own shape: compiles (or loads) the fused
        # encode+CRC program at the writer's batch width
        self.bucket.write_key("warm-0", self.pool.payload(0))

    def window(self, seconds: float):
        def op(i: int):
            # key 0's payload went to the warm-up
            self.bucket.write_key(f"k-{i}", self.pool.payload(i + 1))
            return "put", self.key_bytes, i

        return closed_loop(self.ctx.traffic["threads"], seconds, op)

    def verify(self, ops, t0: float, t1: float) -> dict:
        ctx, scheme = self.ctx, self.ctx.scheme
        acked = [o for o in ops if o.ok]
        done = in_window(ops, "put", t0, t1)
        # every acknowledged key is there at its size
        missing = 0
        for o in acked:
            info = ctx.client.om.lookup_key("bench", "ockg", f"k-{o.tag}")
            missing += int(info["size"]) != self.key_bytes
        # a sample drawn from the seed, the first and the last
        # acknowledged in it: read back through the client, and every
        # unit, data and parity, straight off its datanode
        by_end = sorted(done or acked, key=lambda o: o.end)
        sample = [by_end[j] for j in seeded_sample(
            ctx.rng(2), len(by_end), ctx.traffic["verify_keys"],
            {0, len(by_end) - 1})]
        if ctx.control:
            _plant(ctx, sample[-1])

        def one(o) -> tuple[storecheck.Tally, int]:
            tally = storecheck.Tally()  # one per thread, merged below
            differs = 0
            payload = self.pool.payload(o.tag + 1)
            got = self.bucket.read_key(f"k-{o.tag}")
            if got.size != payload.size or not np.array_equal(
                    np.asarray(got).reshape(-1), payload):
                differs = 1
                tally.note(f"k-{o.tag}: read-back differs")
            info = ctx.client.om.lookup_key("bench", "ockg", f"k-{o.tag}")
            at = 0
            for g in ctx.client.om.key_block_groups(info):
                units = storecheck.expected_units(
                    scheme, payload[at:at + g.length])
                at += g.length
                for u, dn_id in enumerate(g.pipeline.nodes):
                    storecheck.check_unit(
                        ctx.client.clients.get(dn_id), g.block_id,
                        g.length, units[:, u], scheme, tally,
                        f"k-{o.tag} unit {u} on {dn_id}")
            return tally, differs

        tally = storecheck.Tally()
        readback_differ = 0
        with ThreadPoolExecutor(max_workers=4) as tp:
            for part, differs in tp.map(one, sample):
                tally.merge(part)
                readback_differ += differs
        storecheck.finish(tally, scheme)
        n = scheme["k"] + scheme["p"]
        ctx.notes["first_error"] = tally.first_error
        return {
            "acked_keys_missing": check(missing, 0),
            "readback_keys_differ": check(readback_differ, 0),
            "stored_records_wrong": check(tally.records_wrong, 0),
            "stored_bytes_differ": check(tally.stored_bytes_differ, 0),
            "stored_crcs_differ": check(tally.stored_crcs_differ, 0),
            "units_compared": check(tally.units_compared,
                                    len(sample) * n, ">="),
            "keys_compared": check(len(sample), min(1, len(acked)), ">="),
        }


def _plant(ctx: Context, o) -> None:
    """Controls: break what the configuration guarantees of one sampled
    key, on its datanode, after the window and before the comparison."""
    from benchmarks.harness import faults

    info = ctx.client.om.lookup_key("bench", "ockg", f"k-{o.tag}")
    g = ctx.client.om.key_block_groups(info)[0]
    faults.plant(ctx.control, ctx, g)
