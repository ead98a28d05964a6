"""The repair drill (`repair_drill.py`: one coordinator, one repair at a
time, each operation wipes one replica of a closed EC container and has
`ECReconstructionCoordinator` rebuild it onto the wiped node) on a
locally repairable code. The drill's set-up and its window are
`repair_drill.py`'s; this file brings the order, the second warm repair
and a comparison against the LRC reference (`harness/reference_lrc.py`),
and holds the window to what the deployment is chosen for.

Traffic parameters: those of `repair_drill.py`.

The order: rounds over all containers, each round a permutation drawn
from the seed; the lost unit goes over ALL k + l + r units in every run
of that many repairs, in an order drawn anew for each such run. So of
every 16 repairs of LRC(12,2,2), whatever the window's length, 14 lose a
data unit or a local parity (read: the group's 6 survivors) and 2 lose a
global parity (read: the 12 data units). Two warm repairs before the
window, one of each kind: the two decode shapes.

Beside the rebuilt replicas (bytes and CRCs against the reference, the
sample always holding the last repair and, where the window rebuilt one,
a data unit, a local parity and a global parity) the comparison holds
that at least one local repair of the window fetched exactly the other
members of its group, that every rebuilt stripe went through a device
dispatch (the codec service's, or a mesh's), and that nothing compiled between the window's
first repair and its last. How many local plans widened is in the notes
and in `lrc_local_kept_pct.repair`: reported, not limited.
"""

from __future__ import annotations

from benchmarks.generators.repair_drill import Generator as Drill
from benchmarks.harness import program, reference_lrc, storecheck
from benchmarks.harness.context import Context, check, seeded_sample
from benchmarks.harness.loop import closed_loop
from benchmarks.harness.stats import in_window

#: what `_repair` books of the coordinator's registry, per repair
BOOKED = ("repairs_local", "repairs_global", "repairs_widened",
          "survivor_units_read", "survivor_bytes_read",
          "blocks_reconstructed")


class Generator(Drill):
    def __init__(self, ctx: Context):
        from ozone_tpu.client import ec_reader

        super().__init__(ctx)
        if not hasattr(ec_reader, "RecoveryTally"):
            raise RuntimeError(
                "this program's reader does not say what a repair planned "
                "and read (no kind, width or survivor counters): the "
                "cell's comparison cannot be made on it")
        #: one entry per repair, warm ones too: the deltas of BOOKED
        self.booked: list[dict] = []
        self._counters = ({}, {})

    # ------------------------------------------------------------ set-up
    def prepare(self) -> None:
        super().prepare()  # ends with one repair, of `order[-1]`
        ctx = self.ctx
        k, n_u = ctx.scheme["k"], ctx.scheme["k"] + ctx.scheme["p"]
        first_global = k + ctx.scheme["l"]
        rng = ctx.rng(7)
        cids = sorted(self.containers)
        rounds = [cids[j] for _r in range(4 * n_u)
                  for j in rng.permutation(len(cids))]
        units = [int(u) for _ in range(-(-len(rounds) // n_u))
                 for u in rng.permutation(n_u)]
        self.order = list(zip(rounds, units))
        # the drill's warm repair was of one kind: one of the other
        warmed = self.booked[-1]["unit"]
        self._repair(cids[0], first_global if warmed < first_global else 0)
        ctx.notes["warm_repairs"] = self.booked[:]

    def _repair(self, cid: int, unit: int) -> None:
        before = self._booked_now()
        try:
            super()._repair(cid, unit)
        finally:  # a failed repair too: the n-th entry is the n-th repair
            after = self._booked_now()
            self.booked.append({"container": cid, "unit": unit,
                                **{n: after[n] - before[n] for n in BOOKED}})

    def _booked_now(self) -> dict:
        return {n: self.coord.metrics.counter(n).value for n in BOOKED}

    # ------------------------------------------------------------ window
    def window(self, seconds: float):
        warm = len(self.booked)

        def op(i: int):
            cid, unit = self.order[i % len(self.order)]
            self._repair(cid, unit)
            return "repair", self._replica_bytes(cid), (cid, unit)

        before = program.snapshot()
        out = closed_loop(1, seconds, op)
        # after the repair in flight at the close has ended
        self._counters = (before, program.snapshot())
        self._window_booked = self.booked[warm:]
        return out

    # ------------------------------------------------------- comparison
    def _sample(self, rebuilt) -> list[tuple[int, int]]:
        """(container, unit) pairs to read back: every pair holds what
        its LAST repair left; the last repair of all, the newest pair of
        each class of unit the window rebuilt, the rest drawn from the
        seed."""
        ctx, s = self.ctx, self.ctx.scheme
        last: dict[tuple, int] = {}
        for n, o in enumerate(rebuilt):
            last[o.tag] = n
        pairs = sorted(last, key=last.get)
        keep = {len(pairs) - 1}
        for lo, hi in ((0, s["k"]), (s["k"], s["k"] + s["l"]),
                       (s["k"] + s["l"], s["k"] + s["p"])):
            of_class = [j for j, (_c, u) in enumerate(pairs) if lo <= u < hi]
            if of_class:
                keep.add(of_class[-1])
        return [pairs[j] for j in seeded_sample(
            ctx.rng(2), len(pairs), ctx.traffic["verify_replicas"], keep)]

    def verify(self, ops, t0: float, t1: float) -> dict:
        ctx, scheme = self.ctx, self.ctx.scheme
        done = in_window(ops, "repair", t0, t1)
        rebuilt = [o for o in ops if o.ok]
        sample = self._sample(rebuilt)
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
                    dn, g.block_id, g.length, reference_lrc.expected_unit(
                        scheme, self.pool.payload(i), unit),
                    scheme, tally,
                    f"container {cid} unit {unit} on {dn_id} (k-{i})")
        storecheck.finish(tally, scheme)
        groups = sum(len(self.containers[cid]["groups"])
                     for cid, _u in sample)
        compared = {
            "rebuilt_records_wrong": check(tally.records_wrong, 0),
            "rebuilt_bytes_differ": check(tally.stored_bytes_differ, 0),
            "rebuilt_crcs_differ": check(tally.stored_crcs_differ, 0),
            "rebuilt_units_compared": check(tally.units_compared, groups,
                                            ">="),
            "replicas_compared": check(len(sample),
                                       min(1, len(rebuilt)), ">="),
        }
        compared.update(self._held_to_the_deployment(ops))
        ctx.notes["first_error"] = tally.first_error
        ctx.notes["repairs_in_window"] = len(done)
        ctx.notes["sample_units"] = sorted(u for _c, u in sample)
        return compared

    def _held_to_the_deployment(self, ops) -> dict:
        """What the window's repairs planned and read, against the
        reference's read sets; the codec service's and the compiler's
        counters between the window's first repair and its last."""
        ctx, scheme = self.ctx, self.ctx.scheme
        # one thread: the n-th operation is the n-th repair booked
        ok = [b for o, b in zip(ops, self._window_booked) if o.ok]
        exact = local = widened = 0
        units_read: dict[str, int] = {}
        for b in ok:
            blocks = max(1, b["blocks_reconstructed"])
            want = len(reference_lrc.read_set(scheme, [b["unit"]]))
            got = b["survivor_units_read"] / blocks
            units_read[f"{got:g}"] = units_read.get(f"{got:g}", 0) + 1
            if reference_lrc.group_of(scheme, b["unit"]) is None:
                continue
            local += 1
            widened += b["repairs_widened"] > 0
            exact += (b["repairs_local"] == blocks and got == want
                      and not b["repairs_widened"])
        ctx.notes["repairs_planned"] = {
            "local_by_the_reference": local, "read_their_group_alone": exact,
            "widened": widened, "global_parities": len(ok) - local,
            "survivor_units_read_per_block": units_read}
        c0, c1 = self._counters
        stripes = sum(o.nbytes for o in ops if o.ok) // scheme["cell"]
        dispatched = program.delta(
            c1, c0, "codec.service/stripes_dispatched") + program.delta(
            c1, c0, "mesh/stripes_dispatched")
        compiled = sum(program.delta(c1, c0, name) for name in c1
                       if name.startswith("compile/"))
        return {
            "local_repairs_reading_their_group_alone": check(
                exact, min(1, local), ">="),
            "rebuilt_stripes_not_dispatched": check(
                max(0, stripes - dispatched), 0),
            "compile_events_in_window": check(compiled, 0),
        }
