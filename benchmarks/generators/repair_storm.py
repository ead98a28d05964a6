"""Time-bounded node-loss repair storm: the streams of one
`ReconstructionStorm` (client/reconstruction.py), each wiping one
replica of a closed EC container and having the storm's per-container
method rebuild it onto the wiped node. The commands are the drill's own,
one for the replica it has just wiped: no node is dead, so the storm's
`plan()` has nothing to plan and is not run here. All streams share the
storm's coordinator, so their decode batches meet in the lanes of the
mesh executor `mesh_executor.maybe_executor()` hands out. The repair
drill's set-up, order and comparison of the rebuilt replicas are
`repair_drill.py`'s; this file adds the streams and holds the window to
the mesh.

Traffic parameters: those of `repair_drill.py`. The configuration gives
`reconstruction_streams`, the threads of the closed loop.

The order is the drill's: rounds over all containers, each round a
permutation drawn from the seed, the lost unit rotating over all k+p
units, so up to k+p erasure patterns are in flight at once. A stream
takes the next entry whose container no other stream is repairing.
"""

from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor

from benchmarks.generators.repair_drill import Generator as Drill
from benchmarks.harness import program
from benchmarks.harness.context import Context, check
from benchmarks.harness.loop import closed_loop


class Generator(Drill):
    def __init__(self, ctx: Context):
        from ozone_tpu.client.reconstruction import ReconstructionStorm

        super().__init__(ctx)
        if not hasattr(ReconstructionStorm, "repair_container"):
            raise RuntimeError(
                "this program's ReconstructionStorm has no per-container "
                "method to drive and plans from an in-process SCM only: "
                "the cell cannot run on it")
        self.storm = ReconstructionStorm(
            ctx.scm, ctx.client.clients,
            max_parallel_containers=ctx.config["reconstruction_streams"])
        self.streams = self.storm.max_parallel_containers
        self._free = threading.Condition()
        self._busy: set[int] = set()
        self._next = 0          # the first entry of the order not yet ahead
        self._ahead: list[tuple[int, int]] = []  # entries to take, oldest first
        self._counters = ({}, {})

    # ------------------------------------------------------------ set-up
    def prepare(self) -> None:
        # the drill's set-up ends with one repair through `_repair`:
        # here, the first dispatch of the mesh's decode program
        super().prepare()
        if self.storm.executor is None:
            raise RuntimeError(
                "no mesh executor on this host (one device, or switched "
                "off): the cell measures nothing without it")
        # one storm of the cell's own shapes outside the window: every
        # stream at once, one round over the containers (more of them
        # than lost units, and the unit rotates: each has been decoded)
        with ThreadPoolExecutor(max_workers=self.streams) as tp:
            list(tp.map(self._op, range(len(self.containers))))
        self._next, self._ahead = 0, []

    # ------------------------------------------------------------ window
    def _take(self) -> tuple[int, int]:
        """(container, lost unit): the oldest entry of the order whose
        container no stream is repairing. A round's worth of entries is
        kept ahead; where each of them waits for a repair in flight, so
        does the caller."""
        with self._free:
            while True:
                while len(self._ahead) < len(self.containers):
                    self._ahead.append(
                        self.order[self._next % len(self.order)])
                    self._next += 1
                for at, (cid, unit) in enumerate(self._ahead):
                    if cid not in self._busy:
                        del self._ahead[at]
                        self._busy.add(cid)
                        return cid, unit
                self._free.wait()

    def _repair(self, cid: int, unit: int) -> None:
        from ozone_tpu.storage.reconstruction import ReconstructionCommand

        nodes = self.containers[cid]["nodes"]
        self.ctx.client.clients.get(nodes[unit]).delete_container(
            cid, force=True)
        # with k+p nodes the pipeline spans them all: the spare is the
        # wiped node itself, as in the placement policy's candidate set
        self.storm.repair_container(ReconstructionCommand(
            cid, self.opts,
            sources={u + 1: nodes[u] for u in range(len(nodes))
                     if u != unit},
            targets={unit + 1: nodes[unit]}))

    def _op(self, _i: int):
        cid, unit = self._take()
        try:
            self._repair(cid, unit)
        finally:
            with self._free:
                self._busy.discard(cid)
                self._free.notify_all()
        return "repair", self._replica_bytes(cid), (cid, unit)

    def window(self, seconds: float):
        before = program.snapshot()
        out = closed_loop(self.streams, seconds, self._op)
        # after the last repair in flight at the close has ended
        self._counters = (before, program.snapshot())
        return out

    # ------------------------------------------------------- comparison
    def verify(self, ops, t0: float, t1: float) -> dict:
        import jax

        compared = super().verify(ops, t0, t1)
        c0, c1 = self._counters
        dispatches = program.delta(c1, c0, "mesh/dispatches")
        compared["single_chip_decode_stripes"] = check(
            program.delta(c1, c0, "codec.service/stripes_dispatched"), 0)
        compared["mesh_decode_stripes"] = check(
            program.delta(c1, c0, "mesh/stripes_dispatched"), 1, ">=")
        self.ctx.notes["mesh_dispatches_to_window_end"] = dispatches
        if jax.devices()[0].platform != "tpu":
            # a rehearsal: on the CPU the program picks the host twin by
            # its own rule, and its outputs are numpy arrays
            return compared
        stats = self.storm.executor.stats()
        chips = self.ctx.config["cluster"]["chips"]
        compared["mesh_output_shards"] = check(
            program.delta(c1, c0, "mesh/output_shards_dispatched")
            / max(dispatches, 1.0), chips, ">=")
        compared["mesh_host_twin_programs"] = check(
            stats["programs_host_twin"], 0)
        return compared
