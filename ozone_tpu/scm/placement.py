"""Placement policies: rack-scatter (EC), rack-aware, capacity, random.

Mirrors server-scm container/placement (SCMContainerPlacementRackScatter —
EC spreads d+p across as many racks as possible; ...RackAware,
...Capacity, ...Random; SCMCommonPlacementPolicy validation).
"""

from __future__ import annotations

import random
from collections import defaultdict
from typing import Optional, Sequence, TypeVar

from ozone_tpu.scm.node_manager import NodeInfo, NodeManager


T = TypeVar("T")


class PlacementError(Exception):
    pass


class PlacementPolicy:
    def __init__(self, nodes: NodeManager, seed: Optional[int] = None):
        self.nodes = nodes
        self.rng = random.Random(seed)

    def choose(
        self, count: int, excluded: Sequence[str] = ()
    ) -> list[NodeInfo]:
        raise NotImplementedError

    def _candidates(self, excluded: Sequence[str]) -> list[NodeInfo]:
        ex = set(excluded)
        return [n for n in self.nodes.healthy_in_service()
                if n.dn_id not in ex and n.healthy_volumes != 0]


class RandomPlacement(PlacementPolicy):
    def choose(self, count, excluded=()):
        cands = self._candidates(excluded)
        if len(cands) < count:
            raise PlacementError(
                f"need {count} nodes, only {len(cands)} available"
            )
        return self.rng.sample(cands, count)


class CapacityPlacement(PlacementPolicy):
    """Prefer lower-utilization nodes (SCMContainerPlacementCapacity)."""

    def choose(self, count, excluded=()):
        cands = self._candidates(excluded)
        if len(cands) < count:
            raise PlacementError(
                f"need {count} nodes, only {len(cands)} available"
            )
        def util(n: NodeInfo) -> float:
            return n.used_bytes / n.capacity_bytes if n.capacity_bytes else 0.0
        # weighted-random among the least-utilized half to avoid herding
        cands.sort(key=util)
        pool = cands[: max(count, len(cands) // 2 + 1)]
        return self.rng.sample(pool, count)


def rack_scatter(cands: Sequence[tuple[str, T]], count: int,
                 rng: random.Random) -> list[T]:
    """`count` of the candidates `cands` ((rack, node) pairs), scattered
    across racks round-robin, most populous racks in the draw first
    (SCMContainerPlacementRackScatter). The one choosing rule of EC
    placement: the SCM's policy below draws with its own generator, a
    repair storm that plans from the SCM's listings
    (client/reconstruction.py) with one seeded by the container."""
    if len(cands) < count:
        raise PlacementError(
            f"need {count} nodes, only {len(cands)} available"
        )
    by_rack: dict[str, list[T]] = defaultdict(list)
    for rack, n in cands:
        by_rack[rack].append(n)
    for nodes in by_rack.values():
        rng.shuffle(nodes)
    racks = sorted(by_rack, key=lambda r: -len(by_rack[r]))
    rng.shuffle(racks)
    chosen: list[T] = []
    while len(chosen) < count:
        for r in racks:
            if by_rack[r] and len(chosen) < count:
                chosen.append(by_rack[r].pop())
    return chosen


class RackScatterPlacement(PlacementPolicy):
    """EC placement: scatter across racks, round-robin by rack
    (SCMContainerPlacementRackScatter)."""

    def choose(self, count, excluded=()):
        return rack_scatter(
            [(n.rack, n) for n in self._candidates(excluded)], count,
            self.rng)

    @staticmethod
    def validate(racks_used: int, total_racks: int, count: int) -> bool:
        """Mis-replication check: placement is valid when it uses
        min(count, total_racks) distinct racks."""
        return racks_used >= min(count, max(total_racks, 1))
