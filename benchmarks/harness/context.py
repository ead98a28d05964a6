"""What a generator is handed, and the helpers generators share."""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

MIB = 2 ** 20


@dataclass
class Context:
    cell: dict          # the workload's entry in BENCHMARK.json
    config: dict        # benchmarks/configs/<config>.json
    traffic: dict       # benchmarks/traffic/<traffic>.json
    seed: int
    client: object      # OzoneClient, the chip-owning client
    scm: object         # GrpcScmClient
    cluster: object     # harness.cluster.Cluster (None in-process)
    control: str = ""   # a planted fault's name (never in a driver's run)
    notes: dict = field(default_factory=dict)  # generator -> readers/PERF

    @property
    def scheme(self) -> dict:
        return self.config["scheme"]

    @property
    def stripe_bytes(self) -> int:
        return self.scheme["k"] * self.scheme["cell"]

    def bucket(self, name: str):
        """Create (once) and return volume `bench` / bucket `name` with
        the configuration's replication."""
        om = self.client.om
        for make in (lambda: om.create_volume("bench"),
                     lambda: om.create_bucket(
                         "bench", name, self.config["replication"])):
            try:
                make()
            except Exception as e:  # noqa: BLE001 - only "exists" is fine
                if "EXISTS" not in repr(e).upper():
                    raise
        return self.client.get_volume("bench").get_bucket(name)

    def rng(self, stream: int) -> np.random.Generator:
        """An independent stream of this run's seed."""
        return np.random.default_rng([self.seed, stream])


def seeded_sample(rng: np.random.Generator, n: int, want: int,
                  keep: set[int]) -> list[int]:
    """Up to `want` of the indexes 0..n-1, ascending: those in `keep`
    and the rest drawn by `rng`."""
    picked = {j for j in keep if 0 <= j < n}
    for j in rng.permutation(n):
        if len(picked) >= min(want, n):
            break
        picked.add(int(j))
    return sorted(picked)


def check(value, limit, cmp: str = "<=") -> dict:
    """One number compared, beside its limit."""
    ok = value <= limit if cmp == "<=" else value >= limit
    return {"value": value, "limit": limit, "cmp": cmp, "ok": bool(ok)}


class PayloadPool:
    """A different payload for every key, all from the seed: one random
    buffer of key_bytes + 16 MiB, and key i's payload is the key_bytes
    that start 257 * i bytes into it (wrapping after 65,280 keys). Every
    key differs from every other at every stripe, and none costs the
    load generator a pass over memory inside the window."""

    EXTRA = 16 * MIB
    STRIDE = 257

    def __init__(self, rng: np.random.Generator, key_bytes: int):
        self.key_bytes = key_bytes
        self._buf = rng.integers(0, 256, key_bytes + self.EXTRA,
                                 dtype=np.uint8)
        self._buf.setflags(write=False)

    def payload(self, i: int) -> np.ndarray:
        at = (i * self.STRIDE) % self.EXTRA
        return self._buf[at:at + self.key_bytes]
