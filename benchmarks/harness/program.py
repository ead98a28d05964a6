"""Everything the benchmark takes from the program besides the client
entry points the generators drive: its counters, its device report and
its compile counts. One place, so a renamed counter breaks one file.

Imports JAX (through the program): call nothing here before the
cluster's launcher has been started.
"""

from __future__ import annotations

import sys


def _named_registries() -> dict:
    """{name: registry} of every registry a module of the program holds:
    made once through `utils/metrics.registry(name)` and shared by all
    that ask for the name (`codec.service`, `mesh`, `client.ops`,
    `datapath`, `lifecycle`, `tracing`, ...). A registry an object makes
    for itself (each repair coordinator's `ec.reconstruction`, the OM's,
    a datanode's) is replaced by the next one of its name, so deltas of
    it mean nothing: no module holds it, and it is left out."""
    from ozone_tpu.utils.metrics import MetricsRegistry

    out = {}
    for mod_name, mod in list(sys.modules.items()):
        if mod is None or mod_name.partition(".")[0] != "ozone_tpu":
            continue
        for value in list(vars(mod).values()):
            if isinstance(value, MetricsRegistry):
                out[value.name] = value
    return out


def snapshot() -> dict[str, float]:
    """Flat {"<registry>/<name>": value} of the counters of every named
    registry, which readers may take deltas of. A histogram gives `.sum`
    and `.count`."""
    # the two registries read from the first: there before any module
    # that submits work is imported
    from ozone_tpu.codec import service  # noqa: F401
    from ozone_tpu.parallel import mesh_executor  # noqa: F401
    from ozone_tpu.utils.compile_cache import compile_counts

    out: dict[str, float] = {}
    for prefix, reg in _named_registries().items():
        for name, c in list(reg._counters.items()):
            out[f"{prefix}/{name}"] = float(c.value)
        for name, h in list(reg._histograms.items()):
            out[f"{prefix}/{name}.sum"] = float(h.total)
            out[f"{prefix}/{name}.count"] = float(h.count)
    for name, v in compile_counts().items():
        out[f"compile/{name}"] = float(v)
    return out


def delta(after: dict, before: dict, name: str) -> float:
    return after.get(name, 0.0) - before.get(name, 0.0)


def start_counting_compiles() -> None:
    from ozone_tpu.utils.compile_cache import count_compiles

    count_compiles()


def compile_cache_dir() -> str:
    """The directory JAX's own cache variable names if it is set, else
    <checkout>/.jax_cache: the program's own rule (utils/compile_cache.py,
    the one file that names the variable), applied before JAX loads."""
    from ozone_tpu.utils.compile_cache import ensure_compile_cache

    return ensure_compile_cache()


def backend_report() -> dict:
    from ozone_tpu.codec import fused

    return fused.backend_report()


def connect(om_address: str):
    """(OzoneClient, GrpcScmClient) against a running cluster, built as
    `tools/cli.py` `_client` builds the CLI's."""
    from ozone_tpu.client.dn_client import DatanodeClientFactory
    from ozone_tpu.client.ozone_client import OzoneClient
    from ozone_tpu.net.om_service import GrpcOmClient
    from ozone_tpu.net.ratis_service import RatisClientFactory
    from ozone_tpu.net.scm_service import GrpcScmClient

    clients = DatanodeClientFactory()
    om = GrpcOmClient(om_address, clients=clients)
    scm = GrpcScmClient(om_address)
    addresses, locations = scm.node_topology()
    for dn_id, addr in addresses.items():
        clients.register_remote(dn_id, addr)
    clients.learn_locations(locations)
    ratis = RatisClientFactory(address_source=clients.remote_address)
    return OzoneClient(om, clients, ratis_clients=ratis), scm
