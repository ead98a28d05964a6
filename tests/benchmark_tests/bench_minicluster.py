"""Stands in for harness.cluster.Cluster in the tests: the repo's
in-process MiniOzoneCluster behind the few calls run.measure() and the
generators make of a cluster and of an SCM client."""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.harness import manifest as mf  # noqa: E402


class _Scm:
    def __init__(self, mini):
        self.mini = mini

    def admin(self, op: str, target=None) -> dict:
        return self.mini.scm.apply_admin_op(op, target)

    def list_containers(self) -> list[dict]:
        self.mini.tick()  # heartbeats deliver and confirm close commands
        return [{"id": c.id, "state": c.state.value}
                for c in self.mini.scm.containers.containers()]

    def node_addresses(self) -> dict:
        return {d.id: "" for d in self.mini.datanodes}


class MiniCluster:
    def __init__(self, tmp_path, datanodes: int):
        from ozone_tpu.testing.minicluster import MiniOzoneCluster

        self.root = Path(tmp_path) / "cluster"
        self.mini = MiniOzoneCluster(self.root, num_datanodes=datanodes,
                                     stale_after_s=1000.0,
                                     dead_after_s=2000.0)
        self.om = "in-process"

    def wait_up(self) -> None:
        pass

    def connect(self):
        return self.mini.client(), _Scm(self.mini)

    def check_alive(self) -> None:
        pass

    def grep_logs(self, needle: str) -> dict:
        return {}

    def kill_datanode(self, dn_id: str) -> None:
        self.mini.stop_datanode(dn_id)

    def close(self) -> None:
        self.mini.close()


#: cells at a size a test run can hold: the cell's own configuration and
#: traffic files with 4 KiB cells and a handful of small keys
TINY = {
    "ockg.rs-6-3": {"threads": 3, "stripes_per_key": 2, "verify_keys": 3},
    "ockv-degraded.rs-10-4": {"threads": 3, "stripes_per_key": 2,
                              "preload_keys": 6, "kill_datanodes": 2},
    "ecrd.rs-6-3": {"stripes_per_key": 2, "keys_per_container": [1, 2, 1],
                    "verify_replicas": 3, "settle_s": 0.0},
}


def tiny_cell(name: str):
    """(manifest, cell, config, traffic) of a real cell, cut to 4 KiB
    cells and the TINY traffic."""
    manifest = mf.load()
    cell = mf.cell(manifest, name)
    config = copy.deepcopy(mf.config_of(manifest, cell))
    s = config["scheme"]
    s["cell"], s["bpc"] = 4096, 4096
    config["replication"] = f"rs-{s['k']}-{s['p']}-4096"
    traffic = {**mf.traffic_of(cell), **TINY[name]}
    return manifest, cell, config, traffic


def run_cell(tmp_path, name: str, seed: int = 7, seconds: float = 1.0,
             control: str = "") -> dict:
    """Drive the rest of a run (everything after the look for a chip and
    the launcher) against an in-process cluster; the result line's dict,
    round-tripped through JSON as a driver would read it."""
    import benchmarks.run as bench_run

    manifest, cell, config, traffic = tiny_cell(name)
    cluster = MiniCluster(tmp_path, config["cluster"]["datanodes"])
    args = argparse.Namespace(workload=name, seed=seed, seconds=seconds,
                              trace=0, rehearse=True, control=control,
                              dump_trace="")
    try:
        return json.loads(json.dumps(bench_run.measure(
            args, manifest, cluster, cell, config, traffic)))
    finally:
        cluster.close()


def processes_mentioning(needle: str) -> list[str]:
    out = []
    for pid in os.listdir("/proc"):
        if pid.isdigit() and int(pid) != os.getpid():
            try:
                with open(f"/proc/{pid}/cmdline", "rb") as f:
                    cmd = f.read().decode(errors="replace")
            except OSError:
                continue
            if needle in cmd:
                out.append(cmd.replace("\0", " "))
    return out
