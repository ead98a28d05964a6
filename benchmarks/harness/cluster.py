"""Boot and stop the deployment a cell runs against: one scm-om and N
datanode processes started by the program's own launcher
(`python -m ozone_tpu.tools cluster`), which pins its children to the
CPU. The launcher, the pid bookkeeping and the "native datapath
listening" check are copied from `chip_smoke.py` (Smoke.boot /
_children / teardown), which stays what it is.

Imports no JAX: the cluster is started before this process touches it.
"""

from __future__ import annotations

import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
GIB = 2 ** 30


class ClusterFailure(Exception):
    """The deployment did not come up, or lost a daemon it should have."""


def _alive(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except OSError:
        return False


def _children(pid: int) -> dict[int, list[str]]:
    """pid -> argv of every live child of `pid`."""
    out = {}
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
            if ppid == pid:
                with open(f"/proc/{entry}/cmdline", "rb") as f:
                    out[int(entry)] = f.read().decode().split("\0")[:-1]
        except OSError:
            continue  # exited while we looked
    return out


class Cluster:
    def __init__(self, datanodes: int, need_free_gib: float):
        self.datanodes = datanodes
        self.work = Path(tempfile.mkdtemp(prefix="ozbench_"))
        free = shutil.disk_usage(self.work).free / GIB
        if free < need_free_gib:
            shutil.rmtree(self.work, ignore_errors=True)
            raise ClusterFailure(
                f"{free:.1f} GiB free under {self.work.parent}, this cell "
                f"needs {need_free_gib:.1f} GiB for the cluster's root")
        self.root = self.work / "cluster"
        self.launcher: subprocess.Popen | None = None
        self.daemons: dict[int, list[str]] = {}
        self.killed: set[int] = set()
        self.om = ""

    def start(self) -> None:
        """Start the launcher and return at once; wait_up() blocks."""
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        self.om = f"127.0.0.1:{port}"
        # the launcher gets THIS environment, chip and all: that it pins
        # the daemons it spawns to the CPU is the program's own rule
        env = dict(os.environ, PYTHONPATH=str(ROOT), PYTHONUNBUFFERED="1")
        env.pop("BENCH_RUN", None)
        with open(self.work / "launcher.log", "w") as log:
            self.launcher = subprocess.Popen(
                [sys.executable, "-m", "ozone_tpu.tools", "cluster",
                 "--datanodes", str(self.datanodes), "--port", str(port),
                 "--root", str(self.root)],
                cwd=ROOT, env=env, stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True)

    def wait_up(self, timeout: float = 180.0) -> None:
        log = self.work / "launcher.log"
        deadline = time.monotonic() + timeout
        while "cluster up:" not in log.read_text():
            self.daemons.update(_children(self.launcher.pid))
            if self.launcher.poll() is not None \
                    or time.monotonic() > deadline:
                raise ClusterFailure(
                    "cluster did not come up:\n" + log.read_text()[-2000:]
                    + self.daemon_logs())
            time.sleep(0.2)
        self.daemons.update(_children(self.launcher.pid))
        if len(self.daemons) != self.datanodes + 1:
            raise ClusterFailure(
                f"expected {self.datanodes + 1} daemons under the "
                f"launcher, found {len(self.daemons)}")
        # a datanode without the native datapath serves the slow
        # transport and says nothing: here that is an error
        for i in range(self.datanodes):
            text = (self.root / f"dn{i}.log").read_text()
            if "native datapath listening" not in text:
                raise ClusterFailure(
                    f"dn{i} has no native datapath:\n{text[-1500:]}")

    def connect(self):
        """(OzoneClient, GrpcScmClient): imports the program, and JAX."""
        from benchmarks.harness import program

        return program.connect(self.om)

    def daemon_logs(self, tail: int = 800) -> str:
        out = []
        for f in sorted(self.root.glob("*.log")):
            out.append(f"\n--- {f.name}\n{f.read_text()[-tail:]}")
        return "".join(out)

    def grep_logs(self, needle: str) -> dict[str, list]:
        """log name -> [lines that contain `needle`, the first of them]:
        the background work the daemons did, for PERF.md's account of a
        window."""
        out = {}
        for f in sorted(self.root.glob("*.log")):
            hits = [line for line in
                    f.read_text(errors="replace").splitlines()
                    if needle in line]
            if hits:
                out[f.name] = [len(hits), hits[0][:200]]
        return out

    def kill_datanode(self, dn_id: str) -> None:
        pid = next(p for p, argv in self.daemons.items()
                   if argv[-2:] == ["--id", dn_id])
        os.kill(pid, signal.SIGKILL)
        deadline = time.monotonic() + 30
        while _alive(pid):
            if time.monotonic() > deadline:
                raise ClusterFailure(f"{dn_id} (pid {pid}) survived SIGKILL")
            time.sleep(0.05)
        self.killed.add(pid)

    def check_alive(self) -> None:
        dead = [argv[-1] for pid, argv in self.daemons.items()
                if pid not in self.killed and not _alive(pid)]
        if dead:
            raise ClusterFailure(f"daemons died during the run: {dead}")

    def _stragglers(self) -> set[int]:
        """Every live process whose argv names this cluster's root: the
        daemons, whoever their parent is by now (a launcher killed while
        still booting orphans what it had spawned)."""
        out = set()
        needle = str(self.root).encode()
        for entry in os.listdir("/proc"):
            if not entry.isdigit() or int(entry) == os.getpid():
                continue
            try:
                with open(f"/proc/{entry}/cmdline", "rb") as f:
                    if needle in f.read() and _alive(int(entry)):
                        out.add(int(entry))
            except OSError:
                continue
        return out

    def teardown(self) -> None:
        """Stop every process started here, by pid, and remove the
        cluster's root. Safe to call twice and from a signal's unwind."""
        if self.launcher is not None:
            # the cluster is thrown away with its root: its daemons are
            # killed outright, not asked to flush what nobody will read
            # (a clean SIGTERM of 10-15 Python daemons costs every run
            # some 25 s)
            self.daemons.update(_children(self.launcher.pid))
            for pid in self.daemons:
                if _alive(pid):
                    os.kill(pid, signal.SIGKILL)
            if self.launcher.poll() is None:
                self.launcher.send_signal(signal.SIGTERM)
                try:
                    self.launcher.wait(timeout=20)
                except subprocess.TimeoutExpired:
                    self.launcher.kill()
                    self.launcher.wait()
            deadline = time.monotonic() + 15
            while True:
                left = self._stragglers() | {
                    p for p in self.daemons if _alive(p)}
                if not left or time.monotonic() > deadline:
                    break
                for pid in left:
                    try:
                        os.kill(pid, signal.SIGKILL)
                    except ProcessLookupError:
                        pass
                time.sleep(0.05)
            self.launcher = None
        shutil.rmtree(self.work, ignore_errors=True)
