#!/usr/bin/env python3
"""chip_smoke.py: the served EC path, end to end, on one TPU host.

What it drives (ISSUE 21; upstream's default EC deployment, unshrunk in
shape): `rs-6-3-1024k` — k=6, p=3, 1 MiB cells, CRC32C per 16 KiB,
16 MiB blocks — on one scm-om and 9 datanode processes started by the
normal launcher (`python -m ozone_tpu.tools cluster`, which pins its
daemons to the CPU), then, one chip-owning client process at a time:

  kernels   every jitted program of the codec at production shapes
            (B=8 and B=128), bit-for-bit against the numpy coder and
            utils/checksum; the Pallas kernel compiled non-interpret
  ockg      40 x 32 MiB keys, 8 threads, --validate: 1.25 GiB of user
            data through the shared codec service's fused encode+CRC
  ockv      every key read back and compared
  ecrd      2 rounds of ECReconstructionCoordinator over the real wire
            (64 MiB keys), each rebuilt replica read straight off its
            target datanode and compared, bytes and stored CRCs
  degraded  one datanode killed, every key read back and compared: the
            reader decodes the missing unit on the chip

It exits 0 only if every phase passed and every phase that should have
used the chip reports platform tpu, fused backend jax and a non-zero
dispatch count. It has no CPU mode: where JAX finds no TPU it says so
and exits non-zero before booting anything.

This process never imports JAX — a parent that has touched JAX holds
the chip and its children cannot get it. Everything that needs the chip
is a child, and they run strictly one at a time.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
MIB = 2**20

REPLICATION = "rs-6-3-1024k"
K, P, CELL, BPC = 6, 3, MIB, 16 * 1024
DATANODES = 9
KEYS, KEY_BYTES, THREADS = 40, 32 * MIB, 8
ECRD_ROUNDS, ECRD_BYTES = 2, 64 * MIB
#: B=8 is the writer's stripe_batch (client/ec_writer.py), B=128 the
#: bulk width the headline kernel numbers were taken at
KERNEL_BATCHES = (8, 128)
#: one full scrubber dispatch: 64 MiB of 16 KiB slices
SCRUB_SLICES = 4096
SEED = 21
PLATFORM = "tpu"


class SmokeFailure(Exception):
    """A phase did not pass; the message says which and why."""


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


# ---------------------------------------------------------------- kernels
def check_kernels(batches=KERNEL_BATCHES, cell=CELL, pallas=True) -> dict:
    """Runs in the FIRST chip-owning child (`--kernels`): compile every
    jitted codec program at production shapes and compare each output
    bit-for-bit with a reference that shares no code with it — the numpy
    coder for parity, utils/checksum for CRCs, and the original bytes
    for everything a decode recovers."""
    import numpy as np

    from ozone_tpu.codec import crc_device, fused
    from ozone_tpu.codec.api import CoderOptions
    from ozone_tpu.codec.numpy_coder import NumpyRSEncoder
    from ozone_tpu.utils.checksum import ChecksumType, crc32c
    from ozone_tpu.utils.compile_cache import compile_counts, count_compiles

    count_compiles()

    opts = CoderOptions(K, P, "rs", cell_size=cell)
    spec = fused.FusedSpec(opts, ChecksumType.CRC32C, BPC)
    rng = np.random.default_rng(SEED)
    checked: list[str] = []

    def same(name: str, got, want: np.ndarray) -> None:
        got = np.asarray(got)
        if got.shape != want.shape or not np.array_equal(got, want):
            raise SmokeFailure(f"kernel {name} differs from the reference")
        checked.append(name)

    def ref_crcs(units: np.ndarray) -> np.ndarray:
        rows = np.ascontiguousarray(units).reshape(-1, BPC)
        flat = np.fromiter((crc32c(r) for r in rows), np.uint32, len(rows))
        return flat.reshape(*units.shape[:-1], -1)

    for b in batches:
        data = rng.integers(0, 256, (b, K, cell), dtype=np.uint8)
        parity = NumpyRSEncoder(opts).encode(data)
        units = np.concatenate([data, parity], axis=1)
        crcs = ref_crcs(units)

        got_parity, got_crcs = fused.make_fused_encoder(spec)(data)
        same(f"encode B={b}", got_parity, parity)
        same(f"encode crc B={b}", got_crcs, crcs)

        for erased in ([1], [1, K + 1]):
            valid = [u for u in range(K + P) if u not in erased][:K]
            rec, rec_crcs = fused.make_fused_decoder(
                spec, valid, erased)(units[:, valid])
            same(f"decode e={len(erased)} B={b}", rec, units[:, erased])
            same(f"decode crc e={len(erased)} B={b}", rec_crcs,
                 crcs[:, erased])

        # XOR(1) group with unit `lost` replaced by the XOR parity in,
        # the lost unit + the RS parity of the full group out
        lost = 2
        group = data.copy()
        group[:, lost] = np.bitwise_xor.reduce(data, axis=1)
        out, group_crcs, out_crcs = fused.make_fused_reencoder(
            spec, lost)(group)
        same(f"reencode B={b}", out,
             np.concatenate([data[:, lost:lost + 1], parity], axis=1))
        same(f"reencode crc B={b}", fused.reencode_layout_crcs(
            np.asarray(group_crcs), np.asarray(out_crcs), lost), crcs)

        if pallas and b == batches[0]:
            from ozone_tpu.codec.pallas_kernel import (
                make_pallas_fused_encoder,
            )

            pl_parity, pl_crcs = make_pallas_fused_encoder(spec)(data)
            same(f"pallas encode B={b}", pl_parity, np.asarray(got_parity))
            same(f"pallas encode crc B={b}", pl_crcs, np.asarray(got_crcs))

    # the scrubber's program, at one full scrub dispatch
    slices = units.reshape(-1, BPC)[:SCRUB_SLICES]
    same(f"crc_fn [{len(slices)}, {BPC}]",
         crc_device.make_crc_fn(BPC)(slices), ref_crcs(slices))
    return {"checked": checked,
            "device": {**fused.backend_report(), **compile_counts()}}


def kernels_child() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != PLATFORM:
        print(f"chip_smoke: no TPU found: JAX reports platform "
              f"{dev.platform!r} ({dev.device_kind}); this script has no "
              f"CPU mode", file=sys.stderr)
        return 3
    print(json.dumps(check_kernels()))
    return 0


# ------------------------------------------------------------- processes
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


class Smoke:
    def __init__(self, work: Path):
        self.work = work
        self.env = dict(os.environ, PYTHONPATH=str(HERE),
                        PYTHONUNBUFFERED="1")
        # a cache directory given from outside keeps JAX's 1 s floor
        # (utils/compile_cache.py touches nothing then); this script
        # counts entries across runs, so its children cache everything
        self.env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                            "0")
        self.launcher: subprocess.Popen | None = None
        self.daemons: dict[int, list[str]] = {}
        self.om = ""
        self.totals = {"compiles": 0, "cache_hits": 0, "cache_writes": 0}
        self.device: dict = {}

    # one child at a time, its stdout parsed as the phase's JSON summary
    def child(self, phase: str, argv: list[str], timeout: float,
              pinned: bool = False) -> dict:
        env = dict(self.env, JAX_PLATFORMS="cpu") if pinned else self.env
        t0 = time.time()
        with open(self.work / f"{phase}.err", "w") as err:
            proc = subprocess.Popen(
                [sys.executable, *argv], cwd=HERE, env=env, text=True,
                stdout=subprocess.PIPE, stderr=err)
            try:
                out, _ = proc.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                raise SmokeFailure(
                    f"{phase}: no result after {timeout:.0f}s") from None
            finally:  # also on the way out of a SIGTERM
                if proc.poll() is None:
                    proc.kill()
                    proc.wait()
        tail = (self.work / f"{phase}.err").read_text()[-3000:]
        if proc.returncode != 0:
            raise SmokeFailure(
                f"{phase}: exit code {proc.returncode}\n{out[-2000:]}\n{tail}")
        try:
            summary = json.loads(out[out.index("{"):])
        except ValueError:
            raise SmokeFailure(
                f"{phase}: no JSON summary in its output\n{out[-2000:]}"
            ) from None
        summary["wall_s"] = round(time.time() - t0, 1)
        return summary

    def chip_phase(self, phase: str, argv: list[str], timeout: float,
                   codec_work: bool) -> dict:
        """A child that owns the chip: whatever else it reports, it ran
        on the TPU — and where the phase has codec work, its fused
        passes were the jitted programs and dispatches were launched."""
        s = self.child(phase, argv, timeout)
        dev = s.get("device") or {}
        if s.get("failures", 0) != 0:
            raise SmokeFailure(
                f"{phase}: {s['failures']} failed ops "
                f"({s.get('first_error', 'no error recorded')})")
        if dev.get("platform") != PLATFORM:
            raise SmokeFailure(
                f"{phase}: ran on platform {dev.get('platform')!r}, "
                f"not {PLATFORM!r}")
        mesh = dev.get("mesh") or {}
        dispatches = dev.get("dispatches", 0) + mesh.get("dispatches", 0)
        if codec_work and dev.get("fused_backend") != "jax":
            raise SmokeFailure(
                f"{phase}: fused backend {dev.get('fused_backend')!r}, "
                f"not 'jax'")
        if codec_work and dispatches <= 0:
            raise SmokeFailure(f"{phase}: no dispatch reached the chip")
        for key in self.totals:
            self.totals[key] += dev.get(key, 0)
        self.device = {"platform": dev["platform"],
                       "kind": dev["device_kind"],
                       "count": dev["device_count"]}
        say(f"{phase}: ok in {s['wall_s']}s on {dev['device_count']} x "
            f"{dev['device_kind']}; fused={dev.get('fused_backend')} "
            f"dispatches={dispatches} compiles={dev.get('compiles')} "
            f"cache_hits={dev.get('cache_hits')} "
            f"cache_writes={dev.get('cache_writes')}")
        return s

    def freon(self, phase: str, *args: str, timeout: float = 600,
              codec_work: bool = True) -> dict:
        return self.chip_phase(
            phase, ["-m", "ozone_tpu.tools", "freon", *args,
                    "--om", self.om], timeout, codec_work)

    # ------------------------------------------------------------ cluster
    def boot(self, datanodes: int) -> None:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        self.om = f"127.0.0.1:{port}"
        root = self.work / "cluster"
        # the launcher runs with THIS environment, not a pinned one: that
        # it pins the daemons it spawns is part of what is being proved
        # (an unpinned dn0 would take the chip and every client after it
        # would fail)
        with open(self.work / "launcher.log", "w") as log:
            self.launcher = subprocess.Popen(
                [sys.executable, "-m", "ozone_tpu.tools", "cluster",
                 "--datanodes", str(datanodes), "--port", str(port),
                 "--root", str(root)],
                cwd=HERE, env=self.env, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True)
        deadline = time.time() + 180
        while "cluster up:" not in (self.work / "launcher.log").read_text():
            self.daemons.update(_children(self.launcher.pid))
            if self.launcher.poll() is not None or time.time() > deadline:
                raise SmokeFailure(
                    "cluster did not come up:\n"
                    + (self.work / "launcher.log").read_text()[-2000:]
                    + self._daemon_logs())
            time.sleep(0.5)
        self.daemons.update(_children(self.launcher.pid))
        if len(self.daemons) != datanodes + 1:
            raise SmokeFailure(
                f"expected {datanodes + 1} daemons under the launcher, "
                f"found {len(self.daemons)}")
        # a datanode without the native datapath serves the slow
        # transport and says nothing: here that is an error
        for i in range(datanodes):
            text = (root / f"dn{i}.log").read_text()
            if "native datapath listening" not in text:
                raise SmokeFailure(
                    f"dn{i} has no native datapath:\n{text[-1500:]}")
        say(f"cluster up: om={self.om}, {datanodes} datanodes, "
            f"pids {sorted(self.daemons)}")

    def _daemon_logs(self) -> str:
        out = []
        for f in sorted((self.work / "cluster").glob("*.log")):
            out.append(f"\n--- {f.name}\n{f.read_text()[-800:]}")
        return "".join(out)

    def kill_datanode_holding(self, key: str) -> None:
        """SIGKILL the datanode that holds DATA unit 0 of `key`'s first
        block group, so reading the key back has to decode."""
        info = self.child(
            "keyinfo", ["-m", "ozone_tpu.tools", "sh", "key", "info", key,
                        "--om", self.om], 120, pinned=True)
        dn_id = info["block_groups"][0]["nodes"][0]
        pid = next(p for p, argv in self.daemons.items()
                   if argv[-2:] == ["--id", dn_id])
        os.kill(pid, signal.SIGKILL)
        deadline = time.time() + 30
        while _alive(pid):
            if time.time() > deadline:
                raise SmokeFailure(f"{dn_id} (pid {pid}) survived SIGKILL")
            time.sleep(0.1)
        say(f"killed {dn_id} (pid {pid}), holder of {key} unit 0")

    def teardown(self) -> None:
        """Stop every process this script started, by pid."""
        if self.launcher is None:
            return
        if self.launcher.poll() is None:
            self.launcher.send_signal(signal.SIGTERM)  # reaps its daemons
            try:
                self.launcher.wait(timeout=45)
            except subprocess.TimeoutExpired:
                self.launcher.kill()
                self.launcher.wait()
        for pid in self.daemons:
            if _alive(pid):
                os.kill(pid, signal.SIGKILL)
        self.launcher = None


def cache_entries(cache_dir: str) -> int:
    try:
        return sum(1 for f in os.listdir(cache_dir) if f.endswith("-cache"))
    except OSError:
        return 0


def run(smoke: Smoke, cache_dir: str, datanodes: int) -> None:
    entries0 = cache_entries(cache_dir)

    # first: the only phase that needs no cluster. Where there is no TPU
    # it fails here, within seconds, before anything is booted.
    kernels = smoke.chip_phase(
        "kernels", [str(HERE / "chip_smoke.py"), "--kernels"], 900,
        codec_work=False)
    if kernels["device"]["fused_backend"] != "jax":
        raise SmokeFailure(
            f"kernels: the factories handed out "
            f"{kernels['device']['fused_backend']!r}, not the jitted "
            f"programs")
    say(f"kernels: {len(kernels['checked'])} outputs match the "
        f"reference: {', '.join(kernels['checked'])}")

    smoke.boot(datanodes)
    size, n, t = str(KEY_BYTES), str(KEYS), str(THREADS)
    ockg = smoke.freon("ockg", "ockg", "-n", n, "-s", size, "-t", t,
                       "--replication", REPLICATION, "--validate")
    smoke.freon("ockv", "ockv", "-n", n, "-s", size, "-t", t,
                codec_work=False)  # a healthy read decodes nothing
    ecrd = smoke.freon("ecrd", "ecrd", "-n", str(ECRD_ROUNDS),
                       "-s", str(ECRD_BYTES), "--replication", REPLICATION)
    smoke.kill_datanode_holding("/freon-vol/freon-bucket/key-0")
    degraded = smoke.freon("degraded", "ockv", "-n", n, "-s", size,
                           "-t", t)

    # (failures == 0 was required of every phase above, so every key
    # and every round completed.) A repair that rebuilt nothing must not
    # pass on the dispatches of the drill's own writes: the coordinator
    # itself dispatched, and at least every full stripe's cell of the
    # lost unit was read off the target and found equal.
    floor = ECRD_ROUNDS * (ECRD_BYTES // (K * CELL)) * CELL
    if not (ecrd["repair_dispatches"] > 0
            and ecrd["bytes_reconstructed"] >= ecrd["bytes_verified"]
            >= floor):
        raise SmokeFailure(
            f"ecrd: coordinator dispatches {ecrd['repair_dispatches']}, "
            f"bytes reconstructed {ecrd['bytes_reconstructed']}, "
            f"verified {ecrd['bytes_verified']}, wanted >= {floor}")
    mesh = ecrd["device"].get("mesh")
    count = smoke.device["count"]
    if count > 1:
        # several chips: the repair's decode batches must have been
        # SPMD-sharded over all of them, not run on device 0 or the host
        want = {"devices": count, "output_shards": count,
                "programs_host_twin": 0}
        got = {k: (mesh or {}).get(k) for k in want}
        # (in the ecrd process only the coordinator holds the executor)
        if got != want or mesh["stripes_dispatched"] <= 0:
            raise SmokeFailure(
                f"ecrd: mesh executor reports {mesh}, wanted {want} and "
                f"stripes_dispatched > 0")

    fill = ockg["device"]["stripes_dispatched"] / max(
        1, ockg["device"]["slots_dispatched"])
    say(f"bytes written {KEYS * KEY_BYTES} (validated on write), read "
        f"{KEYS * KEY_BYTES} healthy + {KEYS * KEY_BYTES} degraded, "
        f"reconstructed {ecrd['bytes_reconstructed']} "
        f"({ecrd['bytes_verified']} of them the drill keys' own unit, "
        f"compared on the target) in {ECRD_ROUNDS} rounds "
        f"{ecrd['times_s']}s with {ecrd['repair_dispatches']} coordinator "
        f"dispatches"
        + (f", mesh {mesh}" if mesh else ""))
    say(f"ockg dispatches {ockg['device']['dispatches']} at mean fill "
        f"{fill:.2f}; degraded-read dispatches "
        f"{degraded['device']['dispatches']}")
    entries1 = cache_entries(cache_dir)
    say(f"compile cache {cache_dir}: {entries0} entries before, "
        f"{entries1} after ({entries1 - entries0} new); children built "
        f"{smoke.totals['compiles']} programs, "
        f"{smoke.totals['cache_hits']} from the cache, "
        f"{smoke.totals['cache_writes']} written to it")


def main(argv: list[str]) -> int:
    if argv == ["--kernels"]:
        return kernels_child()
    if argv:
        print("usage: python chip_smoke.py", file=sys.stderr)
        return 2
    # outside a checkout there is nothing to drive: this import fails
    from ozone_tpu.utils.compile_cache import ensure_compile_cache

    cache_dir = ensure_compile_cache()  # children inherit it by environment
    work = Path(tempfile.mkdtemp(prefix="chip_smoke_"))
    smoke = Smoke(work)

    def stop(signum, _frame):
        raise SystemExit(128 + signum)

    signal.signal(signal.SIGTERM, stop)
    t0 = time.time()
    try:
        run(smoke, cache_dir, DATANODES)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        print(f"chip_smoke: logs kept in {work}", file=sys.stderr)
        return 1
    finally:
        smoke.teardown()
    say(f"all phases passed in {time.time() - t0:.0f}s")
    print(json.dumps({"ok": True, "device": smoke.device}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
