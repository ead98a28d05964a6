"""Acceptance suite: real daemon subprocesses driven through the CLI.

Analog of the reference's robot-framework smoketests run against
docker-compose clusters (hadoop-ozone/dist smoketest/ + compose/): here
the scm-om and datanode daemons run as actual OS processes and every
interaction goes through the public `ozone-tpu` CLI, validating the
process entry points end-to-end (basic + EC suite).
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent

#: every test here forks daemon process trees whose jax imports cost
#: seconds each; overlapping another subprocess-heavy suite on a
#: one-core rig starves the spawn deadlines (CHANGES.md PR 2) — the
#: serial marker takes a cross-process lock (conftest) so at most one
#: such suite runs at a time
pytestmark = pytest.mark.serial


def _budget(base_s: float) -> float:
    """Load-aware deadline: scale a spawn/poll allowance by how
    oversubscribed the CPU is. A fixed constant is wrong in both
    directions — too tight on a loaded one-core rig (where forking a
    jax-importing child takes many times longer) and needlessly long on
    an idle machine. Capped at 4x so a pathological load average can't
    turn a real hang into an hour-long wait."""
    try:
        load = os.getloadavg()[0]
    except OSError:  # platform without getloadavg
        return base_s
    scale = load / max(1, os.cpu_count() or 1)
    return base_s * min(4.0, max(1.0, scale))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _cli(args: list[str], check=True, timeout=60) -> subprocess.CompletedProcess:
    env = dict(os.environ, PYTHONPATH=str(REPO), JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "ozone_tpu.tools", *args],
        capture_output=True, text=True, timeout=_budget(timeout),
        check=check, cwd=str(REPO), env=env,
    )


@pytest.fixture(scope="module")
def live_cluster(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("acc")
    port = _free_port()
    env = dict(os.environ, PYTHONPATH=str(REPO), JAX_PLATFORMS="cpu")
    procs = []
    with open(tmp / "meta.log", "w") as meta_log:
        meta = subprocess.Popen(
            [sys.executable, "-m", "ozone_tpu.tools", "scm-om",
             "--db", str(tmp / "om.db"), "--port", str(port)],
            stdout=meta_log, stderr=subprocess.STDOUT, text=True,
            cwd=str(REPO), env=env,
        )  # the child holds its own duplicated descriptor
    procs.append(meta)
    om = f"127.0.0.1:{port}"
    # wait for the metadata server (generous: each status poll is a
    # full CLI process whose jax import costs seconds under suite load;
    # the loop exits as soon as the server answers)
    t0 = time.time()
    # budget re-derived per poll: the spawned cluster itself
    # drives the load average up mid-test
    while time.time() - t0 < _budget(90):
        try:
            _cli(["admin", "status", "--om", om], timeout=10)
            break
        except (subprocess.CalledProcessError, subprocess.TimeoutExpired):
            time.sleep(0.5)
    else:
        meta.kill()
        pytest.fail("scm-om daemon did not come up")
    for i in range(5):
        p = subprocess.Popen(
            [sys.executable, "-m", "ozone_tpu.tools", "datanode",
             "--root", str(tmp / f"dn{i}"), "--scm", om, "--id", f"dn{i}"],
            stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT, text=True,
            cwd=str(REPO), env=env,
        )
        procs.append(p)
    # wait for registrations (same contention headroom as above)
    t0 = time.time()
    # budget re-derived per poll: the spawned cluster itself
    # drives the load average up mid-test
    while time.time() - t0 < _budget(90):
        out = _cli(["admin", "datanode", "--om", om]).stdout
        if len(json.loads(out)) == 5:
            break
        time.sleep(0.5)
    else:
        pytest.fail("datanodes did not register")
    yield om, tmp
    for p in procs:
        p.send_signal(signal.SIGTERM)
    for p in procs:
        try:
            p.wait(timeout=5)
        except subprocess.TimeoutExpired:
            p.kill()


def test_smoke_basic_namespace(live_cluster):
    om, tmp = live_cluster
    _cli(["sh", "volume", "create", "/vol1", "--om", om])
    _cli(["sh", "bucket", "create", "/vol1/b1", "--om", om,
          "--replication", "rs-3-2-4096"])
    out = _cli(["sh", "bucket", "list", "/vol1", "--om", om]).stdout
    assert [b["name"] for b in json.loads(out)] == ["b1"]


def test_smoke_ec_key_roundtrip(live_cluster):
    om, tmp = live_cluster
    _cli(["sh", "volume", "create", "/vol2", "--om", om])
    _cli(["sh", "bucket", "create", "/vol2/ec", "--om", om,
          "--replication", "rs-3-2-4096"])
    payload = bytes(np.random.default_rng(0).integers(0, 256, 100_000,
                                                      dtype=np.uint8))
    src = tmp / "in.bin"
    src.write_bytes(payload)
    _cli(["sh", "key", "put", "/vol2/ec/key1", str(src), "--om", om])
    dst = tmp / "out.bin"
    _cli(["sh", "key", "get", "/vol2/ec/key1", str(dst), "--om", om])
    assert dst.read_bytes() == payload
    info = json.loads(
        _cli(["sh", "key", "info", "/vol2/ec/key1", "--om", om]).stdout
    )
    assert info["size"] == 100_000
    # replica verification over the wire
    rep = _cli(["debug", "verify-replicas", "/vol2/ec/key1", "--om", om])
    statuses = {r["status"] for r in json.loads(rep.stdout)}
    assert statuses == {"ok"}


def test_smoke_freon_ockg(live_cluster):
    om, tmp = live_cluster
    out = _cli(["freon", "ockg", "-n", "10", "-s", "4096", "-t", "2",
                "--om", om, "--replication", "rs-3-2-4096"],
               timeout=120).stdout
    rep = json.loads(out)
    assert rep["ops"] == 10 and rep["failures"] == 0


def test_smoke_data_lifecycle_verbs(live_cluster):
    """The session's lifecycle surface end-to-end through the CLI:
    quota, snapshots (+.snapshot reads), composite checksum, bucket
    links, hsync freon, audit parser (robot ec/ + admincli parity)."""
    om, tmp = live_cluster
    _cli(["sh", "volume", "create", "/lc", "--om", om])
    _cli(["sh", "bucket", "create", "/lc/b", "--om", om,
          "--replication", "rs-3-2-4096"])
    payload = bytes(np.random.default_rng(7).integers(0, 256, 30_000,
                                                      dtype=np.uint8))
    src = tmp / "lc.bin"
    src.write_bytes(payload)

    # quota: set, exceed, inspect
    _cli(["sh", "bucket", "setquota", "/lc/b", "--om", om,
          "--quota", "40KB"])
    _cli(["sh", "key", "put", "/lc/b/doc", str(src), "--om", om])
    over = _cli(["sh", "key", "put", "/lc/b/doc2", str(src), "--om", om],
                check=False)
    assert over.returncode != 0 and "QUOTA_EXCEEDED" in over.stderr
    info = json.loads(
        _cli(["sh", "bucket", "info", "/lc/b", "--om", om]).stdout)
    assert info["used_bytes"] == 30_000

    # composite checksum equals a local CRC32C of the payload
    cs = json.loads(
        _cli(["sh", "key", "checksum", "/lc/b/doc", "--om", om]).stdout)
    from ozone_tpu.utils.checksum import crc32c

    assert int(cs["checksum"], 16) == crc32c(
        np.frombuffer(payload, np.uint8))

    # snapshot + .snapshot read + diff
    _cli(["sh", "snapshot", "create", "/lc/b", "--om", om,
          "--name", "s1"])
    _cli(["sh", "key", "delete", "/lc/b/doc", "--om", om])
    diff = json.loads(_cli(["sh", "snapshot", "diff", "/lc/b", "--om",
                            om, "--name", "s1"]).stdout)
    assert diff["deleted"] == ["doc"]
    snap_out = tmp / "snap.bin"
    _cli(["sh", "key", "get", "/lc/b/.snapshot/s1/doc", str(snap_out),
          "--om", om])
    assert snap_out.read_bytes() == payload
    _cli(["sh", "snapshot", "delete", "/lc/b", "--om", om,
          "--name", "s1"])

    # bucket link: write through the alias, read from the source
    _cli(["sh", "volume", "create", "/lk", "--om", om])
    _cli(["sh", "bucket", "link", "/lc/b", "--to", "/lk/alias",
          "--om", om])
    _cli(["sh", "bucket", "setquota", "/lc/b", "--om", om,
          "--quota", "clear"])
    _cli(["sh", "key", "put", "/lk/alias/via-link", str(src),
          "--om", om])
    got = tmp / "via.bin"
    _cli(["sh", "key", "get", "/lc/b/via-link", str(got), "--om", om])
    assert got.read_bytes() == payload

    # hsync generator (RATIS replication)
    rep = json.loads(_cli(["freon", "hsg", "-n", "4", "-s", "4096",
                           "--om", om], timeout=120).stdout)
    assert rep["failures"] == 0

    # audit parser over the REAL daemon log: this suite's own verbs
    # must appear in the aggregation
    top = json.loads(
        _cli(["audit", "top", str(tmp / "meta.log")]).stdout)
    actions = {row["action"] for row in top}
    assert {"CreateVolume", "CommitKey", "CreateSnapshot"} <= actions


def test_ha_cluster_subprocesses(tmp_path):
    """HA acceptance: three scm-om OS processes on one raft ring, five
    datanode processes, CLI writes through the failover address list,
    SIGKILL the leader process, writes continue, old data intact."""
    from ozone_tpu.testing.minicluster import free_ports

    env = dict(os.environ, PYTHONPATH=str(REPO), JAX_PLATFORMS="cpu")
    ports = free_ports(3)
    peers = {f"m{i}": f"127.0.0.1:{ports[i]}" for i in range(3)}
    peer_flags = []
    for mid, addr in peers.items():
        peer_flags += ["--peer", f"{mid}={addr}"]
    procs: dict[str, subprocess.Popen] = {}

    def start_meta(mid: str) -> None:
        procs[mid] = subprocess.Popen(
            [sys.executable, "-m", "ozone_tpu.tools", "scm-om",
             "--db", str(tmp_path / mid / "om.db"),
             "--port", peers[mid].rsplit(":", 1)[1],
             "--ha-id", mid, *peer_flags],
            stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT, text=True,
            cwd=str(REPO), env=env,
        )

    oms = ",".join(peers.values())
    dn_procs = []
    try:
        for mid in peers:
            start_meta(mid)
        t0 = time.time()
        # budget re-derived per poll: the spawned cluster itself
        # drives the load average up mid-test
        while time.time() - t0 < _budget(90):
            try:
                _cli(["admin", "status", "--om", oms], timeout=10)
                break
            except (subprocess.CalledProcessError,
                    subprocess.TimeoutExpired):
                time.sleep(0.5)
        else:
            pytest.fail("HA ring did not come up")
        for i in range(5):
            p = subprocess.Popen(
                [sys.executable, "-m", "ozone_tpu.tools", "datanode",
                 "--root", str(tmp_path / f"dn{i}"), "--scm", oms,
                 "--id", f"dn{i}"],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
                text=True, cwd=str(REPO), env=env,
            )
            dn_procs.append(p)
        t0 = time.time()
        # budget re-derived per poll: the spawned cluster itself
        # drives the load average up mid-test
        while time.time() - t0 < _budget(90):
            try:
                out = _cli(["admin", "status", "--om", oms],
                           timeout=20).stdout
            except (subprocess.CalledProcessError,
                    subprocess.TimeoutExpired):
                time.sleep(0.5)
                continue
            if out.count("HEALTHY") >= 5 and '"safemode": false' in out:
                break
            time.sleep(0.5)

        payload = np.random.default_rng(3).integers(
            0, 256, 120_000, dtype=np.uint8).tobytes()
        src = tmp_path / "payload.bin"
        src.write_bytes(payload)
        _cli(["sh", "volume", "create", "/v", "--om", oms])
        _cli(["sh", "bucket", "create", "/v/b", "--om", oms,
              "--replication", "rs-3-2-4096"])
        _cli(["sh", "key", "put", "/v/b/k1", str(src), "--om", oms])

        # find and SIGKILL the leader process: a follower's error names
        # the leader address
        leader_addr = None
        for mid, addr in peers.items():
            r = _cli(["admin", "om", "prepare", "--om", addr],
                     check=False, timeout=15)
            if r.returncode != 0 and "OM_NOT_LEADER" in r.stderr:
                hint = r.stderr.rsplit(":", 1)[-1].strip()
                if hint.isdigit():
                    leader_addr = f"127.0.0.1:{hint}"
                    break
            elif r.returncode == 0:
                leader_addr = addr  # this one IS the leader
                _cli(["admin", "om", "cancelprepare", "--om", addr],
                     timeout=15)
                break
        assert leader_addr, "could not locate the leader"
        leader_id = next(m for m, a in peers.items() if a == leader_addr)
        procs[leader_id].kill()
        procs[leader_id].wait(timeout=10)

        # failover: writes and reads continue against the survivors
        _cli(["sh", "key", "put", "/v/b/k2", str(src), "--om", oms],
             timeout=90)
        for key in ("k1", "k2"):
            dst = tmp_path / f"out_{key}.bin"
            _cli(["sh", "key", "get", f"/v/b/{key}", str(dst),
                  "--om", oms], timeout=90)
            assert dst.read_bytes() == payload, key
    finally:
        for p in dn_procs:
            p.send_signal(signal.SIGTERM)
        for p in procs.values():
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in [*dn_procs, *procs.values()]:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


def test_smoke_round3_verbs(live_cluster):
    """This round's operator surface end-to-end through the CLI:
    delegation tokens, key rewrite/cat/cp, bucket set-replication,
    volume owner update, list-open-files, paged snapshot diff, live
    reconfig, dnsim."""
    om, tmp = live_cluster
    _cli(["sh", "volume", "create", "/r3", "--om", om])
    _cli(["sh", "bucket", "create", "/r3/b", "--om", om,
          "--replication", "RATIS/THREE"])
    payload = bytes(np.random.default_rng(11).integers(
        0, 256, 20_000, dtype=np.uint8))
    src = tmp / "r3.bin"
    src.write_bytes(payload)
    _cli(["sh", "key", "put", "/r3/b/k", str(src), "--om", om])

    # delegation tokens: get -> print -> renew -> cancel -> renew fails
    tok = tmp / "tok.json"
    # renewer must be the CLI's login identity: anonymous remote renew
    # is refused since round 4, and the CLI renews as the login user
    import getpass

    _cli(["sh", "token", "get", "--om", om, "--renewer",
          getpass.getuser(), "--token", str(tok)])
    assert json.loads(tok.read_text())["renewer"] == getpass.getuser()
    _cli(["sh", "token", "renew", "--om", om, "--token", str(tok)])
    _cli(["sh", "token", "cancel", "--om", om, "--token", str(tok)])
    dead = _cli(["sh", "token", "renew", "--om", om,
                 "--token", str(tok)], check=False)
    assert dead.returncode != 0 and "TOKEN_ERROR" in dead.stderr

    # rewrite RATIS -> EC, data intact, cat matches
    _cli(["sh", "key", "rewrite", "/r3/b/k", "--om", om,
          "--replication", "rs-3-2-4096"])
    info = json.loads(
        _cli(["sh", "key", "info", "/r3/b/k", "--om", om]).stdout)
    assert info["replication"] == "rs-3-2-4096"
    # cat streams raw bytes to stdout: run binary-mode
    cat = subprocess.run(
        [sys.executable, "-m", "ozone_tpu.tools", "sh", "key", "cat",
         "/r3/b/k", "--om", om],
        capture_output=True, timeout=60, check=True, cwd=str(REPO),
        env=dict(os.environ, PYTHONPATH=str(REPO), JAX_PLATFORMS="cpu"),
    )
    assert cat.stdout == payload
    out = tmp / "cat.bin"
    _cli(["sh", "key", "get", "/r3/b/k", str(out), "--om", om])
    assert out.read_bytes() == payload

    # cp into a second bucket; destination bucket's replication applies
    _cli(["sh", "bucket", "create", "/r3/b2", "--om", om,
          "--replication", "rs-3-2-4096"])
    _cli(["sh", "key", "cp", "/r3/b/k", "--om", om, "--to", "/r3/b2/k2"])
    got = tmp / "cp.bin"
    _cli(["sh", "key", "get", "/r3/b2/k2", str(got), "--om", om])
    assert got.read_bytes() == payload

    # bucket set-replication + volume owner update
    _cli(["sh", "bucket", "set-replication", "/r3/b", "--om", om,
          "--replication", "rs-3-2-4096"])
    binfo = json.loads(
        _cli(["sh", "bucket", "info", "/r3/b", "--om", om]).stdout)
    assert binfo["replication"] == "rs-3-2-4096"
    _cli(["sh", "volume", "update", "/r3", "--om", om, "--user", "alice"])
    vinfo = json.loads(
        _cli(["sh", "volume", "info", "/r3", "--om", om]).stdout)
    assert vinfo["owner"] == "alice"

    # paged snapshot diff as JSON lines
    _cli(["sh", "snapshot", "create", "/r3/b", "--om", om,
          "--name", "d1"])
    _cli(["sh", "key", "delete", "/r3/b/k", "--om", om])
    _cli(["sh", "snapshot", "create", "/r3/b", "--om", om,
          "--name", "d2"])
    paged = _cli(["sh", "snapshot", "diff", "/r3/b", "--om", om,
                  "--name", "d1", "--to", "d2", "--page-size", "1"])
    lines = [json.loads(line) for line in paged.stdout.splitlines()]
    assert {"op": "DELETE", "key": "k"} in lines

    # list-open-files over gRPC (no sessions open right now)
    lof = json.loads(_cli(["admin", "om", "list-open-files", "/r3/b",
                           "--om", om]).stdout)
    assert lof["open_files"] == []

    # dnsim registers simulated nodes without polluting placement
    rep = json.loads(_cli(["freon", "dnsim", "-n", "4", "--containers",
                           "2", "--duration", "1", "--interval", "0.3",
                           "--om", om], timeout=120).stdout)
    assert rep["failures"] == 0 and rep["datanodes"] == 4
    nodes = json.loads(_cli(["admin", "datanode", "--om", om]).stdout)
    sims = [n for n in nodes if n["dn_id"].startswith("simdn")]
    assert len(sims) == 4
    assert all(n["op_state"] == "IN_MAINTENANCE" for n in sims)


def test_cluster_launcher_supervises_and_tears_down(tmp_path):
    """`ozone-tpu cluster`: the one-command compose-cluster analog
    spawns scm-om + datanodes, serves traffic, and SIGTERM reaps every
    child. Whatever platform the launcher's own environment names, the
    daemons it spawns are pinned to the CPU: N datanodes on one host
    cannot share its chip, which is left to the client."""
    env = dict(os.environ, PYTHONPATH=str(REPO))
    env.pop("JAX_PLATFORMS", None)
    port = _free_port()
    sup = subprocess.Popen(
        [sys.executable, "-m", "ozone_tpu.tools", "cluster",
         "--datanodes", "2", "--port", str(port),
         "--root", str(tmp_path / "cl")],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=str(REPO), env=env,
    )
    om = f"127.0.0.1:{port}"
    try:
        t0 = time.time()
        ready = False
        # budget re-derived per poll: the launcher's children drive the
        # load average up mid-test
        while time.time() - t0 < _budget(60):
            try:
                out = _cli(["admin", "datanode", "--om", om],
                           timeout=10).stdout
                if len(json.loads(out)) == 2:
                    ready = True
                    break
            except (subprocess.CalledProcessError,
                    subprocess.TimeoutExpired):
                pass
            time.sleep(0.5)
        assert ready, "cluster launcher never became healthy"
        kids = Path(f"/proc/{sup.pid}/task/{sup.pid}/children") \
            .read_text().split()
        assert len(kids) == 3  # scm-om + 2 datanodes
        for kid in kids:
            kid_env = Path(f"/proc/{kid}/environ").read_bytes().split(b"\0")
            assert b"JAX_PLATFORMS=cpu" in kid_env
        _cli(["sh", "volume", "create", "/clv", "--om", om])
    finally:
        sup.send_signal(signal.SIGTERM)
        try:
            sup.wait(timeout=20)
        except subprocess.TimeoutExpired:
            sup.kill()
    # all children reaped: the om port stops answering
    t0 = time.time()
    gone = False
    while time.time() - t0 < _budget(15):
        r = _cli(["admin", "status", "--om", om], check=False, timeout=10)
        if r.returncode != 0:
            gone = True
            break
        time.sleep(0.5)
    assert gone, "children survived supervisor teardown"


def test_secure_ha_gateway_combined(tmp_path, monkeypatch):
    """The verdict-3 combined-dimension acceptance (reference's
    ozonesecure compose + omha smoketests in ONE cluster): CA + mTLS +
    block tokens on, THREE metadata replicas on one ring, five
    datanodes, S3 and HttpFS gateway processes — run a workload, SIGKILL
    the ring leader, and assert gateway requests ride the failover with
    certs and tokens intact (old objects still GET, new PUTs land)."""
    # the secure stack needs the cryptography package; on rigs without
    # it every secure daemon dies at import and this test burned minutes
    # of suite budget "waiting" for a ring that could never form — skip
    # cleanly instead (the unit TLS suites hit the same gate as
    # collection errors)
    pytest.importorskip("cryptography")
    import urllib.request

    from ozone_tpu.testing.minicluster import free_ports

    secret = "combined-drill"
    ports = free_ports(4)
    enroll_port = ports[3]
    enroll = f"127.0.0.1:{enroll_port}"
    peers = {f"m{i}": f"127.0.0.1:{ports[i]}" for i in range(3)}
    oms = ",".join(peers.values())
    peer_flags = []
    for mid, addr in peers.items():
        peer_flags += ["--peer", f"{mid}={addr}"]
    cert_dir = tmp_path / "client-certs"
    # in os.environ so the shared _cli helper (admin status, etc.)
    # presents a client cert too — every control call needs mTLS here
    monkeypatch.setenv("OZONE_TPU_CERT_DIR", str(cert_dir))
    monkeypatch.setenv("OZONE_TPU_ENROLL", enroll)
    monkeypatch.setenv("OZONE_TPU_ENROLL_SECRET", secret)
    env = dict(os.environ, PYTHONPATH=str(REPO), JAX_PLATFORMS="cpu")
    metas: dict[str, subprocess.Popen] = {}
    others: list[subprocess.Popen] = []

    def start_meta(mid: str) -> None:
        sec = (["--secure", "--block-tokens", "--enroll-port",
                str(enroll_port), "--enrollment-secret", secret]
               if mid == "m0" else
               ["--secure", "--block-tokens", "--ca", enroll,
                "--enrollment-secret", secret])
        metas[mid] = subprocess.Popen(
            [sys.executable, "-m", "ozone_tpu.tools", "scm-om",
             "--db", str(tmp_path / mid / "om.db"),
             "--port", peers[mid].rsplit(":", 1)[1],
             "--ha-id", mid, *peer_flags, *sec],
            stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT,
            text=True, cwd=str(REPO), env=env)

    def http(method, url, data=None, timeout=30):
        req = urllib.request.Request(url, data=data, method=method)
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.read()

    try:
        # the primordial hosts the CA; replicas enroll there before
        # joining the ring, so it must come up first
        start_meta("m0")
        t0 = time.time()
        # budget re-derived per poll: the spawned cluster itself
        # drives the load average up mid-test
        while time.time() - t0 < _budget(60):
            r = _cli(["admin", "status", "--om", peers["m0"]],
                     check=False, timeout=15)
            if r.returncode == 0 or "NOT_LEADER" in (r.stderr or ""):
                break
            time.sleep(0.5)
        for mid in ("m1", "m2"):
            start_meta(mid)
        t0 = time.time()
        # budget re-derived per poll: the spawned cluster itself
        # drives the load average up mid-test
        while time.time() - t0 < _budget(120):
            r = _cli(["admin", "status", "--om", oms], check=False,
                     timeout=15)
            if r.returncode == 0:
                break
            time.sleep(0.5)
        else:
            pytest.fail("secure HA ring did not come up")

        for i in range(5):
            others.append(subprocess.Popen(
                [sys.executable, "-m", "ozone_tpu.tools", "datanode",
                 "--root", str(tmp_path / f"dn{i}"), "--scm", oms,
                 "--id", f"dn{i}", "--ca", enroll,
                 "--enrollment-secret", secret],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
                text=True, cwd=str(REPO), env=env))
        t0 = time.time()
        # budget re-derived per poll: the spawned cluster itself
        # drives the load average up mid-test
        while time.time() - t0 < _budget(120):
            r = _cli(["admin", "status", "--om", oms], check=False,
                     timeout=20)
            if r.returncode == 0 and r.stdout.count("HEALTHY") >= 5 \
                    and '"safemode": false' in r.stdout:
                break
            time.sleep(0.5)
        else:
            pytest.fail("datanodes never registered over mTLS")
        # block-token enforcement is actually ON ring-wide
        assert '"block_tokens": true' in _cli(
            ["admin", "status", "--om", oms], timeout=20).stdout

        s3_port, hf_port = free_ports(2)
        # gateway processes enroll their own client certs (separate
        # dirs: each is its own identity, like real deployments)
        s3_env = dict(env, OZONE_TPU_CERT_DIR=str(tmp_path / "s3-certs"))
        hf_env = dict(env, OZONE_TPU_CERT_DIR=str(tmp_path / "hf-certs"))
        others.append(subprocess.Popen(
            [sys.executable, "-m", "ozone_tpu.tools", "s3g",
             "--om", oms, "--port", str(s3_port),
             "--replication", "rs-3-2-4096"],
            stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT,
            text=True, cwd=str(REPO), env=s3_env))
        others.append(subprocess.Popen(
            [sys.executable, "-m", "ozone_tpu.tools", "httpfs",
             "--om", oms, "--port", str(hf_port),
             "--replication", "rs-3-2-4096"],
            stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT,
            text=True, cwd=str(REPO), env=hf_env))
        s3 = f"http://127.0.0.1:{s3_port}"
        hf = f"http://127.0.0.1:{hf_port}/webhdfs/v1"
        t0 = time.time()
        # budget re-derived per poll: the spawned cluster itself
        # drives the load average up mid-test
        while time.time() - t0 < _budget(90):
            try:
                http("GET", f"{s3}/", timeout=5)
                http("GET", f"{hf}/?op=LISTSTATUS", timeout=5)
                break
            except OSError:
                time.sleep(1.0)
        else:
            pytest.fail("gateways never came up")

        payload = np.random.default_rng(11).integers(
            0, 256, 60_000, dtype=np.uint8).tobytes()
        # workload through BOTH gateways (tokens + mTLS under the hood)
        http("PUT", f"{s3}/combined")
        http("PUT", f"{s3}/combined/before", data=payload)
        assert http("GET", f"{s3}/combined/before") == payload
        http("PUT", f"{hf}/v1/hbkt?op=MKDIRS")
        r = urllib.request.urlopen(urllib.request.Request(
            f"{hf}/v1/hbkt/f1?op=CREATE&data=true", data=payload,
            method="PUT"), timeout=60)
        assert r.status in (200, 201)

        # locate + SIGKILL the ring leader process
        leader_addr = None
        for mid, addr in peers.items():
            r = _cli(["admin", "om", "prepare", "--om", addr],
                     check=False, timeout=20)
            if r.returncode != 0 and "OM_NOT_LEADER" in r.stderr:
                hint = r.stderr.rsplit(":", 1)[-1].strip()
                if hint.isdigit():
                    leader_addr = f"127.0.0.1:{hint}"
                    break
            elif r.returncode == 0:
                leader_addr = addr
                _cli(["admin", "om", "cancelprepare", "--om", addr],
                     timeout=20)
                break
        assert leader_addr, "could not locate the leader"
        leader_id = next(m for m, a in peers.items()
                         if a == leader_addr)
        metas[leader_id].kill()
        metas[leader_id].wait(timeout=10)

        # the gateways must ride the failover: old data still GETs, new
        # PUTs land, all THROUGH the same gateway processes (their OM
        # clients rotate to a surviving replica; fresh block tokens are
        # minted by the new leader; mTLS certs stay valid)
        def retry(fn, deadline_s=120):
            last = None
            t0 = time.time()
            while time.time() - t0 < _budget(deadline_s):
                try:
                    return fn()
                except OSError as e:
                    last = e
                    time.sleep(2.0)
            raise AssertionError(f"gateway never recovered: {last}")

        assert retry(lambda: http(
            "GET", f"{s3}/combined/before")) == payload
        retry(lambda: http("PUT", f"{s3}/combined/after", data=payload))
        assert retry(lambda: http(
            "GET", f"{s3}/combined/after")) == payload
        got = retry(lambda: http("GET", f"{hf}/v1/hbkt/f1?op=OPEN"))
        assert got == payload
    finally:
        for p in others:
            p.send_signal(signal.SIGTERM)
        for p in metas.values():
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in [*others, *metas.values()]:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
