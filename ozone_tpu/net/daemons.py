"""Service daemons: datanode and SCM+OM server processes.

Mirrors the reference's service mains (HddsDatanodeService.java:99 start
:207 with the DatanodeStateMachine register->heartbeat loop and command
handlers; StorageContainerManagerStarter; OzoneManagerStarter). The SCM
and OM run co-located in one server process here (separate processes are a
deployment choice, not an architecture change — both are already
independent objects behind independent gRPC services).
"""

from __future__ import annotations

import json
import logging
import os
import queue
import threading
import time
from pathlib import Path
from typing import Optional

from ozone_tpu.client.dn_client import DatanodeClientFactory
from ozone_tpu.net.dn_service import DatanodeGrpcService
from ozone_tpu.net.om_service import OmGrpcService
from ozone_tpu.net.rpc import RpcServer
from ozone_tpu.net.scm_service import GrpcScmClient, ScmGrpcService
from ozone_tpu.om.om import OzoneManager

# registration side effect (OMRequest.__init_subclass__): any process
# that may APPLY replicated sharding entries — a shard ring follower
# replaying its log — must import the sharding request classes before
# the first replay, or from_json cannot resolve them
import ozone_tpu.om.sharding  # noqa: F401,E402

from ozone_tpu.scm.replication_manager import (
    DeleteReplicaCommand,
    ReplicateCommand,
)
from ozone_tpu.scm.scm import StorageContainerManager
from ozone_tpu.storage.datanode import Datanode
from ozone_tpu.storage.ids import BlockData, StorageError
from ozone_tpu.storage.reconstruction import (
    ECReconstructionCoordinator,
    ReconstructionCommand,
)

log = logging.getLogger(__name__)


class DatanodeDaemon:
    """Datanode process: gRPC service + SCM heartbeat/command loop."""

    def __init__(
        self,
        root: Path,
        dn_id: str,
        scm_address: str,
        host: str = "127.0.0.1",
        port: int = 0,
        rack: str = "/default-rack",
        heartbeat_interval_s: float = 1.0,
        scan_interval_s: float = 300.0,
        ca_address: str | None = None,
        enrollment_secret: str | None = None,
        num_volumes: int = 1,
        volume_policy: str = "round-robin",
        replication_bandwidth_mbps: float | None = None,
    ):
        self.dn = Datanode(Path(root), dn_id=dn_id,
                           num_volumes=num_volumes,
                           volume_policy=volume_policy)
        # secure mode: enroll against the SCM CA's plaintext enrollment
        # endpoint, then run EVERYTHING (our server, SCM client, peer
        # datapath/raft channels) over mutual TLS — the reference's
        # grpc.tls.enabled cluster posture
        self.tls = None
        self.cert_renewal = None
        if ca_address is not None:
            from ozone_tpu.utils.ca import (
                CertificateClient,
                CertRenewalService,
            )

            cc = self.cert_client = CertificateClient(
                Path(root) / "certs", f"datanode-{dn_id}",
                hostnames=["localhost", "127.0.0.1", dn_id],
            )
            if not cc.enrolled:
                cc.enroll_remote(ca_address, secret=enrollment_secret)
            # live TLS view + auto-renewal: a renewed cert is served on
            # the next handshake without a daemon restart
            self.tls = cc.rotating_tls()
            # recurring trust refresh ONLY when the bootstrap secret
            # authenticates the responses — without it, a periodic
            # unauthenticated fetch would be a standing MITM
            # trust-poisoning channel (enrollment stays one-shot TOFU)
            trust = (
                (lambda: cc.refresh_trust_remote(
                    ca_address, secret=enrollment_secret))
                if enrollment_secret is not None else None)
            self.cert_renewal = CertRenewalService(
                self.tls,
                lambda: cc.renew_remote(ca_address,
                                        secret=enrollment_secret),
                trust_fn=trust)
        self.server = RpcServer(host, port, tls=self.tls)
        if self.tls is not None:
            # revocation: refuse peers whose cert serial is on the CRL
            # (learned via the MAC'd trust refresh)
            self.server.crl_provider = self.tls.crl
        # datapath token verification (BlockTokenVerifier on the
        # HddsDispatcher): starts disabled; the SCM's register/heartbeat
        # responses deliver the secret keys and flip it on
        from ozone_tpu.utils.security import (
            BlockTokenVerifier,
            SecretKeyManager,
        )

        self.secrets = SecretKeyManager(generate=False)
        self.verifier = BlockTokenVerifier(self.secrets, enabled=False)
        # layout-version / upgrade finalization (reference
        # VersionedDatanodeFeatures + finalizeNewLayoutVersion command);
        # the gRPC service gates layout-gated verbs on it
        from ozone_tpu.utils.upgrade import (
            LayoutVersionManager,
            UpgradeFinalizer,
        )

        self.layout = LayoutVersionManager(Path(root) /
                                           "layout_version.json")
        self.finalizer = UpgradeFinalizer(self.layout)
        # native C++ datapath sidecar for the bulk verbs (insecure
        # clusters; mTLS clusters keep the authenticated gRPC channel).
        # A missing toolchain just leaves gRPC serving everything.
        self.datapath = None
        import os as _os

        if self.tls is None and _os.environ.get(
                "OZONE_TPU_NATIVE_DATAPATH", "1") != "0":
            from ozone_tpu.storage.fast_datapath import DatapathSidecar

            sc = DatapathSidecar(self.dn, verifier=self.verifier,
                                 layout=self.layout, host=host)
            if sc.start() is not None:
                self.datapath = sc
        self.service = DatanodeGrpcService(
            self.dn, self.server, verifier=self.verifier,
            layout=self.layout,
            datapath_port=lambda: (self.datapath.advertise()
                                   if self.datapath else None))
        # per-DN replication bandwidth cap (ReplicationSupervisor limit
        # analog): paces BOTH the pull loop below and served export
        # streams; None = unlimited
        self.replication_throttle = None
        if replication_bandwidth_mbps:
            from ozone_tpu.utils.throttle import Throttle

            self.replication_throttle = Throttle(
                replication_bandwidth_mbps * 1024 * 1024,
                metrics=self.dn.metrics)
            self.service.throttle = self.replication_throttle
        # datanode raft pipelines (XceiverServerRatis analog): raft RPCs
        # and the client Submit/Watch surface ride the same RpcServer
        from ozone_tpu.net.raft_transport import RaftRpcService
        from ozone_tpu.net.ratis_service import RatisGrpcService
        from ozone_tpu.storage.ratis import RatisXceiverServer

        self.raft_rpc = RaftRpcService(self.server)
        self.xceiver_ratis = RatisXceiverServer(
            self.dn, Path(root), self.server.address,
            rpc_service=self.raft_rpc, tls=self.tls,
        )
        self.ratis_service = RatisGrpcService(self.xceiver_ratis, self.server,
                                              verifier=self.verifier)
        self._groups_file = Path(root) / "ratis" / "groups.json"
        from ozone_tpu.utils.insight import InsightService

        self.insight = InsightService(self.server, f"datanode:{dn_id}")
        # span export to the cluster collector on the metadata server
        # (TracingUtil's Jaeger sender analog)
        from ozone_tpu.utils.tracing import SpanExporter, Tracer

        self.trace_exporter = SpanExporter(
            Tracer.instance(), f"datanode-{dn_id}",
            scm_address.split(",")[0].strip(), tls=self.tls)
        self.scm = GrpcScmClient(scm_address, tls=self.tls)
        self.rack = rack
        self.heartbeat_interval = heartbeat_interval_s
        # peer clients for reconstruction/replication work
        self.clients = DatanodeClientFactory()
        self.clients.tls = self.tls
        self.clients.register_local(self.dn)
        # this daemon's own topology position: reconstruction reads
        # prefer the nearest surviving replicas
        self.clients.location = rack
        self.clients.node_id = dn_id
        # multi-chip hosts repair across every local chip (DP over the
        # default mesh); single-chip hosts take the fused path
        from ozone_tpu.parallel.sharded import default_codec_mesh

        self._codec_mesh = default_codec_mesh()
        self.reconstruction = ECReconstructionCoordinator(
            self.clients, mesh=self._codec_mesh)
        self._pending_acks: list[int] = []
        self._acks_lock = threading.Lock()
        #: the SCM's commands, executed in order on a thread of their
        #: own (`_command_loop`) once the daemon's loops run: a
        #: replication or a reconstruction takes seconds, and a heartbeat
        #: that waited for it made a busy datanode look STALE to the SCM
        #: (9 s), which then refused every allocation that needs all
        #: nodes (upstream: the datanode's CommandDispatcher has its own
        #: handler threads, the heartbeat its own). Bounded: with this
        #: many waiting the heartbeat waits for room, as it always did
        self._commands: "queue.Queue" = queue.Queue(maxsize=1024)
        self._cmd: Optional[threading.Thread] = None
        # container-report gating (see heartbeat_once)
        self.full_report_every_s = 10.0
        self._last_report_fp = None
        self._last_report_t = 0.0
        self._last_used = 0
        self._stop = threading.Event()
        self._hb: Optional[threading.Thread] = None
        # background data scanner (BackgroundContainerDataScanner analog):
        # one container per tick, round-robin, device-batched CRC verify;
        # a poisoned replica reaches the SCM via the next container report
        from ozone_tpu.storage.scrubber import DeviceScrubber

        self.scan_interval = scan_interval_s
        self._scrubber = DeviceScrubber(mesh=self._codec_mesh)
        self._scan_cursor = 0
        self._scanner: Optional[threading.Thread] = None
        # persisted operational state (reference persistedOpState): set
        # by SCM commands, echoed back at registration so a restarted
        # SCM relearns an in-progress drain
        self._op_state_file = Path(root) / "op_state.json"
        self._op_state: Optional[str] = None
        if self._op_state_file.exists():
            try:
                loaded = json.loads(self._op_state_file.read_text())
                if isinstance(loaded, dict):
                    self._op_state = loaded.get("op_state")
            except ValueError:  # ozlint: allow[error-swallowing] -- corrupt marker: start IN_SERVICE, SCM re-drives
                pass

    @property
    def address(self) -> str:
        return self.server.address

    def _sync_security(self) -> None:
        """Install secret keys delivered on SCM responses and enable
        datapath token enforcement + the reconstruction self-issuer
        (TokenHelper analog — this DN signs its own repair traffic)."""
        sec = self.scm.security
        if not sec.get("block_tokens"):
            return
        if not self.verifier.enabled:
            # fail CLOSED from the first moment we learn tokens are on:
            # with no keys yet, every verification fails — better to
            # refuse requests than to serve an enforcement-off window
            self.verifier.enabled = True
            log.info("%s: block-token enforcement enabled", self.dn.id)
        if sec.get("secret_keys"):
            self.secrets.import_keys(sec["secret_keys"])
            if self.clients.tokens.issuer is None:
                from ozone_tpu.utils.security import BlockTokenIssuer

                self.clients.tokens.issuer = BlockTokenIssuer(self.secrets)

    def start(self) -> None:
        self.server.start()
        if self.cert_renewal is not None:
            self.cert_renewal.start()
        self.trace_exporter.start()
        self._rejoin_pipelines()
        self.scm.register(self.dn.id, self.address, rack=self.rack,
                          op_state=self._op_state,
                          capacity_bytes=self._capacity_bytes())
        self._sync_security()
        self._cmd = threading.Thread(
            target=self._command_loop, name=f"cmd-{self.dn.id}",
            daemon=True)
        self._cmd.start()
        self._hb = threading.Thread(
            target=self._heartbeat_loop, name=f"hb-{self.dn.id}", daemon=True
        )
        self._hb.start()
        if self.scan_interval and self.scan_interval > 0:
            self._scanner = threading.Thread(
                target=self._scan_loop, name=f"scan-{self.dn.id}",
                daemon=True)
            self._scanner.start()

    def scan_once(self) -> None:
        """Scrub the next scannable container in round-robin order
        (throttle unit of the background scanner). Only writer-free
        states are data-scanned — an OPEN or RECOVERING replica has
        concurrent writers whose in-flight chunks would read torn."""
        from ozone_tpu.storage.scrubber import SCANNABLE_STATES

        containers = [c for c in self.dn.list_containers()
                      if c.state in SCANNABLE_STATES]
        if not containers:
            return
        c = containers[self._scan_cursor % len(containers)]
        self._scan_cursor += 1
        errs = self._scrubber.scrub_container(self.dn, c.id)
        if errs:
            log.warning("%s: container %d failed scrub: %s",
                        self.dn.id, c.id, errs[:4])

    def _scan_loop(self) -> None:
        while not self._stop.wait(self.scan_interval):
            try:
                # disk health first (StorageVolumeChecker cadence): a
                # failed volume's replicas leave the container set, the
                # next heartbeat's FCR reports the loss, SCM repairs
                self.dn.check_volumes()
                self.scan_once()
            except Exception:
                log.exception("%s background scan failed", self.dn.id)

    def _rejoin_pipelines(self) -> None:
        """Re-open raft groups this node served before a restart (the
        reference reloads its RaftGroups from the ratis storage dirs)."""
        if not self._groups_file.exists():
            return
        try:
            groups = json.loads(self._groups_file.read_text())
        except ValueError:
            log.exception("%s: corrupt %s", self.dn.id, self._groups_file)
            return
        for g in groups.values():
            try:
                self.xceiver_ratis.join(int(g["pipeline_id"]), g["peers"])
            except Exception:
                log.exception("%s: rejoin pipeline %s failed",
                              self.dn.id, g.get("pipeline_id"))

    def _join_pipeline(self, cmd: dict) -> None:
        pid = int(cmd["pipeline_id"])
        peers = dict(cmd["peers"])
        self.xceiver_ratis.join(pid, peers)
        self._groups_file.parent.mkdir(parents=True, exist_ok=True)
        groups = {}
        if self._groups_file.exists():
            try:
                groups = json.loads(self._groups_file.read_text())
            except ValueError:
                groups = {}
        groups[str(pid)] = {"pipeline_id": pid, "peers": peers}
        tmp = self._groups_file.with_suffix(".tmp")
        tmp.write_text(json.dumps(groups))
        tmp.replace(self._groups_file)

    def _set_op_state(self, state: Optional[str]) -> None:
        self._op_state = state if state != "IN_SERVICE" else None
        if self._op_state is None:
            self._op_state_file.unlink(missing_ok=True)
        else:
            tmp = self._op_state_file.with_suffix(".tmp")
            tmp.write_text(json.dumps({"op_state": self._op_state}))
            tmp.replace(self._op_state_file)

    def _close_container(self, cmd: dict) -> None:
        cid = int(cmd["container_id"])
        pid = cmd.get("pipeline_id")
        if pid is not None and self.xceiver_ratis.get(int(pid)) is not None:
            # RATIS: ordered through the ring — only the leader submits;
            # followers apply the committed close from the log
            try:
                from ozone_tpu.client import resilience

                self.xceiver_ratis.submit(int(pid), {
                    "verb": "close_container", "container_id": cid,
                }, timeout=resilience.op_timeout(10.0, "close_container"))
            except StorageError as e:
                if e.code != "NOT_LEADER":
                    log.warning("%s: raft close of container %d failed: %s",
                                self.dn.id, cid, e)
            return
        try:
            self.dn.close_container(cid)
        except StorageError:  # ozlint: allow[error-swallowing] -- already closed / not replicated here yet
            pass

    def _leave_pipeline(self, pid: int) -> None:
        """Retire a closed pipeline's raft group: stop the node, drop it
        from the rejoin record, delete its log (container data stays)."""
        import json
        import shutil

        self.xceiver_ratis.leave(pid)
        if self._groups_file.exists():
            try:
                groups = json.loads(self._groups_file.read_text())
            except ValueError:
                groups = {}
            if groups.pop(str(pid), None) is not None:
                tmp = self._groups_file.with_suffix(".tmp")
                tmp.write_text(json.dumps(groups))
                tmp.replace(self._groups_file)
        shutil.rmtree(
            self._groups_file.parent / self.xceiver_ratis.group_id(pid),
            ignore_errors=True,
        )

    def _capacity_bytes(self) -> int:
        """Filesystem capacity across healthy volumes (the reference's
        StorageLocationReport capacity from df) — feeds the SCM node
        table's usage columns and the capacity placement policy."""
        import shutil

        total = 0
        seen_devices = set()
        for v in self.dn.volumes:
            if v.failed:
                continue
            try:
                dev = v.root.stat().st_dev
                if dev in seen_devices:
                    # vol dirs sharing one filesystem (the common dev/
                    # test layout) must not multiply-count the disk
                    continue
                seen_devices.add(dev)
                total += shutil.disk_usage(v.root).total
            except OSError:  # ozlint: allow[error-swallowing] -- a vanished volume dir just drops out of the capacity report
                pass
        return total

    def heartbeat_once(self, defer_commands: bool = False) -> None:
        """One heartbeat: report, acknowledge, take the SCM's commands.
        They are executed here, before returning (tests and drills that
        tick a cluster by hand), or with `defer_commands` handed to the
        command thread, as the daemon's own loop does."""
        # full container reports only on change or every
        # full_report_every_s (the reference's ICR-on-change +
        # periodic-FCR cadence): building one walks every container's
        # block table — per-heartbeat it makes an IDLE datanode burn a
        # core's worth of sqlite scans as containers accumulate
        fp = (self.dn.mutation_count,
              tuple(sorted((c.id, c.state.value)
                           for c in self.dn.containers)))
        now = time.monotonic()
        if (fp != self._last_report_fp
                or now - self._last_report_t >= self.full_report_every_s):
            report = self.dn.container_report()
            self._last_used = sum(r["used_bytes"] for r in report)
        else:
            report = None
        used = self._last_used
        with self._acks_lock:
            acks, self._pending_acks = self._pending_acks, []
        commands = self.scm.heartbeat(
            self.dn.id, container_report=report, used_bytes=used,
            layout_version=self.layout.metadata_version,
            deleted_block_acks=acks,
            healthy_volumes=self.dn.healthy_volume_count,
        )
        if report is not None:
            # delivered-only bookkeeping: a heartbeat that raised (every
            # SCM briefly unreachable) must NOT consume the change —
            # the report retries on the next beat, not in 10 s
            self._last_report_fp = fp
            self._last_report_t = now
        self._sync_security()
        for cmd in commands:
            # opening a raft group is quick and writers wait for it: it
            # never queues behind a close or a replication
            if defer_commands and not (
                    isinstance(cmd, dict)
                    and cmd.get("type") == "join-pipeline"):
                self._commands.put(cmd)
            else:
                self._execute(cmd)

    def _heartbeat_loop(self) -> None:
        while not self._stop.wait(self.heartbeat_interval):
            try:
                self.heartbeat_once(defer_commands=True)
                self._drain_scan_requests()
            except Exception:
                log.exception("%s heartbeat failed", self.dn.id)

    def _command_loop(self) -> None:
        """The SCM's commands in the order they came, one at a time
        (`_execute` logs what fails and goes on)."""
        while True:
            cmd = self._commands.get()
            if cmd is None:
                return
            self._execute(cmd)

    def _drain_scan_requests(self) -> None:
        """On-demand verification scans (OnDemandContainerDataScanner
        trigger-on-error analog): a write-fence violation or read error
        queued the container; scrub it as soon as it is writer-free (an
        OPEN replica's in-flight chunks would read torn, so those stay
        queued until the container closes)."""
        from ozone_tpu.storage.scrubber import SCANNABLE_STATES

        for cid in self.dn.pop_scan_requests():
            try:
                c = self.dn.get_container(cid)
            except StorageError:  # ozlint: allow[error-swallowing] -- container deleted since the scan trigger
                continue
            if c.state not in SCANNABLE_STATES:
                self.dn.request_scan(cid)  # not writer-free yet: retry
                continue
            errs = self._scrubber.scrub_container(self.dn, cid)
            if errs:
                log.warning("%s: on-demand scan of container %d found: %s",
                            self.dn.id, cid, errs[:4])

    def _learn_addresses(self, addresses: dict[str, str]) -> None:
        for dn_id, addr in addresses.items():
            if dn_id != self.dn.id and self.clients.maybe_get(dn_id) is None:
                self.clients.register_remote(dn_id, addr)

    def _learn_topology(self) -> None:
        """One NodeAddresses round-trip feeds both the address book and
        the nearest-first read ordering."""
        try:
            addresses, locations = self.scm.node_topology()
        except (StorageError, OSError):
            return  # topology is an optimization, not a requirement
        self._learn_addresses(addresses)
        self.clients.learn_locations(locations)

    def _execute(self, cmd) -> None:
        from ozone_tpu.scm.block_deletion import DeleteBlocksCommand

        try:
            if isinstance(cmd, DeleteBlocksCommand):
                for bid in cmd.blocks:
                    try:
                        self.dn.delete_block(bid)
                    except StorageError as e:
                        # deletes are idempotent and the container
                        # scanner re-finds orphans, but a failure must
                        # not vanish silently from the operator's view
                        log.warning("%s: delete of block %s failed "
                                    "(tx still acked): %s",
                                    self.dn.id, bid, e)
                with self._acks_lock:
                    self._pending_acks.extend(cmd.tx_ids)
            elif isinstance(cmd, ReconstructionCommand):
                self._learn_topology()
                self.reconstruction.reconstruct_container_group(cmd)
            elif isinstance(cmd, DeleteReplicaCommand):
                self.dn.delete_container(cmd.container_id, force=True)
            elif isinstance(cmd, ReplicateCommand):
                self._learn_topology()
                self._replicate(cmd)
            elif isinstance(cmd, dict) and cmd.get("type") == "register":
                self.scm.register(self.dn.id, self.address, rack=self.rack,
                                  op_state=self._op_state,
                                  capacity_bytes=self._capacity_bytes())
            elif isinstance(cmd, dict) and cmd.get("type") == "set-op-state":
                self._set_op_state(cmd.get("op_state"))
            elif isinstance(cmd, dict) and cmd.get("type") == "join-pipeline":
                self._join_pipeline(cmd)
            elif isinstance(cmd, dict) and cmd.get("type") == "leave-pipeline":
                self._leave_pipeline(int(cmd["pipeline_id"]))
                # group stopped: no more applies can land, so a replica
                # that missed the raft close converges by direct close
                if cmd.get("container_id") is not None:
                    try:
                        self.dn.close_container(int(cmd["container_id"]))
                    except StorageError:  # ozlint: allow[error-swallowing] -- replica already closed/absent; convergence is the goal
                        pass
            elif isinstance(cmd, dict) and \
                    cmd.get("type") == "close-container":
                self._close_container(cmd)
            elif isinstance(cmd, dict) and cmd.get("type") == "finalize":
                out = self.finalizer.finalize()
                log.info("%s layout finalize: %s -> v%d", self.dn.id,
                         out.value, self.layout.metadata_version)
            else:
                log.debug("%s ignoring command %r", self.dn.id, cmd)
        except Exception:
            log.exception("%s command %r failed", self.dn.id, cmd)

    def _replicate(self, cmd: ReplicateCommand) -> None:
        src = self.clients.get(cmd.source)
        blocks = src.list_blocks(cmd.container_id)
        try:
            self.dn.create_container(cmd.container_id, cmd.replica_index)
        except StorageError as e:
            if e.code != "CONTAINER_EXISTS":
                raise
        for bd in blocks:
            for info in bd.chunks:
                # the bandwidth cap bites BEFORE each pull so repair
                # traffic paces itself rather than bursting then
                # stalling foreground IO
                if self.replication_throttle is not None:
                    self.replication_throttle.take(info.length)
                self.dn.write_chunk(
                    bd.block_id, info, src.read_chunk(bd.block_id, info)
                )
            self.dn.put_block(
                BlockData(bd.block_id, bd.chunks, bd.block_group_length)
            )
        self.dn.close_container(cmd.container_id)

    def stop(self) -> None:
        self._stop.set()
        if self.cert_renewal is not None:
            self.cert_renewal.stop()
        self.trace_exporter.stop()
        if self._hb:
            # bounded daemon shutdown joins: stop() has no operation
            # deadline to derive from, and an unbounded join would let
            # a wedged loop hang process exit
            self._hb.join(timeout=5)  # ozlint: allow[deadline-propagation] -- bounded shutdown join, no ambient op deadline at stop()
        if self._cmd:
            try:
                self._commands.put_nowait(None)  # after what is queued
            except queue.Full:  # ozlint: allow[error-swallowing] -- a backlog that deep is not drained at shutdown: the thread is a daemon and goes with the process
                pass
            self._cmd.join(timeout=5)  # ozlint: allow[deadline-propagation] -- bounded shutdown join, no ambient op deadline at stop()
        if self._scanner:
            self._scanner.join(timeout=5)  # ozlint: allow[deadline-propagation] -- bounded shutdown join, no ambient op deadline at stop()
        self.xceiver_ratis.stop()
        if self.datapath is not None:
            self.datapath.stop()
        self.server.stop()
        self.scm.close()
        self.clients.close()
        self.dn.close()


class ScmOmDaemon:
    """Metadata server process: SCM + OM behind one gRPC endpoint."""

    def __init__(
        self,
        om_db: Path,
        host: str = "127.0.0.1",
        port: int = 0,
        min_datanodes: int = 1,
        block_size: int = 16 * 1024 * 1024,
        container_size: int = 256 * 1024 * 1024,
        stale_after_s: float = 9.0,
        dead_after_s: float = 30.0,
        background_interval_s: float = 1.0,
        http_port: int | None = None,
        recon_port: int | None = None,
        recon_interval_s: float = 30.0,
        ha_id: str | None = None,
        ha_peers: dict[str, str] | None = None,
        block_tokens: bool = False,
        secure: bool = False,
        enroll_port: int = 0,
        enrollment_secret: str | None = None,
        insecure_secrets: bool = False,
        ca_address: str | None = None,
        shard_config: dict | None = None,
        shard_map: dict | None = None,
    ):
        self.scm = StorageContainerManager(
            min_datanodes=min_datanodes,
            container_size=container_size,
            stale_after_s=stale_after_s,
            dead_after_s=dead_after_s,
            db_path=Path(om_db).parent / "scm.db",
            block_tokens=block_tokens,
        )
        # secure mode: this process hosts the cluster CA (the reference
        # puts the root CA in the SCM), serves the main plane over
        # mutual TLS, and signs CSRs on a separate PLAINTEXT enrollment
        # server (optionally gated by a shared bootstrap secret) — a
        # fresh datanode has no cert yet, so enrollment cannot ride the
        # mTLS plane
        self.tls = None
        self.ca = None
        self.enroll_server = None
        self.cert_renewal = None
        if secure:
            from ozone_tpu.utils.ca import (
                CertificateAuthority,
                CertificateClient,
                CertRenewalService,
                EnrollmentService,
            )

            # the meta-HA raft transport dials peers with
            # server_name=<ha id>, so the cert must carry it as a SAN
            names = ["localhost", "127.0.0.1"] + ([ha_id] if ha_id else [])
            cc = self.cert_client = CertificateClient(
                Path(om_db).parent / "certs", "scm-om", hostnames=names)
            if ca_address is not None:
                # non-primordial HA replica: the root CA lives in the
                # primordial metadata server (reference: SCM hosts it)
                if not cc.enrolled:
                    cc.enroll_remote(ca_address, secret=enrollment_secret)
                renew = lambda: cc.renew_remote(  # noqa: E731
                    ca_address, secret=enrollment_secret)
                # same MITM gate as the datanode side: no secret, no
                # recurring plaintext trust refresh
                trust = (
                    (lambda: cc.refresh_trust_remote(
                        ca_address, secret=enrollment_secret))
                    if enrollment_secret is not None else None)
            else:
                self.ca = CertificateAuthority(Path(om_db).parent / "ca")
                if not cc.enrolled:
                    cc.enroll(self.ca)
                self.enroll_server = RpcServer(host, enroll_port)
                EnrollmentService(self.ca, self.enroll_server,
                                  secret=enrollment_secret)
                renew = lambda: cc.renew(self.ca)  # noqa: E731
                trust = lambda: cc.refresh_trust(self.ca)  # noqa: E731
            self.tls = cc.rotating_tls()
            self.cert_renewal = CertRenewalService(self.tls, renew,
                                                   trust_fn=trust)
        if block_tokens and not secure and not insecure_secrets:
            raise ValueError(
                "block_tokens without secure=True would hand the signing "
                "keys to any caller of Register/Heartbeat; pass "
                "secure=True (mTLS) or insecure_secrets=True (tests only)")
        if block_tokens and secure and self.enroll_server is not None \
                and enrollment_secret is None:
            # open CSR signing would admit ANY network caller into the
            # mTLS trust domain, where the admin token ops live — the
            # bootstrap secret is this cluster's admission credential
            # (the role Kerberos plays in the reference)
            raise ValueError(
                "secure block-token clusters require an "
                "enrollment_secret: open CSR signing would let any "
                "caller enroll and mint admin tokens")
        self.server = RpcServer(host, port, tls=self.tls)
        if self.tls is not None:
            self.server.crl_provider = self.tls.crl
        self.scm_service = ScmGrpcService(self.scm, self.server)
        if self.ca is not None:
            # this replica hosts the cluster CA: serve cert lifecycle
            # admin ops (list issued, revoke by serial)
            def _cert_ops(op, target):
                if op == "cert-list":
                    return self.ca.issued()
                try:
                    serial = int(str(target), 0)
                except (TypeError, ValueError):
                    raise StorageError("INVALID",
                                       f"bad serial {target!r}")
                try:
                    self.ca.revoke(serial)
                except ValueError as e:
                    raise StorageError("INVALID", str(e))
                # our own server must enforce the new CRL immediately;
                # peers learn it on their next trust refresh
                if self.cert_renewal is not None:
                    self.cert_renewal.check_once()
                out = {"revoked": serial,
                       "crl": sorted(self.ca.crl())}
                if enrollment_secret is None:
                    # without the bootstrap secret, peers never run the
                    # (MAC-authenticated) recurring trust refresh — the
                    # CRL only reaches them at their next re-enrollment
                    out["warning"] = (
                        "no enrollment secret: datanodes cannot fetch "
                        "CRL updates; revocation takes effect on their "
                        "next renewal, not immediately")
                return out

            self.scm_service.cert_ops = _cert_ops
        if insecure_secrets:
            self.scm_service.distribute_secrets = True
        # RatisPipelineProvider analog: a freshly placed RATIS pipeline is
        # announced to its members so each opens the raft group (command
        # rides the next heartbeat response; the client's leader-retry
        # loop covers the one-heartbeat join latency)
        from ozone_tpu.scm.pipeline import ReplicationType

        def _announce_pipeline(p):
            if p.replication.type is not ReplicationType.RATIS \
                    or p.replication.factor < 2:
                return
            peers = {
                dn: self.scm_service.addresses.get(dn, "")
                for dn in p.nodes
            }
            for dn in p.nodes:
                self.scm.nodes.queue_command(dn, {
                    "type": "join-pipeline",
                    "pipeline_id": p.id,
                    "peers": peers,
                })

        self.scm.containers.on_pipeline_created = _announce_pipeline

        def _announce_container_close(c):
            # RATIS containers close THROUGH the pipeline raft ring so the
            # close is ordered after every in-flight replicated write; the
            # member that is leader submits, the others ignore. EC /
            # standalone replicas close directly.
            via_raft = (
                c.pipeline is not None
                and c.pipeline.replication.type is ReplicationType.RATIS
                and c.pipeline.replication.factor > 1
            )
            for dn in (c.pipeline.nodes if c.pipeline else []):
                self.scm.nodes.queue_command(dn, {
                    "type": "close-container", "container_id": c.id,
                    "pipeline_id": c.pipeline.id if via_raft else None,
                })

        self.scm.containers.on_container_closing = _announce_container_close

        def _retire_pipeline(p):
            if p.replication.type is not ReplicationType.RATIS \
                    or p.replication.factor < 2:
                return
            # carry the (1:1) container so a member that had not yet
            # applied the raft close still converges after the group stops
            cid = next((c.id for c in self.scm.containers.containers()
                        if c.pipeline is not None and c.pipeline.id == p.id),
                       None)
            for dn in p.nodes:
                self.scm.nodes.queue_command(dn, {
                    "type": "leave-pipeline", "pipeline_id": p.id,
                    "container_id": cid,
                })

        self.scm.containers.on_pipeline_closed = _retire_pipeline

        def _reannounce_pipelines_of(dn_id):
            from ozone_tpu.scm.pipeline import PipelineState

            for p in self.scm.containers.pipelines():
                # a retired (CLOSED) pipeline must never be revived on a
                # datanode's re-registration
                if dn_id in p.nodes and p.state is PipelineState.OPEN:
                    _announce_pipeline(p)

        self.scm_service.on_register = _reannounce_pipelines_of
        self.om = OzoneManager(Path(om_db), self.scm, block_size=block_size)
        if block_tokens:
            # mint the first signing key before serving (single-node:
            # synchronous; under HA the ring replicates rotations and
            # this pre-start key is replaced by the leader's)
            if ha_id is None:
                self.scm.ensure_secret_key()
            from ozone_tpu.utils.security import BlockTokenIssuer

            self.om.enable_block_tokens(BlockTokenIssuer(self.scm.secret_keys))
        self.om_service = OmGrpcService(
            self.om, self.server,
            addresses_provider=lambda: dict(self.scm_service.addresses),
            locations_provider=self.scm_service.node_locations,
        )
        # lifecycle sweeper (lifecycle/service.py): leader-singleton on
        # the metadata ring, term-fenced with the ring's raft term; its
        # datanode clients resolve lazily from heartbeat-learned
        # addresses. OZONE_TPU_LIFECYCLE_MBPS throttles source reads so
        # tiering never starves foreground traffic.
        from ozone_tpu.lifecycle.service import LifecycleService

        self._lifecycle_clients = None
        lc_throttle = None
        from ozone_tpu.utils.config import env_float

        mbps = env_float("OZONE_TPU_LIFECYCLE_MBPS", 0.0)
        if mbps > 0:
            from ozone_tpu.utils.throttle import Throttle

            lc_throttle = Throttle(mbps * 1024 * 1024,
                                   metrics=self.om.metrics)
        lc_deadline = env_float("OZONE_TPU_LIFECYCLE_DEADLINE_S",
                                30.0)
        self.lifecycle = LifecycleService(
            self.om,
            clients_fn=self._lifecycle_client_factory,
            term_fn=lambda: (self.ha.node.storage.term
                             if self.ha is not None else 0),
            leader_fn=lambda: (self.ha.is_ready
                               if self.ha is not None else True),
            throttle=lc_throttle,
            # tighter default than the standalone service's 300 s: the
            # daemon's sweep shares the OM background loop with key
            # deletion AND raft log compaction — a long sweep stalling
            # compaction lets the log grow without bound (the cursor
            # makes short bounded sweeps equivalent anyway)
            sweep_deadline_s=lc_deadline,
            alloc_barrier=lambda: (self.ha._await_records()
                                   if self.ha is not None else None),
        )
        self.om.lifecycle = self.lifecycle
        # geo-replication shipper (replication_geo/shipper.py):
        # leader-singleton on the metadata ring, term-fenced with the
        # ring's raft term like the lifecycle sweeper; tails the OM
        # WAL delta feed and replays key commits/deletes to remote
        # clusters. OZONE_TPU_GEO_MBPS throttles source reads so
        # shipping never starves foreground traffic.
        from ozone_tpu.replication_geo.shipper import ReplicationShipper

        geo_throttle = None
        geo_mbps = env_float("OZONE_TPU_GEO_MBPS", 0.0)
        if geo_mbps > 0:
            from ozone_tpu.utils.throttle import Throttle

            geo_throttle = Throttle(geo_mbps * 1024 * 1024,
                                    metrics=self.om.metrics)
        self.geo = ReplicationShipper(
            self.om,
            clients_fn=self._lifecycle_client_factory,
            term_fn=lambda: (self.ha.node.storage.term
                             if self.ha is not None else 0),
            leader_fn=lambda: (self.ha.is_ready
                               if self.ha is not None else True),
            throttle=geo_throttle,
            ship_deadline_s=env_float("OZONE_TPU_GEO_DEADLINE_S", 30.0),
            tls=self.tls,
        )
        self.om.geo = self.geo
        # ---- metadata HA: one raft ring for OM + SCM state ----
        # (the reference's OM-HA + SCM-HA Ratis rings; co-located here,
        # so one ring and one leader for both roles)
        self.ha = None
        self._ha_peers = dict(ha_peers or {})
        if ha_id is not None:
            self._init_ha(ha_id, Path(om_db).parent / "meta-raft")
        # ---- sharded metadata plane (om/sharding) ----
        # shard_config: this daemon's InstallShardConfig payload (epoch,
        # shard_id, slot_count, owned) — the replicated ownership row its
        # OM enforces via check_shard. shard_map: the root map json this
        # daemon serves from GetShardMap so clients can discover the
        # shard rings through any replica.
        self._shard_config = shard_config
        self._shard_map = shard_map
        self._shard_installed = shard_config is None and shard_map is None
        if not self._shard_installed:
            from ozone_tpu.om.sharding.leases import follower_reads_enabled

            if self.ha is None:
                self._install_sharding()
            else:
                # HA: install needs a ready leader — deferred to the
                # background loop's leader section (epoch guards make
                # the replay-after-restart re-install idempotent)
                if follower_reads_enabled():
                    # fresh commit index per write so follower leases
                    # serve read-your-writes without a heartbeat lag
                    self.ha.push_commit_on_write = True
        from ozone_tpu.utils.insight import InsightService

        self.insight = InsightService(self.server, "scm-om")
        # cluster trace collector (Jaeger-collector role) + this
        # process's own spans fed straight in (no wire round-trip)
        from ozone_tpu.utils.tracing import (
            SpanExporter,
            TraceCollector,
            Tracer,
        )

        self.trace_collector = TraceCollector(self.server)
        self.trace_exporter = SpanExporter(
            Tracer.instance(), "scm-om",
            collector=self.trace_collector)
        self._bg_interval = background_interval_s
        # optional HTTP endpoint: /prom, /prof, /stacks, and live
        # reconfiguration of the service knobs (ReconfigureProtocol
        # analog, reference feature/Reconfigurability.md)
        self.http = None
        if http_port is not None:
            from ozone_tpu.utils.config import (
                OzoneConfiguration,
                ReconfigurationHandler,
            )
            from ozone_tpu.utils.http_server import ServiceHttpServer

            conf = OzoneConfiguration()
            reconfig = ReconfigurationHandler(conf)

            def _set_float(attr):
                def apply(v):
                    setattr(self.scm.nodes, attr, float(v))

                return apply

            # seed the config with the effective values so
            # /reconfig/properties reports reality, not null
            conf.set("ozone.scm.stale.node.interval", stale_after_s)
            reconfig.register(
                "ozone.scm.stale.node.interval",
                _set_float("stale_after"), validator=float,
                description="seconds of heartbeat silence before STALE")
            conf.set("ozone.scm.dead.node.interval", dead_after_s)
            reconfig.register(
                "ozone.scm.dead.node.interval",
                _set_float("dead_after"), validator=float,
                description="seconds of heartbeat silence before DEAD")

            def _set_block_size(v):
                self.om.block_size = int(v)

            conf.set("ozone.om.block.size", block_size)
            reconfig.register(
                "ozone.om.block.size", _set_block_size, validator=int,
                description="allocation unit for new keys (bytes)")
            self.http = ServiceHttpServer(
                "scm-om", host, http_port,
                status_provider=lambda: {
                    "address": self.address,
                    "safemode": self.scm.safemode.in_safemode(),
                },
                reconfig=reconfig,
            )
        # optional embedded Recon (observability warehouse + UI); the
        # reference runs Recon as its own role fed by OM WAL deltas —
        # here it rides the metadata process and tails the same store
        self.recon = None
        if recon_port is not None:
            from ozone_tpu.recon.recon import ReconServer

            self.recon = ReconServer(
                self.om, self.scm, host=host, port=recon_port,
                db_path=Path(om_db).parent / "recon.db",
            )
            # slow-trace view serves the cluster collector's ring, not
            # just this process's own recorder
            self.recon.trace_collector = self.trace_collector
        # recon tasks do full-namespace scans + warehouse inserts: they
        # run on their own minute-scale cadence (reference
        # ReconTaskController schedules), never per background tick
        self._recon_interval = recon_interval_s
        self._recon_last = 0.0

    @property
    def address(self) -> str:
        return self.server.address

    @property
    def enroll_address(self) -> str | None:
        """Plaintext cert-enrollment endpoint (secure mode only)."""
        return (self.enroll_server.address
                if self.enroll_server is not None else None)

    def _leader_address(self, hint: str | None) -> str:
        return self._ha_peers.get(hint or "", "")

    def _ha_call(self, fn, not_leader_code: str):
        """Run a ring operation, translating NotRaftLeaderError into the
        wire error (with the leader's address) clients fail over on, and
        operator-input errors (unknown member, change in flight) into
        INVALID instead of an opaque INTERNAL."""
        from ozone_tpu.consensus.raft import NotRaftLeaderError

        try:
            return fn()
        except NotRaftLeaderError as e:
            raise StorageError(not_leader_code,
                               self._leader_address(e.leader_hint))
        except (ValueError, RuntimeError) as e:
            raise StorageError("INVALID", str(e))

    def _init_ha(self, ha_id: str, raft_dir: Path) -> None:
        from ozone_tpu.consensus.meta_ring import MetaHARing
        from ozone_tpu.net.raft_transport import (
            GrpcRaftTransport,
            RaftRpcService,
        )
        from ozone_tpu.om import requests as rq

        raft_rpc = RaftRpcService(self.server)
        transport = GrpcRaftTransport("meta-ha", self._ha_peers, owner=ha_id,
                                      tls=self.tls)
        self.ha = MetaHARing(
            self.om, self.scm, raft_dir,
            ha_id, list(self._ha_peers), transport=transport,
        )
        raft_rpc.register("meta-ha", self.ha.node)

        om = self.om

        def _ha_submit(request):
            with om.metrics.timer(request.audit_action).time():
                try:
                    result = self._ha_call(
                        lambda: self.ha.submit_om(request), "OM_NOT_LEADER")
                except rq.OMError as e:
                    om.audit.log(request.audit_action, vars(request),
                                 ok=False, error=e.code)
                    raise
                om.audit.log(request.audit_action, vars(request), ok=True)
                om.metrics.counter("write_ops").inc()
                return result

        # route every OM write through the ring (OzoneManager methods all
        # funnel into submit); reads are leader-gated at the service edge
        # so clients get read-your-writes
        om.submit = _ha_submit
        om.prepare = lambda: self._ha_call(
            self.ha.prepare_om, "OM_NOT_LEADER")
        om.cancel_prepare = lambda: self._ha_call(
            self.ha.cancel_prepare_om, "OM_NOT_LEADER")
        self.om_service.gate = self._leader_gate
        self.om_service.scm_barrier = lambda: self._ha_call(
            self.ha._await_records, "OM_NOT_LEADER")
        # stamped on responses so shard-routing clients can carry a
        # read-your-writes floor into lease-based follower reads
        self.om_service.applied_index_fn = \
            lambda: self.ha.node.last_applied

        def _scm_gate():
            if not self.ha.is_ready:
                raise StorageError(
                    "SCM_NOT_LEADER",
                    self._leader_address(self.ha.leader_hint))

        self.scm_service.gate = _scm_gate
        self.scm_service.barrier = lambda: self._ha_call(
            self.ha._await_records, "SCM_NOT_LEADER")
        self.scm_service.admin_submitter = \
            lambda op, target: self._ha_call(
                lambda: self.ha.submit_admin(op, target), "SCM_NOT_LEADER")
        # token-key rotation is a replicated decision: every replica's
        # OM issuer must sign with the keys datanodes verify against
        self.scm.on_secret_rotate = lambda key: self.ha.submit_admin(
            "import-secret-key", key.to_json())
        # ring membership (ring-add/ring-remove admin verbs): config
        # entries carry peer addresses, so every replica's client-hint
        # address book follows the ring
        def _ring_ops(op, target):
            if op == "ring-add":
                node_id, _, address = str(target).partition("=")
                if not address:
                    raise StorageError(
                        "INVALID", "ring-add needs id=host:port")
                return self.ha.ring_add(node_id, address)
            if op == "ring-transfer":
                return self.ha.ring_transfer(str(target))
            return self.ha.ring_remove(str(target))

        self.scm_service.ring_ops = lambda op, target: self._ha_call(
            lambda: _ring_ops(op, target), "SCM_NOT_LEADER")
        self.scm_service.ring_status = self.ha.ring_status

        def _on_ring_config(members: dict) -> None:
            self._ha_peers = {
                k: (v or self._ha_peers.get(k, ""))
                for k, v in members.items()
            }

        self.ha.node.on_config = _on_ring_config
        self.scm_service.ring_provider = \
            lambda: [a for a in self._ha_peers.values() if a]

    def _lifecycle_client_factory(self) -> DatanodeClientFactory:
        """Datanode clients for the lifecycle executor, refreshed from
        heartbeat-learned addresses before each sweep (daemons learn
        datanodes after construction, so resolution must be lazy)."""
        if self._lifecycle_clients is None:
            f = DatanodeClientFactory()
            f.tls = self.tls
            if self.om.token_issuer is not None:
                f.tokens.issuer = self.om.token_issuer
            self._lifecycle_clients = f
        for dn_id, addr in dict(self.scm_service.addresses).items():
            # update, not register: re-registering an unchanged address
            # would drop the pooled connection every sweep
            self._lifecycle_clients.update_remote(dn_id, addr)
        return self._lifecycle_clients

    def _install_sharding(self) -> None:
        """Install this daemon's shard ownership + the root map copy.

        Single-node: at construction. HA: from the background loop once
        this replica is the ready leader (the install replicates to
        followers through the ring like any other OM request)."""
        from ozone_tpu.om.sharding.shardmap import (
            InstallShardConfig,
            InstallShardMap,
        )

        if self._shard_config is not None:
            self.om.submit(InstallShardConfig(**self._shard_config))
        if self._shard_map is not None:
            self.om.submit(InstallShardMap(dict(self._shard_map)))
        self._shard_installed = True

    def _leader_gate(self, verb: str | None = None,
                     req: bytes | None = None) -> None:
        # ready-leader, not just leader: a freshly elected leader must
        # apply the prior terms' committed entries (its no-op marker)
        # before serving reads, or a failover client could read stale
        # state it wrote through the previous leader
        if self.ha is None or self.ha.is_ready:
            return
        # lease-based follower reads (om/sharding/leases.py): a replica
        # holding a live read lease answers read verbs locally, provided
        # its applied state has reached the caller's floor — leader-read
        # fallback happens client-side on the OM_NOT_LEADER bounce below
        if verb is not None and req is not None:
            from ozone_tpu.net import wire
            from ozone_tpu.om.sharding.leases import (
                follower_reads_enabled,
            )

            if follower_reads_enabled():
                m, _ = wire.unpack(req)
                floor = int(m.get("_min_applied") or 0)
                if self.ha.read_gate.try_serve(verb, floor):
                    return
        raise StorageError(
            "OM_NOT_LEADER",
            self._leader_address(self.ha.leader_hint))

    def start(self) -> None:
        if self.enroll_server is not None:
            self.enroll_server.start()
        self.server.start()
        if self.http is not None:
            self.http.start()
        if self.recon is not None:
            self.recon.start()
        if self.cert_renewal is not None:
            self.cert_renewal.start()
        self.trace_exporter.start()
        if self.ha is not None:
            self.ha.start()
        else:
            self.scm.start_background(self._bg_interval)
        # OM background services (reference service/: KeyDeletingService,
        # DirectoryDeletingService) — purge detached subtrees and hand
        # deleted blocks to the SCM deletion chain. Under HA only the
        # leader runs background mutators (the reference starts these
        # services on the Ratis leader only); the SCM scan rides the same
        # loop in HA mode so it obeys the same leadership gate.
        self._om_bg_stop = threading.Event()
        self._om_bg_ticks = 0
        # lifecycle sweep cadence (seconds between sweep starts);
        # OZONE_TPU_LIFECYCLE_PERIOD_S overrides
        from ozone_tpu.utils.config import env_float

        self._lc_period = env_float("OZONE_TPU_LIFECYCLE_PERIOD_S",
                                    60.0)
        self._lc_last = time.monotonic()
        # geo-replication ship cadence (seconds between cycle starts);
        # OZONE_TPU_GEO_PERIOD_S overrides
        self._geo_period = env_float("OZONE_TPU_GEO_PERIOD_S", 30.0)
        self._geo_last = time.monotonic()

        def _om_services():
            while not self._om_bg_stop.wait(self._bg_interval):
                if self.ha is not None:
                    # every replica compacts its own raft log behind a
                    # full-state snapshot (ContainerStateMachine
                    # .takeSnapshot cadence); without this the log and
                    # the OM store's dirty cache grow without bound
                    try:
                        node = self.ha.node
                        if node.last_applied - node.storage.snapshot_index \
                                > 512:
                            node.take_snapshot()
                    except Exception:  # noqa: BLE001
                        log.exception("raft log compaction failed")
                if self.ha is not None and not self.ha.is_leader:
                    continue
                # tick first: a persistently failing fast service must
                # not starve the slow-cadence sweeps below
                self._om_bg_ticks += 1
                try:
                    if not self._shard_installed:
                        # deferred HA shard install: this replica just
                        # became the ready leader
                        self._install_sharding()
                    if self.ha is not None:
                        self.scm.run_background_once()
                    self.om.run_dir_deleting_service_once()
                    self.om.run_key_deleting_service_once()
                    # slow-cadence sweeps (reference OpenKeyCleanupService
                    # / MultipartUploadCleanupService / ExpiredTokenRemover
                    # run on multi-minute schedules): every ~60 ticks
                    if self._om_bg_ticks % 60 == 0:
                        self.om.run_open_key_cleanup_once()
                        self.om.run_mpu_cleanup_once()
                        self.om.run_dtoken_cleanup_once()
                    # lifecycle sweep: leader-gated + term-fenced
                    # internally; no-rule clusters scan nothing. Gated
                    # by wall time, not ticks — test configs run this
                    # loop at sub-second intervals, and sweeping every
                    # few seconds would let background tiering compete
                    # with foreground IO for the leader
                    now_m = time.monotonic()
                    if now_m - self._lc_last >= self._lc_period:
                        self._lc_last = now_m
                        self.lifecycle.run_once()
                        # needle compaction rides the same cadence:
                        # leader-gated internally, scans nothing when
                        # no slab crosses the dead-ratio threshold
                        self.lifecycle.compact_slabs_once()
                    # geo-replication ship cycle: leader-gated +
                    # term-fenced internally; no-rule clusters scan
                    # nothing (same wall-time gating rationale as the
                    # lifecycle sweep above)
                    now_m = time.monotonic()
                    if now_m - self._geo_last >= self._geo_period:
                        self._geo_last = now_m
                        self.geo.run_once()
                    now = time.monotonic()
                    if self.recon is not None and \
                            now - self._recon_last >= self._recon_interval:
                        self._recon_last = now
                        self.recon.run_tasks_once()
                except Exception:  # noqa: BLE001 - service must survive
                    log.exception("om background service pass failed")

        self._om_bg = threading.Thread(target=_om_services, daemon=True,
                                       name="om-background")
        self._om_bg.start()

    def stop(self) -> None:
        if hasattr(self, "_om_bg_stop"):
            self._om_bg_stop.set()
            # the background thread may be mid recon scan / OM purge;
            # it must finish the pass before the stores close under it
            self._om_bg.join(timeout=30.0)  # ozlint: allow[deadline-propagation] -- bounded shutdown join, no ambient op deadline at stop()
        if self.ha is not None:
            self.ha.stop()
        self.geo.close()
        if self.http is not None:
            self.http.stop()
        if self.recon is not None:
            self.recon.stop()
        if self.cert_renewal is not None:
            self.cert_renewal.stop()
        self.trace_exporter.stop()
        self.scm.stop()
        self.server.stop()
        if self.enroll_server is not None:
            self.enroll_server.stop()
        self.om.close()
