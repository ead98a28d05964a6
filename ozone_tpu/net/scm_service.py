"""SCM gRPC service + remote client: registration, heartbeats, allocation.

Mirrors the reference's SCM protocol surface (ScmServerDatanodeHeartbeat
Protocol.proto for DN registration/heartbeat with piggybacked commands;
ScmServerProtocol block allocation used by the OM). Commands are
serialized with a type tag and the node address book, so remote datanodes
can execute reconstruction against peers they have never met.
"""

from __future__ import annotations

from dataclasses import asdict
from typing import Optional

from ozone_tpu import admission
from ozone_tpu.codec.api import CoderOptions
from ozone_tpu.net import wire
from ozone_tpu.net.rpc import RpcChannel, RpcServer
from ozone_tpu.scm.pipeline import ReplicationConfig
from ozone_tpu.scm.replication_manager import (
    DeleteReplicaCommand,
    ReplicateCommand,
)
from ozone_tpu.scm.scm import StorageContainerManager
from ozone_tpu.storage.ids import StorageError
from ozone_tpu.storage.reconstruction import ReconstructionCommand

SERVICE = "ozone.tpu.ScmService"


def serialize_command(cmd, addresses: dict[str, str]) -> dict:
    if isinstance(cmd, ReconstructionCommand):
        return {
            "type": "reconstruct",
            "container_id": cmd.container_id,
            "replication": str(
                CoderOptions(
                    cmd.replication.data_units,
                    cmd.replication.parity_units,
                    cmd.replication.codec,
                    cmd.replication.cell_size,
                )
            ),
            "sources": {str(k): v for k, v in cmd.sources.items()},
            "targets": {str(k): v for k, v in cmd.targets.items()},
            "addresses": addresses,
        }
    from ozone_tpu.scm.block_deletion import DeleteBlocksCommand

    if isinstance(cmd, DeleteBlocksCommand):
        return {
            "type": "delete_blocks",
            "tx_ids": cmd.tx_ids,
            "blocks": [b.to_json() for b in cmd.blocks],
        }
    if isinstance(cmd, DeleteReplicaCommand):
        return {"type": "delete_replica", **asdict(cmd)}
    if isinstance(cmd, ReplicateCommand):
        return {"type": "replicate", **asdict(cmd), "addresses": addresses}
    if isinstance(cmd, dict):
        return cmd
    return {"type": "unknown", "repr": repr(cmd)}


def deserialize_command(d: dict):
    t = d.get("type")
    if t == "reconstruct":
        return ReconstructionCommand(
            container_id=d["container_id"],
            replication=CoderOptions.parse(d["replication"]),
            sources={int(k): v for k, v in d["sources"].items()},
            targets={int(k): v for k, v in d["targets"].items()},
        )
    if t == "delete_blocks":
        from ozone_tpu.scm.block_deletion import DeleteBlocksCommand
        from ozone_tpu.storage.ids import BlockID

        return DeleteBlocksCommand(
            list(d["tx_ids"]),
            [BlockID.from_json(b) for b in d["blocks"]],
        )
    if t == "delete_replica":
        return DeleteReplicaCommand(d["container_id"], d.get("replica_index", 0))
    if t == "replicate":
        return ReplicateCommand(
            d["container_id"], d["source"], d["target"],
            d.get("replica_index", 0),
        )
    return d


class ScmGrpcService:
    def __init__(self, scm: StorageContainerManager, server: RpcServer):
        self.scm = scm
        #: secret keys leave the SCM only over channels that
        #: authenticated the caller — mutual TLS on this server — or
        #: when the operator explicitly opted into insecure distribution
        #: (test clusters); otherwise ANY caller of Register/Heartbeat
        #: could mint its own tokens and the datapath enforcement would
        #: be decorative
        self.distribute_secrets = server.tls_enabled
        self.addresses: dict[str, str] = {}
        #: optional hook fired when a node (re)registers with a new
        #: address (daemon wires pipeline re-announcement through it)
        self.on_register = None
        #: HA hooks, set by the daemon. `gate` rejects state-mutating
        #: client calls on followers (registration/heartbeats stay open
        #: on every replica — the reference's datanodes heartbeat all
        #: SCMs); `barrier` blocks until the decision records a leader
        #: allocation produced are quorum-committed.
        self.gate = None
        self.barrier = None
        #: HA hook: replicates a mutating admin op through the metadata
        #: ring (callable(op, target) -> dict) so the decision survives
        #: leader failover; None = apply directly to the local SCM
        self.admin_submitter = None
        #: HA hook: ring membership changes (callable(op, target) ->
        #: members dict); None = not an HA deployment
        self.ring_ops = None
        #: HA hook: this replica's ring view (roles verb); any replica
        #: answers, so it is NOT leader-gated
        self.ring_status = None
        #: CA lifecycle hook (callable(op, target)); set by the daemon
        #: that hosts the cluster CA (cert-list / cert-revoke)
        self.cert_ops = None
        #: HA hook: current ring replica addresses, shipped on
        #: register/heartbeat responses so datanodes follow an online-
        #: grown ring without reconfiguration (a freshly added replica
        #: that never receives heartbeats would sit in safemode forever
        #: if it won an election)
        self.ring_provider = None
        server.add_service(
            SERVICE,
            {
                "Register": self._register,
                "Heartbeat": self._heartbeat,
                "AllocateBlock": self._allocate_block,
                "NodeAddresses": self._node_addresses,
                "Status": self._status,
                "ListContainers": self._list_containers,
                "AdminOp": self._admin_op,
            },
            # bounded request queue: client-facing verbs are refused
            # past the in-flight bound; node liveness traffic is exempt
            # — shedding heartbeats under load would convert overload
            # into a dead-node storm (re-replication on top of the
            # flood), the opposite of graceful degradation
            admission=admission.controller(
                "scm",
                exempt=frozenset({"Register", "Heartbeat",
                                  "NodeAddresses", "Status"})),
        )

    def _register(self, req: bytes) -> bytes:
        m, _ = wire.unpack(req)
        changed = self.addresses.get(m["dn_id"]) != m["address"]
        self.addresses[m["dn_id"]] = m["address"]
        self.scm.register_datanode(
            m["dn_id"], m.get("rack", "/default-rack"),
            m.get("capacity_bytes", 0),
            op_state=m.get("op_state"),
        )
        if changed and self.on_register is not None:
            # a restarted node binds a new port: peers holding the old
            # address (e.g. its pipelines' raft transports) are refreshed
            self.on_register(m["dn_id"])
        return wire.pack(self._security_fields())

    def _security_fields(self) -> dict:
        """Token secret-key distribution rides the register/heartbeat
        responses (the reference's SecretKeyProtocol served from the
        SCM): datanodes import the keys and turn on datapath token
        verification."""
        out = {}
        if self.ring_provider is not None:
            out["ring"] = list(self.ring_provider())
        if not getattr(self.scm, "block_tokens", False):
            return out
        out["block_tokens"] = True
        if self.distribute_secrets:
            out["secret_keys"] = self.scm.secret_keys.export_keys()
        return out

    def _heartbeat(self, req: bytes) -> bytes:
        m, _ = wire.unpack(req)
        cmds = self.scm.heartbeat(
            m["dn_id"],
            container_report=m.get("container_report"),
            used_bytes=m.get("used_bytes", 0),
            deleted_block_acks=m.get("deleted_block_acks"),
            layout_version=m.get("layout_version"),
            healthy_volumes=m.get("healthy_volumes"),
        )
        return wire.pack(
            {
                "commands": [
                    serialize_command(c, dict(self.addresses)) for c in cmds
                ],
                **self._security_fields(),
            }
        )

    def _allocate_block(self, req: bytes) -> bytes:
        if self.gate is not None:
            self.gate()  # follower-local allocation would never replicate
        m, _ = wire.unpack(req)
        g = self.scm.allocate_block(
            ReplicationConfig.parse(m["replication"]),
            m["block_size"],
            m.get("excluded"),
        )
        if self.barrier is not None:
            self.barrier()  # allocation must survive leader failover
        return wire.pack({"group": g.to_json(), "addresses": dict(self.addresses)})

    def node_locations(self) -> dict[str, str]:
        """dn_id -> topology location path (multi-level: "/dc/rack")."""
        return {n.dn_id: n.rack for n in self.scm.nodes.nodes()}

    def _node_addresses(self, req: bytes) -> bytes:
        return wire.pack({"addresses": dict(self.addresses),
                          "locations": self.node_locations()})

    #: admin verbs that change cluster state (leader-only under HA; the
    #: read-only ones may be answered by any replica)
    _MUTATING_ADMIN = frozenset({
        "decommission", "recommission", "maintenance",
        "balancer-start", "balancer-stop",
        "safemode-enter", "safemode-exit",
        "close-container", "close-pipeline", "finalize-upgrade",
    })

    def _admin_op(self, req: bytes) -> bytes:
        """Operator verbs (`ozone admin` analog: NodeDecommissionManager,
        ContainerBalancerCommands, SafeModeCommands, pipeline list)."""
        m, _ = wire.unpack(req)
        op, target = m["op"], m.get("target")
        scm = self.scm
        if op == "ring-status":
            # any replica answers (followers report the leader hint);
            # NOT leader-gated, unlike the membership mutations below
            if self.ring_status is None:
                raise StorageError("UNSUPPORTED_REQUEST",
                                   "not an HA deployment")
            return wire.pack(self.ring_status())
        if op in ("ring-add", "ring-remove", "ring-transfer"):
            # membership change IS its own replication (the config
            # entry rides the raft log), so it does not go through the
            # admin submitter; transfer likewise acts directly on the
            # leader's raft node
            if self.ring_ops is None:
                raise StorageError("UNSUPPORTED_REQUEST",
                                   "not an HA deployment")
            if self.gate is not None:
                self.gate()
            out = self.ring_ops(op, target)
            if op == "ring-transfer":
                return wire.pack(out)
            return wire.pack({"members": out})
        if op in ("cert-list", "cert-revoke"):
            # CA lifecycle ops: answered by the replica hosting the
            # root CA (daemon wires cert_ops when it owns one)
            if self.cert_ops is None:
                raise StorageError(
                    "UNSUPPORTED_REQUEST",
                    "this replica does not host the cluster CA")
            return wire.pack({"result": self.cert_ops(op, target)})
        if op in self._MUTATING_ADMIN:
            if self.gate is not None:
                self.gate()
            if self.admin_submitter is not None:
                out = self.admin_submitter(op, target)  # via the HA ring
            else:
                out = scm.apply_admin_op(op, target)
        elif op == "balancer-status":
            out = scm.balancer_status()
        elif op == "upgrade-status":
            # finalization progress (ozone admin scm finalizationstatus
            # analog): read-only view of the layout-feature catalog
            if scm.finalizer is not None:
                out = scm.finalizer.status()
            else:
                from ozone_tpu.utils.upgrade import FEATURES, LATEST_VERSION

                out = {"metadata_version": LATEST_VERSION,
                       "software_version": LATEST_VERSION,
                       "needs_finalization": False,
                       "features": [{"name": f.name, "version": f.version,
                                     "allowed": True} for f in FEATURES]}
        elif op in ("container-token", "block-token"):
            # operator token minting for dn-direct debug/repair tools
            # (SCMSecurityProtocol.getContainerToken analog); no-op on
            # insecure clusters so tools need no mode switch
            if not getattr(scm, "block_tokens", False):
                out = {"token": None}
            else:
                from ozone_tpu.storage.ids import BlockID
                from ozone_tpu.utils.security import (
                    AccessMode,
                    BlockTokenIssuer,
                )

                issuer = BlockTokenIssuer(scm.secret_keys)
                if op == "container-token":
                    tok = issuer.issue_container(int(target), owner="admin")
                else:
                    tok = issuer.issue(
                        BlockID.from_json(target),
                        [AccessMode.READ, AccessMode.WRITE], owner="admin")
                out = {"token": tok}
        elif op == "pipelines":
            out = {"pipelines": [
                {"id": p.id, "nodes": p.nodes,
                 "replication": str(p.replication),
                 "state": p.state.value}
                for p in scm.containers.pipelines()
            ]}
        elif op == "replication-status":
            from ozone_tpu.recon.recon import ReconScmView

            health = ReconScmView(scm).container_health()
            out = {k: len(v) for k, v in health.items()}
        elif op == "container-info":
            c = scm.containers.get_or_none(int(target))
            if c is None:
                raise StorageError("CONTAINER_NOT_FOUND",
                                   f"no container {target}")
            out = {
                "id": c.id,
                "state": c.state.value,
                "replication": str(c.replication),
                "pipeline": c.pipeline.id if c.pipeline else None,
                "nodes": c.pipeline.nodes if c.pipeline else [],
                "used_bytes": c.used_bytes,
                "replicas": [
                    {"dn_id": r.dn_id, "state": r.state,
                     "replica_index": r.replica_index,
                     "block_count": r.block_count,
                     "used_bytes": r.used_bytes}
                    for r in list(c.replicas.values())
                ],
            }
        elif op == "container-report":
            # ReplicationManagerReport analog (admin container report):
            # container-state census + replication-health census in one
            # view (tools/.../container/ReportSubcommand.java)
            from collections import Counter

            from ozone_tpu.recon.recon import ReconScmView

            states = Counter(
                c.state.value for c in scm.containers.containers())
            health = ReconScmView(scm).container_health()
            out = {
                "containers_total": sum(states.values()),
                "states": dict(states),
                "health": {k: len(v) for k, v in health.items()},
            }
        else:
            raise StorageError("UNSUPPORTED_REQUEST", f"admin op {op!r}")
        return wire.pack(out)

    def _list_containers(self, req: bytes) -> bytes:
        return wire.pack({"containers": self.scm.list_containers()})

    def _status(self, req: bytes) -> bytes:
        return wire.pack(
            {
                "safemode": self.scm.safemode.in_safemode(),
                "safemode_status": self.scm.safemode.status(),
                "block_tokens": getattr(self.scm, "block_tokens", False),
                "nodes": self.scm.list_nodes(),
                "containers": len(self.scm.containers.containers()),
            }
        )


class GrpcScmClient:
    """Remote SCM client. `address` may be a comma-separated HA replica
    list: datanodes register/heartbeat to EVERY replica (the reference's
    datanodes heartbeat all SCMs so each tracks liveness and a promoted
    leader starts with fresh node state; commands only come back from the
    leader), while reads rotate to the first reachable replica."""

    def __init__(self, address: str, tls=None):
        from ozone_tpu.net.rpc import FailoverChannels

        self._pool = FailoverChannels(address, tls=tls)
        self.addresses = self._pool.addresses
        #: latest security fields seen on register/heartbeat responses
        #: ({"block_tokens": bool, "secret_keys": [...]}); the datanode
        #: daemon drains this into its verifier after each exchange
        self.security: dict = {}

    def _merge_security(self, responses: list[dict]) -> None:
        import time

        for m in responses:
            if m.get("ring"):
                # online ring growth AND retirement: adopt the full
                # shipped membership so removed replicas stop being
                # dialed on every heartbeat round
                self._pool.reconcile(m["ring"])
            if m.get("block_tokens"):
                self.security["block_tokens"] = True
                keys = {k["key_id"]: k
                        for k in self.security.get("secret_keys", [])}
                for k in m.get("secret_keys", []):
                    keys[k["key_id"]] = k
                now = time.time()  # expired keys must not accumulate
                self.security["secret_keys"] = [
                    k for k in keys.values() if k.get("expires", 0) >= now]

    def _call(self, method: str, meta: dict,
              timeout: Optional[float] = 30.0) -> dict:
        from ozone_tpu.client import resilience

        payload = wire.pack(meta)
        last: Optional[Exception] = None
        # backoff between failover attempts: during an election every
        # replica answers SCM_NOT_LEADER instantly, and a sleepless
        # loop burns the whole retry budget in milliseconds instead of
        # outliving the election. Tuning shared with the OM client —
        # see resilience.failover_retry_policy.
        attempts = max(4, 3 * len(self.addresses))
        policy = resilience.failover_retry_policy(attempts)
        for attempt in range(attempts):
            floor_s = None
            addr, ch = self._pool.channel()
            try:
                m, _ = wire.unpack(ch.call(
                    SERVICE, method, payload, timeout=timeout))
                return m
            except StorageError as e:
                last = e
                if e.code == "SCM_NOT_LEADER":
                    self._pool.follow_hint(e.msg)
                elif e.code == "UNAVAILABLE":
                    # drop the (possibly wedged) channel so the next
                    # attempt redials — see FailoverChannels.invalidate
                    self._pool.invalidate(addr)
                    if len(self.addresses) == 1:
                        raise
                    self._pool.rotate()
                elif e.code == resilience.SERVER_BUSY:
                    # healthy-peer pushback: back off to the server's
                    # Retry-After hint, same replica — see the OM client
                    floor_s = resilience.server_pushback_floor(e, "scm")
                else:
                    raise
            if not policy.sleep(attempt, floor_s=floor_s):
                resilience.check_deadline("scm_failover")
                break
        raise last

    def _broadcast(self, method: str, meta: dict,
                   timeout: Optional[float] = 2.0) -> list[dict]:
        """Send to every replica concurrently; return the successful
        responses (at least one required). Concurrency matters: a
        blackholed replica must cost one timeout in parallel, not one
        per replica per heartbeat."""
        payload = wire.pack(meta)

        def one(addr):
            _, ch = self._pool.channel(addr)
            try:
                m, _ = wire.unpack(ch.call(SERVICE, method, payload,
                                           timeout=timeout))
            except StorageError as e:
                if e.code == "UNAVAILABLE":
                    self._pool.invalidate(addr)  # redial next beat
                raise
            return m

        if len(self.addresses) == 1:
            return [one(self.addresses[0])]
        from concurrent.futures import ThreadPoolExecutor

        out, last = [], None
        with ThreadPoolExecutor(max_workers=len(self.addresses)) as ex:
            futs = {ex.submit(one, a): a for a in self.addresses}
            for f in futs:
                try:
                    out.append(f.result())
                except StorageError as e:
                    last = e
        if not out:
            raise last
        return out

    def register(self, dn_id: str, address: str, rack: str = "/default-rack",
                 capacity_bytes: int = 0,
                 op_state: Optional[str] = None) -> None:
        responses = self._broadcast("Register", {
            "dn_id": dn_id, "address": address, "rack": rack,
            "capacity_bytes": capacity_bytes, "op_state": op_state,
        })
        self._merge_security(responses)

    def heartbeat(self, dn_id: str, container_report=None,
                  used_bytes: int = 0,
                  deleted_block_acks: Optional[list[int]] = None,
                  layout_version: Optional[int] = None,
                  healthy_volumes: Optional[int] = None) -> list:
        responses = self._broadcast("Heartbeat", {
            "dn_id": dn_id,
            "container_report": container_report,
            "used_bytes": used_bytes,
            "deleted_block_acks": deleted_block_acks or [],
            "layout_version": layout_version,
            "healthy_volumes": healthy_volumes,
        })
        self._merge_security(responses)
        cmds = []
        for m in responses:  # only the leader queues commands
            cmds.extend(deserialize_command(c) for c in m["commands"])
        return cmds

    def allocate_block(self, replication: str, block_size: int,
                       excluded: Optional[list[str]] = None):
        m = self._call("AllocateBlock", {
            "replication": replication,
            "block_size": block_size,
            "excluded": excluded or [],
        })
        return m["group"], m["addresses"]

    def list_containers(self) -> list[dict]:
        return self._call("ListContainers", {})["containers"]

    def list_nodes(self) -> list[dict]:
        return self.status()["nodes"]

    def node_addresses(self) -> dict[str, str]:
        return self._call("NodeAddresses", {})["addresses"]

    def node_topology(self) -> tuple[dict[str, str], dict[str, str]]:
        """(addresses, locations) from ONE NodeAddresses round-trip."""
        m = self._call("NodeAddresses", {})
        return m["addresses"], m.get("locations", {})

    def node_locations(self) -> dict[str, str]:
        """dn_id -> topology location (for nearest-first read ordering)."""
        return self.node_topology()[1]

    def admin(self, op: str, target: Optional[str] = None) -> dict:
        return self._call("AdminOp", {"op": op, "target": target})

    def status(self) -> dict:
        return self._call("Status", {})

    def close(self) -> None:
        self._pool.close()


class AdminTokenFetcher:
    """TokenStore issuer that fetches operator tokens from the SCM
    (SCMSecurityProtocol.getContainerToken analog) — lets dn-direct
    debug/repair/freon tools run against token-enforcing clusters
    without holding the secret keys. On insecure clusters the SCM
    answers None and requests go out untokened."""

    def __init__(self, scm_client: GrpcScmClient):
        self.scm = scm_client

    def issue(self, block_id, modes=None, owner="admin"):
        try:
            return self.scm.admin(
                "block-token", block_id.to_json()).get("token")
        except StorageError:
            return None

    def issue_container(self, container_id, modes=None, owner="admin"):
        try:
            return self.scm.admin(
                "container-token", int(container_id)).get("token")
        except StorageError:
            return None
