"""StorageContainerManager facade: wires node/pipeline/container/block
management, safemode, and the replication control loop.

Mirror of server-scm StorageContainerManager.java:228
(initializeSystemManagers:648 wiring) at framework scale: one object the
OM, datanodes, and admin tools talk to. Heartbeat handling mirrors
SCMNodeManager.processHeartbeat (commands ride the response); dead-node
events trigger replica cleanup + replication scans (DeadNodeHandler).
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Optional

from ozone_tpu.client.ec_writer import BlockGroup
from ozone_tpu.scm import node_manager as nm
from ozone_tpu.scm.container_manager import ContainerManager
from ozone_tpu.scm.node_manager import NodeManager, NodeOperationalState
from ozone_tpu.scm.placement import RackScatterPlacement
from ozone_tpu.scm.replication_manager import ReplicationManager
from ozone_tpu.scm.safemode import SafeModeConfig, SafeModeManager
from ozone_tpu.scm.pipeline import ReplicationConfig
from ozone_tpu.utils.events import EventQueue
from ozone_tpu.utils.metrics import MetricsRegistry

log = logging.getLogger(__name__)


class StorageContainerManager:
    def __init__(
        self,
        min_datanodes: int = 1,
        container_size: int = 5 * 1024 * 1024 * 1024,
        placement_seed: Optional[int] = None,
        stale_after_s: float = 9.0,
        dead_after_s: float = 30.0,
        db_path=None,
        block_tokens: bool = False,
    ):
        self.events = EventQueue()
        # symmetric secret keys for block/container tokens (reference
        # security/symmetric/SecretKeyManager lives in the SCM and feeds
        # OM + datanodes). Keys are minted lazily by ensure_secret_key so
        # HA replicas can replicate the material through the ring instead
        # of each inventing their own.
        from ozone_tpu.utils.security import SecretKeyManager

        self.block_tokens = block_tokens
        self.secret_keys = SecretKeyManager(generate=False,
                                            activation_s=10.0)
        #: HA hook: leader routes freshly minted keys through the ring
        #: (apply lands in apply_admin_op("import-secret-key")); None =
        #: single-node, install directly
        self.on_secret_rotate = None
        self.nodes = NodeManager(
            self.events, stale_after_s=stale_after_s, dead_after_s=dead_after_s
        )
        self.placement = RackScatterPlacement(self.nodes, seed=placement_seed)
        self.containers = ContainerManager(
            self.nodes, self.placement, container_size=container_size,
            db_path=db_path,
        )
        # durable op-state round trip: the SCM store is authoritative
        # across restarts; DN echoes cover a store-less SCM
        self.nodes.seed_op_states(self.containers.node_op_states())
        self.nodes.on_op_state_change = \
            self.containers.persist_node_op_state
        self.safemode = SafeModeManager(
            self.nodes, self.containers, SafeModeConfig(min_datanodes)
        )
        # layout-version manager for the metadata services themselves
        # (HDDSLayoutFeature analog); persisted next to the SCM store
        # when one exists, in-memory (fresh = finalized) otherwise
        self.layout = None
        self.finalizer = None
        if db_path is not None:
            from pathlib import Path

            from ozone_tpu.utils.upgrade import (
                LayoutVersionManager,
                UpgradeFinalizer,
            )

            self.layout = LayoutVersionManager(
                Path(db_path).parent / "layout_version.json"
            )
            # ONE persistent finalizer so future features can register
            # migration actions on it (BasicUpgradeFinalizer contract)
            self.finalizer = UpgradeFinalizer(self.layout)
        self.replication = ReplicationManager(
            self.containers, self.nodes, self.placement
        )
        from ozone_tpu.scm.balancer import ContainerBalancer
        from ozone_tpu.scm.block_deletion import (
            BlockDeletingService,
            DeletedBlockLog,
        )
        from ozone_tpu.scm.decommission import DecommissionMonitor

        self.balancer = ContainerBalancer(self.containers, self.nodes)
        # resume a persisted balancing run (the reference's
        # StatefulServiceStateManager read at ContainerBalancer start,
        # ContainerBalancer.java:391): config + progress counters come
        # back from the replicated store; the running flag itself is
        # always read live from it (see balancer_enabled)
        self._hydrate_balancer_from_state()
        self.decommission_monitor = DecommissionMonitor(
            self.nodes, self.containers, self.replication
        )
        self.deleted_blocks = DeletedBlockLog()
        self.block_deleting = BlockDeletingService(
            self.deleted_blocks, self.nodes
        )
        self.metrics = MetricsRegistry("scm")
        self.events.subscribe(nm.DEAD_NODE, self._on_dead_node)
        self._bg: Optional[threading.Thread] = None
        self._stop = threading.Event()

    # ------------------------------------------------------------- datanodes
    def register_datanode(
        self, dn_id: str, rack: str = "/default-rack",
        capacity_bytes: int = 0, op_state=None,
    ) -> None:
        self.nodes.register(dn_id, rack, capacity_bytes, op_state=op_state)
        self.metrics.counter("registrations").inc()

    def heartbeat(
        self,
        dn_id: str,
        container_report: Optional[list[dict]] = None,
        used_bytes: int = 0,
        deleted_block_acks: Optional[list[int]] = None,
        layout_version: Optional[int] = None,
        healthy_volumes: Optional[int] = None,
    ) -> list:
        """Process a heartbeat (+optional full container report and block-
        deletion acks); return the commands queued for this datanode."""
        if deleted_block_acks:
            self.deleted_blocks.ack(dn_id, deleted_block_acks)
        if container_report is not None:
            self.containers.process_container_report(dn_id, container_report)
            # CLOSING -> CLOSED once replicas report closed
            for r in container_report:
                c = self.containers.get_or_none(int(r["container_id"]))
                if (
                    c is not None
                    and r["state"] in ("CLOSED", "QUASI_CLOSED")
                    and c.state.value in ("OPEN", "CLOSING")
                ):
                    self.containers.mark_closed(c.id)
        self.metrics.counter("heartbeats").inc()
        if layout_version is not None or healthy_volumes is not None:
            n = self.nodes.get(dn_id)
            if n is not None:
                if layout_version is not None:
                    n.layout_version = int(layout_version)
                if healthy_volumes is not None:
                    n.healthy_volumes = int(healthy_volumes)
        return self.nodes.process_heartbeat(dn_id, used_bytes)

    def _on_dead_node(self, dn_id: str) -> None:
        # events are published outside the NodeManager lock (deadlock
        # avoidance), so the node may have heartbeated back between the
        # transition and this dispatch — re-validate before purging a
        # healthy node's replica records
        n = self.nodes.get(dn_id)
        if n is None or n.state is not nm.NodeState.DEAD:
            log.info("node %s recovered before dead-node handling; skipped",
                     dn_id)
            return
        affected = self.containers.remove_replicas_of_node(dn_id)
        log.info("node %s dead; %d containers affected", dn_id, len(affected))
        self.metrics.counter("dead_nodes").inc()

    # ------------------------------------------------------------- allocation
    def allocate_block(
        self,
        replication: ReplicationConfig,
        block_size: int,
        excluded: Optional[list[str]] = None,
        excluded_containers: Optional[list[int]] = None,
    ) -> BlockGroup:
        self.safemode.check_allocation_allowed()
        g = self.containers.allocate_block(replication, block_size, excluded,
                                           excluded_containers)
        self.metrics.counter("blocks_allocated").inc()
        return g

    def delete_blocks(self, entries: list[tuple]) -> list[int]:
        """OM -> SCM deletion handoff (ScmBlockLocationProtocol
        .deleteKeyBlocks analog): entries of (BlockID, datanode ids)."""
        tx_ids = [
            self.deleted_blocks.add(bid, nodes) for bid, nodes in entries
        ]
        self.metrics.counter("block_delete_txs").inc(len(tx_ids))
        return tx_ids

    # -------------------------------------------------------------- listings
    def list_containers(self) -> list[dict]:
        """Every container with its replicas, as plain values: what the
        ListContainers RPC answers (`ozone admin container list`
        analog) and what repair tools plan from, in-process or remote."""
        return [
            {
                "id": c.id,
                "state": c.state.value,
                "replication": str(c.replication),
                "nodes": c.pipeline.nodes if c.pipeline else [],
                "used_bytes": c.used_bytes,
                # snapshot: heartbeat threads mutate replicas live
                "replicas": [
                    {"dn_id": r.dn_id, "state": r.state,
                     "replica_index": r.replica_index}
                    for r in list(c.replicas.values())
                ],
            }
            for c in self.containers.containers()
        ]

    def list_nodes(self) -> list[dict]:
        """Every registered datanode as plain values (the `nodes` of the
        Status RPC; `ozone admin datanode list` / `usageinfo` analog)."""
        return [
            {
                "dn_id": n.dn_id,
                "rack": n.rack,
                "state": n.state.value,
                "op_state": n.op_state.value,
                "capacity_bytes": n.capacity_bytes,
                "used_bytes": n.used_bytes,
                "used_pct": round(
                    100.0 * n.used_bytes / n.capacity_bytes, 2)
                if n.capacity_bytes else None,
                "healthy_volumes": n.healthy_volumes,
                "layout_version": n.layout_version,
            }
            for n in self.nodes.nodes()
        ]

    # ------------------------------------------------------------- admin ops
    def decommission(self, dn_id: str) -> None:
        """Start draining a node (NodeDecommissionManager.java:60): out of
        placement; the replication manager re-protects its containers and
        the monitor finalizes once drained."""
        self.decommission_monitor.start_decommission(dn_id)

    def apply_admin_op(self, op: str, target=None) -> dict:
        """Deterministic admin mutation + state read-back. One function
        serves both the direct (single-node) path and the HA ring's
        replicated apply, so every replica ends in the same state
        (`ozone admin` node/balancer/safemode verbs)."""
        from ozone_tpu.storage.ids import ContainerState, StorageError

        if op in ("decommission", "recommission", "maintenance"):
            node = self.nodes.get(target) if target else None
            if node is None:
                raise StorageError("NODE_NOT_FOUND",
                                   f"unknown datanode {target!r}")
            if op == "decommission":
                self.decommission(target)
            elif op == "recommission":
                self.decommission_monitor.recommission(target)
            else:
                self.decommission_monitor.start_maintenance(target)
            return {"node": target, "op_state": node.op_state.value}
        if op == "finalize-upgrade":
            state = None
            if self.finalizer is not None:
                state = self.finalizer.finalize().value
            for n in self.nodes.nodes():
                self.nodes.queue_command(n.dn_id, {"type": "finalize"})
            return {"scm": state,
                    "datanodes_notified": self.nodes.node_count()}
        def _numeric_id(kind: str) -> int:
            try:
                return int(target)
            except (TypeError, ValueError):
                raise StorageError("INVALID",
                                   f"{kind} id must be numeric: "
                                   f"{target!r}")

        if op == "close-container":
            cid = _numeric_id("container")
            c = self.containers.get_or_none(cid)
            if c is None:
                raise StorageError("CONTAINER_NOT_FOUND",
                                   f"unknown container {target!r}")

            if c.state is ContainerState.OPEN:
                # the normal close flow: CLOSING + close commands to the
                # replicas; convergence marks it CLOSED
                self.containers.finalize_container(c.id)
            return {"container": c.id, "state": c.state.value}
        if op == "close-pipeline":
            # ozone admin pipeline close <id>: pipelines are 1:1 with
            # their container here, so closing the pipeline finalizes
            # the container (writes stop, members drop the raft group)
            pid = _numeric_id("pipeline")
            for c in self.containers.containers():
                if c.pipeline is not None and c.pipeline.id == pid:
                    if c.state is ContainerState.OPEN:
                        self.containers.finalize_container(c.id)
                    return {"pipeline": pid, "container": c.id,
                            "state": c.state.value}
            raise StorageError("PIPELINE_NOT_FOUND",
                               f"unknown pipeline {target!r}")
        if op == "import-secret-key":
            # token secret-key rotation decision (possibly replicated
            # through the HA ring): install the material on this replica
            from ozone_tpu.utils.security import SecretKey

            self.secret_keys.import_key(SecretKey.from_json(target))
            return {"key_id": target["key_id"]}
        if op == "balancer-start":
            if isinstance(target, dict):
                # operator config overrides ride the replicated admin
                # decision, so every replica balances identically
                self._apply_balancer_config(target)
            self.balancer_enabled = True
        elif op == "balancer-stop":
            self.balancer_enabled = False
        elif op == "safemode-enter":
            self.safemode.force(True)
        elif op == "safemode-exit":
            self.safemode.force(False)
        else:
            raise StorageError("UNSUPPORTED_REQUEST", f"admin op {op!r}")
        if op.startswith("balancer"):
            return self.balancer_status()
        return {"safemode": self.safemode.in_safemode(),
                **self.safemode.status()}

    # ------------------------------------------------------------- balancer
    def _apply_balancer_config(self, src: dict) -> None:
        """Copy config knobs present in `src` onto the live config — the
        ONE field list (dataclasses.fields) shared by operator override,
        row hydration, and persistence, so a new knob cannot silently
        drop out of one of them."""
        import dataclasses

        cfg = self.balancer.config
        for f in dataclasses.fields(cfg):
            if f.name in src:
                cur = getattr(cfg, f.name)
                setattr(cfg, f.name, type(cur)(src[f.name]))

    def _hydrate_balancer_from_state(self) -> None:
        """Pull the replicated service row into the live balancer. The
        row is authoritative for CONFIG (a promoted follower's in-memory
        balancer still holds defaults — using them would clobber the
        operator's replicated settings); progress counters take the max
        of memory and row so an idle leader's unpersisted iteration
        count is never rolled back."""
        svc = self.containers.service_state("balancer")
        if not svc:
            return
        self._apply_balancer_config(svc)
        st = self.balancer.status
        st.iterations = max(st.iterations, int(svc.get("iterations", 0)))
        st.moves_scheduled = max(
            st.moves_scheduled, int(svc.get("moves_scheduled", 0)))
        st.bytes_scheduled = max(
            st.bytes_scheduled, int(svc.get("bytes_scheduled", 0)))

    @property
    def balancer_enabled(self) -> bool:
        """Live view of the persisted running flag: replicas learn it
        through the replicated service-state row, so a promoted follower
        resumes balancing without any re-start command."""
        svc = self.containers.service_state("balancer")
        return bool(svc and svc.get("running"))

    @balancer_enabled.setter
    def balancer_enabled(self, running: bool) -> None:
        self._persist_balancer_state(running=bool(running))

    def _persist_balancer_state(self, running=None) -> None:
        """Write the balancer's StatefulService record (config + progress,
        ContainerBalancer.java:281 saveConfiguration) through the store so
        restart and failover resume mid-run."""
        import dataclasses

        svc = self.containers.service_state("balancer") or {}
        if running is None:
            running = bool(svc.get("running"))
        st = self.balancer.status
        self.containers.persist_service_state("balancer", {
            "running": bool(running),
            **dataclasses.asdict(self.balancer.config),
            "iterations": st.iterations,
            "moves_scheduled": st.moves_scheduled,
            "bytes_scheduled": st.bytes_scheduled,
        })

    def balancer_status(self) -> dict:
        """Live progress: in-memory counters run ahead of the persisted
        row on move-less iterations (which are not persisted), so report
        whichever is larger — status must not look frozen while
        running."""
        svc = self.containers.service_state("balancer") or {}
        st = self.balancer.status
        return {
            "running": self.balancer_enabled,
            "iterations": max(st.iterations,
                              int(svc.get("iterations", 0))),
            "moves_scheduled": max(st.moves_scheduled,
                                   int(svc.get("moves_scheduled", 0))),
            "bytes_scheduled": max(st.bytes_scheduled,
                                   int(svc.get("bytes_scheduled", 0))),
            "threshold": float(
                svc.get("threshold", self.balancer.config.threshold)),
        }

    # ------------------------------------------------------------- security
    def ensure_secret_key(self) -> None:
        """Mint/rotate the token-signing key when due. Single-node
        installs directly; under HA the daemon's on_secret_rotate hook
        replicates the material through the metadata ring so every
        replica (and thus every OM issuer) signs with the same keys."""
        if not self.block_tokens or not self.secret_keys.needs_rotation():
            return
        key = self.secret_keys.new_key()
        if self.on_secret_rotate is not None:
            self.on_secret_rotate(key)
        else:
            self.secret_keys.import_key(key)

    # ------------------------------------------------------------- background
    def run_background_once(self) -> None:
        """One tick of the SCM control loops (liveness + replication +
        decommission + balancer)."""
        self.ensure_secret_key()
        self.nodes.check_liveness()
        if not self.safemode.in_safemode():
            self.replication.run_once()
            self.decommission_monitor.run_once()
            self.block_deleting.run_once()
            self.containers.resend_closing()
            if self.balancer_enabled:
                # replicated row first: a freshly promoted follower must
                # balance with the operator's config, not defaults
                self._hydrate_balancer_from_state()
                moves = self.balancer.run_iteration()
                if moves:
                    # persist progress only when something was scheduled —
                    # an idle tick must not append a WAL/replication
                    # record every second
                    self._persist_balancer_state()

    def start_background(self, interval_s: float = 1.0) -> None:
        def loop():
            while not self._stop.wait(interval_s):
                try:
                    self.run_background_once()
                except Exception:
                    log.exception("scm background tick failed")

        self._bg = threading.Thread(target=loop, name="scm-bg", daemon=True)
        self._bg.start()

    def stop(self) -> None:
        self._stop.set()
        if self._bg:
            self._bg.join(timeout=5)
