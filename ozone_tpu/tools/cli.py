"""ozone-tpu CLI: shell, admin, freon, daemons, debug.

Mirror of the reference's CLI surface (hadoop-ozone/tools shell/
OzoneShell.java `ozone sh` volume/bucket/key verbs; `ozone admin`
safemode/datanode/container commands; `ozone freon` generators;
`ozone debug`; service starters). Talks to a running cluster over gRPC.

Usage examples:
  ozone-tpu scm-om --db /data/om.db --port 9860
  ozone-tpu datanode --root /data/dn1 --scm 127.0.0.1:9860
  ozone-tpu sh volume create /vol1 --om 127.0.0.1:9860
  ozone-tpu sh key put /vol1/bucket1/key1 ./file --om ...
  ozone-tpu admin safemode status --om ...
  ozone-tpu freon ockg -n 1000 -s 1048576 --om ...
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
from pathlib import Path

import numpy as np

from ozone_tpu.storage.ids import StorageError


def _client_tls():
    """CLI mTLS material for secure clusters, driven by environment:
    OZONE_TPU_CERT_DIR (where the client keypair/cert live) plus, for
    first contact, OZONE_TPU_ENROLL (the SCM enrollment address) and
    optional OZONE_TPU_ENROLL_SECRET."""
    import os

    cert_dir = os.environ.get("OZONE_TPU_CERT_DIR")
    if not cert_dir:
        return None
    from ozone_tpu.utils.ca import CertificateClient

    cc = CertificateClient(Path(cert_dir), "client-cli")
    if not cc.enrolled:
        enroll = os.environ.get("OZONE_TPU_ENROLL")
        if not enroll:
            print("error: OZONE_TPU_CERT_DIR set but not enrolled; set "
                  "OZONE_TPU_ENROLL to the SCM enrollment address",
                  file=sys.stderr)
            sys.exit(1)
        cc.enroll_remote(enroll,
                         secret=os.environ.get("OZONE_TPU_ENROLL_SECRET"))
    return cc.tls()


def _client(args):
    from ozone_tpu.client.dn_client import DatanodeClientFactory
    from ozone_tpu.client.ozone_client import OzoneClient
    from ozone_tpu.net.om_service import GrpcOmClient

    tls = _client_tls()
    clients = DatanodeClientFactory()
    clients.tls = tls
    om = GrpcOmClient(args.om, clients=clients, tls=tls)
    # learn datanode addresses up front
    from ozone_tpu.net.scm_service import AdminTokenFetcher, GrpcScmClient

    import os

    clients.location = os.environ.get("OZONE_TPU_CLIENT_LOCATION")
    try:
        scm = GrpcScmClient(args.om, tls=tls)
        addresses, locations = scm.node_topology()
        for dn_id, addr in addresses.items():
            clients.register_remote(dn_id, addr)
        clients.learn_locations(locations)
        if scm.status().get("block_tokens"):
            # dn-direct debug/repair verbs fetch operator tokens from
            # the SCM instead of holding the secret keys
            clients.tokens.issuer = AdminTokenFetcher(scm)
    except Exception:
        pass
    from ozone_tpu.net.ratis_service import RatisClientFactory

    ratis = RatisClientFactory(address_source=clients.remote_address)
    ratis.tls = tls
    return OzoneClient(om, clients, ratis_clients=ratis)


def _serve(stop_fn) -> int:
    """Run a daemon until SIGTERM/SIGINT, then shut it down cleanly —
    a TERM'd daemon must flush buffered state (OM double buffer) before
    the process dies."""
    import signal

    done = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: done.set())
    try:
        while not done.wait(3600):
            pass
    except KeyboardInterrupt:
        pass
    finally:
        stop_fn()
    return 0


def _parse_path(path: str) -> list[str]:
    return [p for p in path.strip("/").split("/") if p]


def _emit(obj) -> None:
    print(json.dumps(obj, indent=2, default=str))


def _quota_arg(v: str):
    """'10MB'/'1073741824' -> bytes; '' -> None (leave unchanged);
    'clear' -> -1 (unlimited)."""
    from ozone_tpu.utils.config import parse_size

    if not v:
        return None
    if v == "clear":
        return -1
    return int(parse_size(v))


#: verbs valid per sh object; anything else errors instead of no-opping
_SH_VERBS = {
    "volume": {"create", "delete", "info", "list", "setquota", "update"},
    "bucket": {"create", "delete", "info", "list", "setquota", "link",
               "set-replication", "set-smallobj"},
    "key": {"put", "get", "delete", "info", "list", "rename", "checksum",
            "cat", "cp", "rewrite"},
    "snapshot": {"create", "list", "info", "delete", "diff", "rename"},
    "token": {"get", "renew", "cancel", "print"},
}


def _sh_token(args, verb: str) -> int:
    """`ozone sh token get|renew|cancel|print` (reference shell token
    verbs over OzoneManager.getDelegationToken/renew/cancel). Tokens are
    portable JSON files; --token names the file, --renewer the renewer
    principal on get."""
    from ozone_tpu.net.om_service import GrpcOmClient

    def _read_token():
        if not args.token:
            print("error: --token FILE required", file=sys.stderr)
            return None
        try:
            with open(args.token) as f:
                return json.load(f)
        except (OSError, ValueError) as e:
            print(f"error: cannot read token file {args.token}: {e}",
                  file=sys.stderr)
            return None

    if verb == "print":
        tok = _read_token()
        if tok is None:
            return 2
        _emit(tok)
        return 0
    om = GrpcOmClient(args.om, tls=_client_tls())
    if verb == "get":
        if not args.renewer:
            print("error: --renewer required", file=sys.stderr)
            return 2
        # the token's owner is the local OS user (the reference binds
        # the Kerberos principal; the CLI analog is the login identity)
        import getpass

        with om.user_context(getpass.getuser()):
            tok = om.get_delegation_token(args.renewer)
        if args.token:
            with open(args.token, "w") as f:
                json.dump(tok, f)
            print(f"token written to {args.token}")
        else:
            _emit(tok)
        return 0
    tok = _read_token()
    if tok is None:
        return 2
    # renew/cancel require an authenticated caller (the OM refuses
    # anonymous remote renewals — an unauthenticated holder of the token
    # file must not be able to extend or revoke it); the CLI's identity
    # is the login user, same convention as `get`
    import getpass

    with om.user_context(getpass.getuser()):
        if verb == "renew":
            _emit({"expiry": om.renew_delegation_token(tok)})
        elif verb == "cancel":
            om.cancel_delegation_token(tok)
            print("token cancelled")
    return 0


# ---------------------------------------------------------------------- sh
def cmd_sh(args) -> int:
    kind, verb = args.object, args.verb
    if verb not in _SH_VERBS[kind]:
        print(f"error: '{verb}' is not a {kind} verb (expected one of "
              f"{sorted(_SH_VERBS[kind])})", file=sys.stderr)
        return 2
    if kind == "token":
        return _sh_token(args, verb)
    if not args.path:
        print(f"error: {kind} {verb} requires a /volume[/bucket[/key]] "
              f"path", file=sys.stderr)
        return 2
    oz = _client(args)
    parts = _parse_path(args.path)
    if kind == "volume":
        if verb == "list":  # accepts "/" (no volume component)
            _emit(oz.list_volumes())
            return 0
        (vol,) = parts
        if verb == "create":
            oz.create_volume(vol)
        elif verb == "delete":
            oz.om.delete_volume(vol)
        elif verb == "info":
            _emit(oz.om.volume_info(vol))
        elif verb == "setquota":
            _emit(oz.om.set_quota(
                vol, quota_bytes=_quota_arg(args.quota),
                quota_namespace=args.namespace_quota))
        elif verb == "update":
            if not args.user:
                print("error: volume update requires --user NEWOWNER",
                      file=sys.stderr)
                return 2
            _emit(oz.om.set_volume_owner(vol, args.user))
    elif kind == "bucket":
        if verb == "list":
            (vol,) = parts
            _emit(oz.om.list_buckets(vol))
        else:
            vol, bucket = parts
            if verb == "create":
                oz.om.create_bucket(vol, bucket, args.replication,
                                    layout=args.layout,
                                    encryption_key=args.encryption_key,
                                    gdpr=args.gdpr)
            elif verb == "delete":
                oz.om.delete_bucket(vol, bucket)
            elif verb == "info":
                _emit(oz.om.bucket_info(vol, bucket))
            elif verb == "setquota":
                _emit(oz.om.set_quota(
                    vol, bucket, quota_bytes=_quota_arg(args.quota),
                    quota_namespace=args.namespace_quota))
            elif verb == "set-smallobj":
                _emit(oz.om.set_bucket_smallobj(vol, bucket))
            elif verb == "link":
                if not args.to:
                    print("error: bucket link requires --to "
                          "/volume/bucket", file=sys.stderr)
                    return 1
                dvol, dbkt = _parse_path(args.to)
                oz.om.create_bucket_link(vol, bucket, dvol, dbkt)
                print(f"linked /{dvol}/{dbkt} -> /{vol}/{bucket}")
            elif verb == "set-replication":
                if not args.replication:
                    print("error: set-replication requires "
                          "--replication", file=sys.stderr)
                    return 2
                b = oz.om.set_bucket_replication(vol, bucket,
                                                 args.replication)
                _emit({"bucket": f"/{vol}/{bucket}",
                       "replication": b["replication"]})
    elif kind == "snapshot":
        if verb == "list":
            vol, bucket = parts
            _emit(oz.om.list_snapshots(vol, bucket))
        elif verb == "diff":
            vol, bucket = parts
            if not args.name:
                print("error: snapshot diff requires --name",
                      file=sys.stderr)
                return 1
            if args.page_size:
                # job-based paged flow (SnapshotDiffManager job model):
                # submit, poll to a terminal state, stream pages
                import time as _time

                job = oz.om.snapshot_diff_submit(vol, bucket, args.name,
                                                 args.to or None)
                deadline = _time.time() + 300
                while (job["status"] == "IN_PROGRESS"
                       and _time.time() < deadline):
                    _time.sleep(0.1)
                    job = oz.om.snapshot_diff_submit(
                        vol, bucket, args.name, args.to or None)
                if job["status"] != "DONE":
                    _emit(job)
                    return 1
                token = ""
                while True:
                    page = oz.om.snapshot_diff_page(
                        job["job_id"], token, args.page_size)
                    for e in page["entries"]:
                        print(json.dumps(e))
                    token = page["next_token"]
                    if not token:
                        break
                print(json.dumps({"job_id": job["job_id"],
                                  "total": page["total"],
                                  "mode": page["mode"]}),
                      file=sys.stderr)
            else:
                _emit(oz.om.snapshot_diff(vol, bucket, args.name,
                                          args.to or None))
        else:
            vol, bucket = parts
            if not args.name:
                print(f"error: snapshot {verb} requires --name",
                      file=sys.stderr)
                return 1
            if verb == "create":
                _emit(oz.om.create_snapshot(vol, bucket, args.name))
            elif verb == "rename":
                if not args.to:
                    print("error: snapshot rename requires --to",
                          file=sys.stderr)
                    return 1
                _emit(oz.om.rename_snapshot(vol, bucket, args.name,
                                            args.to))
            elif verb == "info":
                _emit(oz.om.snapshot_info(vol, bucket, args.name))
            elif verb == "delete":
                oz.om.delete_snapshot(vol, bucket, args.name)
                print(f"deleted snapshot {args.name}")
    elif kind == "key":
        if verb == "list":
            vol, bucket = parts
            _emit(oz.om.list_keys(vol, bucket, args.prefix,
                                  args.start_after, args.limit))
            return 0
        vol, bucket, *rest = parts
        key = "/".join(rest)
        b = oz.get_volume(vol).get_bucket(bucket)
        if verb == "put":
            data = Path(args.file).read_bytes()
            b.write_key(key, np.frombuffer(data, np.uint8),
                        args.replication if args.replication else None)
            print(f"wrote {len(data)} bytes to {args.path}")
        elif verb == "get":
            if args.offset or args.length is not None:
                info = b.lookup_key_info(key)
                size = int(info["size"])
                off = min(max(0, args.offset), size)
                ln = (size - off if args.length is None
                      else max(0, min(args.length, size - off)))
                data = b.read_key_info_range(info, off, ln)
            else:
                data = b.read_key(key)
            out = Path(args.file) if args.file else None
            if out:
                out.write_bytes(data.tobytes())
                print(f"read {data.size} bytes to {out}")
            else:
                sys.stdout.buffer.write(data.tobytes())
        elif verb == "delete":
            b.delete_key(key)
        elif verb == "info":
            _emit(oz.om.lookup_key(vol, bucket, key))
        elif verb == "checksum":
            _emit(b.file_checksum(key))
        elif verb == "rename":
            b.rename_key(key, args.to)
        elif verb == "cat":
            sys.stdout.buffer.write(b.read_key(key).tobytes())
        elif verb == "cp":
            if not args.to:
                print("error: cp requires --to /volume/bucket/key",
                      file=sys.stderr)
                return 2
            dparts = _parse_path(args.to)
            if len(dparts) < 3:
                print("error: cp --to needs a full /volume/bucket/key "
                      f"path, got {args.to!r}", file=sys.stderr)
                return 2
            dv, db_, *drest = dparts
            b.copy_key(key, oz.get_volume(dv).get_bucket(db_),
                       "/".join(drest),
                       replication=args.replication or None)
            print(f"copied {args.path} to {args.to}")
        elif verb == "rewrite":
            if not args.replication:
                print("error: rewrite requires --replication",
                      file=sys.stderr)
                return 2
            b.rewrite_key(key, args.replication)
            print(f"rewrote {args.path} as {args.replication}")
    return 0


# ---------------------------------------------------------------- acl/tenant
def cmd_acl(args) -> int:
    """Native ACL verbs (reference: ozone sh volume|bucket|key|prefix
    addacl/removeacl/setacl/getacl)."""
    oz = _client(args)
    parts = _parse_path(args.path)
    vol = parts[0]
    bucket = parts[1] if len(parts) > 1 else ""
    path = "/".join(parts[2:]) if len(parts) > 2 else ""
    if args.verb == "get":
        _emit(oz.om.get_acls(args.object, vol, bucket, path))
    else:
        op = {"add": "add", "remove": "remove", "set": "set"}[args.verb]
        changed = oz.om.modify_acl(args.object, vol, bucket, path, op,
                                   args.acl)
        print("changed" if changed else "unchanged")
    return 0


def cmd_tenant(args) -> int:
    """Tenant admin verbs (reference: ozone tenant create/delete/list,
    ozone tenant user assign/revoke/list)."""
    oz = _client(args)
    om = oz.om
    if args.verb == "create":
        om.create_tenant(args.tenant)
        print(f"tenant {args.tenant} created")
    elif args.verb == "delete":
        om.delete_tenant(args.tenant)
        print(f"tenant {args.tenant} deleted")
    elif args.verb == "list":
        _emit(om.list_tenants())
    elif args.verb == "assign":
        _emit(om.tenant_assign_user(args.tenant, args.user))
    elif args.verb == "revoke":
        om.tenant_revoke_access(args.access_id)
        print(f"revoked {args.access_id}")
    elif args.verb == "users":
        _emit(om.list_tenant_users(args.tenant))
    return 0


# ---------------------------------------------------------------------- fs
def cmd_fs(args) -> int:
    """Filesystem verbs against FSO buckets (reference: ozone fs via the
    Hadoop shell — mkdir/ls/stat/rm on o3fs paths)."""
    oz = _client(args)
    vol, bucket, *rest = _parse_path(args.path)
    path = "/".join(rest)
    om = oz.om
    if args.verb == "mkdir":
        om.create_directory(vol, bucket, path)
        print(f"created directory /{vol}/{bucket}/{path}")
    elif args.verb == "ls":
        _emit(om.list_status(vol, bucket, path))
    elif args.verb == "stat":
        _emit(om.get_file_status(vol, bucket, path))
    elif args.verb == "rm":
        st = om.get_file_status(vol, bucket, path)
        if st["type"] == "DIRECTORY":
            om.delete_directory(vol, bucket, path, recursive=args.recursive)
        else:
            om.delete_key(vol, bucket, path)
        print(f"deleted /{vol}/{bucket}/{path}")
    elif args.verb == "recover-lease":
        _emit(om.recover_lease(vol, bucket, path))
    return 0


def _cmd_audit(args) -> int:
    from ozone_tpu.tools.audit_parser import run_cli

    return run_cli(args)


# -------------------------------------------------------------------- admin
def cmd_admin(args) -> int:
    from ozone_tpu.net.scm_service import GrpcScmClient

    def usage(msg: str) -> int:
        print(f"error: {msg}", file=sys.stderr)
        return 2

    scm = GrpcScmClient(args.om, tls=_client_tls())
    subject, verb, target = args.subject, args.verb, args.target
    if subject == "safemode":
        if verb in ("enter", "exit"):
            _emit(scm.admin(f"safemode-{verb}"))
        elif verb in (None, "status"):
            st = scm.status()
            _emit({"safemode": st["safemode"], **st["safemode_status"]})
        else:
            return usage(f"unknown safemode verb {verb!r} "
                         "(expected enter|exit|status)")
    elif subject == "datanode":
        if verb in ("decommission", "recommission", "maintenance"):
            if not target:
                return usage(f"datanode {verb} needs a datanode id")
            _emit(scm.admin(verb, target))
        elif verb in (None, "list"):
            _emit(scm.status()["nodes"])
        else:
            return usage(f"unknown datanode verb {verb!r} (expected "
                         "list|decommission|recommission|maintenance)")
    elif subject == "pipeline":
        if verb == "close":
            if not target:
                return usage("pipeline close requires a pipeline id")
            _emit(scm.admin("close-pipeline", target))
        elif verb in (None, "list"):
            _emit(scm.admin("pipelines"))
        else:
            return usage(f"unknown pipeline verb {verb!r} "
                         "(expected list|close)")
    elif subject == "upgrade":
        # finalization progress view (`ozone admin scm finalizationstatus`
        # analog): which layout features are live vs gated
        _emit(scm.admin("upgrade-status"))
    elif subject == "finalizeupgrade":
        # non-rolling upgrade completion (ozone admin scm
        # finalizeupgrade analog): bump the metadata services' layout
        # and command every datanode to finalize
        _emit(scm.admin("finalize-upgrade"))
    elif subject == "container":
        if verb == "close":
            if not target:
                return usage("container close requires a container id")
            _emit(scm.admin("close-container", target))
        elif verb == "info":
            if not target:
                return usage("container info requires a container id")
            _emit(scm.admin("container-info", target))
        elif verb == "report":
            # ReplicationManagerReport analog: state + health census
            _emit(scm.admin("container-report"))
        elif verb in (None, "list"):
            _emit(scm.list_containers())
        else:
            return usage(f"unknown container verb {verb!r} "
                         "(expected list|info <id>|report|close <id>)")
    elif subject == "balancer":
        if verb not in (None, "status", "start", "stop"):
            return usage(f"unknown balancer verb {verb!r} "
                         "(expected start|stop|status)")
        cfg = {}
        if args.threshold is not None:
            cfg["threshold"] = args.threshold
        if args.max_moves is not None:
            cfg["max_moves_per_iteration"] = args.max_moves
        if args.max_size is not None:
            cfg["max_size_per_iteration"] = args.max_size
        if cfg and verb != "start":
            # config only applies at start; silently dropping it would
            # leave the operator believing the settings took
            return usage("balancer config flags require the 'start' verb")
        _emit(scm.admin(f"balancer-{verb or 'status'}", cfg or None))
    elif subject == "replicationmanager":
        _emit(scm.admin("replication-status"))
    elif subject == "ring":
        # metadata-ring membership (OM bootstrap / decommission-OM
        # analog): add a started-but-empty replica, or retire one
        if verb == "add":
            if not target or "=" not in target:
                return usage("ring add needs <id>=<host:port>")
            _emit(scm.admin("ring-add", target))
        elif verb == "remove":
            if not target:
                return usage("ring remove needs the replica id")
            _emit(scm.admin("ring-remove", target))
        elif verb == "transfer":
            # `ozone admin om transfer --node` analog: planned
            # leadership hand-off to the named replica
            if not target:
                return usage("ring transfer needs the target replica id")
            _emit(scm.admin("ring-transfer", target))
        elif verb in (None, "status", "roles"):
            # `ozone admin om roles` analog: role/term/leader from the
            # replica that answered (any replica, incl. followers)
            _emit(scm.admin("ring-status"))
        else:
            return usage(f"unknown ring verb {verb!r} "
                         "(expected add <id>=<addr>|remove <id>|"
                         "transfer <id>|status)")
    elif subject == "cert":
        # CA lifecycle (ozone admin cert list/revoke analog): answered
        # by the replica hosting the cluster CA
        if verb in (None, "list"):
            _emit(scm.admin("cert-list", None))
        elif verb == "revoke":
            if not target:
                return usage("cert revoke needs the cert serial")
            _emit(scm.admin("cert-revoke", target))
        else:
            return usage(f"unknown cert verb {verb!r} "
                         "(expected list|revoke <serial>)")
    elif subject == "kms":
        # TDE master-key authority (ozone admin + KMS keyadmin analog)
        from ozone_tpu.net.om_service import GrpcOmClient

        om = GrpcOmClient(args.om, tls=_client_tls())
        if verb == "create-key":
            if not target:
                return usage("kms create-key needs a key name")
            _emit(om.kms_create_key(target))
        elif verb == "rotate-key":
            if not target:
                return usage("kms rotate-key needs a key name")
            _emit(om.kms_create_key(target, rotate=True))
        elif verb in (None, "list"):
            _emit(om.kms_list_keys())
        elif verb == "info":
            if not target:
                return usage("kms info needs a key name")
            _emit(om.kms_key_info(target))
        else:
            return usage(f"unknown kms verb {verb!r} (expected "
                         "create-key|rotate-key|list|info)")
    elif subject == "om":
        from ozone_tpu.net.om_service import GrpcOmClient

        om = GrpcOmClient(args.om, tls=_client_tls())
        if verb == "prepare":
            _emit(om.prepare())
        elif verb == "cancelprepare":
            om.cancel_prepare()
            _emit({"prepared": False})
        elif verb == "list-open-files":
            vol = bkt = ""
            if args.target:
                parts = _parse_path(args.target)
                vol = parts[0] if parts else ""
                bkt = parts[1] if len(parts) > 1 else ""
            _emit(om.list_open_files(
                vol, bkt, prefix=args.prefix,
                start_after=args.start_after,
                limit=args.limit if args.limit is not None else 100))
        elif verb in (None, "status"):
            _emit(om.prepare_status())
        else:
            return usage(f"unknown om verb {verb!r} "
                         "(expected prepare|cancelprepare|status|"
                         "list-open-files)")
    elif subject == "shards":
        # sharded metadata plane: show the root shard map (epoch,
        # slot ownership, address book) as any routing client sees it
        from ozone_tpu.net.om_service import GrpcOmClient
        from ozone_tpu.om.sharding.shardmap import ShardMap

        om = GrpcOmClient(args.om, tls=_client_tls(), shard_aware=False)
        try:
            if verb in (None, "map", "status"):
                mj = om.get_shard_map()
                if not mj:
                    print("no shard map installed (unsharded deployment)")
                    return 0
                m = ShardMap.from_json(mj)
                counts: dict[str, int] = {}
                for idx in m.slots:
                    sid = m.shards[idx]
                    counts[sid] = counts.get(sid, 0) + 1
                _emit({
                    "epoch": m.epoch,
                    "slot_count": len(m.slots),
                    "shards": sorted(counts),
                    "slots_per_shard": counts,
                    "addresses": m.addresses,
                })
            else:
                return usage(f"unknown shards verb {verb!r} "
                             "(expected map|status)")
        finally:
            om.close()
    elif subject == "namespace":
        # `ozone admin namespace summary <path>` analog: per-directory
        # du / entity counts from Recon's NSSummary warehouse
        import urllib.request
        from urllib.parse import quote

        if not args.http:
            print("error: namespace summary requires --http host:port "
                  "(the Recon endpoint)", file=sys.stderr)
            return 2
        # `admin namespace summary /vol/bucket/dir` (or the path given
        # directly as the verb slot — paths always start with /)
        if verb == "summary":
            path = target or "/"
        elif verb is None or verb.startswith("/"):
            path = verb or "/"
        else:
            return usage(f"unknown namespace verb {verb!r} "
                         "(expected: summary <path>)")
        url = (f"http://{args.http}/api/nssummary?path="
               f"{quote(path, safe='/')}")
        try:
            with urllib.request.urlopen(url, timeout=10) as r:
                print(r.read().decode())
        except urllib.error.HTTPError as e:
            print(f"error: {e.code} {e.read().decode()}", file=sys.stderr)
            return 1
        except urllib.error.URLError as e:
            print(f"error: cannot reach {args.http}: {e.reason}",
                  file=sys.stderr)
            return 1
        return 0
    elif subject == "reconfig":
        # live reconfiguration (ozone admin reconfig analog over the
        # daemon's /reconfig HTTP endpoint, ReconfigureProtocol.proto)
        import urllib.request
        from urllib.parse import quote

        if not args.http:
            print("error: reconfig requires --http host:port (the "
                  "daemon's HTTP/metrics port)", file=sys.stderr)
            return 2
        if verb in (None, "properties"):
            url = f"http://{args.http}/reconfig/properties"
        elif verb == "set":
            if not args.target or args.value is None:
                print("error: reconfig set needs a KEY target and "
                      "--value", file=sys.stderr)
                return 2
            url = (f"http://{args.http}/reconfig?key={quote(args.target)}"
                   f"&value={quote(args.value)}")
        else:
            return usage(f"unknown reconfig verb {verb!r} "
                         "(expected properties|set)")
        try:
            with urllib.request.urlopen(url, timeout=10) as r:
                body = r.read().decode()
        except urllib.error.HTTPError as e:
            print(f"error: {e.code} {e.read().decode()}", file=sys.stderr)
            return 1
        except urllib.error.URLError as e:
            print(f"error: cannot reach {args.http}: {e.reason}",
                  file=sys.stderr)
            return 1
        print(body)
        return 0
    elif subject == "status":
        _emit(scm.status())
    return 0


# -------------------------------------------------------------------- freon
def cmd_freon(args) -> int:
    """Run one generator, print its summary, and exit 1 when the
    summary counts any failed op — a load run that lost operations is
    not a success, whatever rate it printed."""
    from ozone_tpu.tools import freon
    from ozone_tpu.utils.compile_cache import count_compiles

    count_compiles()  # the summaries' device block reports them
    failed = False

    def emit(summary: dict) -> None:
        nonlocal failed
        _emit(summary)
        failed = failed or summary.get("failures", 0) > 0

    if args.generator == "ockg":
        oz = _client(args)
        rep = freon.ockg(
            oz, n_keys=args.num, size=args.size, threads=args.threads,
            replication=args.replication or None, validate=args.validate,
            warmup=args.warmup,
        )
        emit(rep.summary())
    elif args.generator == "ockr":
        oz = _client(args)
        emit(freon.ockr(oz, args.num, threads=args.threads).summary())
    elif args.generator == "ockrr":
        oz = _client(args)
        emit(freon.ockrr(oz, args.num, size=args.size,
                         threads=args.threads,
                         n_keys=args.keys).summary())
    elif args.generator == "ockv":
        oz = _client(args)
        emit(freon.ockv(oz, n_keys=args.num, size=args.size,
                        threads=args.threads).summary())
    elif args.generator == "fskg":
        oz = _client(args)
        emit(freon.fskg(
            oz, n_files=args.num, size=args.size, threads=args.threads,
            replication=args.replication or None,
        ).summary())
    elif args.generator == "mpug":
        oz = _client(args)
        emit(freon.mpug(
            oz, n_uploads=args.num, part_size=args.size,
            threads=args.threads,
            replication=args.replication or None,
        ).summary())
    elif args.generator == "fsg":
        emit(freon.fsg(
            _client(args), n_files=args.num, size=args.size,
            threads=args.threads,
            replication=args.replication or None).summary())
    elif args.generator == "ecrd":
        from ozone_tpu.net.scm_service import GrpcScmClient

        scm = GrpcScmClient(args.om, tls=_client_tls())
        emit(freon.ecrd(
            _client(args), scm, size=args.size, rounds=args.num,
            replication=args.replication or "rs-6-3-1048576",
        ))
    elif args.generator == "sdg":
        # -t is deliberately not honored: the snapshot chain is ordered
        emit(freon.sdg(
            _client(args), n_rounds=args.num, size=args.size,
            replication=args.replication or None).summary())
    elif args.generator == "s3kg":
        emit(freon.s3kg(
            args.endpoint, n_keys=args.num, size=args.size,
            threads=args.threads, validate=args.validate,
        ).summary())
    elif args.generator == "swarm":
        # closed-loop multi-tenant overload swarm against the S3
        # gateway; anonymous tenants from the CLI (signed tenants need
        # OM-provisioned credentials — the bench wires those)
        tenants = [{"name": f"tenant-{i}", "rate": 0.0}
                   for i in range(max(1, args.threads))]
        emit(freon.swarm(
            args.endpoint, tenants, duration_s=args.duration,
            n_keys=args.num, tiny=args.tiny,
        ).summary())
    elif args.generator == "tinyg":
        oz = _client(args)
        emit(freon.tinyg(
            oz, n_keys=args.num, size=args.size, threads=args.threads,
            replication=args.replication or "rs-3-2-4096",
            packer=not args.no_packer, mix=args.tiny,
            validate=args.validate,
        ).summary())
    elif args.generator == "lcg":
        oz = _client(args)
        emit(freon.lcg(
            oz, n_keys=args.num, size=args.size, threads=args.threads,
            replication=args.replication or "RATIS/THREE",
            target=args.target,
        ).summary())
    elif args.generator == "geo":
        if not args.dest:
            print("error: freon geo needs --dest HOST:PORT (the "
                  "destination cluster endpoint)", file=sys.stderr)
            return 1
        oz = _client(args)
        emit(freon.geo(
            oz, args.dest, n_keys=args.num, size=args.size,
            threads=args.threads,
            replication=args.replication or "RATIS/THREE",
            scheme=args.scheme,
        ).summary())
    elif args.generator == "hsg":
        oz = _client(args)
        emit(freon.hsg(
            oz, n_keys=args.num, size=args.size, threads=args.threads,
            replication=args.replication or "RATIS/THREE",
        ).summary())
    elif args.generator == "rawcoder":
        emit(
            freon.rawcoder_bench(
               schema=args.schema, cell=args.cell, batch=args.batch
            )
        )
    elif args.generator == "omkg":
        emit(freon.omkg(_client(args), n_keys=args.num,
                        threads=args.threads).summary())
    elif args.generator == "ommg":
        emit(freon.ommg(_client(args), n_ops=args.num,
                        threads=args.threads, mix=args.mix).summary())
    elif args.generator == "scmtb":
        emit(freon.scmtb(
            _client(args), n_blocks=args.num, threads=args.threads,
            replication=args.replication or "rs-3-2-4096",
        ).summary())
    elif args.generator == "dnsim":
        from ozone_tpu.net.scm_service import GrpcScmClient

        scm = GrpcScmClient(args.om, tls=_client_tls())
        emit(freon.dnsim(
            scm, n_datanodes=args.num, n_containers=args.containers,
            duration_s=args.duration, interval_s=args.interval,
            threads=args.threads,
        ).summary())
    elif args.generator == "cmdw":
        emit(freon.cmdw(args.root or "/tmp/ozone-cmdw", n_chunks=args.num,
                        size=args.size, threads=args.threads).summary())
    elif args.generator == "dbgen":
        emit(freon.dbgen(args.root or "/tmp/ozone-dbgen.db",
                         n_keys=args.num).summary())
    elif args.generator == "ralg":
        import tempfile

        root = args.root or tempfile.mkdtemp(prefix="ozone-ralg-")
        emit(freon.ralg(root, n_entries=args.num, size=args.size,
                        threads=args.threads).summary())
    elif args.generator in ("dcg", "dcb", "dcv", "dsg", "dnbp"):
        oz = _client(args)
        dn_ids = list(oz.clients.known_ids())
        if not dn_ids:
            print(f"error: no datanodes known (is the SCM at {args.om} "
                  "reachable?)", file=sys.stderr)
            return 1
        if args.generator == "dnbp":
            emit(freon.dnbp(oz.clients, dn_ids, args.num,
                            threads=args.threads).summary())
            return int(failed)
        gen = {"dcg": freon.dcg, "dcb": freon.dcb, "dcv": freon.dcv,
               "dsg": freon.dsg}[args.generator]
        emit(gen(oz.clients, dn_ids, args.num, size=args.size,
                 threads=args.threads).summary())
    return int(failed)


# ------------------------------------------------------------------ daemons
def cmd_datanode(args) -> int:
    import logging

    from ozone_tpu.net.daemons import DatanodeDaemon

    logging.basicConfig(level=logging.INFO)
    dn_id = args.id or Path(args.root).name
    d = DatanodeDaemon(
        Path(args.root), dn_id, args.scm, port=args.port, rack=args.rack,
        scan_interval_s=args.scan_interval,
        ca_address=args.ca or None,
        enrollment_secret=args.enrollment_secret or None,
        num_volumes=args.volumes,
        volume_policy=args.volume_policy,
        replication_bandwidth_mbps=args.replication_bandwidth_mbps,
    )
    d.start()
    print(f"datanode {dn_id} serving on {d.address}, scm={args.scm}")
    return _serve(d.stop)


def cmd_cluster(args) -> int:
    """One-command local cluster (the reference's docker-compose
    ozone/ cluster analog): spawns a scm-om subprocess and N datanode
    subprocesses under one supervisor, waits until healthy, prints the
    endpoints, serves until SIGTERM/Ctrl-C, then tears every child
    down. For demos and smoke runs, not production layout."""
    import os
    import signal
    import subprocess
    import tempfile
    import time as _time

    root = Path(args.root or tempfile.mkdtemp(prefix="ozone-cluster-"))
    root.mkdir(parents=True, exist_ok=True)
    # N datanodes on one host cannot share its chip: the daemons this
    # launcher spawns are pinned to the CPU and the chip is left to the
    # client or gateway. A datanode started by hand on its own host is
    # not pinned and uses its chip.
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(Path(__file__).resolve().parents[2]))
    procs: list = []

    def spawn(argv, log_name):
        logf = open(root / log_name, "w")
        p = subprocess.Popen(
            [sys.executable, "-m", "ozone_tpu.tools", *argv],
            stdout=logf, stderr=subprocess.STDOUT, env=env)
        procs.append(p)
        return p

    def teardown():
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()

    meta_args = ["scm-om", "--db", str(root / "om.db"),
                 "--port", str(args.port)]
    if args.http_port:
        meta_args += ["--http-port", str(args.http_port)]
    if args.recon_port:
        meta_args += ["--recon-port", str(args.recon_port)]
    spawn(meta_args, "scm-om.log")
    om = f"127.0.0.1:{args.port}"

    from ozone_tpu.net.scm_service import GrpcScmClient

    scm = GrpcScmClient(om)
    try:
        deadline = _time.time() + 60
        up = False
        while _time.time() < deadline:
            try:
                scm.status()
                up = True
                break
            except Exception:
                _time.sleep(0.5)
        if not up:
            teardown()
            print(f"error: metadata server did not come up (see "
                  f"{root}/scm-om.log)", file=sys.stderr)
            return 1
        for i in range(args.datanodes):
            spawn(["datanode", "--root", str(root / f"dn{i}"),
                   "--scm", om, "--id", f"dn{i}"], f"dn{i}.log")
        deadline = _time.time() + 60
        registered = False
        while _time.time() < deadline:
            try:
                st = scm.status()
                if len(st.get("nodes", [])) >= args.datanodes:
                    registered = True
                    break
            except Exception:
                pass
            _time.sleep(0.5)
        if not registered:
            teardown()
            print(f"error: datanodes did not register (see "
                  f"{root}/dn*.log)", file=sys.stderr)
            return 1
    except BaseException:
        teardown()
        raise
    finally:
        scm.close()
    print(f"cluster up: om={om} datanodes={args.datanodes} "
          f"root={root}")
    print(f"try: ozone-tpu sh volume create /v --om {om}")
    # _serve's own finally runs teardown; teardown is idempotent so a
    # second call on an exception path is safe but not needed here
    return _serve(teardown)


def cmd_scm_om(args) -> int:
    import logging

    from ozone_tpu.net.daemons import ScmOmDaemon

    logging.basicConfig(level=logging.INFO)
    ha_peers = None
    if args.peer:
        ha_peers = dict(p.split("=", 1) for p in args.peer)
        if not args.ha_id or args.ha_id not in ha_peers:
            print("--ha-id must name one of the --peer entries",
                  file=sys.stderr)
            return 1
    d = ScmOmDaemon(Path(args.db), port=args.port,
                    min_datanodes=args.min_datanodes,
                    http_port=args.http_port,
                    recon_port=args.recon_port,
                    ha_id=args.ha_id if ha_peers else None,
                    ha_peers=ha_peers,
                    block_tokens=args.block_tokens,
                    secure=args.secure,
                    enroll_port=args.enroll_port,
                    enrollment_secret=args.enrollment_secret or None,
                    ca_address=args.ca or None)
    d.start()
    print(f"scm+om serving on {d.address}"
          + (f" as HA node {args.ha_id}" if ha_peers else "")
          + (" [mTLS]" if d.tls is not None else "")
          + (f", enrollment on {d.enroll_address}" if d.enroll_server
             else "")
          + (f", http on {d.http.address}" if d.http else "")
          + (f", recon on {d.recon.address}" if d.recon else ""))
    return _serve(d.stop)


def cmd_s3g(args) -> int:
    """Run the S3 gateway daemon against a remote OM (reference:
    `ozone s3g`, s3gateway Gateway.java main)."""
    import logging

    from ozone_tpu.gateway.s3 import S3Gateway

    logging.basicConfig(level=logging.INFO)
    gw = S3Gateway(_client(args), port=args.port,
                   replication=args.replication,
                   require_auth=args.require_auth,
                   domain=args.domain or None)
    gw.start()
    print(f"s3 gateway serving on {gw.address}, om={args.om}")
    return _serve(gw.stop)


def cmd_httpfs(args) -> int:
    """Run the WebHDFS-compatible HttpFS gateway daemon (reference:
    `ozone httpfs`, httpfsgateway HttpFSServerWebServer)."""
    import logging

    from ozone_tpu.gateway.httpfs import HttpFSGateway

    logging.basicConfig(level=logging.INFO)
    gw = HttpFSGateway(_client(args), port=args.port,
                       replication=args.replication,
                       trash_interval_s=args.trash_interval or None)
    gw.start()
    print(f"httpfs gateway serving on {gw.address}, om={args.om}")
    return _serve(gw.stop)


def cmd_csi(args) -> int:
    """Run the CSI driver daemon (reference: `ozone csi`, csi
    CsiServer)."""
    import logging

    from ozone_tpu.gateway.csi import CsiServer

    logging.basicConfig(level=logging.INFO)
    srv = CsiServer(_client(args), s3_endpoint=args.s3_endpoint,
                    port=args.port, replication=args.replication)
    srv.start()
    print(f"csi driver serving on {srv.address}, om={args.om}")
    return _serve(srv.stop)


def cmd_s3(args) -> int:
    """S3 secret management (reference: `ozone s3 getsecret` /
    `revokesecret`)."""
    om = _client(args).om
    if args.verb == "getsecret":
        secret = om.get_s3_secret(args.access_id)
        _emit({"access_id": args.access_id, "secret": secret})
    elif args.verb == "revokesecret":
        om.revoke_s3_secret(args.access_id)
        _emit({"access_id": args.access_id, "revoked": True})
    return 0


def cmd_insight(args) -> int:
    """Per-subsystem introspection (ozone insight analog): list points,
    read metrics, tail logs, bump log levels on a running daemon."""
    from ozone_tpu.utils.insight import InsightClient

    cli = InsightClient(args.address or args.om, tls=_client_tls())
    try:
        if args.verb == "list":
            _emit(cli.list_points())
        elif args.verb == "metrics":
            _emit(cli.metrics())
        elif args.verb == "logs":
            for r in cli.logs(n=args.num, logger=args.logger,
                              level=args.level):
                print(f"{r['ts']:.3f} {r['level']:<8} {r['logger']}: "
                      f"{r['message']}")
        elif args.verb == "log-level":
            _emit(cli.set_log_level(args.logger, args.level or "DEBUG"))
        elif args.verb == "partition":
            if not args.dst:
                print("error INVALID: partition requires --dst",
                      file=sys.stderr)
                return 1
            _emit(cli.partition(args.dst, owner=args.owner))
        elif args.verb == "heal":
            if args.owner and not args.dst:
                print("error INVALID: heal --owner requires --dst",
                      file=sys.stderr)
                return 1
            _emit(cli.heal(args.dst, owner=args.owner))
        elif args.verb == "partitions":
            _emit({"blocked": cli.partition_list(),
                   "delayed": cli.delays()})
    finally:
        cli.close()
    return 0


def _scan_referenced_blocks(oz) -> set:
    """All (container, local) pairs referenced by committed keys."""
    referenced: set[tuple[int, int]] = set()
    for v in oz.om.list_volumes():
        for b in oz.om.list_buckets(v["name"]):
            for k in oz.om.list_keys(v["name"], b["name"]):
                for g in k.get("block_groups", []):
                    referenced.add(
                        (int(g["container_id"]), int(g["local_id"]))
                    )
    return referenced


def _repair_offline(args) -> int:
    """Offline OM-db surgery (reference: ozone repair's RDBRepair family
    — repair/om/SnapshotRepair.java re-points snapshot chain links,
    repair/TransactionInfoRepair.java resets the raft applied marker).
    Run against a STOPPED OM's db; dry-run unless --apply."""
    from pathlib import Path

    from ozone_tpu.om.metadata import OMMetadataStore
    from ozone_tpu.om.requests import snapmeta_key

    if not args.db:
        print("error: --db OM_DB_PATH required (service must be stopped)",
              file=sys.stderr)
        return 2
    if not Path(args.db).exists():
        # OMMetadataStore would happily create a fresh empty db at a
        # typo'd path and "repair" it, reporting success against nothing
        print(f"error: no OM db at {args.db}", file=sys.stderr)
        return 2
    store = OMMetadataStore(Path(args.db))
    try:
        if args.tool == "snapshot-chain":
            if not args.snap_path or not args.name:
                print("error: snapshot-chain requires --path /vol/bucket "
                      "and --name SNAPSHOT", file=sys.stderr)
                return 2
            vol, bkt = _parse_path(args.snap_path)
            k = snapmeta_key(vol, bkt, args.name)
            row = store.get("open_keys", k)
            if row is None:
                print(f"error: no snapshot {args.name} in "
                      f"/{vol}/{bkt}", file=sys.stderr)
                return 1
            if args.apply and args.previous is None:
                print("error: snapshot-chain --apply requires "
                      "--previous (use 'none' to clear the link)",
                      file=sys.stderr)
                return 2
            newprev = (None if args.previous in (None, "", "none")
                       else args.previous)
            if newprev is not None:
                if newprev == row.get("snap_id"):
                    print("error: --previous would make the snapshot "
                          "its own predecessor", file=sys.stderr)
                    return 1
                siblings = {
                    v["snap_id"]
                    for _, v in store.iterate(
                        "open_keys", snapmeta_key(vol, bkt, ""))
                } - {row.get("snap_id")}
                if newprev not in siblings:
                    print(f"error: --previous {newprev} is not a "
                          f"snapshot id in /{vol}/{bkt} "
                          f"(have: {sorted(siblings)})", file=sys.stderr)
                    return 1
            out = {"snapshot": args.name, "snap_id": row.get("snap_id"),
                   "previous": row.get("previous"),
                   "new_previous": newprev, "applied": False}
            if args.apply:
                row["previous"] = newprev
                store.put("open_keys", k, row)
                store.flush()
                out["applied"] = True
            _emit(out)
        else:  # transaction
            cur = store.get("system", "raft_applied")
            out = {"raft_applied": cur,
                   "new_index": args.index, "applied": False}
            if args.apply:
                if args.index is None:
                    print("error: transaction --apply requires --index",
                          file=sys.stderr)
                    return 2
                store.put("system", "raft_applied",
                          {"index": int(args.index)})
                store.flush()
                out["applied"] = True
            _emit(out)
        return 0
    finally:
        store.close()


def cmd_repair(args) -> int:
    """Repair tools (ozone repair analog). `orphans`: blocks present on
    datanodes but referenced by no key — left behind by failed writes or
    interrupted deletes; reports them, --delete reclaims.

    Deletion safety: blocks are enumerated BEFORE the namespace scan (a
    key committed mid-scan is still seen as referenced), OPEN containers
    are report-only (in-flight writes target OPEN containers exclusively,
    so closed containers cannot gain new blocks), and the namespace is
    re-checked immediately before each delete."""
    from ozone_tpu.net.scm_service import GrpcScmClient
    from ozone_tpu.storage.ids import BlockID

    if args.tool in ("snapshot-chain", "transaction"):
        return _repair_offline(args)
    oz = _client(args)
    if args.tool == "quota":
        if not args.volume:
            print("error: repair quota requires --volume", file=sys.stderr)
            return 1
        _emit(oz.om.repair_quota(args.volume))
        return 0
    scm = GrpcScmClient(args.om, tls=_client_tls())
    if args.tool != "orphans":
        print(f"unknown repair tool {args.tool}", file=sys.stderr)
        return 1
    # 1. candidates first: (pair, dn, container_state)
    candidates: list[tuple[tuple[int, int], str, str]] = []
    for c in scm.list_containers():
        if c["state"] == "DELETED":
            continue
        for rep in c["replicas"]:
            client = oz.clients.maybe_get(rep["dn_id"])
            if client is None:
                continue
            try:
                blocks = client.list_blocks(int(c["id"]))
            except Exception:
                continue
            for blk in blocks:
                candidates.append((
                    (blk.block_id.container_id, blk.block_id.local_id),
                    rep["dn_id"], c["state"],
                ))
    # 2. namespace after the block listing
    referenced = _scan_referenced_blocks(oz)
    orphans = [c for c in candidates if c[0] not in referenced]
    # 3. optional reclaim, with a final re-check right before deleting
    if args.delete and orphans:
        recheck = _scan_referenced_blocks(oz)
    report = []
    for pair, dn_id, state in orphans:
        entry = {
            "container_id": pair[0],
            "local_id": pair[1],
            "datanode": dn_id,
            "container_state": state,
            "action": "none",
        }
        if args.delete:
            if state == "OPEN":
                # an in-flight write may still commit this block
                entry["action"] = "skipped-open-container"
            elif pair in recheck:
                entry["action"] = "skipped-now-referenced"
            else:
                oz.clients.get(dn_id).delete_block(BlockID(*pair))
                entry["action"] = "deleted"
        report.append(entry)
    _emit({"orphans": report, "count": len(report)})
    return 0


def cmd_lifecycle(args) -> int:
    """Bucket lifecycle admin (`lifecycle set/get/clear/run-now/status`):
    age-based hot->warm tiering rules (replicated -> EC on device) and
    TTL expiry, enforced by the leader-singleton sweeper. A deliberate
    extension beyond Apache Ozone 1.5 (docs/PARITY.md)."""
    from ozone_tpu.net.om_service import GrpcOmClient

    def usage(msg: str) -> int:
        print(f"error: {msg}", file=sys.stderr)
        return 2

    om = GrpcOmClient(args.om, tls=_client_tls())
    verb = args.verb
    if verb in ("run-now", "status", "compact-slabs"):
        if verb == "run-now":
            _emit(om.run_lifecycle_once(args.max_keys))
        elif verb == "compact-slabs":
            _emit(om.run_slab_compaction_once())
        else:
            _emit(om.lifecycle_status())
        return 0
    if not args.path:
        return usage(f"lifecycle {verb} needs a /volume/bucket path")
    parts = _parse_path(args.path)
    if len(parts) != 2:
        return usage(f"expected /volume/bucket, got {args.path!r}")
    vol, bucket = parts
    if verb == "get":
        _emit(om.get_bucket_lifecycle(vol, bucket))
    elif verb == "clear":
        om.delete_bucket_lifecycle(vol, bucket)
        print(f"lifecycle cleared on /{vol}/{bucket}")
    elif verb == "set":
        action = {"transition": "TRANSITION_TO_EC",
                  "expire": "EXPIRE"}.get(args.action)
        if action is None:
            return usage(f"unknown action {args.action!r} "
                         "(expected transition|expire)")
        rules = (om.get_bucket_lifecycle(vol, bucket)
                 if args.append else [])
        rule = {
            "id": args.id or f"rule-{len(rules)}",
            "prefix": args.prefix,
            "age_days": args.age_days,
            "action": action,
            "enabled": True,
        }
        if action == "TRANSITION_TO_EC":
            rule["target"] = args.target
        rules = [*rules, rule]
        _emit(om.set_bucket_lifecycle(vol, bucket,
                                      rules).get("lifecycle", []))
    else:
        return usage(f"unknown lifecycle verb {verb!r}")
    return 0


def cmd_replication(args) -> int:
    """Geo replication admin (`replication set/get/clear/run-now/
    status`): per-bucket cross-cluster async replication rules,
    enforced by the leader-singleton WAL-tailing shipper. A deliberate
    extension beyond Apache Ozone 1.5 (docs/PARITY.md row 47)."""
    from ozone_tpu.net.om_service import GrpcOmClient

    def usage(msg: str) -> int:
        print(f"error: {msg}", file=sys.stderr)
        return 2

    om = GrpcOmClient(args.om, tls=_client_tls())
    verb = args.verb
    if verb in ("run-now", "status"):
        if verb == "run-now":
            _emit(om.run_geo_once(args.max_entries))
        else:
            _emit(om.geo_status())
        return 0
    if not args.path:
        return usage(f"replication {verb} needs a /volume/bucket path")
    parts = _parse_path(args.path)
    if len(parts) != 2:
        return usage(f"expected /volume/bucket, got {args.path!r}")
    vol, bucket = parts
    if verb == "get":
        _emit(om.get_bucket_geo_replication(vol, bucket))
    elif verb == "clear":
        om.delete_bucket_geo_replication(vol, bucket)
        print(f"replication cleared on /{vol}/{bucket}")
    elif verb == "set":
        if not args.dest:
            return usage("replication set needs --dest HOST:PORT "
                         "(the destination cluster endpoint)")
        rules = (om.get_bucket_geo_replication(vol, bucket)
                 if args.append else [])
        rule = {
            "id": args.id or f"rule-{len(rules)}",
            "endpoint": args.dest,
            "prefix": args.prefix,
            "bucket": args.dest_bucket,
            "volume": args.dest_volume,
            "scheme": args.scheme,
            "enabled": True,
        }
        rules = [*rules, rule]
        _emit(om.set_bucket_geo_replication(
            vol, bucket, rules).get("geo_replication", []))
    else:
        return usage(f"unknown replication verb {verb!r}")
    return 0


def cmd_version(args) -> int:
    """`ozone version` analog: framework + runtime stack versions.
    Must ALWAYS succeed — device discovery initializes the JAX backend,
    which can fail when another process owns the accelerator."""
    import jax
    import numpy

    import ozone_tpu

    try:
        devices = [str(d) for d in jax.devices()]
    except RuntimeError as e:
        devices = [f"unavailable: {e}"]
    _emit({
        "ozone_tpu": ozone_tpu.__version__,
        "jax": jax.__version__,
        "numpy": numpy.__version__,
        "python": sys.version.split()[0],
        "devices": devices,
    })
    return 0


def cmd_getconf(args) -> int:
    """`ozone getconf` analog: the generated defaults document for
    every typed config group (the @Config annotation surface)."""
    from ozone_tpu.utils.config import ALL_GROUPS, generate_defaults

    print(generate_defaults(list(ALL_GROUPS)))
    return 0


def cmd_trace(args) -> int:
    """`ozone-tpu trace slow|show`: the slow-request flight recorder —
    list traces retained past their per-op SLO, or print one trace's
    critical path (ordered stage -> micros latency attribution) from
    the cluster trace collector."""
    from ozone_tpu.net import wire
    from ozone_tpu.net.rpc import RpcChannel
    from ozone_tpu.utils.tracing import TRACING_SERVICE

    ch = RpcChannel(args.om.split(",")[0].strip(), tls=_client_tls())
    try:
        if args.verb == "slow":
            m, _ = wire.unpack(ch.call(
                TRACING_SERVICE, "Slow",
                wire.pack({"limit": args.limit})))
            _emit(m.get("traces", []))
            return 0
        if not args.trace_id:
            print("error: trace show requires a trace id",
                  file=sys.stderr)
            return 2
        m, _ = wire.unpack(ch.call(
            TRACING_SERVICE, "Slow",
            wire.pack({"trace_id": args.trace_id})))
        entry = m.get("trace")
        if not entry:
            print(f"error: trace {args.trace_id!r} not retained "
                  "(only over-SLO traces are pinned)", file=sys.stderr)
            return 1
        print(f"trace {entry['traceId']}  root={entry['root']}  "
              f"{entry['durationMs']}ms (slo {entry['sloMs']}ms)  "
              f"{len(entry['spans'])} spans")
        print("critical path:")
        total = sum(s["micros"] for s in entry["criticalPath"]) or 1
        for st in entry["criticalPath"]:
            share = 100.0 * st["micros"] / total
            print(f"  {st['stage']:<28} {st['micros']:>12} us  "
                  f"{share:5.1f}%")
        # what the spans cost their threads (those bracketed on one):
        # a span whose CPU is far under its duration waited
        cost: dict[str, list] = {}
        for sp in entry["spans"]:
            if "cpuMs" in sp:
                c = cost.setdefault(sp["name"], [0, 0.0, 0.0, 0])
                c[0] += 1
                c[1] += sp["durationMs"]
                c[2] += sp["cpuMs"]
                c[3] += sp.get("blocks", 0)
        if cost:
            print("cost by span (children included):")
            for name, (n, ms, cpu, blocks) in sorted(
                    cost.items(), key=lambda kv: -kv[1][1]):
                print(f"  {name:<28} x{n:<4} {ms:>10.3f} ms  "
                      f"cpu {cpu:>9.3f} ms  blocks {blocks}")
        return 0
    finally:
        ch.close()


# -------------------------------------------------------------------- main
def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="ozone-tpu")
    sub = ap.add_subparsers(dest="command", required=True)

    ver = sub.add_parser("version", help="framework + stack versions")
    ver.set_defaults(fn=cmd_version)
    gc = sub.add_parser("getconf",
                        help="generated config defaults (ozone getconf)")
    gc.set_defaults(fn=cmd_getconf)

    sh = sub.add_parser("sh", help="object store shell (ozone sh analog)")
    sh.add_argument("object",
                    choices=["volume", "bucket", "key", "snapshot",
                             "token"])
    sh.add_argument("verb",
                    choices=["create", "delete", "info", "list", "put",
                             "get", "rename", "checksum", "setquota",
                             "diff", "link", "renew", "cancel", "print",
                             "cat", "cp", "rewrite",
                             "set-replication", "set-smallobj",
                             "update"])
    sh.add_argument("path", nargs="?", default="",
                    help="/volume[/bucket[/key]] (token verbs take none)")
    sh.add_argument("file", nargs="?", help="local file for key put/get")
    sh.add_argument("--om", default="127.0.0.1:9860")
    sh.add_argument("--replication", default="")
    sh.add_argument("--to", default="", help="rename target")
    sh.add_argument("--prefix", default="",
                    help="key list: name prefix filter")
    sh.add_argument("--offset", type=int, default=0,
                    help="key get: positioned read start byte")
    sh.add_argument("--length", type=int, default=None,
                    help="key get: positioned read byte count")
    sh.add_argument("--start-after", default="",
                    help="key list: resume after this key (paging)")
    sh.add_argument("--limit", type=int, default=None,
                    help="key list: page size")
    sh.add_argument("--name", default="",
                    help="snapshot verbs: snapshot name (diff: the "
                         "from-snapshot)")
    sh.add_argument("--user", default="",
                    help="volume update: new owner principal")
    sh.add_argument("--page-size", type=int, default=0,
                    help="snapshot diff: run as a paged job, streaming "
                         "entries as JSON lines (0 = one-shot report)")
    sh.add_argument("--renewer", default="",
                    help="token get: renewer principal")
    sh.add_argument("--token", default="",
                    help="token verbs: token file path")
    sh.add_argument("--quota", default="",
                    help="setquota: space quota (e.g. 10MB; 'clear' "
                         "for unlimited)")
    sh.add_argument("--namespace-quota", type=int, default=None,
                    help="setquota: max key count (-1 clears to "
                         "unlimited; omitted leaves unchanged)")
    sh.add_argument("--encryption-key", default="",
                    help="TDE: bucket master-key name (admin kms "
                         "create-key first)")
    sh.add_argument("--gdpr", action="store_true",
                    help="GDPR right-to-erasure bucket (per-key secret "
                         "destroyed on delete)")
    sh.add_argument("--layout", default="OBJECT_STORE",
                    choices=["OBJECT_STORE", "FILE_SYSTEM_OPTIMIZED",
                             "LEGACY"],
                    help="bucket layout (reference: ozone sh bucket create "
                         "--layout)")
    sh.set_defaults(fn=cmd_sh)

    fs = sub.add_parser("fs", help="file-system verbs on FSO buckets "
                                   "(ozone fs analog)")
    fs.add_argument("verb", choices=["mkdir", "ls", "stat", "rm",
                                     "recover-lease"])
    fs.add_argument("path", help="/volume/bucket[/dir/path]")
    fs.add_argument("-r", "--recursive", action="store_true")
    fs.add_argument("--om", default="127.0.0.1:9860")
    fs.set_defaults(fn=cmd_fs)

    acl = sub.add_parser("acl", help="native ACL grants (ozone sh "
                                     "addacl/removeacl/setacl/getacl analog)")
    acl.add_argument("object",
                     choices=["volume", "bucket", "key", "prefix"])
    acl.add_argument("verb", choices=["add", "remove", "set", "get"])
    acl.add_argument("path", help="/volume[/bucket[/key-or-prefix]]")
    acl.add_argument("-a", "--acl", action="append", default=[],
                     help="grant like user:alice:rwl[DEFAULT] (repeatable)")
    acl.add_argument("--om", default="127.0.0.1:9860")
    acl.set_defaults(fn=cmd_acl)

    tn = sub.add_parser("tenant", help="multi-tenant admin (ozone tenant "
                                       "analog)")
    tn.add_argument("verb", choices=["create", "delete", "list", "assign",
                                     "revoke", "users"])
    tn.add_argument("tenant", nargs="?", default="")
    tn.add_argument("--user", default="")
    tn.add_argument("--access-id", default="")
    tn.add_argument("--om", default="127.0.0.1:9860")
    tn.set_defaults(fn=cmd_tenant)

    ad = sub.add_parser("admin", help="cluster admin (ozone admin analog)")
    ad.add_argument("subject", choices=[
        "safemode", "datanode", "status", "pipeline", "container",
        "balancer", "replicationmanager", "om", "finalizeupgrade",
        "upgrade", "ring", "kms", "cert", "reconfig", "namespace",
        "shards",
    ])
    ad.add_argument("verb", nargs="?", default=None,
                    help="safemode: enter|exit; datanode: decommission|"
                         "recommission|maintenance <id>; balancer: "
                         "start|stop|status; container: "
                         "list|info <id>|report|close <id>")
    ad.add_argument("target", nargs="?", default=None,
                    help="datanode id for decommission/recommission/"
                         "maintenance")
    ad.add_argument("--om", default="127.0.0.1:9860")
    ad.add_argument("--threshold", type=float, default=None,
                    help="balancer start: utilization band around the "
                         "cluster average (e.g. 0.1)")
    ad.add_argument("--http", default="",
                    help="reconfig: daemon HTTP/metrics host:port")
    ad.add_argument("--value", default=None,
                    help="reconfig set: new value for the KEY target")
    ad.add_argument("--prefix", default="",
                    help="om list-open-files: key-name prefix filter")
    ad.add_argument("--start-after", default="",
                    help="om list-open-files: resume after this row "
                         "(previous page's continuation)")
    ad.add_argument("--limit", type=int, default=None,
                    help="om list-open-files: page size")
    ad.add_argument("--max-moves", type=int, default=None,
                    help="balancer start: moves per iteration")
    ad.add_argument("--max-size", type=int, default=None,
                    help="balancer start: bytes moved per iteration")
    ad.set_defaults(fn=cmd_admin)

    lc = sub.add_parser("lifecycle",
                        help="bucket lifecycle: age-based tiering "
                             "(replicated->EC) + TTL expiry")
    lc.add_argument("verb", choices=["set", "get", "clear", "run-now",
                                     "status", "compact-slabs"])
    lc.add_argument("path", nargs="?", default="",
                    help="/volume/bucket (set/get/clear)")
    lc.add_argument("--om", default="127.0.0.1:9860")
    lc.add_argument("--prefix", default="",
                    help="set: key-name prefix filter")
    lc.add_argument("--age-days", type=float, default=0.0,
                    help="set: minimum age before the action applies")
    lc.add_argument("--action", default="transition",
                    help="set: transition (replicated->EC) or expire")
    lc.add_argument("--target", default="rs-6-3-1024k",
                    help="set: EC scheme for transition rules")
    lc.add_argument("--id", default="",
                    help="set: rule id (default rule-<n>)")
    lc.add_argument("--append", action="store_true",
                    help="set: append to existing rules instead of "
                         "replacing them")
    lc.add_argument("--max-keys", type=int, default=None,
                    help="run-now: bound the sweep's scan")
    lc.set_defaults(fn=cmd_lifecycle)

    geo = sub.add_parser("replication",
                         help="cross-cluster async bucket replication "
                              "(geo-DR)")
    geo.add_argument("verb", choices=["set", "get", "clear", "run-now",
                                      "status"])
    geo.add_argument("path", nargs="?", default="",
                     help="/volume/bucket (set/get/clear)")
    geo.add_argument("--om", default="127.0.0.1:9860")
    geo.add_argument("--dest", default="",
                     help="set: destination cluster OM endpoint "
                          "HOST:PORT (comma-separated for HA)")
    geo.add_argument("--prefix", default="",
                     help="set: key-name prefix filter")
    geo.add_argument("--dest-bucket", default="",
                     help="set: destination bucket (default: same "
                          "name as the source bucket)")
    geo.add_argument("--dest-volume", default="",
                     help="set: destination volume (default: same "
                          "name as the source volume)")
    geo.add_argument("--scheme", default="",
                     help="set: destination replication scheme "
                          "(default: keep the source key's scheme; "
                          "an EC scheme re-encodes on device)")
    geo.add_argument("--id", default="",
                     help="set: rule id (default rule-<n>)")
    geo.add_argument("--append", action="store_true",
                     help="set: append to existing rules instead of "
                          "replacing them")
    geo.add_argument("--max-entries", type=int, default=None,
                     help="run-now: bound the WAL-delta scan")
    geo.set_defaults(fn=cmd_replication)

    fr = sub.add_parser("freon", help="load generators")
    fr.add_argument("generator",
                    choices=["ockg", "ockr", "ockrr", "ockv", "ecrd",
                             "rawcoder", "omkg",
                             "ommg", "scmtb", "cmdw", "dbgen", "dcg",
                             "dcb", "dcv", "dsg", "hsg", "dnbp", "ralg",
                             "fskg", "mpug", "s3kg", "fsg", "sdg",
                             "dnsim", "lcg", "geo", "swarm", "tinyg"])
    fr.add_argument("-n", "--num", type=int, default=100)
    fr.add_argument("-s", "--size", type=int, default=10240)
    fr.add_argument("--keys", type=int, default=1,
                    help="ockrr: size of the key pool to range-read over")
    fr.add_argument("--warmup", type=int, default=0,
                    help="unmeasured warm-up keys before the clock "
                    "(absorbs the first-dispatch XLA compile)")
    fr.add_argument("-t", "--threads", type=int, default=4)
    fr.add_argument("--om", default="127.0.0.1:9860")
    fr.add_argument("--replication", default="")
    fr.add_argument("--validate", action="store_true")
    fr.add_argument("--endpoint", default="127.0.0.1:9878",
                    help="s3kg: S3 gateway host:port")
    fr.add_argument("--schema", default="rs-6-3")
    fr.add_argument("--cell", type=int, default=1024 * 1024)
    fr.add_argument("--batch", type=int, default=8)
    fr.add_argument("--mix", default="crudl",
                    help="ommg op mix (c/r/u/d/l per char)")
    fr.add_argument("--target", default="rs-3-2-4096",
                    help="lcg: EC scheme the lifecycle rule tiers to")
    fr.add_argument("--no-packer", action="store_true",
                    help="tinyg: force the classic per-key stripe path "
                         "(the small-object before/after baseline)")
    fr.add_argument("--tiny", action="store_true",
                    help="tinyg/swarm: draw sizes from the tiny-key "
                         "mix instead of a fixed --size")
    fr.add_argument("--dest", default="",
                    help="geo: destination cluster OM endpoint")
    fr.add_argument("--scheme", default="",
                    help="geo: destination replication scheme "
                         "(default: keep the source scheme)")
    fr.add_argument("--root", default="",
                    help="local path for cmdw/dbgen")
    fr.add_argument("--containers", type=int, default=5,
                    help="dnsim: fabricated containers per simulated "
                         "datanode")
    fr.add_argument("--duration", type=float, default=5.0,
                    help="dnsim: seconds to heartbeat; "
                         "swarm: seconds to drive load")
    fr.add_argument("--interval", type=float, default=0.5,
                    help="dnsim: per-datanode heartbeat interval")
    fr.set_defaults(fn=cmd_freon)

    dn = sub.add_parser("datanode", help="run a datanode daemon")
    dn.add_argument("--root", required=True)
    dn.add_argument("--scm", required=True)
    dn.add_argument("--id", default="")
    dn.add_argument("--port", type=int, default=0)
    dn.add_argument("--rack", default="/default-rack")
    dn.add_argument("--volumes", type=int, default=1,
                    help="storage volumes under --root (hdds.datanode"
                         ".dir analog)")
    dn.add_argument("--volume-policy", default="round-robin",
                    choices=["round-robin", "capacity"],
                    help="volume chooser for new containers")
    dn.add_argument("--scan-interval", type=float, default=300.0,
                    help="seconds between background container scrubs "
                         "(0 disables)")
    dn.add_argument("--replication-bandwidth-mbps", type=float,
                    default=None,
                    help="cap container-replication traffic this node "
                         "pulls/serves (MiB/s; ReplicationSupervisor "
                         "limit analog; default unlimited)")
    dn.add_argument("--ca", default="",
                    help="SCM cert-enrollment address (host:port) — "
                         "enroll and serve/dial everything over mTLS")
    dn.add_argument("--enrollment-secret", default="",
                    help="shared bootstrap secret for CSR signing")
    dn.set_defaults(fn=cmd_datanode)

    s3g = sub.add_parser("s3g", help="run the S3 gateway daemon")
    s3g.add_argument("--om", default="127.0.0.1:9860")
    s3g.add_argument("--port", type=int, default=9878)
    s3g.add_argument("--replication", default="rs-6-3-1024k")
    s3g.add_argument("--domain", default="",
                     help="serve virtual-host-style addressing for "
                          "Host: <bucket>.<domain>")
    s3g.add_argument("--require-auth", action="store_true",
                     help="enforce SigV4 signatures")
    s3g.set_defaults(fn=cmd_s3g)

    hf = sub.add_parser("httpfs", help="run the WebHDFS-compatible gateway")
    hf.add_argument("--om", default="127.0.0.1:9860")
    hf.add_argument("--port", type=int, default=14000)
    hf.add_argument("--replication", default=None,
                    help="replication for implicitly created buckets")
    hf.add_argument("--trash-interval", type=float, default=0.0,
                    help="fs.trash.interval seconds: rotate + purge "
                         "trash checkpoints on this cadence (0 = off)")
    hf.set_defaults(fn=cmd_httpfs)

    csi = sub.add_parser("csi", help="run the CSI driver daemon")
    csi.add_argument("--om", default="127.0.0.1:9860")
    csi.add_argument("--port", type=int, default=9899)
    csi.add_argument("--s3-endpoint", default="")
    csi.add_argument("--replication", default=None)
    csi.set_defaults(fn=cmd_csi)

    s3 = sub.add_parser("s3", help="s3 secret management")
    s3.add_argument("verb", choices=["getsecret", "revokesecret"])
    s3.add_argument("access_id")
    s3.add_argument("--om", default="127.0.0.1:9860")
    s3.set_defaults(fn=cmd_s3)

    cl = sub.add_parser("cluster",
                        help="one-command local demo cluster "
                             "(compose analog): scm-om + N datanodes")
    cl.add_argument("--datanodes", type=int, default=5)
    cl.add_argument("--port", type=int, default=9860)
    cl.add_argument("--root", default="",
                    help="data directory (default: a fresh tmp dir)")
    cl.add_argument("--http-port", type=int, default=None)
    cl.add_argument("--recon-port", type=int, default=None)
    cl.set_defaults(fn=cmd_cluster)

    so = sub.add_parser("scm-om", help="run the SCM+OM metadata server")
    so.add_argument("--db", required=True)
    so.add_argument("--port", type=int, default=9860)
    so.add_argument("--min-datanodes", type=int, default=1)
    so.add_argument("--http-port", type=int, default=None,
                    help="serve /prom /prof /stacks /reconfig on this port")
    so.add_argument("--recon-port", type=int, default=None,
                    help="serve the Recon API + web UI on this port")
    so.add_argument("--ha-id", default=None,
                    help="this node's id in the metadata HA ring")
    so.add_argument("--peer", action="append", default=[],
                    help="HA ring member as id=host:port (repeat; must "
                         "include --ha-id itself)")
    so.add_argument("--block-tokens", action="store_true",
                    help="enforce HMAC block/container tokens on the "
                         "datanode datapath (hdds.block.token.enabled)")
    so.add_argument("--secure", action="store_true",
                    help="host the cluster CA and serve the main plane "
                         "over mutual TLS (grpc.tls.enabled)")
    so.add_argument("--enroll-port", type=int, default=0,
                    help="plaintext cert-enrollment port (secure mode)")
    so.add_argument("--enrollment-secret", default="",
                    help="shared bootstrap secret gating CSR signing")
    so.add_argument("--ca", default="",
                    help="primordial metadata server's enrollment "
                         "address (secure HA replicas enroll there "
                         "instead of hosting their own CA)")
    so.set_defaults(fn=cmd_scm_om)

    ins = sub.add_parser("insight",
                         help="subsystem introspection (ozone insight)")
    ins.add_argument("verb", choices=["list", "metrics", "logs",
                                      "log-level", "partition", "heal",
                                      "partitions"])
    ins.add_argument("--om", default="127.0.0.1:9860")
    ins.add_argument("--address", default="",
                     help="daemon address (defaults to --om)")
    ins.add_argument("--logger", default="")
    ins.add_argument("--level", default="")
    ins.add_argument("--dst", default="",
                     help="partition/heal: peer address to cut/restore")
    ins.add_argument("--owner", default="",
                     help="partition scope tag (default: whole process)")
    ins.add_argument("-n", "--num", type=int, default=100)
    ins.set_defaults(fn=cmd_insight)

    au = sub.add_parser("audit",
                        help="audit log parser (ozone auditparser analog)")
    au.add_argument("verb", choices=["parse", "top", "failures"])
    au.add_argument("logfile", help="audit log file (JSON lines)")
    au.add_argument("--user", default="")
    au.add_argument("--action", default="")
    au.add_argument("--result", default="")
    au.add_argument("--by", default="action",
                    choices=["action", "user", "result"])
    au.add_argument("-n", "--num", type=int, default=50)
    au.set_defaults(fn=_cmd_audit)

    rp = sub.add_parser("repair", help="repair tools (ozone repair analog)")
    rp.add_argument("tool", choices=["orphans", "quota", "snapshot-chain",
                                     "transaction"])
    rp.add_argument("--om", default="127.0.0.1:9860")
    rp.add_argument("--volume", default="",
                    help="quota: volume whose usage counters to rebuild")
    rp.add_argument("--delete", action="store_true",
                    help="reclaim orphaned blocks")
    rp.add_argument("--db", default="",
                    help="snapshot-chain/transaction: OM db path "
                         "(offline; stop the OM first)")
    rp.add_argument("--path", dest="snap_path", default="",
                    help="snapshot-chain: /volume/bucket")
    rp.add_argument("--name", default="",
                    help="snapshot-chain: snapshot name")
    rp.add_argument("--previous", default=None,
                    help="snapshot-chain: new previous snap_id "
                         "('none' clears the link); required with "
                         "--apply")
    rp.add_argument("--index", type=int, default=None,
                    help="transaction: new raft applied index")
    rp.add_argument("--apply", action="store_true",
                    help="snapshot-chain/transaction: write the change "
                         "(default dry-run)")
    rp.set_defaults(fn=cmd_repair)

    dbg = sub.add_parser("debug", help="debug tools (ozone debug analog)")
    dbg.add_argument("tool", choices=["ldb", "chunk-info", "verify-replicas",
                                      "export-container",
                                      "import-container", "trace",
                                      "container-list",
                                      "container-inspect"])
    dbg.add_argument("--root", default="",
                     help="container-list/inspect: local datanode root "
                          "directory (offline)")
    dbg.add_argument("target", nargs="?", default="",
                     help="db path (ldb), /vol/bucket/key, a container "
                          "id (export/import), or a trace id (trace; "
                          "empty = list recent)")
    dbg.add_argument("--table", default="keys")
    dbg.add_argument("--prefix", default="")
    dbg.add_argument("--om", default="127.0.0.1:9860")
    dbg.add_argument("--dn", default="",
                     help="export/import-container: datanode id")
    dbg.add_argument("--file", default="",
                     help="export/import-container: local tarball path")
    dbg.set_defaults(fn=cmd_debug)

    tr = sub.add_parser("trace", help="slow-request flight recorder: "
                                      "retained over-SLO traces and "
                                      "their critical paths")
    tr.add_argument("verb", choices=["slow", "show"],
                    help="slow = list retained slow traces; "
                         "show <id> = one trace's critical path")
    tr.add_argument("trace_id", nargs="?", default="")
    tr.add_argument("--om", default="127.0.0.1:9860")
    tr.add_argument("--limit", type=int, default=20,
                    help="slow: max traces to list")
    tr.set_defaults(fn=cmd_trace)

    fsck = sub.add_parser("fsck", help="namespace health walk "
                                       "(ozone fsck analog)")
    fsck.add_argument("--om", default="127.0.0.1:9860")
    fsck.add_argument("--volume", default="")
    fsck.add_argument("--bucket", default="")
    fsck.set_defaults(fn=cmd_fsck)

    return ap


# --------------------------------------------------------------------- fsck
def cmd_fsck(args) -> int:
    """Namespace-wide health walk (ozone fsck analog): for every key in
    scope, check each block group's unit metadata on its datanodes and
    classify HEALTHY (all units present) / DEGRADED (readable but
    missing units — EC with >= k survivors, replication with >= 1) /
    UNRECOVERABLE (too few units to reconstruct)."""
    from ozone_tpu.scm.pipeline import ReplicationType

    oz = _client(args)
    if not oz.clients.known_ids():
        print(f"error: no datanode addresses learned from {args.om} — "
              "cannot distinguish missing units from an unreachable "
              "SCM; aborting", file=sys.stderr)
        return 2
    vols = ([args.volume] if args.volume
            else [v["name"] for v in oz.om.list_volumes()])
    summary = {"HEALTHY": 0, "DEGRADED": 0, "UNRECOVERABLE": 0}
    issues = []
    for vol in vols:
        buckets = ([args.bucket] if args.bucket
                   else [b["name"] for b in oz.om.list_buckets(vol)])
        for bucket in buckets:
            try:
                binfo = oz.om.bucket_info(vol, bucket)
                if binfo.get("source"):
                    continue  # links resolve to their source: walking
                    # both would double-count every key
                keys = oz.om.list_keys(vol, bucket)
            except StorageError as e:
                issues.append({"bucket": f"/{vol}/{bucket}",
                               "state": e.code})
                continue
            for k in keys:
                # listed rows carry the full stored record; no per-key
                # lookup RPC needed
                groups = oz.om.key_block_groups(k)
                worst = "HEALTHY"
                missing: list[dict] = []
                for g in groups:
                    repl = g.pipeline.replication
                    # a short EC key legitimately never wrote its
                    # trailing data units: only units holding bytes are
                    # expected, and recovery needs as many survivors as
                    # there are non-zero data units (absent units are
                    # known-zero cells)
                    if repl.type is ReplicationType.EC:
                        from ozone_tpu.client.ec_writer import (
                            block_lengths,
                        )

                        lens = block_lengths(g.length, repl.ec.data_units,
                                             repl.ec.cell_size)
                        data_expected = [i for i, ln in enumerate(lens)
                                         if ln > 0]
                        expected = data_expected + (
                            list(range(repl.ec.data_units,
                                       len(g.pipeline.nodes)))
                            if g.length else [])
                        need = len(data_expected)
                    else:
                        expected = (list(range(len(g.pipeline.nodes)))
                                    if g.length else [])
                        need = 1 if expected else 0
                    present = 0
                    for i in expected:
                        dn_id = g.pipeline.nodes[i]
                        client = oz.clients.maybe_get(dn_id)
                        ok = False
                        if client is not None:
                            try:
                                client.get_block(g.block_id)
                                ok = True
                            except Exception:
                                ok = False
                        if ok:
                            present += 1
                        else:
                            missing.append({
                                "container_id": g.container_id,
                                "datanode": dn_id,
                                "replica_index": i + 1,
                            })
                    if present >= len(expected):
                        state = "HEALTHY"
                    elif present >= need:
                        state = "DEGRADED"
                    else:
                        state = "UNRECOVERABLE"
                    order = ["HEALTHY", "DEGRADED", "UNRECOVERABLE"]
                    if order.index(state) > order.index(worst):
                        worst = state
                summary[worst] += 1
                if worst != "HEALTHY":
                    issues.append({
                        "key": f"/{vol}/{bucket}/{k['name']}",
                        "state": worst,
                        "missing_units": missing,
                    })
    _emit({"keys": summary, "issues": issues})
    return 1 if summary["UNRECOVERABLE"] else 0


# -------------------------------------------------------------------- debug
def cmd_debug(args) -> int:
    if args.tool == "ldb":
        # OM/volume metadata explorer (ozone debug ldb analog)
        from ozone_tpu.om.metadata import OMMetadataStore

        store = OMMetadataStore(args.target)
        try:
            for k, v in store.iterate(args.table, args.prefix):
                print(json.dumps({"key": k, "value": v}, default=str))
        finally:
            store.close()
        return 0

    if args.tool in ("container-list", "container-inspect"):
        # offline container explorer against a LOCAL datanode root
        # (ozone debug container list/info/inspect analog: runs on the
        # datanode host with the service stopped). STRICTLY read-only:
        # volumes are opened by their DISCOVERED directories (a root
        # with vol0+vol2 loads both; nothing is fabricated) and an
        # inspect scan reports checksum errors without committing the
        # UNHEALTHY state the online scanner would
        from ozone_tpu.storage.container import HddsVolume
        from ozone_tpu.utils.checksum import Checksum, ChecksumError

        if not args.root:
            print("error: debug container verbs need --root DN_ROOT",
                  file=sys.stderr)
            return 2
        vol_dirs = sorted(p for p in Path(args.root).glob("vol*")
                          if p.is_dir())
        if not vol_dirs:
            print(f"error: no vol* directories under {args.root} — "
                  "not a datanode root", file=sys.stderr)
            return 2
        vols = []
        containers = []
        load_errors = []
        for d in vol_dirs:
            try:
                v = HddsVolume(d, readonly=True)
            except Exception as e:  # noqa: BLE001 - forensic tool
                load_errors.append(f"{d}: cannot open volume db: {e}")
                continue
            vols.append(v)
            containers.extend(
                v.load_containers(on_error=load_errors.append))
        try:
            containers.sort(key=lambda c: c.id)
            for err in load_errors:
                print(f"warning: {err}", file=sys.stderr)
            if args.tool == "container-list":
                rows = []
                for c in containers:
                    blocks = c.list_blocks()
                    rows.append({
                        "id": c.id,
                        "state": c.state.value,
                        "replica_index": c.replica_index,
                        "blocks": len(blocks),
                        "used_bytes": sum(b.length for b in blocks),
                        "path": str(c.root),
                    })
                _emit(rows)
            else:  # container-inspect <id>
                try:
                    cid = int(args.target)
                except ValueError:
                    print(f"error: container id must be numeric, got "
                          f"{args.target!r}", file=sys.stderr)
                    return 2
                c = next((c for c in containers if c.id == cid), None)
                if c is None:
                    print(f"error: no container {cid} under "
                          f"{args.root}", file=sys.stderr)
                    return 1
                errors = []
                blocks = c.list_blocks()
                for b in blocks:
                    for ci in b.chunks:
                        try:
                            data = c.chunks.read_chunk(b.block_id, ci)
                            if ci.checksum.checksums:
                                Checksum().verify(data, ci.checksum)
                        except (StorageError, ChecksumError) as e:
                            errors.append(
                                f"{b.block_id}/{ci.name}: {e}")
                _emit({
                    "id": c.id,
                    "state": c.state.value,
                    "replica_index": c.replica_index,
                    "path": str(c.root),
                    "blocks": [
                        {"local_id": b.block_id.local_id,
                         "length": b.length,
                         "chunks": len(b.chunks)}
                        for b in blocks
                    ],
                    "scan_errors": errors,
                })
        finally:
            for v in vols:
                v.close()
        return 0

    if args.tool != "trace" and not args.target:
        # target became optional only for `trace` (empty = recent list)
        print(f"error: debug {args.tool} requires a target",
              file=sys.stderr)
        return 1
    if args.tool == "trace":
        # cluster trace assembly (the Jaeger-query role): list recent
        # traces, or print one trace's span tree across services
        from ozone_tpu.net import wire
        from ozone_tpu.net.rpc import RpcChannel
        from ozone_tpu.utils.tracing import TRACING_SERVICE

        ch = RpcChannel(args.om.split(",")[0].strip(),
                        tls=_client_tls())
        try:
            if not args.target:
                m, _ = wire.unpack(ch.call(TRACING_SERVICE, "Recent",
                                           wire.pack({})))
                _emit(m["traces"])
                return 0
            m, _ = wire.unpack(ch.call(
                TRACING_SERVICE, "Query",
                wire.pack({"trace_id": args.target})))
            spans = m["spans"]
            if not spans:
                print(f"error: no trace {args.target!r}",
                      file=sys.stderr)
                return 1
            # roots = spans whose parent never reached the collector
            # (external clients usually don't export), not just
            # parentId == ""
            ids = {s["spanId"] for s in spans}
            by_parent: dict = {}
            roots = []
            for s in spans:
                pid = s.get("parentId", "")
                if pid and pid in ids:
                    by_parent.setdefault(pid, []).append(s)
                else:
                    roots.append(s)

            def walk(items, depth):
                for s in sorted(items, key=lambda x: x["start"]):
                    svc = s.get("service", "?")
                    print(f"{'  ' * depth}{s['name']}  "
                          f"[{svc}]  {s['durationMs']}ms")
                    walk(by_parent.get(s["spanId"], []), depth + 1)

            walk(roots, 0)
            return 0
        finally:
            ch.close()

    oz = _client(args)
    if args.tool in ("export-container", "import-container"):
        # container replica backup/restore over the replication-download
        # path (ozone debug container export/import analog)
        if not args.dn or not args.file:
            print("error: requires --dn <id> and --file <path>",
                  file=sys.stderr)
            return 1
        client = oz.clients.maybe_get(args.dn)
        if client is None:
            print(f"error: unknown datanode {args.dn!r}", file=sys.stderr)
            return 1
        try:
            cid = int(args.target)
        except ValueError:
            print(f"error: container id must be numeric: {args.target!r}",
                  file=sys.stderr)
            return 1
        if args.tool == "export-container":
            data = client.export_container(cid)
            Path(args.file).write_bytes(data)
            print(f"exported container {args.target} from {args.dn}: "
                  f"{len(data)} bytes -> {args.file}")
        else:
            data = Path(args.file).read_bytes()
            out = client.import_container(data, container_id=cid)
            print(f"imported container {out} on {args.dn}")
        return 0
    vol, bucket, *rest = _parse_path(args.target)
    key = "/".join(rest)
    info = oz.om.lookup_key(vol, bucket, key)
    groups = oz.om.key_block_groups(info)
    if args.tool == "chunk-info":
        out = []
        for g in groups:
            unit_chunks = {}
            for i, dn_id in enumerate(g.pipeline.nodes):
                client = oz.clients.maybe_get(dn_id)
                if client is None:
                    unit_chunks[dn_id] = "unreachable"
                    continue
                try:
                    bd = client.get_block(g.block_id)
                    unit_chunks[dn_id] = {
                        "replica_index": i + 1,
                        "chunks": [c.to_json() for c in bd.chunks],
                    }
                except Exception as e:
                    unit_chunks[dn_id] = f"error: {e}"
            out.append({
                "container_id": g.container_id,
                "local_id": g.local_id,
                "length": g.length,
                "replicas": unit_chunks,
            })
        _emit(out)
    elif args.tool == "verify-replicas":
        # read every unit with checksum verification (replicas verify analog)
        report = []
        for g in groups:
            for i, dn_id in enumerate(g.pipeline.nodes):
                client = oz.clients.maybe_get(dn_id)
                status = "ok"
                if client is None:
                    status = "unreachable"
                else:
                    try:
                        bd = client.get_block(g.block_id)
                        for c in bd.chunks:
                            client.read_chunk(g.block_id, c, verify=True)
                    except Exception as e:
                        status = f"corrupt/unavailable: {e}"
                report.append({
                    "container_id": g.container_id,
                    "datanode": dn_id,
                    "replica_index": i + 1,
                    "status": status,
                })
        _emit(report)
        bad = [r for r in report if r["status"] != "ok"]
        return 1 if bad else 0
    return 0


def _ship_spans(args) -> None:
    """One-shot span export for short-lived CLI invocations: daemons run
    a periodic SpanExporter, but a `sh key put` exits before any 2 s
    batch fires — without this flush the client:put root span (and the
    slow-trace retention it drives) never reaches the collector."""
    from ozone_tpu.utils.tracing import SpanExporter, Tracer

    om = getattr(args, "om", "")
    tracer = Tracer.instance()
    if not om or not tracer.spans:
        return
    exp = SpanExporter(tracer, service="cli",
                       address=om.split(",")[0].strip(),
                       tls=_client_tls())
    # the command's spans finished before the exporter existed, so they
    # never entered its queue — hand them over wholesale
    with tracer._lock:
        exp._q.extend(tracer.spans)
    while exp._q:
        shipped = exp.exported
        exp.flush()
        if exp.exported == shipped:
            break  # collector unreachable: lossy by design
    if exp._ch is not None:
        exp._ch.close()


def main(argv=None) -> int:
    from ozone_tpu.utils.compile_cache import ensure_compile_cache

    args = build_parser().parse_args(argv)
    ensure_compile_cache()
    try:
        return args.fn(args)
    except StorageError as e:
        # one clean line, not a traceback (ozone sh prints the OMException
        # result code the same way)
        print(f"error {e.code}: {e.msg}", file=sys.stderr)
        return 1
    except BrokenPipeError:
        # downstream pager/head closed the pipe: exit quietly like any
        # well-behaved unix tool
        import os

        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        return 0
    finally:
        try:
            _ship_spans(args)
        except Exception:
            pass  # ozlint: allow[error-swallowing] -- best-effort span export on exit; tracing never fails a CLI verb


if __name__ == "__main__":
    sys.exit(main())
