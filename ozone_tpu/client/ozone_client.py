"""OzoneClient: the user-facing object-store API.

Mirror of the reference's client object model (hadoop-ozone/client
OzoneClient -> ObjectStore -> OzoneVolume -> OzoneBucket -> key ops;
RpcClient.java:192 createKey:1377 / getKey:1570): volume/bucket CRUD and
key write/read streams that dispatch to the EC or replicated datapath by
the key's replication config.
"""

from __future__ import annotations

import time
from typing import Any, Optional

import numpy as np

from ozone_tpu import admission
from ozone_tpu.client import resilience
from ozone_tpu.client.dn_client import DatanodeClientFactory
from ozone_tpu.client.ec_reader import ECBlockGroupReader
from ozone_tpu.client.ec_writer import BlockGroup, ECKeyWriter
from ozone_tpu.client.replicated import ReplicatedKeyReader, ReplicatedKeyWriter
from ozone_tpu.codec import hostmem
from ozone_tpu.om.om import OpenKeySession, OzoneManager
from ozone_tpu.scm.pipeline import ReplicationType
from ozone_tpu.storage.ids import StorageError
from ozone_tpu.utils.checksum import ChecksumType
from ozone_tpu.utils.metrics import registry
from ozone_tpu.utils.tracing import Tracer

#: end-to-end client operation latency (PUT/GET histograms with trace
#: exemplars: the scrape-side view of the same distribution the flight
#: recorder retains outliers from)
METRICS = registry("client.ops")


class KeyWriteHandle:
    """Streaming write handle; commits the key on close. With `dek`
    set (TDE/GDPR bucket) every byte is AES-CTR encrypted client-side
    before it reaches the datapath — datanodes, checksums, scrubbing
    and reconstruction all operate on ciphertext."""

    def __init__(self, session: OpenKeySession, om: OzoneManager, writer,
                 dek: Optional[bytes] = None):
        self._session = session
        self._om = om
        self._writer = writer
        self._committed = False
        self._dek = dek
        self._iv = (bytes.fromhex(session.encryption["iv"])
                    if dek is not None else b"")
        self._enc_offset = 0

    def write(self, data) -> None:
        if self._dek is not None:
            from ozone_tpu.utils.kms import ctr_crypt

            data = ctr_crypt(data, self._dek, self._iv,
                             self._enc_offset)
            self._enc_offset += data.size
        self._writer.write(data)

    def hsync(self) -> None:
        """Make everything written so far durable and readable while the
        stream stays open (KeyOutputStream.hsync): flush to the datanodes,
        then commit the key at the synced length with the session kept
        alive. Not supported for EC keys (reference parity)."""
        groups = self._writer.hsync()
        with Tracer.instance().span("om:commit", hsync=True):
            self._om.hsync_key(
                self._session, groups, self._writer.bytes_written
            )

    def close(self) -> None:
        if self._committed:
            return
        groups = self._writer.close()
        with Tracer.instance().span("om:commit",
                                    key=self._session.key):
            self._om.commit_key(
                self._session, groups, self._writer.bytes_written
            )
        self._committed = True

    def __enter__(self):
        return self

    def __exit__(self, exc_type, *a):
        if exc_type is None:
            self.close()


class MultipartUpload:
    """Client handle for one multipart upload (createMultipartKey flow,
    RpcClient.java:2009): each part streams through the same EC/replicated
    datapath as a whole key, then completion stitches parts at the OM."""

    def __init__(self, bucket: "OzoneBucket", key: str, upload_id: str):
        self.bucket = bucket
        self.key = key
        self.upload_id = upload_id
        self._etags: dict[int, str] = {}

    def write_part(self, part_number: int, data) -> str:
        import hashlib
        import os as _os

        om = self.bucket.client.om
        session = om.open_multipart_part(
            self.bucket.volume, self.bucket.name, self.key, self.upload_id
        )
        writer = self.bucket._make_writer(session)
        etag = hashlib.md5(np.asarray(data, np.uint8).tobytes()).hexdigest()
        iv = ""
        if session.encryption:
            # encrypted upload: each part gets its own IV (parts are
            # written independently, possibly out of order, so a
            # whole-stream counter cannot work)
            from ozone_tpu.utils.kms import ctr_crypt

            dek = self.bucket._data_key(session.encryption)
            raw = _os.urandom(16)
            data = ctr_crypt(data, dek, raw)
            iv = raw.hex()
        writer.write(data)
        groups = writer.close()
        om.commit_multipart_part(
            session, part_number, groups, writer.bytes_written, etag,
            iv=iv,
        )
        self._etags[part_number] = etag
        return etag

    def complete(self, parts: Optional[list[dict]] = None) -> dict:
        if parts is None:
            parts = [
                {"part_number": n, "etag": self._etags[n]}
                for n in sorted(self._etags)
            ]
        return self.bucket.client.om.complete_multipart_upload(
            self.bucket.volume, self.bucket.name, self.key, self.upload_id,
            parts,
        )

    def abort(self) -> None:
        self.bucket.client.om.abort_multipart_upload(
            self.bucket.volume, self.bucket.name, self.key, self.upload_id
        )

    def list_parts(self) -> list[dict]:
        return self.bucket.client.om.list_parts(
            self.bucket.volume, self.bucket.name, self.key, self.upload_id
        )


class OzoneBucket:
    def __init__(self, client: "OzoneClient", volume: str, name: str):
        self.client = client
        self.volume = volume
        self.name = name
        # small-object conf cache: False = not fetched yet, None =
        # fetched, bucket not opted in (see _smallobj_conf)
        self._smallobj: Any = False

    def _make_writer(self, session: OpenKeySession):
        om = self.client.om

        def allocate(excluded, excluded_containers=()):
            return om.allocate_block(session, excluded,
                                     excluded_containers)
        if session.replication.type is ReplicationType.EC:
            return ECKeyWriter(
                session.replication.ec,
                allocate,
                self.client.clients,
                block_size=om.block_size,
                checksum=ChecksumType(session.checksum_type),
                bytes_per_checksum=session.bytes_per_checksum,
                # ambient tenant identity (set by the gateway's
                # admission context) overrides the client-wide class,
                # carrying per-tenant QoS into the codec's fair lanes
                qos_class=admission.ambient_qos(self.client.qos_class),
            )
        if (
            session.replication.type is ReplicationType.RATIS
            and session.replication.factor > 1
            and self.client.ratis_clients is not None
        ):
            from ozone_tpu.client.ratis_client import RatisKeyWriter

            return RatisKeyWriter(
                allocate,
                self.client.clients,
                self.client.ratis_clients,
                block_size=om.block_size,
                checksum=ChecksumType(session.checksum_type),
                bytes_per_checksum=session.bytes_per_checksum,
            )
        return ReplicatedKeyWriter(
            allocate,
            self.client.clients,
            block_size=om.block_size,
            checksum=ChecksumType(session.checksum_type),
            bytes_per_checksum=session.bytes_per_checksum,
        )

    def initiate_multipart_upload(
        self, key: str, replication: Optional[str] = None,
        metadata: Optional[dict] = None,
    ) -> MultipartUpload:
        upload_id = self.client.om.initiate_multipart_upload(
            self.volume, self.name, key, replication, metadata=metadata
        )
        return MultipartUpload(self, key, upload_id)

    def _data_key(self, enc: dict) -> Optional[bytes]:
        """Resolve the DEK for an encryption bundle: GDPR secrets are
        inline; TDE EDEKs unwrap through the OM (access-checked KMS
        decrypt)."""
        if not enc:
            return None
        if "gdpr_secret" in enc:
            return bytes.fromhex(enc["gdpr_secret"])
        return bytes.fromhex(
            self.client.om.kms_decrypt(self.volume, self.name, enc))

    def open_key(
        self, key: str, replication: Optional[str] = None,
        metadata: Optional[dict] = None,
        acls: Optional[list] = None,
    ) -> KeyWriteHandle:
        om = self.client.om
        with Tracer.instance().span("om:open_key", key=key):
            session = om.open_key(self.volume, self.name, key,
                                  replication, metadata=metadata,
                                  acls=acls)
        return KeyWriteHandle(session, om, self._make_writer(session),
                              dek=self._data_key(session.encryption))

    def _smallobj_conf(self) -> Optional[dict]:
        """The bucket's small-object thresholds, fetched once per handle
        (None = bucket never opted in, the overwhelmingly common case —
        a single cached miss keeps the regular PUT path at zero extra
        OM round-trips)."""
        if self._smallobj is False:
            from ozone_tpu.client.slab import smallobj_conf

            self._smallobj = smallobj_conf(
                self.client.om.bucket_info(self.volume, self.name))
        return self._smallobj

    def write_key(self, key: str, data,
                  replication: Optional[str] = None,
                  metadata: Optional[dict] = None) -> None:
        # key-write operation boundary: ONE deadline (operator opt-in,
        # OZONE_TPU_OP_DEADLINE_S) spans open, every stripe/chunk RPC
        # and the commit — each hop times out on the remaining budget.
        # The root span is the flight recorder's SLO unit for a PUT.
        t0 = time.perf_counter()
        with Tracer.instance().operation(
                "client:put", volume=self.volume, bucket=self.name,
                key=key) as sp:
            with resilience.start("key_write"):
                # tiny-object routing: only for scheme-default writes on
                # an opted-in bucket (an explicit per-key replication
                # always takes the regular stripe path)
                conf = None if replication else self._smallobj_conf()
                if conf is not None:
                    raw = (data.tobytes()
                           if isinstance(data, np.ndarray)
                           else bytes(data))
                    if len(raw) <= conf["inline_max"]:
                        self.client.om.put_inline_key(
                            self.volume, self.name, key, raw,
                            metadata=metadata)
                        raw = None
                    elif len(raw) <= conf["needle_max"]:
                        self.client.packer.put(
                            self.volume, self.name, key, raw,
                            metadata=metadata)
                        raw = None
                    if raw is None:
                        METRICS.histogram("put_seconds").observe(
                            time.perf_counter() - t0, sp.trace_id)
                        return
                with self.open_key(key, replication,
                                   metadata=metadata) as h:
                    # the payload's way into the writer (for an EC key,
                    # slicing it into cells) as a stage of its own; what
                    # is then left to the root is the close path's own
                    # work (the batch's np.stack, waking on results)
                    with Tracer.instance().span("client:write"):
                        h.write(data)
        METRICS.histogram("put_seconds").observe(
            time.perf_counter() - t0, sp.trace_id)

    def lookup_key_info(self, key: str) -> dict:
        """Key info lookup with `.snapshot/<name>/<key>` routing (the
        path convention the reference FS exposes) — shared by whole and
        positioned reads so snapshot paths work on both."""
        om = self.client.om
        if key.startswith(".snapshot/"):
            parts = key.split("/", 2)
            if len(parts) != 3 or not parts[2]:
                from ozone_tpu.om.requests import OMError

                raise OMError("KEY_NOT_FOUND",
                              f"no key component in {key}")
            return om.snapshot_lookup_key(self.volume, self.name,
                                          parts[1], parts[2])
        return om.lookup_key(self.volume, self.name, key)

    def read_key(self, key: str) -> np.ndarray:
        return self.read_key_info(self.lookup_key_info(key))

    def read_key_info(self, info: dict) -> np.ndarray:
        """Read a key's bytes from already-fetched key info — callers
        that looked the key up for other reasons (metadata headers,
        checksum type) avoid a second OM round-trip."""
        return self.read_key_info_range(info, 0, int(info["size"]))

    def read_key_range(self, key: str, offset: int,
                       length: int) -> np.ndarray:
        """Positioned read of [offset, offset+length) in key space."""
        return self.read_key_info_range(self.lookup_key_info(key),
                                        offset, length)

    def read_key_info_range(self, info: dict, offset: int,
                            length: int) -> np.ndarray:
        """Positioned read: only the block groups — and within them only
        the cells/chunks — covering [offset, offset+length) move over
        the wire; TDE streams decrypt by seeking the CTR keystream to
        the range offset (the reference's KeyInputStream.seek +
        CryptoInputStream positioned-read path)."""
        om = self.client.om
        size = int(info["size"])
        if offset < 0 or length < 0 or offset + length > size:
            raise ValueError(f"range [{offset},{offset + length}) out of "
                             f"bounds for size {size}")
        t0 = time.perf_counter()
        with Tracer.instance().operation(
                "client:get", volume=self.volume, bucket=self.name,
                key=info.get("key", ""), bytes=length) as sp:
            with resilience.start("key_read"):
                if info.get("inline") is not None:
                    out = self._read_inline(info, offset, length)
                elif info.get("needle"):
                    out = self._read_needle(om, info, offset, length)
                else:
                    out = self._read_groups_range(om, info, offset,
                                                  length)
        METRICS.histogram("get_seconds").observe(
            time.perf_counter() - t0, sp.trace_id)
        METRICS.counter("get_user_bytes").inc(int(out.size))
        return out

    def _read_inline(self, info: dict, offset: int,
                     length: int) -> np.ndarray:
        """Inline value GET: the bytes rode the OM key row (possibly a
        follower's lease read) — zero datapath hops."""
        import base64

        from ozone_tpu.client.slab import METRICS as SMALLOBJ

        raw = base64.b64decode(info["inline"])
        SMALLOBJ.counter("inline_gets").inc()
        return np.frombuffer(raw, np.uint8)[offset:offset + length].copy()

    def _read_needle(self, om, info: dict, offset: int,
                     length: int) -> np.ndarray:
        """Needle GET: slice this key's bytes out of its shared slab via
        ordinary ranged group reads. The WHOLE needle is always fetched
        (they're small by construction) so its commit-time CRC can gate
        the reply — a torn or mis-pointed needle is an error, never
        bytes."""
        from ozone_tpu.client.slab import (METRICS as SMALLOBJ,
                                           NEEDLE_CRC_MISMATCH)
        from ozone_tpu.om.requests import OMError
        from ozone_tpu.utils.checksum import crc32c

        nd = info["needle"]
        whole = self._read_groups_range(om, info, int(nd["offset"]),
                                        int(nd["length"]))
        if int(crc32c(whole)) != int(nd["crc"]):
            SMALLOBJ.counter("needle_crc_errors").inc()
            raise OMError(
                NEEDLE_CRC_MISMATCH,
                f"needle {info.get('key', '')} in slab {nd['slab']} "
                f"failed its CRC gate")
        SMALLOBJ.counter("needle_gets").inc()
        return whole[offset:offset + length].copy()

    def _read_groups_range(self, om, info: dict, offset: int,
                           length: int) -> np.ndarray:
        groups = om.key_block_groups(info)
        # the key's ONE buffer: every covered group's reader writes its
        # bytes into its slice of it, no part is assembled twice. Pool
        # memory, as the wire slabs are: the array the user gets pins
        # its lease, and the pages go back to the pool when the answer
        # and every view of it are dropped. Recycled pages hold another
        # key's bytes: what the groups below do not write is never
        # handed out (the `filled` check).
        out, fresh = hostmem.pool().lease_array(length)
        filled = 0
        pos = 0  # current group's start offset in key space
        for g in groups:
            a = max(offset, pos)
            b = min(offset + length, pos + g.length)
            if a < b:
                dst = out[a - offset:b - offset]
                if g.pipeline.replication.type is ReplicationType.EC:
                    reader = ECBlockGroupReader(
                        g,
                        g.pipeline.replication.ec,
                        self.client.clients,
                        checksum=ChecksumType(
                            info.get("checksum_type", "CRC32C")),
                        bytes_per_checksum=info.get(
                            "bytes_per_checksum", 16 * 1024),
                        # gateway-set tenant context wins over the
                        # client-wide class (see _make_writer)
                        qos_class=admission.ambient_qos(
                            self.client.qos_class),
                    )
                    reader.read(a - pos, b - a, out=dst, out_fresh=fresh)
                else:
                    # the winner of a race between replicas: copied in
                    dst[:] = ReplicatedKeyReader(
                        g, self.client.clients).read(a - pos, b - a)
                    hostmem.count_copy(
                        b - a, site="ozone_client.replicated_group",
                        warn=False)
                filled += b - a
            pos += g.length
        if filled != length:
            # the groups do not cover the range: never hand out the
            # buffer's untouched bytes
            raise StorageError(
                "IO_EXCEPTION", f"block groups of {info.get('key', '')} "
                f"cover {filled} of {length} bytes at offset {offset}")
        enc = info.get("encryption", {})
        if enc and length:
            from ozone_tpu.utils.kms import ctr_crypt

            dek = self._data_key(enc)
            if "enc_parts" in info:
                # multipart: each part was encrypted independently with
                # its own IV at offset 0 — decrypt each covered slice at
                # its part-relative offset
                segs, ppos = [], 0
                for p in info["enc_parts"]:
                    n = int(p["size"])
                    a = max(offset, ppos)
                    b = min(offset + length, ppos + n)
                    if a < b:
                        segs.append(ctr_crypt(
                            out[a - offset:b - offset], dek,
                            bytes.fromhex(p["iv"]), offset=a - ppos))
                    ppos += n
                out = (np.concatenate(segs) if segs
                       else np.zeros(0, np.uint8))
            else:
                out = ctr_crypt(out, dek, bytes.fromhex(enc["iv"]),
                                offset=offset)
        return out

    def file_checksum(self, key: str) -> dict:
        """Composite whole-key checksum from stored chunk CRCs, no data
        read (getFileChecksum / ECFileChecksumHelper analog)."""
        from ozone_tpu.client.file_checksum import file_checksum

        return file_checksum(self.client, self.volume, self.name, key)

    def rewrite_key(self, key: str, replication: str) -> None:
        """Re-write an existing key's data under a new replication
        config in place — the Ratis<->EC migration verb (`ozone sh key
        rewrite`, shell/keys/RewriteKeyHandler.java). Fenced: the commit
        carries the source's object id and the OM refuses it with
        KEY_MODIFIED if the key was overwritten while the rewrite ran
        (the reference's expectedGeneration check), discarding the new
        blocks instead of clobbering the newer data."""
        om = self.client.om
        info = om.lookup_key(self.volume, self.name, key)
        data = self.read_key_info(info)
        # metadata and ACLs ride the open session so the fenced commit
        # lands them atomically — a post-commit ACL restore would leave
        # bucket-default grants live in the failure window
        h = self.open_key(key, replication,
                          metadata=info.get("metadata"),
                          acls=info.get("acls"))
        h._session.expect_object_id = info.get("object_id", "")
        h._session.expect_generation = int(info.get("generation", 0))
        h.write(data)
        h.close()

    def copy_key(self, key: str, dst_bucket: "OzoneBucket",
                 dst_key: str,
                 replication: Optional[str] = None) -> None:
        """Server-side-style key copy (`ozone sh key cp`,
        shell/keys/CopyKeyHandler.java): read once, write under the
        destination bucket's (or an explicit) replication config."""
        info = self.client.om.lookup_key(self.volume, self.name, key)
        dst_bucket.write_key(dst_key, self.read_key_info(info),
                             replication=replication,
                             metadata=info.get("metadata"))

    def delete_key(self, key: str) -> None:
        self.client.om.delete_key(self.volume, self.name, key)

    def rename_key(self, key: str, new_key: str) -> None:
        self.client.om.rename_key(self.volume, self.name, key, new_key)

    def list_keys(self, prefix: str = "") -> list[dict]:
        return self.client.om.list_keys(self.volume, self.name, prefix)


class OzoneVolume:
    def __init__(self, client: "OzoneClient", name: str):
        self.client = client
        self.name = name

    def create_bucket(self, bucket: str, replication: str = "rs-6-3-1024k") -> OzoneBucket:
        self.client.om.create_bucket(self.name, bucket, replication)
        return OzoneBucket(self.client, self.name, bucket)

    def get_bucket(self, bucket: str) -> OzoneBucket:
        self.client.om.bucket_info(self.name, bucket)
        return OzoneBucket(self.client, self.name, bucket)

    def list_buckets(self) -> list[dict]:
        return self.client.om.list_buckets(self.name)


class OzoneClient:
    """Entry point (ObjectStore analog)."""

    def __init__(self, om: OzoneManager, clients: DatanodeClientFactory,
                 ratis_clients=None, qos_class: str = "interactive"):
        self.om = om
        self.clients = clients
        #: optional net/ratis_service.RatisClientFactory: when present,
        #: RATIS/3 writes are ordered through the pipeline raft ring
        #: (XceiverClientRatis path) instead of plain client fan-out
        self.ratis_clients = ratis_clients
        #: shared-codec-service QoS class for this client's EC device
        #: dispatches; background replayers (geo replication) run at
        #: "bulk" so they can never starve interactive traffic
        self.qos_class = qos_class
        self._packer = None

    @property
    def packer(self):
        """Process-wide needle packer, started on first small PUT. Slab
        flushes ride bulk QoS so a mass-ingest burst defers to
        interactive traffic in the codec's fair lanes."""
        if self._packer is None:
            from ozone_tpu.client.slab import SlabPacker

            self._packer = SlabPacker(self.om, self.clients,
                                      qos_class="bulk")
        return self._packer

    def create_volume(self, volume: str) -> OzoneVolume:
        self.om.create_volume(volume)
        return OzoneVolume(self, volume)

    def get_volume(self, volume: str) -> OzoneVolume:
        self.om.volume_info(volume)
        return OzoneVolume(self, volume)

    def list_volumes(self) -> list[dict]:
        return self.om.list_volumes()
