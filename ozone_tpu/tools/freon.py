"""Freon: load generators and benchmarks.

Mirror of the reference's freon suite (hadoop-ozone/tools freon/
Freon.java:40-79 subcommand registry): BaseFreonGenerator-style harness
(thread pool task loop, progress, latency report — BaseFreonGenerator
.java:77,152,182,321) and the key generators:

- ockg: OzoneClientKeyGenerator.java:42 — write n keys of a given size
  through the full client stack, per-op timer, replication selectable.
- ocokr: key read/validate generator (OzoneClientKeyReadWriteOps analog).
- dcg: DatanodeChunkGenerator — raw WriteChunk straight to datanodes,
  bypassing OM/SCM (datapath-only throughput).
- rawcoder: RawErasureCoderBenchmark.java:42-49 — coder encode/decode
  MB/s per backend (numpy / cpp / jax-TPU), batch x cell matrix.
"""

from __future__ import annotations

import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np

from ozone_tpu.utils.metrics import Timer


@dataclass
class FreonReport:
    name: str
    ops: int
    failures: int
    elapsed_s: float
    latencies_s: list[float] = field(default_factory=list)
    bytes_processed: int = 0
    #: generator-specific extra fields merged into summary()
    extras: dict = field(default_factory=dict)

    def summary(self) -> dict:
        lat = sorted(self.latencies_s)
        pct = lambda q: lat[min(len(lat) - 1, int(q * len(lat)))] if lat else 0.0
        return {
            **self.extras,
            "generator": self.name,
            "ops": self.ops,
            "failures": self.failures,
            "elapsed_s": round(self.elapsed_s, 3),
            "ops_per_s": round(self.ops / self.elapsed_s, 2)
            if self.elapsed_s
            else 0,
            "throughput_mib_s": round(
                self.bytes_processed / 2**20 / self.elapsed_s, 2
            )
            if self.elapsed_s
            else 0,
            "mean_ms": round(1e3 * sum(lat) / len(lat), 3) if lat else 0,
            "p50_ms": round(1e3 * pct(0.5), 3),
            "p75_ms": round(1e3 * pct(0.75), 3),
            "p90_ms": round(1e3 * pct(0.9), 3),
            "p95_ms": round(1e3 * pct(0.95), 3),
            "p99_ms": round(1e3 * pct(0.99), 3),
            "p999_ms": round(1e3 * pct(0.999), 3),
            "max_ms": round(1e3 * (lat[-1] if lat else 0), 3),
            "histogram": self.histogram(),
        }

    def histogram(self) -> list[dict]:
        """Power-of-two latency buckets (the HdrHistogram-style
        distribution the reference prints via printReport). PER-BUCKET
        counts: each entry counts ops whose latency falls in
        (previous_le_ms, le_ms] — not cumulative."""
        if not self.latencies_s:
            return []
        import math

        counts: dict[float, int] = {}
        for dt in self.latencies_s:
            ms = dt * 1e3
            le = 2 ** max(0, math.ceil(math.log2(max(ms, 1e-3))))
            counts[le] = counts.get(le, 0) + 1
        return [{"le_ms": k, "count": counts[k]}
                for k in sorted(counts)]


class BaseFreonGenerator:
    """Thread-pooled op loop with latency capture."""

    def __init__(self, name: str, n_ops: int, threads: int = 4):
        self.name = name
        self.n_ops = n_ops
        self.threads = threads
        self._lat: list[float] = []
        self._failures = 0
        self._first_error = ""
        self._bytes = 0
        self._lock = threading.Lock()

    def run(self, op: Callable[[int], int]) -> FreonReport:
        """op(i) -> bytes processed; runs n_ops times across the pool."""
        t0 = time.time()

        def task(i: int) -> None:
            s = time.perf_counter()
            try:
                nbytes = op(i) or 0
                dt = time.perf_counter() - s
                with self._lock:
                    self._lat.append(dt)
                    self._bytes += nbytes
            except Exception as e:
                with self._lock:
                    self._failures += 1
                    if not self._first_error:
                        self._first_error = f"op {i}: {e!r}"

        with ThreadPoolExecutor(max_workers=self.threads) as pool:
            list(pool.map(task, range(self.n_ops)))
        return FreonReport(
            self.name,
            ops=self.n_ops - self._failures,
            failures=self._failures,
            elapsed_s=time.time() - t0,
            latencies_s=self._lat,
            bytes_processed=self._bytes,
            # a count alone cannot say what failed
            extras=({"first_error": self._first_error}
                    if self._failures else {}),
        )


def _client_hist_extras() -> dict:
    """Scrape-side tail latency: p50/p95/p99 (ms) derived from the
    client-ops histograms — the same numbers a Prometheus
    histogram_quantile over `client_ops_{put,get}_seconds_bucket` would
    yield. Reported alongside the raw-list percentiles so workload runs
    record what the monitoring plane will actually see (bucket-quantile
    estimates over every op since process start, warmups included)."""
    from ozone_tpu.client.ozone_client import METRICS as client_ops
    from ozone_tpu.utils.tracing import Tracer

    out: dict = {}
    for verb in ("put", "get"):
        h = client_ops.histogram(f"{verb}_seconds")
        if h.count:
            out[f"hist_{verb}_ms"] = {
                p: round(1e3 * v, 3)
                for p, v in h.percentiles().items()}
    # where the mean operation spent its time: critical-path ms per
    # stage over this process's PUTs / GETs / repairs (warm-ups included)
    stages = Tracer.instance().recorder.stage_means()
    if stages:
        out["op_stage_ms"] = stages
    return out


def _device_extras() -> dict:
    """What the run's codec work ran on, so no summary is read without
    its device: platform / device_kind / device count as JAX reports
    them, the fused path the factories chose (`jax` or `native`), this
    process's compile counters (where its entry point asked for them:
    `cmd_freon` does), and how many fused dispatches the shared codec
    service and the mesh executor launched."""
    from ozone_tpu.codec import fused
    from ozone_tpu.codec import service as codec_service
    from ozone_tpu.parallel import mesh_executor
    from ozone_tpu.utils.compile_cache import compile_counts

    svc = codec_service.METRICS.snapshot()
    out = {**fused.backend_report(), **compile_counts()}
    for name in ("dispatches", "stripes_dispatched", "slots_dispatched"):
        out[name] = int(svc.get(name, 0))
    mex = mesh_executor._executor
    if mex is not None:
        st = mex.stats()
        out["mesh"] = {k: int(st.get(k, 0)) for k in (
            "devices", "dispatches", "stripes_dispatched", "programs",
            "programs_host_twin", "output_shards", "compile_counts")}
    return {"device": out}


def _det_payload(size: int, seed: int = 0) -> np.ndarray:
    """The deterministic ockg payload; ockv re-derives it to validate,
    so both MUST use this one helper (a drifting expression would read
    as cluster-wide corruption)."""
    return np.random.default_rng(seed).integers(0, 256, size,
                                                dtype=np.uint8)


def ockg(
    client,
    n_keys: int = 100,
    size: int = 10 * 1024,
    threads: int = 4,
    volume: str = "freon-vol",
    bucket: str = "freon-bucket",
    replication: Optional[str] = None,
    prefix: str = "key",
    validate: bool = False,
    warmup: int = 0,
) -> FreonReport:
    """Ozone Client Key Generator (freon ockg). `warmup` keys are
    written before the clock starts — on TPU the first fused-encode
    dispatch carries a 20-40 s XLA compile that would otherwise be
    billed to the measured throughput."""
    try:
        client.om.create_volume(volume)
    except Exception:
        pass
    try:
        client.om.create_bucket(volume, bucket,
                                replication or "rs-6-3-1024k")
    except Exception:
        pass
    b = client.get_volume(volume).get_bucket(bucket)
    payload = _det_payload(size)

    def op(i: int) -> int:
        b.write_key(f"{prefix}-{i}", payload, replication)
        if validate:
            got = b.read_key(f"{prefix}-{i}")
            assert np.array_equal(got, payload)
        return size

    for w in range(warmup):
        b.write_key(f"{prefix}-warmup-{w}", payload, replication)
    rep = BaseFreonGenerator("ockg", n_keys, threads).run(op)
    rep.extras.update(_client_hist_extras())
    rep.extras.update(_device_extras())
    return rep


def hsg(
    client,
    n_keys: int = 20,
    size: int = 10 * 1024,
    syncs: int = 4,
    threads: int = 4,
    volume: str = "freon-vol",
    bucket: str = "freon-hsync",
    replication: str = "RATIS/THREE",
) -> FreonReport:
    """Hsync generator (freon HsyncGenerator analog): each op opens a key,
    writes `syncs` slices with an hsync after every slice (the HBase
    WAL-style durability pattern), then closes. The timer therefore covers
    the full open -> (write+hsync)*n -> commit round trip."""
    try:
        client.om.create_volume(volume)
    except Exception:
        pass
    try:
        client.om.create_bucket(volume, bucket, replication)
    except Exception:
        pass
    b = client.get_volume(volume).get_bucket(bucket)
    rng = np.random.default_rng(0)
    payload = rng.integers(0, 256, size, dtype=np.uint8)

    def op(i: int) -> int:
        with b.open_key(f"hsync-{i}") as h:
            for _ in range(syncs):
                h.write(payload)
                h.hsync()
        return size * syncs

    return BaseFreonGenerator("hsg", n_keys, threads).run(op)


def lcg(client, n_keys: int = 20, size: int = 10 * 1024,
        threads: int = 4, volume: str = "freon-vol",
        bucket: str = "freon-tier", replication: str = "RATIS/THREE",
        target: str = "rs-3-2-4096", prefix: str = "tier",
        age_days: float = 0.0) -> FreonReport:
    """Lifecycle-churn workload (write -> age -> sweep -> verify): the
    soak/CI probe for the tiering subsystem. Writes `n_keys` replicated
    keys under an age-based TRANSITION_TO_EC rule, triggers a sweep
    (`lifecycle run-now`), then verifies every key reads back
    byte-exact AND erasure-coded. The timer covers the WRITES only:
    the report's rate and latencies are those of the replicated PUTs,
    and the sweep (run inside the OM daemon, off any chip its host has)
    is not timed here; its outcome rides the report extras
    (`transitioned`, `verify_failures`). The sweep itself is timed by
    the benchmark's cell `tier-mesh.rs-6-3`
    (`benchmarks/generators/tier_sweep.py`)."""
    try:
        client.om.create_volume(volume)
    except Exception:
        pass
    try:
        client.om.create_bucket(volume, bucket, replication)
    except Exception:
        pass
    client.om.set_bucket_lifecycle(volume, bucket, [{
        "id": "freon-tier", "prefix": prefix, "age_days": age_days,
        "action": "TRANSITION_TO_EC", "target": target,
    }])
    b = client.get_volume(volume).get_bucket(bucket)

    def op(i: int) -> int:
        b.write_key(f"{prefix}-{i}", _det_payload(size, seed=i),
                    replication)
        return size

    rep = BaseFreonGenerator("lcg", n_keys, threads).run(op)
    sweep = client.om.run_lifecycle_once()
    verify_failures = 0
    ec_count = 0
    for i in range(n_keys):
        try:
            info = client.om.lookup_key(volume, bucket, f"{prefix}-{i}")
            got = b.read_key_info(info)
            if not np.array_equal(got, _det_payload(size, seed=i)):
                verify_failures += 1
                continue
            if str(info.get("replication", "")).startswith("rs-"):
                ec_count += 1
        except Exception:
            verify_failures += 1
    rep.extras.update({
        "transitioned": sweep.get("transitioned", 0),
        "ec_keys": ec_count,
        "verify_failures": verify_failures,
        "sweep_bytes": sweep.get("bytes", 0),
        "sweep_dispatches": sweep.get("dispatches", 0),
    })
    return rep


#: the tiny-key size mix: 80/15/5 inline / needle / needle-ish — the
#: metadata-bound object population the small-object path exists for
TINY_SIZES = (512, 4 * 1024, 48 * 1024)


def _tiny_size(i: int, size: int, mix: bool) -> int:
    if not mix:
        return size
    r = i % 20
    if r < 16:
        return TINY_SIZES[0]
    if r < 19:
        return TINY_SIZES[1]
    return TINY_SIZES[2]


def tinyg(client, n_keys: int = 200, size: int = 4 * 1024,
          threads: int = 8, volume: str = "freon-vol",
          bucket: str = "freon-tiny",
          replication: str = "rs-3-2-4096", prefix: str = "tiny",
          packer: bool = True, mix: bool = False,
          validate: bool = True) -> FreonReport:
    """Tiny-key generator (freon tinyg): the small-object-path
    workload. Writes `n_keys` tiny keys into a smallobj-enabled EC
    bucket so PUTs route through the inline/needle fast path — inline
    values live in OM metadata, needles coalesce through the client
    SlabPacker into shared EC stripes committed via CommitKeys.

    `packer=False` keeps the same key population but passes an explicit
    per-key replication, forcing every key down the classic
    open/allocate/commit stripe path — the before/after pair the bench
    compares. `mix=True` draws sizes from TINY_SIZES (mostly inline,
    some needles) instead of the fixed `size`; the swarm overload
    workload reuses the same mix via its `tiny` flag.

    Extras report how the population landed (inline/needle/regular key
    counts, distinct slabs) plus byte-exact `verify_failures`."""
    try:
        client.om.create_volume(volume)
    except Exception:
        pass
    try:
        client.om.create_bucket(volume, bucket, replication)
    except Exception:
        pass
    if packer:
        client.om.set_bucket_smallobj(volume, bucket)
    b = client.get_volume(volume).get_bucket(bucket)
    # packer off => explicit replication pins the per-key stripe path
    # (write_key only consults the smallobj config when the caller
    # leaves replication unset)
    per_key_repl = None if packer else replication

    def op(i: int) -> int:
        sz = _tiny_size(i, size, mix)
        b.write_key(f"{prefix}-{i}", _det_payload(sz, seed=i),
                    per_key_repl)
        return sz

    rep = BaseFreonGenerator("tinyg", n_keys, threads).run(op)
    if packer:
        client.packer.flush()
    inline = needle = regular = verify_failures = 0
    slabs: set = set()
    for i in range(n_keys):
        try:
            info = client.om.lookup_key(volume, bucket,
                                        f"{prefix}-{i}")
            if info.get("inline") is not None:
                inline += 1
            elif info.get("needle"):
                needle += 1
                slabs.add(info["needle"]["slab"])
            else:
                regular += 1
            if validate:
                got = b.read_key_info(info)
                want = _det_payload(_tiny_size(i, size, mix), seed=i)
                if not np.array_equal(got, want):
                    verify_failures += 1
        except Exception:
            verify_failures += 1
    rep.extras.update({
        "packer": packer,
        "inline_keys": inline,
        "needle_keys": needle,
        "regular_keys": regular,
        "slabs": len(slabs),
        "verify_failures": verify_failures,
    })
    rep.extras.update(_client_hist_extras())
    return rep


def geo(client, dest_endpoint: str, n_keys: int = 20,
        size: int = 10 * 1024, threads: int = 4,
        volume: str = "freon-vol", bucket: str = "freon-geo",
        replication: str = "RATIS/THREE", scheme: str = "",
        prefix: str = "geo", dest_client=None) -> FreonReport:
    """Geo-replication churn (write -> overwrite -> delete -> ship ->
    verify): the soak/CI probe for the geo-DR subsystem. Writes
    `n_keys` keys under a replication rule pointing at
    `dest_endpoint`, overwrites a third, deletes a fifth, triggers a
    ship cycle (`replication run-now`), then verifies convergence:
    every surviving key reads back byte-exact FROM THE DESTINATION and
    every deleted key is gone there. The timer covers the writes; the
    ship/verify outcome rides the report extras (`shipped`,
    `verify_failures`, `lag_entries`)."""
    try:
        client.om.create_volume(volume)
    except Exception:
        pass
    try:
        client.om.create_bucket(volume, bucket, replication)
    except Exception:
        pass
    client.om.set_bucket_geo_replication(volume, bucket, [{
        "id": "freon-geo", "endpoint": dest_endpoint, "prefix": prefix,
        "scheme": scheme,
    }])
    b = client.get_volume(volume).get_bucket(bucket)

    def op(i: int) -> int:
        b.write_key(f"{prefix}-{i}", _det_payload(size, seed=i),
                    replication)
        return size

    rep = BaseFreonGenerator("geo", n_keys, threads).run(op)
    ship1 = client.om.run_geo_once()  # initial convergence
    # churn AFTER the first ship so overwrites supersede shipped
    # replicas and deletes retire them: every 3rd key overwritten,
    # every 5th (of the rest) deleted
    expect: dict[str, Optional[int]] = {
        f"{prefix}-{i}": i for i in range(n_keys)
    }
    for i in range(0, n_keys, 3):
        b.write_key(f"{prefix}-{i}", _det_payload(size, seed=i + 1000),
                    replication)
        expect[f"{prefix}-{i}"] = i + 1000
    for i in range(1, n_keys, 5):
        b.delete_key(f"{prefix}-{i}")
        expect[f"{prefix}-{i}"] = None
    ship = client.om.run_geo_once()
    ship = {k: ship.get(k, 0) + (ship1.get(k, 0)
                                 if isinstance(ship1.get(k), int)
                                 else 0)
            for k in ("keys_shipped", "deletes_shipped", "conflicts",
                      "bytes")}
    if dest_client is None:
        from ozone_tpu.replication_geo.shipper import resolve_cluster

        dest_client = resolve_cluster(dest_endpoint).oz
    db = dest_client.get_volume(volume).get_bucket(bucket)
    verify_failures = 0
    for name, seed in expect.items():
        try:
            info = dest_client.om.lookup_key(volume, bucket, name)
        except Exception:
            if seed is not None:
                verify_failures += 1  # should exist at the destination
            continue
        if seed is None:
            verify_failures += 1  # deleted at source, still at dest
            continue
        got = db.read_key_info(info)
        if not np.array_equal(got, _det_payload(size, seed=seed)):
            verify_failures += 1
    status = client.om.geo_status()
    rep.extras.update({
        "shipped": ship.get("keys_shipped", 0),
        "deletes_shipped": ship.get("deletes_shipped", 0),
        "conflicts": ship.get("conflicts", 0),
        "ship_bytes": ship.get("bytes", 0),
        "verify_failures": verify_failures,
        "lag_entries": (status.get("lag") or {}).get("entries", 0),
    })
    return rep


def ockr(client, n_keys: int, threads: int = 4, volume: str = "freon-vol",
         bucket: str = "freon-bucket", prefix: str = "key") -> FreonReport:
    """Key read generator (validation pass over ockg output)."""
    b = client.get_volume(volume).get_bucket(bucket)

    def op(i: int) -> int:
        data = b.read_key(f"{prefix}-{i}")
        return int(data.size)

    rep = BaseFreonGenerator("ockr", n_keys, threads).run(op)
    rep.extras.update(_client_hist_extras())
    rep.extras.update(_device_extras())
    return rep


def ockrr(client, n_reads: int, threads: int = 4, size: int = 65536,
          volume: str = "freon-vol", bucket: str = "freon-bucket",
          prefix: str = "key", n_keys: int = 0) -> FreonReport:
    """Random ranged-read generator over ockg output: each op reads
    `size` bytes at a random offset of a random key through the
    positioned path (round 4 — only the covering cells move). `n_keys`
    bounds the key pool (0 = probe with key 0's size and assume `n_reads`
    keys are NOT required; the pool is keys 0..max(1, n_keys)-1)."""
    b = client.get_volume(volume).get_bucket(bucket)
    rng = np.random.default_rng(4)
    pool = max(1, n_keys)
    # one metadata probe sizes the keys (ockg writes equal sizes)
    key_size = int(b.lookup_key_info(f"{prefix}-0")["size"])
    span = max(1, key_size - size + 1)
    # pre-drawn schedule: worker threads must not share a Generator
    keys = rng.integers(0, pool, size=n_reads)
    offs = rng.integers(0, span, size=n_reads)

    def op(i: int) -> int:
        off = int(offs[i])
        ln = min(size, key_size - off)
        data = b.read_key_range(f"{prefix}-{int(keys[i])}", off, ln)
        return int(data.size)

    return BaseFreonGenerator("ockrr", n_reads, threads).run(op)


def _ensure_container(clients, dn_ids: list[str], container_id: int) -> None:
    """Idempotently create the bench container on every target datanode."""
    from ozone_tpu.storage.ids import StorageError

    for dn in dn_ids:
        try:
            clients.get(dn).create_container(container_id)
        except StorageError as e:
            if e.code != "CONTAINER_EXISTS":
                raise


def dcg(
    clients,
    dn_ids: list[str],
    n_chunks: int = 100,
    size: int = 1024 * 1024,
    threads: int = 4,
    container_id: int = 10_000_000,
) -> FreonReport:
    """Datanode chunk generator: raw WriteChunk, bypasses OM/SCM
    (DatanodeChunkGenerator analog)."""
    from ozone_tpu.storage.ids import BlockID, ChunkInfo, StorageError
    from ozone_tpu.utils.checksum import Checksum, ChecksumType

    rng = np.random.default_rng(1)
    payload = rng.integers(0, 256, size, dtype=np.uint8)
    cs = Checksum(ChecksumType.CRC32C, 16 * 1024).compute(payload)
    _ensure_container(clients, dn_ids, container_id)

    def op(i: int) -> int:
        dn = dn_ids[i % len(dn_ids)]
        bid = BlockID(container_id, i + 1)
        info = ChunkInfo(f"chunk_{i}", 0, size, cs)
        clients.get(dn).write_chunk(bid, info, payload)
        return size

    return BaseFreonGenerator("dcg", n_chunks, threads).run(op)


def dcb(
    clients,
    dn_ids: list[str],
    n_blocks: int = 20,
    size: int = 1024 * 1024,
    batch: int = 8,
    threads: int = 4,
    container_id: int = 30_000_000,
) -> FreonReport:
    """Batched chunk generator: `batch` client-checksummed chunks + the
    piggybacked putBlock per ONE WriteChunksCommit stream — the raw-path
    isolation of round 4's batched write verb (dcg pays a transport
    round trip per chunk; this pays one per block)."""
    from ozone_tpu.storage.ids import BlockData, BlockID, ChunkInfo
    from ozone_tpu.utils.checksum import Checksum, ChecksumType

    rng = np.random.default_rng(3)
    payload = rng.integers(0, 256, size, dtype=np.uint8)
    cs = Checksum(ChecksumType.CRC32C, 16 * 1024).compute(payload)
    _ensure_container(clients, dn_ids, container_id)

    def op(i: int) -> int:
        dn = dn_ids[i % len(dn_ids)]
        bid = BlockID(container_id, i + 1)
        pairs = [
            (ChunkInfo(f"{bid}_chunk_{j}", j * size, size, cs), payload)
            for j in range(batch)
        ]
        clients.get(dn).write_chunks_commit(
            bid, pairs, commit=BlockData(bid, [c for c, _ in pairs]))
        return size * batch

    return BaseFreonGenerator("dcb", n_blocks, threads).run(op)


def dsg(
    clients,
    dn_ids: list[str],
    n_blocks: int = 20,
    size: int = 8 * 1024 * 1024,
    frame_size: int = 1024 * 1024,
    chunk_size: int = 4 * 1024 * 1024,
    threads: int = 4,
    container_id: int = 20_000_000,
) -> FreonReport:
    """Datanode streaming-write generator (StreamingGenerator analog):
    whole blocks over the client-streaming RPC, one commit ack each."""
    from ozone_tpu.storage.ids import BlockID, StorageError

    rng = np.random.default_rng(2)
    payload = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
    _ensure_container(clients, dn_ids, container_id)

    def op(i: int) -> int:
        dn = dn_ids[i % len(dn_ids)]
        frames = (payload[o:o + frame_size]
                  for o in range(0, len(payload), frame_size))
        bd = clients.get(dn).stream_write_block(
            BlockID(container_id, i + 1), frames, chunk_size=chunk_size)
        assert bd.length == size
        return size

    return BaseFreonGenerator("dsg", n_blocks, threads).run(op)


def _freon_buckets(client, volume: str, bucket: str,
                   buckets: int) -> list[str]:
    """Create the generator's bucket set. buckets > 1 spreads ops over
    `bucket-<j>` names — on a sharded metadata plane the (volume,
    bucket) hash then fans the load across shard rings instead of
    serializing everything on one ring's slot."""
    try:
        client.om.create_volume(volume)
    except Exception:
        pass
    names = ([bucket] if buckets <= 1
             else [f"{bucket}-{j}" for j in range(buckets)])
    for name in names:
        try:
            client.om.create_bucket(volume, name)
        except Exception:
            pass
    return names


def omkg(client, n_keys: int = 1000, threads: int = 8,
         volume: str = "freon-vol", bucket: str = "freon-meta",
         buckets: int = 1) -> FreonReport:
    """Pure OM metadata op generator: open+commit empty keys without any
    datanode IO (OmKeyGenerator analog — measures namespace throughput)."""
    names = _freon_buckets(client, volume, bucket, buckets)

    def op(i: int) -> int:
        b = names[i % len(names)]
        s = client.om.open_key(volume, b, f"meta-{i}")
        client.om.commit_key(s, [], 0)
        return 0

    return BaseFreonGenerator("omkg", n_keys, threads).run(op)


def dcv(clients, dn_ids: list[str], n_chunks: int, size: int = 1024 * 1024,
        threads: int = 4, container_id: int = 10_000_000) -> FreonReport:
    """Datanode chunk validator: read back + checksum-verify chunks written
    by dcg (DatanodeChunkValidator analog)."""
    from ozone_tpu.storage.ids import BlockID, ChunkInfo
    from ozone_tpu.utils.checksum import Checksum, ChecksumType

    rng = np.random.default_rng(1)
    payload = rng.integers(0, 256, size, dtype=np.uint8)
    cs = Checksum(ChecksumType.CRC32C, 16 * 1024).compute(payload)

    def op(i: int) -> int:
        dn = dn_ids[i % len(dn_ids)]
        bid = BlockID(container_id, i + 1)
        info = ChunkInfo(f"chunk_{i}", 0, size, cs)
        data = clients.get(dn).read_chunk(bid, info, verify=True)
        assert data.size == size
        return size

    return BaseFreonGenerator("dcv", n_chunks, threads).run(op)


def cmdw(root, n_chunks: int = 200, size: int = 4 * 1024 * 1024,
         threads: int = 4) -> FreonReport:
    """Chunk-manager disk write: pure local chunk IO, no network, no
    OM/SCM (ChunkManagerDiskWrite analog — isolates the disk path)."""
    from pathlib import Path

    from ozone_tpu.storage.chunk_store import FilePerBlockStore
    from ozone_tpu.storage.ids import BlockID, ChunkInfo
    from ozone_tpu.utils.checksum import Checksum, ChecksumType

    store = FilePerBlockStore(Path(root))
    rng = np.random.default_rng(3)
    payload = rng.integers(0, 256, size, dtype=np.uint8)
    cs = Checksum(ChecksumType.CRC32C, 16 * 1024).compute(payload)

    def op(i: int) -> int:
        bid = BlockID(1 + i // 64, i + 1)
        store.write_chunk(bid, ChunkInfo(f"c{i}", 0, size, cs), payload)
        return size

    return BaseFreonGenerator("cmdw", n_chunks, threads).run(op)


def scmtb(client, n_blocks: int = 1000, threads: int = 8,
          replication: str = "rs-3-2-4096",
          block_size: int = 16 * 1024 * 1024) -> FreonReport:
    """SCM block-allocation throughput (SCMThroughputBenchmark analog):
    hammers allocateBlock without writing any data."""
    from ozone_tpu.scm.pipeline import ReplicationConfig

    cfg = ReplicationConfig.parse(replication)
    if hasattr(client.om, "scm") and not isinstance(client.om.scm, str):
        # in-process OM: call the SCM manager directly
        op_alloc = lambda: client.om.scm.allocate_block(cfg, block_size)
    else:
        # remote OM: the co-located SCM service honors block_size
        from ozone_tpu.net.scm_service import GrpcScmClient

        scm = GrpcScmClient(client.om.address,
                            tls=getattr(client.om, "tls", None))
        op_alloc = lambda: scm.allocate_block(replication, block_size)

    def op(i: int) -> int:
        op_alloc()
        return 0

    return BaseFreonGenerator("scmtb", n_blocks, threads).run(op)


def dnsim(scm, n_datanodes: int = 50, n_containers: int = 5,
          duration_s: float = 5.0, interval_s: float = 0.5,
          threads: int = 8, prefix: str = "simdn",
          fcr_every_rounds: int = 10) -> FreonReport:
    """Simulated-datanode fleet (freon DatanodeSimulator.java:122
    analog): registers n virtual datanodes with the SCM over the real
    register/heartbeat wire protocol, then heartbeats each of them from
    a thread pool for duration_s, carrying a fabricated full container
    report on the first beat and every fcr_every_rounds after (the
    reference's FCR cadence). Nodes register IN_MAINTENANCE so placement
    never selects them — the reference moves its simulated datanodes to
    read-only for the same reason — and fabricated container ids live in
    a high namespace no real allocation reaches, so the replication
    manager (which walks the container table, not the replica map)
    ignores them. Measures SCM heartbeat ingest: hb/s + latency
    percentiles."""
    ids = [f"{prefix}-{i}" for i in range(n_datanodes)]
    for i, dn_id in enumerate(ids):
        scm.register(dn_id, f"sim://{dn_id}", rack=f"/sim-rack-{i % 8}",
                     capacity_bytes=1 << 40, op_state="IN_MAINTENANCE")
    base = 50_000_000

    def report_for(i: int) -> list[dict]:
        return [{
            "container_id": base + i * n_containers + j,
            "state": "CLOSED",
            "replica_index": 0,
            "block_count": 64,
            "used_bytes": 4 << 20,
        } for j in range(n_containers)]

    lock = threading.Lock()
    lat: list[float] = []
    counts = {"hb": 0, "fcr": 0, "failures": 0}
    stop_at = time.time() + duration_s

    def worker(shard: list[int]) -> None:
        rounds = 0
        while time.time() < stop_at:
            round_t0 = time.time()
            for idx in shard:
                rep = (report_for(idx)
                       if rounds % fcr_every_rounds == 0 else None)
                s = time.perf_counter()
                try:
                    scm.heartbeat(ids[idx], container_report=rep,
                                  used_bytes=(4 << 20) * n_containers)
                except Exception:
                    with lock:
                        counts["failures"] += 1
                    continue
                dt = time.perf_counter() - s
                with lock:
                    lat.append(dt)
                    counts["hb"] += 1
                    if rep is not None:
                        counts["fcr"] += 1
            rounds += 1
            pause = interval_s - (time.time() - round_t0)
            if pause > 0:
                time.sleep(pause)

    threads = max(1, threads)
    shards = [list(range(w, n_datanodes, threads))
              for w in range(threads)]
    ts = [threading.Thread(target=worker, args=(s,), daemon=True)
          for s in shards if s]
    t0 = time.time()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return FreonReport(
        "dnsim", ops=counts["hb"], failures=counts["failures"],
        elapsed_s=time.time() - t0, latencies_s=lat,
        extras={"datanodes": n_datanodes, "fcrs": counts["fcr"],
                "containers_per_dn": n_containers})


def dbgen(db_path, n_keys: int = 10_000, volume: str = "genvol",
          bucket: str = "genbucket", threads: int = 1) -> FreonReport:
    """Offline OM metadata fabrication (freon GeneratorOm analog): writes
    a populated OM database directly — no cluster, no datanodes — for
    testing metadata-scale behavior (billion-key DBs in the reference)."""
    from pathlib import Path

    from ozone_tpu.om.metadata import OMMetadataStore, bucket_key, key_key, \
        volume_key

    store = OMMetadataStore(Path(db_path), flush_every=4096)
    store.put("volumes", volume_key(volume),
              {"name": volume, "owner": "freon", "quota_bytes": -1,
               "created": time.time()})
    store.put("buckets", bucket_key(volume, bucket),
              {"volume": volume, "name": bucket,
               "replication": "rs-6-3-1024k", "layout": "OBJECT_STORE",
               "versioning": False, "created": time.time()})

    def op(i: int) -> int:
        kk = key_key(volume, bucket, f"gen/{i // 1000}/key-{i}")
        store.put("keys", kk, {
            "volume": volume, "bucket": bucket,
            "name": f"gen/{i // 1000}/key-{i}",
            "replication": "rs-6-3-1024k",
            "checksum_type": "CRC32C", "bytes_per_checksum": 16384,
            "size": 1024, "block_groups": [], "created": time.time(),
            "modified": time.time(),
        })
        return 1024

    # single-threaded by design: sqlite writer; flush batching does the work
    report = BaseFreonGenerator("dbgen", n_keys, threads).run(op)
    store.close()
    return report


def ommg(client, n_ops: int = 1000, threads: int = 8,
         volume: str = "freon-vol", bucket: str = "freon-meta",
         mix: str = "crudl", buckets: int = 1) -> FreonReport:
    """Mixed OM metadata ops (OmMetadataGenerator analog): cycles
    create/read(lookup)/update(rename)/delete/list per the mix string."""
    bad = set(mix) - set("crudl")
    if not mix or bad:
        raise ValueError(f"mix must be chars from 'crudl', got {mix!r}")
    names = _freon_buckets(client, volume, bucket, buckets)
    # seed keys the read/delete ops can hit (every bucket gets the full
    # seed set: op i addresses bucket i % len(names))
    for name in names:
        for i in range(min(64, n_ops)):
            s = client.om.open_key(volume, name, f"mix-{i}")
            client.om.commit_key(s, [], 0)

    def op(i: int) -> int:
        kind = mix[i % len(mix)]
        b = names[i % len(names)]
        name = f"mix-{i % 64}"
        if kind == "c":
            s = client.om.open_key(volume, b, f"mix-new-{i}")
            client.om.commit_key(s, [], 0)
        elif kind == "r":
            client.om.lookup_key(volume, b, name)
        elif kind == "u":
            client.om.rename_key(volume, b, name, name + ".r")
            client.om.rename_key(volume, b, name + ".r", name)
        elif kind == "d":
            s = client.om.open_key(volume, b, f"mix-del-{i}")
            client.om.commit_key(s, [], 0)
            client.om.delete_key(volume, b, f"mix-del-{i}")
        elif kind == "l":
            client.om.list_keys(volume, b, "mix-")
        return 0

    return BaseFreonGenerator("ommg", n_ops, threads).run(op)


def rawcoder_bench(
    backends: Optional[list[str]] = None,
    schema: str = "rs-6-3",
    cell: int = 1024 * 1024,
    batch: int = 8,
    iters: int = 5,
) -> list[dict]:
    """Raw coder throughput matrix (RawErasureCoderBenchmark analog)."""
    from ozone_tpu.codec import CoderOptions, create_decoder, create_encoder
    from ozone_tpu.codec.registry import CodecRegistry

    parts = schema.split("-")
    opts = CoderOptions(int(parts[1]), int(parts[2]), parts[0], cell)
    backends = backends or CodecRegistry.instance().backends(opts.codec)
    rng = np.random.default_rng(2)
    data = rng.integers(0, 256, (batch, opts.data_units, cell), dtype=np.uint8)
    out = []
    for be in backends:
        try:
            enc = create_encoder(opts, be)
            enc.encode(data)  # warm
            t0 = time.time()
            for _ in range(iters):
                parity = enc.encode(data)
            enc_dt = (time.time() - t0) / iters

            dec = create_decoder(opts, be)
            units = np.concatenate([data, parity], axis=1)
            erased = list(range(min(2, opts.parity_units)))
            inputs = [
                None if i in erased else units[:, i]
                for i in range(opts.all_units)
            ]
            dec.decode(inputs, erased)  # warm
            t0 = time.time()
            for _ in range(iters):
                dec.decode(inputs, erased)
            dec_dt = (time.time() - t0) / iters
            gib = data.nbytes / 2**30
            out.append(
                {
                    "backend": be,
                    "schema": schema,
                    "encode_gib_s": round(gib / enc_dt, 3),
                    "decode_gib_s": round(gib / dec_dt, 3),
                }
            )
        except Exception as e:
            out.append({"backend": be, "schema": schema, "error": str(e)})
    return out


def dnbp(
    clients,
    dn_ids: list[str],
    n_blocks: int = 200,
    chunks_per_block: int = 4,
    size: int = 1024 * 1024,
    threads: int = 4,
    container_id: int = 30_000_000,
) -> FreonReport:
    """Datanode block putter (DatanodeBlockPutter analog): raw putBlock
    metadata commits against datanodes — block-manager throughput with no
    chunk IO on the timed path."""
    from ozone_tpu.storage.ids import BlockData, BlockID, ChunkInfo
    from ozone_tpu.utils.checksum import Checksum, ChecksumType

    rng = np.random.default_rng(3)
    sample = rng.integers(0, 256, 4096, dtype=np.uint8)
    cs = Checksum(ChecksumType.CRC32C, 4096).compute(sample)
    _ensure_container(clients, dn_ids, container_id)

    def op(i: int) -> int:
        dn = dn_ids[i % len(dn_ids)]
        bid = BlockID(container_id, i + 1)
        chunks = [
            ChunkInfo(f"{bid}_chunk_{c}", c * size, size, cs)
            for c in range(chunks_per_block)
        ]
        clients.get(dn).put_block(BlockData(bid, chunks))
        return 0

    return BaseFreonGenerator("dnbp", n_blocks, threads).run(op)


def ralg(
    root,
    n_entries: int = 2000,
    size: int = 1024,
    threads: int = 1,
) -> FreonReport:
    """Raft log append generator (LeaderAppendLogEntryGenerator analog):
    a local 3-node consensus ring commits payload entries through the
    leader — measures log append + quorum-commit throughput including
    durable log writes."""
    from pathlib import Path

    from ozone_tpu.consensus.raft import InProcessTransport, RaftNode

    root = Path(root)
    transport = InProcessTransport()
    ids = ["r0", "r1", "r2"]
    sink: list = []
    nodes = [
        RaftNode(nid, ids, root / nid, (lambda _e: None) if nid != "r0"
                 else sink.append, transport=transport)
        for nid in ids
    ]
    assert nodes[0].start_election()
    payload = "x" * size

    def op(i: int) -> int:
        nodes[0].propose(f"{i}:{payload}")
        return size

    try:
        return BaseFreonGenerator("ralg", n_entries, threads).run(op)
    finally:
        for n in nodes:
            n.stop()


def ockv(client, n_keys: int = 100, size: int = 10 * 1024,
         threads: int = 4, volume: str = "freon-vol",
         bucket: str = "freon-bucket",
         prefix: str = "key") -> FreonReport:
    """Key VALIDATOR (freon ockv / the validate-writes family): read
    back keys previously written by ockg and verify content — a
    deterministic per-key payload, so corruption anywhere in the path
    (datanode, codec, decrypt) fails the op rather than passing bytes
    through."""
    b = client.get_volume(volume).get_bucket(bucket)
    expect = _det_payload(size)

    def op(i: int) -> int:
        got = b.read_key(f"{prefix}-{i}")
        assert np.array_equal(got, expect), f"corrupt key {prefix}-{i}"
        return int(got.size)

    rep = BaseFreonGenerator("ockv", n_keys, threads).run(op)
    rep.extras.update(_device_extras())
    return rep


def fskg(client, n_files: int = 100, size: int = 10 * 1024,
         depth: int = 3, threads: int = 4, volume: str = "freon-vol",
         bucket: str = "freon-fso",
         replication: Optional[str] = None) -> FreonReport:
    """Nested-file generator over an FSO bucket (the reference's
    HadoopNestedDirGenerator + file create family): each op creates a
    file `depth` directories down, exercising the directory-tree
    resolve/create path rather than the flat key table."""
    try:
        client.om.create_volume(volume)
    except Exception:
        pass
    try:
        client.om.create_bucket(volume, bucket,
                                replication or "rs-6-3-1024k",
                                layout="FILE_SYSTEM_OPTIMIZED")
    except Exception:
        pass
    b = client.get_volume(volume).get_bucket(bucket)
    payload = np.random.default_rng(1).integers(0, 256, size,
                                                dtype=np.uint8)

    def op(i: int) -> int:
        parts = [f"d{(i >> (4 * d)) & 0xF}" for d in range(depth)]
        b.write_key("/".join(parts) + f"/f{i}", payload, replication)
        return size

    return BaseFreonGenerator("fskg", n_files, threads).run(op)


def mpug(client, n_uploads: int = 20, parts: int = 3,
         part_size: int = 16 * 1024, threads: int = 4,
         volume: str = "freon-vol", bucket: str = "freon-mpu",
         replication: Optional[str] = None) -> FreonReport:
    """Multipart-upload generator (S3MultipartUpload freon family):
    each op runs initiate -> N part writes -> complete and counts the
    full upload round trip."""
    try:
        client.om.create_volume(volume)
    except Exception:
        pass
    try:
        client.om.create_bucket(volume, bucket,
                                replication or "rs-6-3-1024k")
    except Exception:
        pass
    b = client.get_volume(volume).get_bucket(bucket)
    payload = np.random.default_rng(2).integers(0, 256, part_size,
                                                dtype=np.uint8)

    def op(i: int) -> int:
        up = b.initiate_multipart_upload(f"mpu-{i}", replication)
        for p in range(1, parts + 1):
            up.write_part(p, payload)
        up.complete()
        return part_size * parts

    return BaseFreonGenerator("mpug", n_uploads, threads).run(op)


def s3kg(endpoint: str, n_keys: int = 100, size: int = 10 * 1024,
         threads: int = 4, bucket: str = "freon-s3",
         validate: bool = False) -> FreonReport:
    """S3 gateway key generator (freon s3kg): PUTs (and optionally
    GET-validates) through the HTTP gateway, covering the full
    XML/HTTP/auth surface rather than the native RPC path."""
    import urllib.request

    base = f"http://{endpoint}"
    try:
        urllib.request.urlopen(urllib.request.Request(
            f"{base}/{bucket}", method="PUT"))
    except Exception:
        pass
    payload = bytes(np.random.default_rng(3).integers(
        0, 256, size, dtype=np.uint8))

    def op(i: int) -> int:
        with urllib.request.urlopen(urllib.request.Request(
                f"{base}/{bucket}/k{i}", data=payload,
                method="PUT")) as r:
            r.read()
        if validate:
            with urllib.request.urlopen(f"{base}/{bucket}/k{i}") as r:
                got = r.read()
            assert got == payload, f"corrupt s3 key k{i}"
        return size * (2 if validate else 1)

    return BaseFreonGenerator("s3kg", n_keys, threads).run(op)


def fsg(client, n_files: int = 50, size: int = 10 * 1024,
        threads: int = 4, volume: str = "freon-vol",
        bucket: str = "freon-ofs",
        replication: Optional[str] = None) -> FreonReport:
    """ofs filesystem generator (HadoopFsGenerator analog): each op is
    a create + read-back through the RootedOzoneFileSystem adapter —
    the path HttpFS and Hadoop-compatible workloads take."""
    from ozone_tpu.gateway.fs import RootedOzoneFileSystem

    fs = RootedOzoneFileSystem(client,
                               replication=replication or "rs-6-3-1024k")
    fs.mkdirs(f"/{volume}/{bucket}")
    payload = bytes(np.random.default_rng(4).integers(
        0, 256, size, dtype=np.uint8))

    def op(i: int) -> int:
        p = f"/{volume}/{bucket}/d{i % 8}/f{i}"
        fs.create(p, payload)
        with fs.open(p) as f:
            got = f.read()
        assert len(got) == size
        return size * 2

    return BaseFreonGenerator("fsg", n_files, threads).run(op)


def sdg(client, n_rounds: int = 10, keys_per_round: int = 5,
        size: int = 2048, volume: str = "freon-vol",
        bucket: str = "freon-snap",
        replication: Optional[str] = None) -> FreonReport:
    """Snapshot-diff generator: each op writes a handful of keys,
    snapshots, and diffs against the previous snapshot — timing the
    incremental-diff path end to end. Single-threaded by design: round
    i diffs against round i-1's snapshot, so concurrency would race
    the chain. Snapshot names carry a per-run prefix so reruns against
    a live cluster don't collide with earlier runs' snapshots."""
    import uuid

    try:
        client.om.create_volume(volume)
    except Exception:
        pass
    try:
        client.om.create_bucket(volume, bucket,
                                replication or "rs-6-3-1024k")
    except Exception:
        pass
    b = client.get_volume(volume).get_bucket(bucket)
    payload = np.random.default_rng(6).integers(0, 256, size,
                                                dtype=np.uint8)
    run = uuid.uuid4().hex[:8]

    def op(i: int) -> int:
        for k in range(keys_per_round):
            b.write_key(f"{run}-r{i}-k{k}", payload)
        client.om.create_snapshot(volume, bucket, f"{run}-s{i}")
        if i > 0:
            d = client.om.snapshot_diff(volume, bucket,
                                        f"{run}-s{i - 1}",
                                        f"{run}-s{i}")
            added = set(d.get("added", []))
            assert all(f"{run}-r{i}-k{k}" in added
                       for k in range(keys_per_round)), d
        return keys_per_round * int(payload.size)

    return BaseFreonGenerator("sdg", n_rounds, threads=1).run(op)


def _dispatch_counts() -> tuple[int, int]:
    """(dispatches, stripes) the shared codec service and the mesh
    executor have launched in this process so far."""
    from ozone_tpu.codec import service as codec_service
    from ozone_tpu.parallel import mesh_executor

    snaps = (codec_service.METRICS.snapshot(),
             mesh_executor.METRICS.snapshot())
    return (sum(int(s.get("dispatches", 0)) for s in snaps),
            sum(int(s.get("stripes_dispatched", 0)) for s in snaps))


class ReplicaMismatch(Exception):
    """A rebuilt replica is not what was written."""


def _verify_rebuilt_unit(dn, group, opts, unit: int,
                         payload: np.ndarray) -> int:
    """Read DATA unit `unit` of `group` straight off datanode client
    `dn` — chunk records, stored CRCs and bytes, no reader and so no
    decode — and hold it to `payload`, the bytes the group was written
    from. Returns the bytes compared; raises ReplicaMismatch (or the
    datanode's StorageError when the replica is not there at all)."""
    from ozone_tpu.client.ec_reader import unit_true_lengths
    from ozone_tpu.utils.checksum import Checksum, ChecksumType

    k, cell = opts.data_units, opts.cell_size
    blk = dn.get_block(group.block_id)
    if blk.block_group_length != group.length:
        raise ReplicaMismatch(
            f"block group length {blk.block_group_length}, "
            f"wrote {group.length}")
    want_len = unit_true_lengths(group, opts)[unit]
    got_len = sum(info.length for info in blk.chunks)
    if got_len != want_len or len(
            {info.offset for info in blk.chunks}) != len(blk.chunks):
        raise ReplicaMismatch(
            f"unit {unit} holds {got_len} bytes in {len(blk.chunks)} "
            f"chunks, wrote {want_len}")
    for info in blk.chunks:
        at = (info.offset // cell * k + unit) * cell
        want = payload[at:at + info.length]
        got = dn.read_chunk(group.block_id, info, verify=True)
        if not np.array_equal(np.asarray(got).reshape(-1), want):
            raise ReplicaMismatch(
                f"unit {unit} chunk at {info.offset}: bytes differ")
        sums = info.checksum
        if sums.type is not ChecksumType.CRC32C or sums != Checksum(
                sums.type, sums.bytes_per_checksum).compute(want):
            raise ReplicaMismatch(
                f"unit {unit} chunk at {info.offset}: stored CRCs differ")
    return got_len


def ecrd(
    client,
    scm,
    size: int = 64 * 1024 * 1024,
    rounds: int = 3,
    replication: str = "rs-6-3-1048576",
    volume: str = "freon-vol",
    bucket: str = "freon-ecrd",
) -> dict:
    """EC Reconstruction Drill: the END-TO-END repair path in BASELINE's
    unit (MiB/s/datanode). Writes an EC key, closes its containers,
    wipes one unit's replica, and times ECReconstructionCoordinator
    repairing it onto a spare datanode — survivor reads + device decode
    + target writes, all over the real wire
    (ECReconstructionCoordinator.java:146 reconstructECContainerGroup).
    On a multi-device host the decode batches ride the process mesh
    executor, as in client/reconstruction.py. Outside the timed region
    every round reads the REBUILT REPLICA ITSELF off the target datanode
    (_verify_rebuilt_unit; never through a reader, which would decode
    around a replica that is missing or wrong) and compares its bytes
    and stored CRCs with what was written; a round that fails this
    counts in `failures`. `repair_dispatches` are the device dispatches
    launched inside the timed region, i.e. by the coordinator alone.
    """
    import time as _time

    from ozone_tpu.codec.api import CoderOptions
    from ozone_tpu.parallel import mesh_executor
    from ozone_tpu.storage.ids import StorageError
    from ozone_tpu.storage.reconstruction import (
        ECReconstructionCoordinator,
        ReconstructionCommand,
    )

    opts = CoderOptions.parse(replication)
    try:
        client.om.create_volume(volume)
    except Exception:
        pass
    try:
        client.om.create_bucket(volume, bucket, replication)
    except Exception:
        pass
    b = client.get_volume(volume).get_bucket(bucket)
    payload = _det_payload(size, seed=9)
    all_nodes = [n["dn_id"] for n in scm.status()["nodes"]]
    coord = ECReconstructionCoordinator(
        client.clients, executor=mesh_executor.maybe_executor())
    results = []
    failures = 0
    first_error = ""
    verified = 0
    repair = [0, 0]  # dispatches, stripes launched by the coordinator
    for r in range(rounds):
        key = f"drill-{r}"
        b.write_key(key, payload, replication)
        groups = client.om.key_block_groups(
            client.om.lookup_key(volume, bucket, key))
        g = groups[0]
        # close replicas DIRECTLY on the datanodes (synchronous): going
        # through the SCM would queue close commands that arrive over
        # later heartbeats and race the drill's RECOVERING container
        for dn_id in set(g.pipeline.nodes):
            try:
                client.clients.get(dn_id).close_container(g.container_id)
            except Exception:
                pass
        lost = 1  # a data unit
        client.clients.get(g.pipeline.nodes[lost]).delete_container(
            g.container_id, force=True)
        # a node holding no replica of this group; when the pipeline
        # spans every node, the wiped node itself (it no longer holds
        # one) — matching the placement policy's candidate set
        spare = next((d for d in all_nodes
                      if d not in g.pipeline.nodes),
                     g.pipeline.nodes[lost])
        cmd = ReconstructionCommand(
            g.container_id, opts,
            sources={u + 1: g.pipeline.nodes[u]
                     for u in range(opts.all_units) if u != lost},
            targets={lost + 1: spare},
        )
        before = _dispatch_counts()
        t0 = _time.perf_counter()
        coord.reconstruct_container_group(cmd)
        dt = _time.perf_counter() - t0
        after = _dispatch_counts()
        repair = [n + a - b for n, a, b in zip(repair, after, before)]
        unit_bytes = -(-g.length // opts.data_units)
        results.append((unit_bytes, dt))
        try:
            verified += _verify_rebuilt_unit(
                client.clients.get(spare), g, opts, lost, payload)
        except (StorageError, ReplicaMismatch) as e:
            failures += 1
            first_error = first_error or f"round {r}: {e!r}"
        b.delete_key(key)
    per_dn = [ub / 2**20 / dt for ub, dt in results]
    per_dn.sort()
    out = {
        "generator": "ecrd",
        "rounds": rounds,
        "failures": failures,
        **({"first_error": first_error} if failures else {}),
        "unit_mib": round(results[0][0] / 2**20, 2),
        # everything the coordinator wrote (the container's other
        # blocks included) / the drill keys' units compared above
        "bytes_reconstructed": int(coord.metrics.counter(
            "bytes_reconstructed").value),
        "bytes_verified": verified,
        "repair_dispatches": repair[0],
        "repair_stripes": repair[1],
        "reconstruct_mib_s_per_datanode": round(
            per_dn[len(per_dn) // 2], 2),
        "best_mib_s_per_datanode": round(per_dn[-1], 2),
        "times_s": [round(dt, 3) for _, dt in results],
        **_device_extras(),
    }
    return out


def swarm(endpoint: str, tenants: list, duration_s: float = 4.0,
          threads_per_tenant: int = 2, n_keys: int = 64,
          sizes: tuple = (4 * 1024, 64 * 1024), zipf_a: float = 1.2,
          seed: int = 1234, bucket: str = "swarm",
          tiny: bool = False) -> FreonReport:
    """freon swarm: the standing multi-tenant overload workload.

    N simulated tenants drive the S3 gateway closed-loop through
    SigV4-signed HTTP — Zipfian key popularity over a bounded working
    set, mixed op sizes (mostly small, some bulk), mixed PUT/GET. Each
    tenant dict carries {"name", "access_id", "secret", "rate"}: rate
    is its offered ops/s (0 = unpaced, as fast as the loop turns), so
    the caller ramps offered load — 1x capacity, then 2x with an
    aggressor unpaced — without changing the workload shape.

    503 SlowDown responses are counted as SHED, not failures: a shed op
    is the admission system doing its job, and the report separates the
    three outcomes (ok / shed / errors) per tenant so shed-not-collapse
    is checkable — goodput and accepted-op latency per tenant, shed
    fraction overall.
    """
    import bisect
    import datetime
    import random as _random
    import urllib.error
    import urllib.request

    from ozone_tpu.gateway.s3_auth import sign_request

    if tiny:
        # tiny-key churn mode: the tinyg size mix drives the swarm, so
        # the overload drills exercise the inline/needle path too (the
        # gateway-side bucket must be smallobj-enabled by the caller)
        sizes = TINY_SIZES
    base = f"http://{endpoint}"

    def _amz_now() -> str:
        return datetime.datetime.now(datetime.timezone.utc).strftime(
            "%Y%m%dT%H%M%SZ")

    def _request(t: dict, method: str, path: str,
                 body: bytes = b"") -> None:
        url = f"{base}{path}"
        headers = {"host": endpoint, "x-amz-date": _amz_now()}
        if t.get("access_id"):
            headers = sign_request(t["access_id"], t["secret"], method,
                                   url, headers, body)
        req = urllib.request.Request(
            url, data=body if method in ("PUT", "POST") else None,
            method=method, headers=headers)
        with urllib.request.urlopen(req) as r:
            r.read()

    # Zipfian popularity: cumulative weights over key ranks, sampled by
    # bisect — rank 0 is the hot key, the tail cools as 1/rank^a
    cum: list[float] = []
    acc = 0.0
    for r in range(max(1, n_keys)):
        acc += 1.0 / (r + 1) ** zipf_a
        cum.append(acc)
    payloads = {sz: bytes(np.random.default_rng(11).integers(
        0, 256, sz, dtype=np.uint8)) for sz in sizes}

    for t in tenants:
        try:
            _request(t, "PUT", f"/{bucket}")
        except Exception:
            pass  # BucketAlreadyExists across phases

    lock = threading.Lock()
    stats = {t["name"]: {"offered": 0, "ok": 0, "shed": 0, "errors": 0,
                         "bytes": 0, "lat": []} for t in tenants}
    written: dict[str, set] = {t["name"]: set() for t in tenants}
    start = time.monotonic()
    end = start + duration_s

    def worker(t: dict, wid: int) -> None:
        st = stats[t["name"]]
        seen = written[t["name"]]
        rng = _random.Random(f"{seed}:{t['name']}:{wid}")
        rate = float(t.get("rate") or 0.0)
        interval = threads_per_tenant / rate if rate > 0 else 0.0
        next_t = time.monotonic() + rng.uniform(0, interval or 0.001)
        while True:
            now = time.monotonic()
            if now >= end:
                return
            if interval:
                # paced offered load: ops fire on a schedule, late ops
                # do NOT bunch up (the schedule advances regardless)
                if next_t >= end:
                    return
                if next_t > now:
                    time.sleep(next_t - now)
                next_t += interval
            rank = bisect.bisect_left(cum, rng.uniform(0.0, cum[-1]))
            key = f"{t['name']}-k{rank}"
            size = sizes[0] if rng.random() < 0.8 else sizes[-1]
            do_put = rank not in seen or rng.random() < 0.5
            s0 = time.perf_counter()
            try:
                if do_put:
                    _request(t, "PUT", f"/{bucket}/{key}",
                             payloads[size])
                else:
                    _request(t, "GET", f"/{bucket}/{key}")
                dt = time.perf_counter() - s0
                with lock:
                    st["offered"] += 1
                    st["ok"] += 1
                    st["bytes"] += size
                    st["lat"].append(dt)
                if do_put:
                    seen.add(rank)
            except urllib.error.HTTPError as e:
                e.close()
                with lock:
                    st["offered"] += 1
                    if e.code == 503:
                        st["shed"] += 1
                    else:
                        st["errors"] += 1
            except Exception:
                with lock:
                    st["offered"] += 1
                    st["errors"] += 1

    threads = [threading.Thread(target=worker, args=(t, w), daemon=True)
               for t in tenants for w in range(threads_per_tenant)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    elapsed = time.monotonic() - start

    def _p99(lat: list) -> float:
        if not lat:
            return 0.0
        ls = sorted(lat)
        return ls[min(len(ls) - 1, int(0.99 * len(ls)))]

    all_lat: list[float] = []
    per_tenant = {}
    offered = ok = shed = errors = nbytes = 0
    for name, st in stats.items():
        all_lat.extend(st["lat"])
        offered += st["offered"]
        ok += st["ok"]
        shed += st["shed"]
        errors += st["errors"]
        nbytes += st["bytes"]
        per_tenant[name] = {
            "offered": st["offered"],
            "ok": st["ok"],
            "shed": st["shed"],
            "errors": st["errors"],
            "goodput_ops_s": round(st["ok"] / elapsed, 2)
            if elapsed else 0.0,
            "p99_ms": round(1e3 * _p99(st["lat"]), 3),
        }
    return FreonReport(
        "swarm", ops=ok, failures=errors, elapsed_s=elapsed,
        latencies_s=all_lat, bytes_processed=nbytes,
        extras={
            "per_tenant": per_tenant,
            "offered": offered,
            "shed": shed,
            "shed_fraction": round(shed / offered, 4) if offered else 0.0,
            "goodput_ops_s": round(ok / elapsed, 2) if elapsed else 0.0,
        })
