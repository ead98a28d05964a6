"""ozone_tpu: a TPU-native distributed object-store framework.

Ground-up re-design of the capabilities of Apache Ozone (reference at
/root/reference) for TPU hardware: erasure-coding (RS/XOR over GF(2^8)) and
CRC32C checksumming run on-device as batched GF(2) linear algebra under
jit/vmap/shard_map, surrounded by a lean host runtime providing Ozone's
storage model (volumes/buckets/keys -> block groups -> containers -> chunks),
metadata services (OM/SCM analogs), replication & reconstruction control
loops, and freon-style benchmarks.

Package map (SURVEY.md section 7 build order):
  codec/    GF(2^8) + RS math, numpy reference coder, JAX/TPU coder,
            device CRC32C, fused encode+checksum, SPI registry
  parallel/ device mesh helpers, shard_map sharded encode/reconstruct
  storage/  containers, chunks (file-per-block), datanode dispatcher
  client/   EC write pipeline (stripe accumulation/commit), EC read +
            degraded read, key IO
  om/       namespace metadata (volume/bucket/key), request/apply split
  scm/      node/pipeline/container management, placement, replication
  utils/    config, checksums (host reference), metrics, events, tracing
  tools/    freon-style load/bench generators
"""

__version__ = "0.1.0"
