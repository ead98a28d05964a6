"""Lifecycle subsystem: policy-driven hot->warm tiering and TTL expiry.

The control loop that turns per-bucket age rules into a continuous,
fault-tolerant stream of batched TPU re-encode work (replicated -> EC)
plus TTL expirations — the role f4's warm-tier conversion (Muralidhar
et al., OSDI '14) and Azure Storage's background erasure coding of
sealed extents (Huang et al., ATC '12) play in production stores.

- policy.py: the rule model + S3 LifecycleConfiguration XML codec;
  rules persist in OM bucket metadata through the replicated ring.
- service.py: the leader-singleton sweeper — term-fenced like
  scm/sequence_id.py, resumable cursor committed through the ring.
- executor.py: the datapath — many keys per device batch
  through the fused encode+CRC, commit fenced against
  concurrent overwrites, old blocks retired via the SCM deletion chain.
"""

from ozone_tpu.lifecycle.policy import (
    ACTION_EXPIRE,
    ACTION_TRANSITION,
    LifecycleRule,
    rules_from_s3_xml,
    rules_to_s3_xml,
)
from ozone_tpu.lifecycle.service import LifecycleService
from ozone_tpu.lifecycle.executor import TieringExecutor

__all__ = [
    "ACTION_EXPIRE",
    "ACTION_TRANSITION",
    "LifecycleRule",
    "LifecycleService",
    "TieringExecutor",
    "rules_from_s3_xml",
    "rules_to_s3_xml",
]
