"""The raw `mesh=` route's depth-1 wrapper, and the decode batch size.

`DeviceBatchPipeline` keeps one unqueued device call in flight for a
caller that was handed a raw device mesh (the datanode daemons'
reconstruction coordinator, `client/ec_reader._decode_pipe`): the call
of batch N+1 is dispatched before batch N's outputs are pulled. Every
other consumer goes through `parallel/dispatch.py`.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Optional

import numpy as np

#: stripes per decode dispatch, and therefore the pipeline's granularity:
#: device work + D2H of one batch overlaps host fetch/writes of the next.
#: 8 matches the writer's stripe_batch default — with the default
#: 16-stripes-per-group geometry a whole-group repair runs as two
#: overlapped batches; larger values amortize dispatch cost at the price
#: of pipeline memory (two batches of [B, k, cell] live at once).
DEFAULT_DECODE_BATCH = 8


def decode_batch_size(default: int = DEFAULT_DECODE_BATCH) -> int:
    """The decode batch-depth knob (OZONE_TPU_DECODE_BATCH)."""
    try:
        n = int(os.environ.get("OZONE_TPU_DECODE_BATCH", default))
    except ValueError:
        return default
    return max(1, n)


def _start_d2h(out: Any) -> None:
    # eager D2H where the backend supports it: the pull runs under the
    # caller's host work on the previous batch
    try:
        out.copy_to_host_async()  # ozlint: allow[span-on-dispatch] -- the D2H hint helper itself; every caller brackets it in its own dispatch span
    except (AttributeError, RuntimeError):  # ozlint: allow[error-swallowing] -- optional eager-D2H hint; backends without it fall back to sync pull
        pass


class DeviceBatchPipeline:
    """One device batch in flight. submit(batch) dispatches fn(batch)
    asynchronously and returns the PREVIOUS batch's host results (or
    None on the first call); drain() returns the last in-flight batch.
    `ctx` rides along untouched so callers can tag batches (stripe
    indexes, group ids) without threading state."""

    def __init__(self, fn: Callable[[np.ndarray], Any]):
        self._fn = fn
        self._pending: Optional[tuple] = None

    def submit(self, batch: np.ndarray, ctx: Any = None) -> Optional[tuple]:
        outs = self._fn(batch)  # async dispatch on device backends
        if not isinstance(outs, tuple):
            outs = (outs,)
        for a in outs:
            _start_d2h(a)  # ozlint: allow[span-on-dispatch] -- per-operation pipeline: the owning op (repair:block / ec:read) brackets submit() in its span
        prev, self._pending = self._pending, (ctx, outs)
        return self._to_host(prev)

    def drain(self) -> Optional[tuple]:
        prev, self._pending = self._pending, None
        return self._to_host(prev)

    @staticmethod
    def _to_host(entry: Optional[tuple]) -> Optional[tuple]:
        if entry is None:
            return None
        ctx, outs = entry
        return ctx, tuple(np.asarray(a) for a in outs)


def batched(seq, n: int):
    """Yield contiguous slices of `seq` of at most n items."""
    for i in range(0, len(seq), n):
        yield seq[i:i + n]
