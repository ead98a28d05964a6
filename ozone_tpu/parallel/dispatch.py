"""The one door to the device: which queue a batch of stripes joins.

Two schedulers feed the device: the codec service (`codec/service.py`:
one chip, weighted fair between classes, packs at submit) and the mesh
executor (`parallel/mesh_executor.py`: every local device, one SPMD
program per key, tuned for throughput). Consumers know neither. They
call `submit` for one batch or `pipeline` for a depth-1 stream of them,
and the route is decided here, once, from what the code can observe:

    a STREAM of bulk-class batches joins the mesh executor when its
    caller was handed one (repair: a storm or a coordinator hands its
    executor to every reader, many groups with one erasure pattern),
    or when it is an encode sweep (lifecycle tiering, a re-encode that
    lost its parity) on a host where the process-wide executor exists,
    which is where more than one device is attached; and the executor
    has a program for the key. The stream is told how many stripes one
    dispatch of its lane carries (`_Pipeline.width`: the caller's width
    on the codec service, that times the devices on the mesh), and a
    sweep that packs its windows to it sends full dispatches; one that
    submits at its own width (the re-encode) lingers and goes out
    partly filled.
    Everything else joins the codec service: every single batch (a
    PUT's flush, a lone-stripe hedge decode), every interactive stream,
    and a bulk degraded read nobody handed an executor.

So on a one-chip host every stripe goes to the service; on a
multi-device host background sweeps and repair storms meet in the mesh
lanes while PUTs, GETs and hedge decodes stay on one chip. A lone bulk
DECODE stream stays there too: on the mesh its 4- or 8-stripe batches
are padded to every device's slots and it runs half as fast (PERF.md
section 6, PR 27), and telling a lone stream from a storm by what is
queued is this module's open item (ROADMAP Queue 1 item 7, once item
5b(iv)). The arrows point one way: consumers -> door -> {service, mesh
executor} -> `fused` / `sharded`.
"""

from __future__ import annotations

import functools
import logging
from concurrent.futures import Future
from typing import Any, Callable, Optional

import numpy as np

from ozone_tpu.codec import service as codec_service
from ozone_tpu.parallel import mesh_executor

__all__ = ["pipeline", "submit"]

log = logging.getLogger(__name__)

#: keys an executor was asked for and had no program for, each logged
#: once: a missing program and a fault swallowed while building one
#: both end here, and both put bulk work on one chip
_no_mesh_program: set = set()


def submit(key: tuple, fn: Callable, stripes: np.ndarray, *, width: int,
           qos: str, tail: bool = False, deadline=None) -> Future:
    """Enqueue ONE batch of `stripes` ([n, ...], n >= 1) under the
    semantic `key` (`codec_service.encode_key` / `decode_key` /
    `reencode_key`) and return a Future of the host output tuple for
    exactly those stripes (`codec_service.wait_result` collects it).
    `fn` is the single-chip fused callable for the key and `width` the
    batch width its shape family compiles at. A single batch always
    joins the codec service: its submitter waits on it (a PUT's flush,
    a hedge at width 1), and the mesh would add its linger and pad the
    batch to every device's slots."""
    return codec_service.get_service().submit(
        key, fn, stripes, width=width, qos=qos, tail=tail,
        deadline=deadline)


def _mesh_lane(key: tuple, width: int, qos: str,
               executor) -> Optional[tuple[Callable[..., Future], int]]:
    """(`submit(stripes, *, tail, deadline)` of the mesh lane a stream
    under `key` joins, the stripes one dispatch of that lane carries),
    or None where it stays on the codec service: the route, decided
    here and nowhere else."""
    if qos != "bulk":
        return None
    if executor is None and key[0] == "encode":
        executor = mesh_executor.maybe_executor()
    if executor is None:
        return None
    try:
        return (executor.pipeline(key, width=width, qos=qos),
                executor.dispatch_width(width))
    except KeyError:
        # the executor's "no program for this key"; the service has one
        # for every key, and the mesh cell's comparison reports a
        # decode that lands there (`single_chip_decode_stripes`)
        if key not in _no_mesh_program:
            _no_mesh_program.add(key)
            log.warning("no mesh program for %r: its bulk streams run "
                        "on one chip, through the codec service", key)
        return None


class _Pipeline:
    """The depth-1 adaptor over either scheduler's futures:
    submit(batch, ctx) enqueues the batch and returns the PREVIOUS
    submission's host results (ctx, outs), or None on the first call;
    drain() returns the last. `ctx` rides along untouched, so every
    depth-1 consumer (degraded reads, repair, re-encode, lifecycle
    tiering) keeps its overlap: the writes of batch N run under the
    device pass and the pull of batch N+1. `width` is the stripes ONE
    dispatch of the lane the stream joined carries: a consumer that
    packs across its operations (the tiering sweep) fills its batches
    to it, whichever scheduler it is."""

    def __init__(self, submit_fn: Callable[..., Future], width: int):
        self._submit = submit_fn
        self.width = width
        self._pending: Optional[tuple] = None

    def submit(self, batch: np.ndarray, ctx: Any = None,
               tail: bool = False) -> Optional[tuple]:
        fut = self._submit(batch, tail=tail)
        prev, self._pending = self._pending, (ctx, fut)
        return self._to_host(prev)

    def drain(self) -> Optional[tuple]:
        prev, self._pending = self._pending, None
        return self._to_host(prev)

    @staticmethod
    def _to_host(entry: Optional[tuple]) -> Optional[tuple]:
        if entry is None:
            return None
        ctx, fut = entry
        return ctx, codec_service.wait_result(fut)


def pipeline(key: tuple, fn: Callable, *, width: int, qos: str,
             executor=None) -> _Pipeline:
    """A depth-1 stream of batches under `key`: the route is taken
    once, here, and every batch follows it. `executor` is the mesh
    executor the caller was handed (None: it was handed none)."""
    route = _mesh_lane(key, width, qos, executor)
    if route is None:
        route = (functools.partial(codec_service.get_service().submit,
                                   key, fn, width=width, qos=qos), width)
    return _Pipeline(*route)
