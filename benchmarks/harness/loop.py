"""The one closed loop every generator drives: `threads` callers that
each wait for a reply before sending the next, for `seconds` seconds.
(The shape of upstream freon's `-t` threads and of `tools/freon.py`
BaseFreonGenerator.run, time-bounded instead of count-bounded and on the
monotonic clock.)
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Callable

from benchmarks.harness.stats import Op


def closed_loop(threads: int, seconds: float,
                op: Callable[[int], tuple[str, int, object]],
                ) -> tuple[list[Op], float, float]:
    """Run op(i) -> (kind, nbytes, tag) with i = 0, 1, 2, ... on
    `threads` threads until the window closes. An operation in flight at
    the close is finished and recorded (its `end` lies past the window,
    so it counts towards no rate). An op that raises is recorded with
    ok=False. Returns (ops, t0, t1) on the monotonic clock."""
    counter = itertools.count()
    lock = threading.Lock()
    ops: list[Op] = []
    t0 = time.monotonic()
    t1 = t0 + seconds

    def worker() -> None:
        while True:
            start = time.monotonic()
            if start >= t1:
                return
            with lock:
                i = next(counter)
            try:
                kind, nbytes, tag = op(i)
                rec = Op(kind, start, time.monotonic(), nbytes, True,
                         tag=tag)
            except Exception as e:  # noqa: BLE001 - a failed op is a result
                rec = Op("failed", start, time.monotonic(), 0, False,
                         error=f"op {i}: {e!r}", tag=i)
            with lock:
                ops.append(rec)

    pool = [threading.Thread(target=worker, name=f"loadgen-{n}", daemon=True)
            for n in range(threads)]
    for t in pool:
        t.start()
    for t in pool:
        t.join()
    return ops, t0, t1
