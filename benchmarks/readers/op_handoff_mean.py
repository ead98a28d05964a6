"""Mean per operation of `root` ended inside the window of its
hand-offs to pool workers, as the program's stage record keeps them
under `handoffs`: {n, waitUs, maxUs, pools}. A hand-off is booked where
a worker thread takes up work another thread gave it a context for; its
wait runs from the context's making to that moment (a thread started or
woken, the pool's queue, the worker's turn at the interpreter).

params: root    the operation's root span
        field   "n" (hand-offs an operation) or "waitUs" (their waits,
                SUMMED over the operation's workers: it may exceed the
                operation's duration)
        scale   what the mean is multiplied by (0.001: us -> ms)

The mean is over the records that keep `handoffs` (the program books
them in its costed operations alone). Nothing where no such operation
of `root` ended in the window (an older commit keeps none).
"""

from benchmarks.harness import spans


def read(params: dict, run) -> float | None:
    ops = [o for o in spans.operations(params["root"], run.t0, run.t1)
           if "handoffs" in o]
    if not ops:
        return None
    total = sum(o["handoffs"][params["field"]] for o in ops)
    return params.get("scale", 1.0) * total / len(ops)
