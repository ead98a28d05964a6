"""Mean, in ms per operation of `root` ended inside the window, of its
unary RPCs' time on one side of the wire, from the program's stage
record's `rpc`: {"/service/method": [calls, client us, server us]},
where client us is the `client:/...` span's duration and server us what
the daemon reported in the call's trailing metadata (its own
`server:<method>` span's duration).

params: root    the operation's root span
        side    "daemon": the sum of server us;
                "client": the sum of client us - server us: the wire,
                gRPC's own threads and the caller's two turns at the
                interpreter

The two sides of one root sum to the mean of its `client:/...` spans'
durations. The mean is over the records that keep `rpc` (the program's
costed operations alone: only their calls ask the daemon for its time).
Nothing where no such operation of `root` ended in the window (an older
commit keeps none); 0 where the operations made no call.
"""

from benchmarks.harness import spans


def read(params: dict, run) -> float | None:
    ops = [o for o in spans.operations(params["root"], run.t0, run.t1)
           if "rpc" in o]
    if not ops:
        return None
    client = sum(r[1] for o in ops for r in o["rpc"].values())
    server = sum(r[2] for o in ops for r in o["rpc"].values())
    us = server if params["side"] == "daemon" else client - server
    return us / len(ops) / 1e3
