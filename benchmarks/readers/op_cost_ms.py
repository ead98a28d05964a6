"""Mean, in ms per operation of `root` ended inside the window, of what
the operation COST this process's threads, not how long it took: the sum
over every span of its trace (on the critical path or off it, on
whichever thread) of the span's self CPU (`field` "cpu") or self wall
time (`field` "wall"), as the program's stage record keeps them under
`cost`: {span name: [self wall us, self CPU us, blocks, preempts]}.

params: root    the operation's root span ("client:get", ...)
        field   "cpu" or "wall"

Nothing where no operation of `root` ended in the window, or the
program's records keep no `cost` (an older commit).
"""

from benchmarks.harness import spans

_FIELD = {"wall": 0, "cpu": 1}


def read(params: dict, run) -> float | None:
    ops = [o for o in spans.operations(params["root"], run.t0, run.t1)
           if "cost" in o]
    if not ops:
        return None
    i = _FIELD[params["field"]]
    return sum(c[i] for o in ops for c in o["cost"].values()) / len(ops) / 1e3
