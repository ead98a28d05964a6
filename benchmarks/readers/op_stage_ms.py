"""Mean, in ms per operation, of the critical-path time that the
operations of `root` ended inside the window spent in the stages the
metric's file names: every instant of a root span belongs to exactly one
stage (the program's own attribution), so the groups of one cell's
files, which partition the stage names, sum to the root's mean duration.

params: root    the operation's root span ("client:put", ...)
        stages  regular expressions; a stage belongs to the group if
                one of them matches its name from the start

Nothing where no operation of `root` ended in the window, or the program
keeps no stage records.
"""

import re

from benchmarks.harness import spans


def read(params: dict, run) -> float | None:
    ops = spans.operations(params["root"], run.t0, run.t1)
    if not ops:
        return None
    patterns = [re.compile(p) for p in params["stages"]]
    micros = sum(us for o in ops for stage, us in o["stages"].items()
                 if any(p.match(stage) for p in patterns))
    return micros / len(ops) / 1e3
