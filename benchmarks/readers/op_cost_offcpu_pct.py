"""Over the window's operations of `root`: of the self wall time of the
spans named in `spans`, the share in % that their threads were NOT on a
core: 100 x (wall - cpu) / wall of the records' `cost` entries. The
spans named are leaves that only copy memory, so a thread inside one
never sleeps by its own choice: what is off the CPU is time it was
runnable and not running (waiting to get the interpreter lock back after
a copy, or its core taken away).

params: root    the operation's root span
        spans   the leaf spans' names

The value is not cut off at 0 or 100: where thread CPU comes in coarse
ticks (10 ms on the chip machines) and a window holds few costed
operations, the summed CPU can exceed the summed wall, and the share
then reads NEGATIVE: that says the window's count is too coarse to
read, and a cut would hide it.

Nothing where no such span is in the window's records, or the program
keeps no `cost`.
"""

from benchmarks.harness import spans


def read(params: dict, run) -> float | None:
    wall = cpu = 0
    for o in spans.operations(params["root"], run.t0, run.t1):
        for name in params["spans"]:
            c = o.get("cost", {}).get(name)
            if c is not None:
                wall += c[0]
                cpu += c[1]
    if wall <= 0:
        return None
    return 100.0 * (wall - cpu) / wall
