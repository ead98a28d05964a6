"""Share in % of the host's cores' time that was not idle over the
window, every process of the cell together (client, scm-om, datanodes):
100 x delta busy / delta total jiffies of the first line of /proc/stat,
between the first and the last sample the program's process sampler
took inside [t0, t1). Nothing where the program keeps no such series,
the window holds fewer than two samples, or the host has no /proc/stat
(the totals did not move)."""

from benchmarks.harness import process_series


def read(params: dict, run) -> float | None:
    s = process_series.samples(run.t0, run.t1)
    if len(s) < 2 or s[-1][3] <= s[0][3]:
        return None
    return 100.0 * (s[-1][2] - s[0][2]) / (s[-1][3] - s[0][3])
