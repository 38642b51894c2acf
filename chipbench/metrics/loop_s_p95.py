"""loop_s_p95: the 95th percentile of the makespans of all loops in the
window (inclusive quantiles of Python's ``statistics``)."""

import statistics


def read(ctx):
    times = [r.seconds for r in ctx.loops]
    if len(times) < 2:
        return None
    return statistics.quantiles(times, n=20, method="inclusive")[18]
