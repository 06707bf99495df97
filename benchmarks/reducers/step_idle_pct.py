"""The share of a step in which the device runs nothing, taken so that the
profiler cannot disturb it: the device's busy time per optimizer step comes
from the profiled slice, the step's length from the stamps outside it. Where
tracing slows the host inside the slice (PERF.md §5) `device_idle_pct` reads
how the slice went; this reads how the window goes."""

import statistics

from benchmarks.reducers import device_trace, stamp_stat


def reduce(ctx):
    busy_ms = device_trace.reduce(ctx, "busy")
    times = stamp_stat.step_times_ms(ctx)
    if busy_ms is None or not times:
        return None
    return 100.0 * (1.0 - busy_ms / statistics.median(times))
