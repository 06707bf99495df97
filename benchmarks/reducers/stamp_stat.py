"""A statistic of the per-step times between consecutive stamps of the
window, outside the profiled slice: the median says how fast the program is,
the largest whether the window held a stall."""

import statistics


def step_times_ms(ctx):
    first, last = ctx["window"]
    cut = ctx["slice"] or (None, None)
    out = []
    for i in range(first, last):
        if cut[0] is not None and cut[0] - 1 <= i <= (cut[1] if cut[1] is not None else last):
            continue  # starting, running or writing the trace
        (t0, n0, _), (t1, n1, _) = ctx["stamps"][i], ctx["stamps"][i + 1]
        if n1 > n0:
            out.append(1e3 * (t1 - t0) / (n1 - n0))
    return out


def reduce(ctx, stat):
    times = step_times_ms(ctx)
    if not times:
        return None
    return {"median": statistics.median, "max": max}[stat](times)
