"""The program's host spans against the device's executions of the step
program, inside the profiled slice. `utils.tracing.span` enters a
jax.profiler annotation, so while a session runs each span is an event of the
host planes on the device trace's clock, and trace.load has it in
`ctx["trace"]["host"]` under its name. Over the executions of the step program
on the fullest device, per execution (as launch_gap_ms is), medians in ms.
`what` is one of:

  fetch_tail  end of an execution on the device to the end of the `fetch`
              span that waited for it: the result coming back to the loop
  launch_lag  start of the `dispatch` span that launched an execution to the
              execution's start on the device

With end of `fetch` to start of the next `dispatch` (host_turnaround_ms less
dispatch_ms) these three make up the gap between two executions. A trace
without the spans (a program from before them) gives None.
"""

import bisect
import statistics

from benchmarks import trace as T


def _named(trace, name):
    return sorted((start, start + dur) for n, start, dur in trace["host"] if n == name)


def reduce(ctx, what):
    trace = ctx["trace"]
    if not trace or not trace["devices"]:
        return None
    runs = T.step_runs(T.fullest_device(trace))
    values = []
    if what == "fetch_tail":
        fetches = sorted(_named(trace, "fetch"), key=lambda f: f[1])
        ends = [f[1] for f in fetches]
        for i, (_, run_end) in enumerate(runs):
            j = bisect.bisect_left(ends, run_end)  # the first fetch to end after the execution
            next_start = runs[i + 1][0] if i + 1 < len(runs) else None
            # an execution's fetch ends before the loop can launch the next one
            if j < len(ends) and (next_start is None or ends[j] <= next_start):
                values.append((ends[j] - run_end) / 1e6)
    elif what == "launch_lag":
        dispatches = _named(trace, "dispatch")
        starts = [d[0] for d in dispatches]
        for i, (run_start, _) in enumerate(runs):
            j = bisect.bisect_right(starts, run_start) - 1  # the last dispatch to start before it
            if j >= 0 and (i == 0 or starts[j] > runs[i - 1][0]):
                values.append((run_start - starts[j]) / 1e6)
    else:
        raise ValueError(f"unknown span_trace reduction {what!r}")
    return statistics.median(values) if values else None
