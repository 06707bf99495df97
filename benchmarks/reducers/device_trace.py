"""Per-step times from the device trace of the profiled slice, on the fullest
device, over whole executions of the step program (start of the first to
start of the last). `what` is one of:

  busy  union of the operation intervals, per optimizer step
  gap   time between the end of one execution of the step program and the
        start of the next, per execution
  idle  100 * (1 - busy / slice)

A step program may hold several optimizer steps (a superstep block): `busy` is
per optimizer step, by the steps the window's stamps give an execution.
"""

from benchmarks import trace as T


def _steps_per_run(ctx):
    first, last = ctx["window"]
    (_, n0, _), (_, n1, _) = ctx["stamps"][first], ctx["stamps"][last]
    return max((n1 - n0) // max(last - first, 1), 1)


def reduce(ctx, what):
    trace = ctx["trace"]
    if not trace or not trace["devices"]:
        return None
    span = T.whole_runs(T.fullest_device(trace))
    if span is None:
        return None
    lo, hi, runs, inside = span
    n_runs = len(runs) - 1
    if what == "busy":
        return T.union_len(inside) / (n_runs * _steps_per_run(ctx)) / 1e6
    if what == "idle":
        return 100.0 * (1.0 - T.union_len(inside) / (hi - lo))
    if what == "gap":
        return sum(max(s1 - e0, 0) for (_, e0), (s1, _) in zip(runs, runs[1:])) / n_runs / 1e6
    raise ValueError(f"unknown device_trace reduction {what!r}")
