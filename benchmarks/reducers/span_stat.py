"""Per-step host times from the program's own span ring
(`atomo_tpu.utils.tracing.spans()`: one flat record `(name, step, parent, t0,
t1)` per span, on the clock the stamps use), as medians over the window's
iterations outside the profiled slice, cut as stamp_stat cuts it. An iteration
is one parent span (`block`: a superstep of K optimizer steps; `step`: one
step of a per-step loop) with its direct children; a time is divided by the
optimizer steps the iteration's dispatch held. `what` is one of:

  dispatch    the `dispatch` span: the jitted step's call until it returns
  fetch       the `fetch` span: the host waiting on the device for the result
  turnaround  end of one iteration's `fetch` to the end of the next one's
              `dispatch`: the time in which this loop has queued nothing
  feed        `feed_take` + `feed_start`: taking the staged block and staging
              the next one (stacking, layout change, transfer)
  untraced    100 * (1 - children / parent): the share of an iteration that
              no span accounts for (a percentage, not per step)

A program without the ring (a commit from before the spans), or a window
whose iterations lack the span asked for, gives None.
"""

import statistics

PARENTS = ("block", "step")
WHATS = ("dispatch", "fetch", "turnaround", "feed", "untraced")


def program_spans():
    try:
        from atomo_tpu.utils import tracing
    except ImportError:
        return []
    read = getattr(tracing, "spans", None)
    return read() if read else []


def iterations(records):
    """[{step, steps, span, kids, last_fetch_end}] in time order. A child
    closes before its parent, so it comes first in the ring; `steps` is the
    distance to the iteration before (None for the first in the ring)."""
    out, kids = [], {}
    for name, step, parent, t0, t1 in records:
        if name in PARENTS and parent is None:
            before = out[-1] if out else None
            out.append({
                "step": step, "span": (t0, t1), "kids": kids,
                "steps": step - before["step"] if before and step > before["step"] else None,
                "last_fetch_end": before["kids"]["fetch"][1] if before and "fetch" in before["kids"] else None,
            })
            kids = {}
        elif parent in PARENTS:
            kids[name] = (t0, t1)
    return out


def kept_step_ranges(ctx):
    """(n0, n1] of optimizer steps for each pair of consecutive stamps of the
    window outside the profiled slice (stamp_stat.step_times_ms's cut)."""
    first, last = ctx["window"]
    cut = ctx["slice"] or (None, None)
    out = []
    for i in range(first, last):
        if cut[0] is not None and cut[0] - 1 <= i <= (cut[1] if cut[1] is not None else last):
            continue  # starting, running or writing the trace
        n0, n1 = ctx["stamps"][i][1], ctx["stamps"][i + 1][1]
        if n1 > n0:
            out.append((n0, n1))
    return out


def kept_iterations(ctx):
    records = ctx["spans"] if ctx.get("spans") is not None else program_spans()
    ranges = kept_step_ranges(ctx)
    return [
        it for it in iterations(records)
        if it["steps"] and any(n0 < it["step"] <= n1 for n0, n1 in ranges)
    ]


def _length(it, name):
    t0, t1 = it["kids"][name]
    return t1 - t0


def reduce(ctx, what):
    if what not in WHATS:
        raise ValueError(f"unknown span_stat reduction {what!r}")
    values = []
    for it in kept_iterations(ctx):
        kids = it["kids"]
        if what == "dispatch" and "dispatch" in kids:
            values.append(1e3 * _length(it, "dispatch") / it["steps"])
        elif what == "fetch" and "fetch" in kids:
            values.append(1e3 * _length(it, "fetch") / it["steps"])
        elif what == "turnaround" and "dispatch" in kids and it["last_fetch_end"] is not None:
            values.append(1e3 * (kids["dispatch"][1] - it["last_fetch_end"]) / it["steps"])
        elif what == "feed" and ("feed_take" in kids or "feed_start" in kids):
            held = sum(_length(it, n) for n in ("feed_take", "feed_start") if n in kids)
            values.append(1e3 * held / it["steps"])
        elif what == "untraced" and kids and it["span"][1] > it["span"][0]:
            inside = sum(t1 - t0 for t0, t1 in kids.values())
            values.append(100.0 * (1.0 - inside / (it["span"][1] - it["span"][0])))
    return statistics.median(values) if values else None
