"""From a profiler trace to intervals, and the arithmetic on intervals.

`load` reads an `.xplane.pb` with `jax.profiler.ProfileData` (nothing but JAX)
into plain lists, so that every reducer works on the same small structure and
a recorded fixture can stand in for a chip:

    {"devices": {"<plane name>": {"ops": [[name, start_ns, dur_ns], ...],
                                  "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}

An operation's `name` is XLA's instruction as the TPU's profiler gives it (the
whole HLO line, `%fusion.3559 = ... fusion(...)`). The v5e's events carry no
jax.named_scope path (PERF.md, Open questions), so nothing here keys on one.

`union_len` is a copy of obs/timeline.py's `_union_len_us` (see PERF.md, Open
questions).
"""

from __future__ import annotations

import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def newest_xplane(trace_dir: str) -> str | None:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def load(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        ops.append([ev.name, int(ev.start_ns), int(ev.duration_ns)])
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        modules.append([ev.name, int(ev.start_ns), int(ev.duration_ns)])
            if ops:
                devices[plane.name] = {"ops": ops, "modules": modules}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0:
                        host.append([ev.name, int(ev.start_ns), int(ev.duration_ns)])
    return {"devices": devices, "host": host}


def union_len(intervals) -> float:
    if not intervals:
        return 0.0
    ivs = sorted(intervals)
    total = 0.0
    cur_s, cur_e = ivs[0]
    for s, e in ivs[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    return total + (cur_e - cur_s)


def merged(intervals) -> list[list[float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle stretches of [lo, hi] that no interval covers."""
    out, cur = [], lo
    for s, e in merged(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def op_intervals(device: dict):
    """[start, end] of a device's operations."""
    return [(start, start + dur) for _, start, dur in device["ops"]]


def step_module(device: dict) -> str | None:
    """The program that takes most of the device's time: the train step."""
    total: dict[str, int] = {}
    for name, _, dur in device["modules"]:
        total[name] = total.get(name, 0) + dur
    return max(total, key=total.get) if total else None


def step_runs(device: dict) -> list[tuple[int, int]]:
    """[start, end] of each execution of the step program, in time order."""
    name = step_module(device)
    return sorted((s, s + d) for n, s, d in device["modules"] if n == name)


def whole_runs(device: dict):
    """The part of the slice that reducers count over: whole executions of the
    step program, start of the first to start of the last. Returns (lo, hi,
    runs, operation intervals clipped to [lo, hi]), or None under two runs."""
    runs = step_runs(device)
    if len(runs) < 2:
        return None
    lo, hi = runs[0][0], runs[-1][0]
    inside = [(max(s, lo), min(e, hi)) for s, e in op_intervals(device) if e > lo and s < hi]
    return lo, hi, runs, inside


def fullest_device(trace: dict) -> dict | None:
    """The device whose operations cover most time."""
    best, best_busy = None, -1.0
    for device in trace["devices"].values():
        busy = union_len(op_intervals(device))
        if busy > best_busy:
            best, best_busy = device, busy
    return best
