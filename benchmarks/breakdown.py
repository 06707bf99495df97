"""What a traced run adds to the result line: the device's busy time over the
traced slice, and where the time went (the heaviest device operations and the
longest idle gaps, each gap named for what the host was doing in it)."""

from __future__ import annotations

import math
import re

from benchmarks import trace as T

TOP = 10
CONTAINERS = ("while", "conditional", "call")  # their time is their bodies', listed beside them


def _span(device: dict):
    """Whole executions of the step program where the slice holds two or
    more, else first operation to last."""
    span = T.whole_runs(device)
    if span is None:
        ivs = T.op_intervals(device)
        span = (min(s for s, _ in ivs), max(e for _, e in ivs), [], ivs)
    return span


def busy_and_window(trace: dict) -> tuple[float, float]:
    """Seconds in which an operation ran, averaged over the devices, and the
    length of the slice they were taken over."""
    busy, window = [], []
    for device in trace["devices"].values():
        lo, hi, _, inside = _span(device)
        busy.append(T.union_len(inside) / 1e9)
        window.append((hi - lo) / 1e9)
    return sum(busy) / len(busy), sum(window) / len(window)


def _clean(text: str) -> str:
    return "".join(c if c.isalnum() or c in "_./-:[]," else "_" for c in text)[:64]


def _label(name: str) -> str:
    """An operation's name for the breakdown: XLA's instruction name without
    its number, and the largest array it writes, since the v5e's trace gives
    no scope ("%fusion.3621 = (f32[4,16,1024]{..}, f32[4,16,1024,1024]{..})
    fusion(..." becomes "fusion:f32[4,16,1024,1024]")."""
    head, _, rest = name.partition(" = ")
    base = head.lstrip("%").split(".")[0]
    shapes = re.findall(r"([a-z]+[0-9]*\[[0-9,]*\])", rest.split(" fusion(")[0].split(f" {base}(")[0])
    size = lambda s: math.prod(int(x) for x in s[s.index("[") + 1 : -1].split(",") if x)  # noqa: E731
    return _clean(f"{base}:{max(shapes, key=size)}" if shapes else base)


def breakdown(trace: dict) -> dict:
    device = T.fullest_device(trace)
    lo, hi, _, _ = _span(device)
    ops: dict[str, float] = {}
    for name, start, dur in device["ops"]:
        if lo <= start < hi and not name.partition(" = ")[0].lstrip("%").startswith(CONTAINERS):
            label = _label(name)
            ops[label] = ops.get(label, 0.0) + dur / 1e9
    idle: dict[str, float] = {}
    host = sorted(trace["host"], key=lambda e: e[1])
    for g0, g1 in T.gaps(T.op_intervals(device), lo, hi):
        if g1 - g0 < 20_000:  # under 20 us: between two operations of one program
            idle["gaps_under_20us_between_operations"] = (
                idle.get("gaps_under_20us_between_operations", 0.0) + (g1 - g0) / 1e9
            )
            continue
        mid, doing, shortest = (g0 + g1) / 2, "host_idle_or_untraced", None
        for name, start, dur in host:  # the innermost host span over the gap's middle
            if start > mid:
                break
            if start + dur >= mid and (shortest is None or dur < shortest):
                doing, shortest = name, dur
        idle[_clean(doing)] = idle.get(_clean(doing), 0.0) + (g1 - g0) / 1e9
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]  # noqa: E731
    return {"device_ops": top(ops), "idle_gaps": top(idle)}
