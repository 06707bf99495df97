"""Trace-based phase timeline — ``report timeline``.

The phase surface of the FUSED step is the ``named_phase``
(``jax.named_scope``) regions — ``encode`` / ``exchange`` /
``decode_mean`` / ``ring_exchange_decode`` / ``delayed_*`` /
``hybrid_exchange`` — survive into the compiled program as HLO op-name
metadata, and a ``--profile-dir`` trace records every op execution with
its timing. This module turns that trace into the per-step phase
timeline:

  1. PARSE: ``jax.profiler`` writes ``plugins/profile/<run>/*.xplane.pb``
     (a TSL XSpace protobuf). :func:`parse_xplane` is a minimal
     stdlib-only wire-format walker for exactly the fields we need — no
     tensorflow/tensorboard dependency is baked into the container, so
     the reader hand-walks varints instead of importing protos (the
     "stub or gate missing deps" rule).
  2. MAP: the ``/host:metadata`` plane carries each program's serialized
     HloProto; instruction name -> ``metadata.op_name`` gives every op
     its full scope path (``jit(step)/.../encode/...``) — the anchor the
     ``named_phase`` scopes planted (tested: a refactor that drops them
     fails tests/test_fabric_obs.py's scope-presence asserts).
     The TPU's trace (read on a v5e, PERF.md §6) carries the same plane
     but its ``XLA Ops`` events carry neither ``program_id`` nor
     ``hlo_op``: an event's name is the instruction's whole text
     (``%fusion.12 = f32[...] fusion(...)``, no ``metadata=``), and the
     program is named by the ``XLA Modules`` event over it
     (``jit_step(<program id>)``). :func:`device_events` reads both forms.
  3. ATTRIBUTE: op events of the training-step module are segmented into
     dispatches (executions) — the ``XLA Modules`` events where the trace
     has them, else the modal-occurrence boundary op — then
     every op lands in a phase by its scope path. Per dispatch and per
     phase the timeline reports ``busy`` (summed op time), ``exposed``
     (the phase's interval union MINUS the compute union — time the
     phase held the device alone) and ``hidden`` (overlapped by
     compute) — live exposed-vs-hidden attribution for fused, superstep,
     stream-encode, and hybrid programs. Ring's fused
     ``ring_exchange_decode`` scope is attributed to ``exchange`` (its
     decode overlaps the transfer BY CONSTRUCTION — the fusion is the
     feature, and no trace can split it).
     The host spans of the loop (``utils.tracing.span``: block / step >
     feed_take, dispatch, feed_start > stack, put, next_batch, fetch,
     boundary) are events of the ``/host:`` planes on the same clock:
     the timeline lists them, and puts every device idle gap down to the
     innermost program span over it.
  4. JOIN: with a ``train_dir``, the spans are joined against
     ``metrics.jsonl`` by absolute time (the trace's
     ``profile_start_time`` is unix ns) and cross-checked: the recorded
     steps in the profiled window must partition evenly over the trace's
     dispatches (superstep blocks cover K steps each), and the device
     wall per step share must not exceed the recorded host step wall
     (device work cannot take longer than the host wall that contains
     it) — a violated fixture fails the check (tested).

A trace is an OBSERVATION artifact: this module never touches devices,
never imports jax — safe on a box that cannot reach the accelerator
(the ``report`` verb contract).
"""

from __future__ import annotations

import bisect
import os
import re
import struct
from typing import Iterator, Optional

from atomo_tpu.utils import tracing

TIMELINE_REPORT_NAME = "timeline_report.json"

# scope token -> reported phase. ring_exchange_decode is exchange-with-
# decode-overlapped by construction (module docstring); the delayed_*
# scopes are the same phases consumed one step late.
PHASE_OF_SCOPE = {
    "encode": "encode",
    "exchange": "exchange",
    "hybrid_exchange": "exchange",
    "delayed_exchange": "exchange",
    "ring_exchange_decode": "exchange",
    "decode": "decode",
    "decode_mean": "decode",
    "delayed_decode_mean": "decode",
    "forward_backward": "forward_backward",
    "attention": "attention",
    "rope": "rope",
    "linear_attention": "linear_attention",
    "delta_chunk": "delta_chunk",
    "delta_scan": "delta_scan",
    "ffn": "ffn",
    "mla": "mla",
    "moe": "moe",
    "moe_route": "moe_route",
    "moe_dispatch": "moe_dispatch",
    "moe_experts": "moe_experts",
    "mtp": "mtp",
    "update": "update",
}
# the codec's and the exchange's phases are measured AGAINST the compute
# side (exposed / hidden); the model's own phases are the compute side,
# split by scope, and report their busy time. An op is in ONE phase, the
# innermost scope's: `forward_backward` is what is left of it outside
# `attention`, `ffn` and the linear layers' mixer core, and
# `linear_attention` what is left of that core (convolution, normalisation,
# gates) outside `delta_chunk` and `delta_scan`: the core is the three. In
# the same way `mla` is the latent attention's projections, norms and
# rotation outside `attention`, and the routed experts are `moe_route`,
# `moe_dispatch` and `moe_experts` with `moe` what is left outside the three.
PHASES = ("encode", "exchange", "decode")
MODEL_PHASES = (
    "forward_backward", "attention", "rope", "linear_attention", "delta_chunk",
    "delta_scan", "ffn", "mla", "moe", "moe_route", "moe_dispatch",
    "moe_experts", "mtp", "update",
)
# the loop's host spans, as utils.tracing names them
HOST_SPANS = (
    tracing.BLOCK, tracing.STEP, tracing.FEED_TAKE, tracing.DISPATCH,
    tracing.FEED_START, tracing.STACK, tracing.PUT, tracing.NEXT_BATCH,
    tracing.FETCH, tracing.BOUNDARY,
)
TPU_OPS_LINE, TPU_MODULES_LINE = "XLA Ops", "XLA Modules"
# their time is their bodies', which the trace lists beside them
CONTAINER_OPS = ("while", "conditional", "call")
MIN_IDLE_GAP_US = 20.0  # shorter: between two operations of one program


# ------------------------------------------------ minimal protobuf walk


def _walk(data: bytes) -> Iterator[tuple[int, int, object]]:
    """Yield ``(field_no, wire_type, value)`` over one message's fields.
    Varint (0), 64-bit (1), length-delimited (2) and 32-bit (5) cover
    every field XSpace/HloProto use; anything else is a parse error the
    caller treats as "no trace"."""
    i, n = 0, len(data)
    while i < n:
        tag = 0
        shift = 0
        while True:
            b = data[i]
            i += 1
            tag |= (b & 0x7F) << shift
            shift += 7
            if not b & 0x80:
                break
        fno, wt = tag >> 3, tag & 7
        if wt == 0:
            v = 0
            shift = 0
            while True:
                b = data[i]
                i += 1
                v |= (b & 0x7F) << shift
                shift += 7
                if not b & 0x80:
                    break
        elif wt == 2:
            ln = 0
            shift = 0
            while True:
                b = data[i]
                i += 1
                ln |= (b & 0x7F) << shift
                shift += 7
                if not b & 0x80:
                    break
            v = data[i:i + ln]
            i += ln
        elif wt == 5:
            v = data[i:i + 4]
            i += 4
        elif wt == 1:
            v = data[i:i + 8]
            i += 8
        else:
            raise ValueError(f"unsupported protobuf wire type {wt}")
        yield fno, wt, v


def _map_entry(data: bytes) -> tuple[Optional[int], bytes]:
    """A proto3 map<int64, Message> entry: key = 1, value = 2."""
    k, v = None, b""
    for fno, _wt, val in _walk(data):
        if fno == 1:
            k = val
        elif fno == 2:
            v = val
    return k, v


def _stat(data: bytes) -> tuple[Optional[int], object]:
    """An XStat: metadata_id = 1; value oneof double(2)/uint(3)/int(4)/
    str(5)/bytes(6)/ref(7)."""
    mid, val = None, None
    for fno, _wt, v in _walk(data):
        if fno == 1:
            mid = v
        elif fno == 2:
            val = struct.unpack("<d", v)[0]
        elif fno in (3, 4, 7):
            val = v
        elif fno == 5:
            val = v.decode("utf-8", "replace")
        elif fno == 6:
            val = v  # bytes (the Hlo Proto stat)
    return mid, val


def parse_xplane(path: str) -> dict:
    """The XSpace fields the timeline needs: per plane its name, stat /
    event metadata name tables, plane-level stats, and per line its
    name, ``timestamp_ns`` and events (metadata id, offset_ps,
    duration_ps, stats resolved to ``{stat name: value}``)."""
    with open(path, "rb") as f:
        data = f.read()
    planes = []
    for fno, _wt, pv in _walk(data):
        if fno != 1:  # XSpace.planes
            continue
        plane = {"name": "", "lines": [], "event_meta": {},
                 "stat_meta": {}, "stats": []}
        for f2, _w2, v2 in _walk(pv):
            if f2 == 2:
                plane["name"] = v2.decode("utf-8", "replace")
            elif f2 == 3:
                plane["lines"].append(v2)
            elif f2 == 4:
                k, ev = _map_entry(v2)
                em = {"name": None, "stats": []}
                for f3, _w3, v3 in _walk(ev):
                    if f3 == 2:
                        em["name"] = v3.decode("utf-8", "replace")
                    elif f3 == 5:
                        em["stats"].append(v3)
                plane["event_meta"][k] = em
            elif f2 == 5:
                k, sv = _map_entry(v2)
                for f3, _w3, v3 in _walk(sv):
                    if f3 == 2:
                        plane["stat_meta"][k] = v3.decode(
                            "utf-8", "replace"
                        )
            elif f2 == 6:
                plane["stats"].append(v2)
        # resolve lines/events against the name tables
        lines = []
        for lv in plane["lines"]:
            line = {"name": "", "timestamp_ns": 0, "events": []}
            for f3, _w3, v3 in _walk(lv):
                if f3 in (2, 11) and not line["name"]:
                    line["name"] = v3.decode("utf-8", "replace")
                elif f3 == 3:
                    line["timestamp_ns"] = int(v3)
                elif f3 == 4:
                    ev = {"metadata_id": None, "offset_ps": 0,
                          "duration_ps": 0, "stats": {}}
                    for f4, _w4, v4 in _walk(v3):
                        if f4 == 1:
                            ev["metadata_id"] = v4
                        elif f4 == 2:
                            ev["offset_ps"] = int(v4)
                        elif f4 == 3:
                            ev["duration_ps"] = int(v4)
                        elif f4 == 4:
                            mid, val = _stat(v4)
                            name = plane["stat_meta"].get(mid, mid)
                            ev["stats"][name] = val
                    em = plane["event_meta"].get(ev["metadata_id"]) or {}
                    ev["name"] = em.get("name")
                    line["events"].append(ev)
            lines.append(line)
        plane["lines"] = lines
        plane["stats"] = dict(
            (plane["stat_meta"].get(mid, mid), val)
            for mid, val in (_stat(s) for s in plane["stats"])
        )
        planes.append(plane)
    return {"path": path, "planes": planes}


def _hlo_scope_map(hlo_proto: bytes) -> dict:
    """``{instruction name: metadata.op_name}`` from a serialized
    HloProto (HloProto.hlo_module=1 -> computations=3 -> instructions=2;
    HloInstructionProto.name=1, metadata=7; OpMetadata.op_name=2)."""
    out = {}
    for f1, _w1, module in _walk(hlo_proto):
        if f1 != 1:
            continue
        for f2, _w2, comp in _walk(module):
            if f2 != 3:
                continue
            for f3, _w3, instr in _walk(comp):
                if f3 != 2:
                    continue
                name, op_name = None, None
                for f4, _w4, v4 in _walk(instr):
                    if f4 == 1:
                        name = v4.decode("utf-8", "replace")
                    elif f4 == 7:
                        for f5, _w5, v5 in _walk(v4):
                            if f5 == 2:
                                op_name = v5.decode("utf-8", "replace")
                if name and op_name:
                    out[name] = op_name
    return out


def scope_maps(space: dict) -> dict:
    """``{program_id: {"module": name, "scopes": {instr: op_name}}}``
    from the ``/host:metadata`` plane's Hlo Proto stats — the join key
    the device events' ``program_id`` stat points at."""
    out = {}
    for plane in space["planes"]:
        if plane["name"] != "/host:metadata":
            continue
        for pid, em in plane["event_meta"].items():
            scopes = {}
            for st in em.get("stats", []):
                _mid, val = _stat(st)
                if isinstance(val, bytes):
                    try:
                        scopes.update(_hlo_scope_map(val))
                    except (ValueError, IndexError):
                        continue  # a truncated proto is "no scopes"
            if scopes:
                out[pid] = {"module": em.get("name"), "scopes": scopes}
    return out


def phase_of(op_name: Optional[str]) -> str:
    """Classify one op's scope path into a phase by its ``named_phase``
    path components, innermost first. A scope crossed by autodiff shows
    inside the transform's brackets (``transpose(jvp(attention))``), so
    every identifier of the path counts, not only whole components."""
    if op_name:
        for token in reversed(re.findall(r"[A-Za-z_][A-Za-z0-9_]*", op_name)):
            ph = PHASE_OF_SCOPE.get(token)
            if ph:
                return ph
    return "compute"


# instructions the TPU's compiler makes as custom calls and gives no
# metadata, by the prefix of their name, with the scope of the one place in
# the program that they come from: the grouped products of models/moe.py
# (`jax.lax.ragged_dot`), forward and both transposes (22 of a step's 75 ms
# in the routed experts on the v5e, PERF.md §6, PR 33)
SCOPE_OF_INSTRUCTION = {"ragged-dot": "moe_experts"}


def _scope_by_instruction(name: str) -> Optional[str]:
    for prefix, scope in SCOPE_OF_INSTRUCTION.items():
        if name.startswith(prefix):
            return scope
    return None


def latest_trace(profile_dir: str) -> Optional[str]:
    """Newest ``*.xplane.pb`` under ``profile_dir`` (jax.profiler writes
    one per capture under plugins/profile/<timestamp>/)."""
    newest, newest_m = None, -1.0
    for base, _dirs, files in os.walk(profile_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                p = os.path.join(base, f)
                m = os.path.getmtime(p)
                if m > newest_m:
                    newest, newest_m = p, m
    return newest


# ---------------------------------------------------------- attribution


def _union_len_us(intervals: list[tuple[float, float]]) -> float:
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


def _intersect_len_us(a: list, b: list) -> float:
    """Length of the intersection of two interval UNIONS (both merged
    first so overlapping ops are not double counted)."""
    def merged(ivs):
        out = []
        for s, e in sorted(ivs):
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return out

    ma, mb = merged(a), merged(b)
    i = j = 0
    total = 0.0
    while i < len(ma) and j < len(mb):
        s = max(ma[i][0], mb[j][0])
        e = min(ma[i][1], mb[j][1])
        if e > s:
            total += e - s
        if ma[i][1] < mb[j][1]:
            i += 1
        else:
            j += 1
    return total


def device_events(space: dict) -> tuple[dict, dict]:
    """``({program_id: [op event]}, {program_id: [(start_us, end_us)]})``
    over every line of every plane: each op execution with its
    instruction name, its ``(plane, line)`` and its interval, and, where
    the trace names them, the program's executions.

    Two forms (module docstring). The CPU's and GPU's events carry
    ``program_id`` and ``hlo_op`` stats and are named for the
    instruction. A TPU plane has an ``XLA Modules`` line whose events are
    the executions, named ``<module>(<program id>)``, and an ``XLA Ops``
    line whose events are named by the instruction's text: an op belongs
    to the execution it lies in, its instruction name is the text before
    `` = ``, and container ops (while, conditional, call) are left out,
    since their bodies' ops are events of their own."""
    events_by_pid: dict = {}
    runs_by_pid: dict = {}
    for plane in space["planes"]:
        by_name = {line["name"]: line for line in plane["lines"]}
        if TPU_MODULES_LINE in by_name and TPU_OPS_LINE in by_name:
            mods = by_name[TPU_MODULES_LINE]
            base = mods["timestamp_ns"] / 1e3
            runs = []
            for ev in mods["events"]:
                found = re.search(r"\((\d+)\)$", ev["name"] or "")
                if found:
                    start = base + ev["offset_ps"] / 1e6
                    runs.append((start, start + ev["duration_ps"] / 1e6,
                                 int(found.group(1))))
            runs.sort()
            for start, end, pid in runs:
                runs_by_pid.setdefault(pid, []).append((start, end))
            ops = by_name[TPU_OPS_LINE]
            base = ops["timestamp_ns"] / 1e3
            starts = [r[0] for r in runs]
            for ev in ops["events"]:
                start = base + ev["offset_ps"] / 1e6
                i = bisect.bisect_right(starts, start) - 1
                if i < 0 or start >= runs[i][1]:
                    continue  # outside every traced execution
                instr = (ev["name"] or "").partition(" = ")[0].lstrip("%")
                if instr.startswith(CONTAINER_OPS):
                    continue
                events_by_pid.setdefault(runs[i][2], []).append({
                    "name": instr,
                    "line": (plane["name"], TPU_OPS_LINE),
                    "start_us": start,
                    "end_us": start + ev["duration_ps"] / 1e6,
                })
            continue
        for line in plane["lines"]:
            base_us = line["timestamp_ns"] / 1e3
            for ev in line["events"]:
                pid = ev["stats"].get("program_id")
                if pid is None or "hlo_op" not in ev["stats"]:
                    continue
                start = base_us + ev["offset_ps"] / 1e6
                events_by_pid.setdefault(pid, []).append({
                    "name": ev["name"],
                    # the (plane, line) identity: _segment_executions
                    # anchors on ONE device line so concurrent devices
                    # do not over-split dispatches
                    "line": (plane["name"], line["name"]),
                    "start_us": start,
                    "end_us": start + ev["duration_ps"] / 1e6,
                })
    return events_by_pid, runs_by_pid


def host_spans(space: dict) -> list[dict]:
    """The loop's own spans (utils.tracing.span) as the ``/host:`` planes
    recorded them, in time order: name, the iteration's step, interval."""
    out = []
    for plane in space["planes"]:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            base_us = line["timestamp_ns"] / 1e3
            for ev in line["events"]:
                if ev["name"] in HOST_SPANS:
                    start = base_us + ev["offset_ps"] / 1e6
                    step = ev["stats"].get("step_num", ev["stats"].get("step"))
                    out.append({
                        "name": ev["name"],
                        "step": int(step) if isinstance(step, int) else None,
                        "start_us": start,
                        "end_us": start + ev["duration_ps"] / 1e6,
                    })
    return sorted(out, key=lambda sp: sp["start_us"])


def ran_ahead(spans: list[dict]) -> tuple[int, int]:
    """``(n, m)``: of the ``m`` iterations (parent spans) among ``spans``,
    the ``n`` that hold a ``dispatch`` of a later step than their own, so
    that launched the next step before they asked for their own loss
    (``cmd_lm`` keeps one step in flight). An iteration without one
    drained; the other loops' always do."""
    parents = [sp for sp in spans if sp["name"] in tracing.PARENT_SPANS]
    launches = [sp for sp in spans if sp["name"] == tracing.DISPATCH]
    ahead = sum(
        any(
            p["start_us"] <= d["start_us"] and d["end_us"] <= p["end_us"]
            and None not in (d["step"], p["step"]) and d["step"] > p["step"]
            for d in launches
        )
        for p in parents
    )
    return ahead, len(parents)


def idle_by_span(busy: list, spans: list[dict], lo: float, hi: float) -> dict:
    """``{span name: idle us}`` over ``[lo, hi]``: every stretch of
    :data:`MIN_IDLE_GAP_US` or more that no interval of ``busy`` covers,
    cut where a host span opens or closes, each piece put down to the
    innermost (shortest) span over it; ``(no span)`` where the loop had
    none open. So the gap between two executions splits into the loss
    coming back (``fetch``), the loop's own work (``boundary``,
    ``next_batch``) and the launch (``dispatch``)."""
    out: dict = {}
    cur = lo
    gaps = []
    for s_us, e_us in sorted(busy):
        if s_us > cur:
            gaps.append((cur, min(s_us, hi)))
        cur = max(cur, e_us)
        if cur >= hi:
            break
    if hi > cur:
        gaps.append((cur, hi))
    for g0, g1 in gaps:
        if g1 - g0 < MIN_IDLE_GAP_US:
            continue
        over = [sp for sp in spans if sp["start_us"] < g1 and sp["end_us"] > g0]
        cuts = sorted({g0, g1} | {
            t for sp in over for t in (sp["start_us"], sp["end_us"]) if g0 < t < g1
        })
        for p0, p1 in zip(cuts, cuts[1:]):
            mid = (p0 + p1) / 2
            inner = min(
                (sp for sp in over if sp["start_us"] <= mid <= sp["end_us"]),
                key=lambda sp: sp["end_us"] - sp["start_us"], default=None,
            )
            name = inner["name"] if inner else "(no span)"
            out[name] = out.get(name, 0.0) + (p1 - p0)
    return out


def _segment_executions(events: list[dict]) -> list[list[dict]]:
    """Split one module's op events (time-sorted) into dispatches.

    A trace of a multi-device program carries every instruction once per
    DEVICE LINE per dispatch, and the devices run concurrently — pooling
    all lines and counting occurrences would over-split each dispatch
    into per-device fragments. So: segment on ONE reference line (the
    line with the most recorded busy time — a full participant of every
    dispatch), where an instruction OUTSIDE any scan loop executes
    exactly once per dispatch while scan-body ops (a superstep program's
    step body) run K times — the MINIMUM per-instruction occurrence
    count on that line is the dispatch count, and the earliest-starting
    minimum-count instruction is the boundary anchor. Every line's
    events are then assigned to dispatches by TIME against the anchor
    windows (a concurrent device may start an op fractionally before the
    reference anchor and land one dispatch early — tolerable noise for
    wall and busy sums, stated here rather than hidden)."""
    if not events:
        return []
    busy_by_line: dict = {}
    for ev in events:
        busy_by_line[ev.get("line")] = busy_by_line.get(
            ev.get("line"), 0.0
        ) + (ev["end_us"] - ev["start_us"])
    ref = max(busy_by_line, key=lambda ln: busy_by_line[ln])
    ref_events = [ev for ev in events if ev.get("line") == ref]
    counts: dict = {}
    for ev in ref_events:
        counts[ev["name"]] = counts.get(ev["name"], 0) + 1
    n_min = min(counts.values())
    boundary = next(
        ev["name"] for ev in ref_events if counts[ev["name"]] == n_min
    )
    anchors = [
        ev["start_us"] for ev in ref_events if ev["name"] == boundary
    ]
    execs: list[list[dict]] = [[] for _ in anchors]
    for ev in events:
        # window i covers [anchors[i], anchors[i+1]); pre-anchor events
        # (another device's head start) join the first window
        i = max(bisect.bisect_right(anchors, ev["start_us"]) - 1, 0)
        execs[i].append(ev)
    return [ex for ex in execs if ex]


def build_timeline(
    profile_dir: str, train_dir: Optional[str] = None
) -> dict:
    """The timeline document (module docstring): per-dispatch phase
    spans from the newest trace under ``profile_dir``, joined against
    ``train_dir/metrics.jsonl`` when given. Pure host-side file reads."""
    checks = []

    def check(name, ok, detail, skipped=False):
        checks.append({"name": name, "ok": bool(ok), "skipped": skipped,
                       "detail": detail})

    doc = {
        "kind": "timeline_report",
        "profile_dir": os.path.abspath(profile_dir),
        "trace": None,
        "module": None,
        "spans": [],
        "checks": checks,
        "consistent": True,
    }
    trace = latest_trace(profile_dir) if os.path.isdir(profile_dir) else None
    if trace is None:
        check("timeline_trace_found", False,
              f"no *.xplane.pb under {profile_dir!r} — run with "
              "--profile-dir to capture one")
        doc["consistent"] = False
        return doc
    doc["trace"] = trace
    try:
        space = parse_xplane(trace)
    except (ValueError, IndexError, OSError) as exc:
        check("timeline_trace_found", False,
              f"unparseable trace {trace!r}: {exc}")
        doc["consistent"] = False
        return doc
    maps = scope_maps(space)
    # the training-step module: the program whose scope map carries the
    # named_phase anchors; ties broken by total device time (an eval or
    # iota program must not shadow the step)
    phased = {
        pid: m for pid, m in maps.items()
        if any(phase_of(op) != "compute" for op in m["scopes"].values())
    }
    if not phased:
        check(
            "timeline_phases_present", False,
            "no named_phase scopes (encode/exchange/decode) in any traced "
            "program — the trace predates the fused step, or the "
            "anchors were dropped (tests/test_fabric_obs.py guards them)",
        )
        doc["consistent"] = False
        return doc

    # op events per program id across every line of every plane, and the
    # programs' executions where the trace names them (the TPU's form)
    events_by_pid, runs_by_pid = device_events(space)
    # Task Environment anchors trace time to unix time
    start_ns = None
    for plane in space["planes"]:
        v = plane["stats"].get("profile_start_time")
        if isinstance(v, int):
            start_ns = v
    doc["profile_start_unix_s"] = (
        start_ns / 1e9 if start_ns is not None else None
    )

    def pid_key(pid):
        evs = events_by_pid.get(pid, [])
        return sum(e["end_us"] - e["start_us"] for e in evs)

    candidates = [p for p in phased if events_by_pid.get(p)]
    if not candidates:
        check(
            "timeline_phases_present", False,
            "named_phase scopes exist in the HLO metadata but no device "
            "op events were recorded for those programs — the profiled "
            "window may not have executed the fused step",
        )
        doc["consistent"] = False
        return doc
    pid = max(candidates, key=pid_key)
    doc["module"] = phased[pid]["module"]
    scopes = phased[pid]["scopes"]
    events = sorted(events_by_pid[pid], key=lambda e: e["start_us"])
    for ev in events:
        ev["phase"] = phase_of(scopes.get(ev["name"]))
        if ev["phase"] == "compute":  # no scope in its metadata: one of the compiler's own?
            ev["phase"] = phase_of(_scope_by_instruction(ev["name"]))
    check(
        "timeline_phases_present", True,
        f"module {doc['module']} carries "
        f"{sum(1 for e in events if e['phase'] != 'compute')} phase-scoped "
        f"op executions across {len(events)} events",
    )

    if runs_by_pid.get(pid):
        # the trace names the executions: an op belongs to the one it lies in
        op_starts = [e["start_us"] for e in events]  # sorted above
        executions = [
            events[bisect.bisect_left(op_starts, r0):bisect.bisect_left(op_starts, r1)]
            for r0, r1 in runs_by_pid[pid]
        ]
        executions = [ex for ex in executions if ex]
    else:
        executions = _segment_executions(events)
    spans = []
    names = PHASES + MODEL_PHASES + ("compute",)
    for i, ex in enumerate(executions):
        ivs: dict = {p: [] for p in names}
        busy: dict = {p: 0.0 for p in names}
        for ev in ex:
            ivs[ev["phase"]].append((ev["start_us"], ev["end_us"]))
            busy[ev["phase"]] += ev["end_us"] - ev["start_us"]
        # the compute side: everything outside the codec and the exchange
        compute_ivs = [iv for p in MODEL_PHASES + ("compute",) for iv in ivs[p]]
        t_start = min(e["start_us"] for e in ex)
        t_end = max(e["end_us"] for e in ex)
        span = {
            "dispatch": i,
            "t_start_us": round(t_start, 3),
            "wall_ms": round((t_end - t_start) / 1e3, 4),
            "compute_ms": round(
                sum(busy[p] for p in MODEL_PHASES + ("compute",)) / 1e3, 4
            ),
            "phases": {},
        }
        if doc["profile_start_unix_s"] is not None:
            span["t_start_unix_s"] = round(
                doc["profile_start_unix_s"] + t_start / 1e6, 3
            )
        for p in PHASES:
            union = _union_len_us(ivs[p])
            hidden = _intersect_len_us(ivs[p], compute_ivs)
            span["phases"][p] = {
                "busy_ms": round(busy[p] / 1e3, 4),
                "exposed_ms": round((union - hidden) / 1e3, 4),
                "hidden_ms": round(hidden / 1e3, 4),
            }
        for p in MODEL_PHASES:
            span["phases"][p] = {"busy_ms": round(busy[p] / 1e3, 4)}
        spans.append(span)
    doc["spans"] = spans
    doc["n_dispatches"] = len(spans)

    # ---- the loop's host spans, and the device's idle time by span ----
    hspans = host_spans(space)
    doc["host_spans"] = [
        {"name": sp["name"], "step": sp["step"],
         "t_start_us": round(sp["start_us"], 3),
         "ms": round((sp["end_us"] - sp["start_us"]) / 1e3, 4)}
        for sp in hspans
    ]
    doc["ran_ahead"] = list(ran_ahead(hspans))
    if executions:
        # idle on the step's reference device line: nothing of ANY program
        # runs there, first execution's start to the last one's end
        busy_by_line: dict = {}
        for e in events:
            busy_by_line[e["line"]] = (
                busy_by_line.get(e["line"], 0.0) + e["end_us"] - e["start_us"]
            )
        ref = max(busy_by_line, key=busy_by_line.get)
        busy_all = [
            (e["start_us"], e["end_us"])
            for evs in events_by_pid.values() for e in evs if e["line"] == ref
        ]
        lo = min(e["start_us"] for e in executions[0])
        hi = max(e["end_us"] for e in executions[-1])
        idle = idle_by_span(busy_all, hspans, lo, hi)
        doc["device_window_ms"] = round((hi - lo) / 1e3, 4)
        doc["device_idle_ms"] = round(sum(idle.values()) / 1e3, 4)
        doc["idle_by_span_ms"] = {
            k: round(v / 1e3, 4)
            for k, v in sorted(idle.items(), key=lambda kv: -kv[1])
        }

    # ---- join against metrics.jsonl ---------------------------------
    if train_dir:
        from atomo_tpu.obs.recorder import FlightRecorder, metrics_path

        recs = FlightRecorder.read(metrics_path(train_dir))
        steps = [r for r in recs if r.get("kind") == "step"]
        window = next(
            (r for r in recs
             if r.get("kind") == "meta"
             and r.get("what") == "profile_window"),
            None,
        )
        if not steps:
            check(
                "timeline_joins_metrics", True,
                "no metrics.jsonl step records to join against "
                "(run with --obs-record to arm the recorder)",
                skipped=True,
            )
        else:
            if window is not None:
                # the exact artifact-side key the loops record when the
                # trace starts: which steps the profiled window covers
                lo = int(window["first_step"])
                hi = int(window["last_step"])
                joined = [
                    r for r in steps if lo <= int(r["step"]) <= hi
                ]
                basis = f"recorded profile_window steps {lo}..{hi}"
            else:
                # fallback for pre-meta artifacts: wall-clock overlap
                # (trace times are unix-anchored via profile_start_time)
                t_lo = min(
                    (s.get("t_start_unix_s") for s in spans
                     if s.get("t_start_unix_s") is not None),
                    default=None,
                )
                t_hi = max(
                    (s.get("t_start_unix_s", 0) + s["wall_ms"] / 1e3
                     for s in spans if s.get("t_start_unix_s") is not None),
                    default=None,
                )
                joined = [
                    r for r in steps
                    if t_lo is not None and t_hi is not None
                    and t_lo - 2.0 <= float(r.get("ts", 0)) <= t_hi + 30.0
                ]
                basis = "wall-clock overlap (no profile_window meta)"
            doc["joined_steps"] = [int(r["step"]) for r in joined]
            if joined and spans and len(joined) % len(spans) == 0:
                # informational only (a trailing async dispatch can leak
                # into the trace, so a non-dividing count is not an
                # error — the wall check below is the contract)
                doc["steps_per_dispatch"] = len(joined) // len(spans)
            if not joined:
                check(
                    "timeline_joins_metrics", False,
                    f"no metrics.jsonl step records join the trace "
                    f"({basis}) — the trace and the metrics stream "
                    "describe different runs",
                )
            else:
                missing = []
                if window is not None:
                    have = {int(r["step"]) for r in joined}
                    missing = [
                        s for s in range(lo, hi + 1) if s not in have
                    ]
                window_ms = sum(
                    float(r["step_ms"]) for r in joined
                    if r.get("step_ms")
                )
                max_wall = max(s["wall_ms"] for s in spans)
                # the quantitative cross-check: the LARGEST device
                # dispatch must fit inside the profiled window's
                # recorded host wall (device work cannot outlast the
                # host wall that dispatched and fetched it; 1.5x guard
                # band for fetch jitter). A metrics stream describing a
                # different — or doctored — run fails here (tested on a
                # violated fixture).
                ok_wall = (
                    window_ms <= 0
                    or max_wall <= window_ms * 1.5 + 1.0
                )
                ok = not missing and ok_wall
                check(
                    "timeline_joins_metrics", ok,
                    f"{len(joined)} recorded step(s) joined ({basis}); "
                    f"largest dispatch {max_wall:.3f} ms vs window host "
                    f"wall {window_ms:.3f} ms"
                    + (
                        f"; steps {missing} missing from metrics.jsonl "
                        "(pruned or never recorded)" if missing else ""
                    )
                    + (
                        "" if ok_wall else
                        " — the device span EXCEEDS the host wall that "
                        "dispatched it; the metrics stream does not "
                        "describe this trace"
                    ),
                )
    else:
        check("timeline_joins_metrics", True,
              "no --train-dir given; trace-only timeline", skipped=True)

    doc["consistent"] = all(c["ok"] for c in checks)
    return doc


def summarize_timeline(doc: dict) -> str:
    """The human rendering: one line per dispatch with the phase
    exposed/hidden split, then the check verdicts."""
    lines = [
        f"phase timeline: {doc.get('trace') or doc.get('profile_dir')}",
    ]
    if doc.get("module"):
        lines.append(
            f"  module {doc['module']}: {doc.get('n_dispatches')} "
            "dispatch(es)"
            + (
                f", {doc['steps_per_dispatch']} step(s)/dispatch"
                if doc.get("steps_per_dispatch") else ""
            )
        )
    for s in doc.get("spans", []):
        ph = s["phases"]
        bits = [
            f"{p} {ph[p]['busy_ms']}ms"
            f" (exposed {ph[p]['exposed_ms']}, hidden {ph[p]['hidden_ms']})"
            for p in PHASES
            if ph[p]["busy_ms"] > 0
        ] + [
            f"{p} {ph[p]['busy_ms']}ms"
            for p in MODEL_PHASES
            if ph.get(p, {}).get("busy_ms", 0) > 0
        ]
        lines.append(
            f"  [dispatch {s['dispatch']}] wall {s['wall_ms']} ms, "
            f"compute {s['compute_ms']} ms"
            + (": " + "; ".join(bits) if bits else " (no phase ops)")
        )
    by_name: dict = {}
    for sp in doc.get("host_spans", []):
        by_name.setdefault(sp["name"], []).append(sp["ms"])
    if by_name:
        lines.append("  host spans (count x median ms): " + "; ".join(
            f"{n} {len(by_name[n])} x {sorted(by_name[n])[len(by_name[n]) // 2]}"
            for n in HOST_SPANS if n in by_name
        ))
    if doc.get("ran_ahead", [0, 0])[1]:
        lines.append("  ran ahead: {} of {} iterations".format(*doc["ran_ahead"]))
    if doc.get("idle_by_span_ms") is not None:
        lines.append(
            f"  device idle {doc['device_idle_ms']} ms of "
            f"{doc['device_window_ms']} ms, by the span over it: "
            + ("; ".join(f"{k} {v}" for k, v in doc["idle_by_span_ms"].items())
               or "none")
        )
    bad = [c["name"] for c in doc.get("checks", []) if not c["ok"]]
    ran = [c for c in doc.get("checks", []) if not c.get("skipped")]
    if doc.get("consistent"):
        lines.append(
            f"  consistency: OK ({len(ran)} check(s) ran, "
            f"{len(doc.get('checks', [])) - len(ran)} skipped)"
        )
    else:
        lines.append(f"  consistency: FAILED ({', '.join(bad)})")
        for c in doc.get("checks", []):
            if not c["ok"]:
                lines.append(f"    {c['name']}: {c['detail']}")
    return "\n".join(lines)
