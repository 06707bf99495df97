"""The seven per-layer metrics that read the program's host spans (PR 26):
each reducer on a constructed window, the identity that splits GPT-2's launch
gap on a constructed trace, a program without the ring, and rehearsals after
which the ring holds the window's iterations."""

import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = ["dispatch_ms", "fetch_wait_ms", "host_turnaround_ms", "feed_ms", "host_untraced_pct",
       "fetch_tail_ms", "launch_lag_ms"]
RESNETS = ["resnet18-1chip-svd3", "resnet18-1chip-dense"]
MS = 1e-3


def reduce(name, ctx):
    """Through the harness's own lookup: metrics/<name>.json names the reducer."""
    from benchmarks import run

    data = run.Data(ROOT / "BENCHMARK.json")
    described = data.json("metrics", name)
    return data.module("reducers", described["reducer"]).reduce(ctx, **described["args"])


def superstep_window(blocks=12, k=8, slice_at=(5, 7), slow=()):
    """A ring and the stamps of a superstep loop: block i ends on step k*(i+1),
    takes 1 + 2 + 100 + 380 + 3 ms in its five spans and 4 ms outside them;
    a block in `slow` fetches 200 ms longer. Stamp i is block i's log line."""
    spans, stamps, t = [], [], 10.0
    for i in range(blocks):
        step, t0 = k * (i + 1), t
        for name, ms in (("feed_take", 1), ("dispatch", 2), ("feed_start", 100),
                         ("fetch", 380 + (200 if i in slow else 0)), ("boundary", 3)):
            if name == "feed_start":
                spans.append(("stack", step, "feed_start", t, t + 60 * MS))
                spans.append(("put", step, "feed_start", t + 60 * MS, t + 100 * MS))
            if name == "boundary":
                stamps.append((t + 1 * MS, step, 2.3))
            spans.append((name, step, "block", t, t + ms * MS))
            t += ms * MS
        t += 4 * MS
        spans.append(("block", step, None, t0, t))
    ctx = {"spans": spans, "stamps": stamps, "window": (1, blocks - 1),
           "slice": list(slice_at) if slice_at else None, "trace": None}
    return ctx


EXPECTED = {  # per optimizer step of a block of 8, or a share of the block
    "dispatch_ms": 2 / 8, "fetch_wait_ms": 380 / 8, "feed_ms": 101 / 8,
    "host_turnaround_ms": (3 + 4 + 1 + 2) / 8, "host_untraced_pct": 100 * 4 / 490,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_span_reducer_reads_the_window_per_optimizer_step(name):
    assert reduce(name, superstep_window()) == pytest.approx(EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_span_reducer_leaves_the_profiled_slice_out(name):
    """Blocks 4 to 8 lie in or beside the slice (stamp_stat's cut: from the
    interval before the slice's first stamp to the one after its last); a
    stall there moves nothing, a stall outside moves only the mean."""
    quiet = reduce(name, superstep_window())
    assert reduce(name, superstep_window(slow=(5, 6, 7, 8))) == pytest.approx(quiet)
    from benchmarks.reducers import span_stat

    kept = [it["step"] // 8 - 1 for it in span_stat.kept_iterations(superstep_window())]
    assert kept == [2, 3, 4, 9, 10, 11]
    assert [it["step"] // 8 - 1 for it in span_stat.kept_iterations(superstep_window(slice_at=None))] \
        == list(range(2, 12))


@pytest.mark.parametrize("name,missing", [
    ("dispatch_ms", "dispatch"), ("fetch_wait_ms", "fetch"), ("host_turnaround_ms", "fetch"),
    ("feed_ms", "feed_"), ("host_untraced_pct", "block"),
])
def test_span_reducer_gives_none_when_its_span_is_missing(name, missing):
    ctx = superstep_window()
    ctx["spans"] = [rec for rec in ctx["spans"] if not rec[0].startswith(missing)]
    assert reduce(name, ctx) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_ring_reports_nothing_and_does_not_raise(name, monkeypatch):
    """The driver lays these files over the parent commit too, whose
    utils/tracing.py has no ring and whose trace has no program span."""
    from atomo_tpu.utils import tracing

    monkeypatch.delattr(tracing, "spans")
    ctx = superstep_window()
    del ctx["spans"]
    ctx["trace"] = gpt2_trace(with_spans=False)
    assert reduce(name, ctx) is None


def per_step_window(steps=40, slice_at=(20, 26)):
    """GPT-2's loop: next_batch 1 ms, dispatch 1.5 ms, fetch 155 ms, boundary
    0.5 ms, 0.1 ms outside any span; one optimizer step an iteration."""
    spans, stamps, t = [], [], 5.0
    for i in range(1, steps + 1):
        t0 = t
        for name, ms in (("next_batch", 1.0), ("dispatch", 1.5), ("fetch", 155.0), ("boundary", 0.5)):
            if name == "boundary":
                stamps.append((t + 0.2 * MS, i, 10.8))
            spans.append((name, i, "step", t, t + ms * MS))
            t += ms * MS
        t += 0.1 * MS
        spans.append(("step", i, None, t0, t))
    return {"spans": spans, "stamps": stamps, "window": (3, steps - 1),
            "slice": list(slice_at), "trace": None}


def gpt2_trace(runs=6, with_spans=True):
    """A slice of that loop on the trace's clock, in ns: the device starts an
    execution 0.4 ms after its dispatch span opens and runs 153 ms; the loss is
    back in the loop 2.6 ms after the device's end."""
    ns = 1_000_000
    modules, ops, host, t = [], [], [], 7_000 * ns
    for _ in range(runs):
        host.append(["next_batch", t, 1 * ns])
        dispatch = t + 1 * ns
        host.append(["dispatch", dispatch, int(1.5 * ns)])
        start = dispatch + int(0.4 * ns)
        modules.append(["jit_spmd_step(1)", start, 153 * ns])
        ops.append(["%fusion.1 = f32[4] fusion()", start, 153 * ns])
        fetch = dispatch + int(1.5 * ns)
        fetch_end = start + 153 * ns + int(2.6 * ns)
        host.append(["fetch", fetch, fetch_end - fetch])
        host.append(["np.asarray(jax.Array)", fetch, fetch_end - fetch])
        host.append(["boundary", fetch_end, int(0.5 * ns)])
        t = fetch_end + int(0.6 * ns)
    if not with_spans:
        host = [ev for ev in host if ev[0] == "np.asarray(jax.Array)"]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}, "host": host}


def test_launch_gap_is_fetch_tail_plus_turnaround_less_dispatch_plus_launch_lag():
    """For a per-step loop the spans share the device's clock, so the gap
    between two executions splits into the loss coming back, the loop's own
    work and the launch."""
    ctx = per_step_window()
    ctx["trace"] = gpt2_trace()
    tail, lag = reduce("fetch_tail_ms", ctx), reduce("launch_lag_ms", ctx)
    assert tail == pytest.approx(2.6) and lag == pytest.approx(0.4)
    turnaround, dispatch = reduce("host_turnaround_ms", ctx), reduce("dispatch_ms", ctx)
    assert dispatch == pytest.approx(1.5) and turnaround == pytest.approx(0.5 + 0.1 + 1.0 + 1.5)
    assert reduce("fetch_wait_ms", ctx) == pytest.approx(155.0)
    assert reduce("host_untraced_pct", ctx) == pytest.approx(100 * 0.1 / 158.1)
    assert reduce("feed_ms", ctx) is None  # no feed in this loop
    gap = reduce("launch_gap_ms", ctx)
    assert gap == pytest.approx(tail + turnaround - dispatch + lag, rel=1e-6)


def test_trace_reducers_keep_to_the_fetch_and_dispatch_of_each_execution():
    """An execution whose fetch the slice did not hold, or whose dispatch
    opened before the slice, is left out and not matched to a neighbour's."""
    trace = gpt2_trace(runs=4)
    first_dispatch = next(ev for ev in trace["host"] if ev[0] == "dispatch")
    last_fetch = [ev for ev in trace["host"] if ev[0] == "fetch"][-1]
    trace["host"] = [ev for ev in trace["host"] if ev is not first_dispatch and ev is not last_fetch]
    ctx = {**per_step_window(), "trace": trace}
    assert reduce("fetch_tail_ms", ctx) == pytest.approx(2.6)
    assert reduce("launch_lag_ms", ctx) == pytest.approx(0.4)
    trace["host"] = [ev for ev in trace["host"] if ev[0] not in ("fetch", "dispatch")]
    assert reduce("fetch_tail_ms", ctx) is None and reduce("launch_lag_ms", ctx) is None


def test_the_seven_metrics_are_the_last_entries_and_name_their_cells():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    assert [m["name"] for m in BENCH["per_layer"]][-7:] == NEW
    for name in NEW:
        assert entries[name]["moves"] == "step_ms" and entries[name]["better"] == "lower"
    assert all(entries[n]["source"] == "program_span" for n in NEW[:5])
    assert all(entries[n]["source"] == "device_trace" for n in NEW[5:])
    assert entries["feed_ms"]["workloads"] == RESNETS and entries["feed_ms"]["layer"] == "data feed"
    for name in ("fetch_tail_ms", "launch_lag_ms"):  # where launch_gap_ms is read, for its reason
        assert entries[name]["workloads"] == entries["launch_gap_ms"]["workloads"]
    for name in ("dispatch_ms", "fetch_wait_ms", "host_turnaround_ms", "host_untraced_pct"):
        assert "workloads" not in entries[name]  # every cell


@pytest.mark.parametrize("cell,parent,children", [
    ("gpt2m-1chip-dense", "step", {"next_batch", "dispatch", "fetch", "boundary"}),
    ("resnet18-1chip-dense", "block", {"feed_take", "dispatch", "feed_start", "fetch", "boundary"}),
])
def test_after_a_rehearsal_the_ring_holds_the_windows_iterations(cell, parent, children,
                                                                 rehearsal_args):
    """run.py closes the window by raising through the log line, from inside
    `boundary` and the parent span: the ring holds those too, and the
    reducers find every stamped interval of the window in it."""
    from atomo_tpu.utils import tracing
    from benchmarks import run
    from benchmarks.reducers import span_stat

    result = run.run_cell(rehearsal_args(cell, seed=26))
    assert result["correct"] is True and result["attempted"] > 0
    records = tracing.spans()
    its = span_stat.iterations(records)
    assert its and {rec[0] for rec in records if rec[2] is None and rec[0] in ("block", "step")} == {parent}
    assert all(set(it["kids"]) == children for it in its[:-1])
    last = its[-1]  # the iteration the window closed in: cut short inside `boundary`, and recorded
    assert "boundary" in last["kids"] and "fetch" in last["kids"]
    stamps = json.loads(max((ROOT / "bench_out" / cell).glob("stamps-seed26-trace0-*.json"),
                            key=lambda p: p.stat().st_mtime).read_text())
    first, last_i = stamps["window"]
    stamped = [s["step"] for s in stamps["stamps"][first + 1: last_i + 1]]
    assert last["step"] == stamped[-1]
    assert set(stamped) <= {it["step"] for it in its}
    ctx = {"stamps": [(s["clock_s"], s["step"], s["loss"]) for s in stamps["stamps"]],
           "window": (first, last_i), "slice": None, "trace": None}
    assert [it["step"] for it in span_stat.kept_iterations(ctx)] == stamped
    for name in ("dispatch_ms", "fetch_wait_ms", "host_turnaround_ms", "host_untraced_pct"):
        value = reduce(name, ctx)
        assert value is not None and value >= 0, name
    assert (reduce("feed_ms", ctx) is not None) == (parent == "block")
