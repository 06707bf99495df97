"""The six per-layer metrics that say where `setup_s` goes (layer `set-up`):
each entry against its file, `reducers/setup_span.py` on a constructed ring
(unions, the window's edge, None where the ring has no such record), and
rehearsals whose ring is read by these six and, unchanged, by the loop's own
span metrics."""

import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
SIX = {  # name: (unit, source, what)
    "setup_init_s": ("s", "program_span", "init"),
    "setup_trace_s": ("s", "program_span", "trace"),
    "setup_lower_s": ("s", "program_span", "lower"),
    "setup_compile_s": ("s", "program_span", "compile"),
    "setup_untraced_s": ("s", "program_span", "untraced"),
    "setup_cache_misses": ("count", "program_counter", "misses"),
}
LOOP_METRICS = ["dispatch_ms", "fetch_wait_ms", "host_turnaround_ms", "host_untraced_pct", "feed_ms"]


def reduce(name, ctx):
    """Through the harness's own lookup: metrics/<name>.json names the reducer."""
    from benchmarks import run

    data = run.Data(ROOT / "BENCHMARK.json")
    described = data.json("metrics", name)
    return data.module("reducers", described["reducer"]).reduce(ctx, **described["args"])


@pytest.mark.parametrize("name", sorted(SIX))
def test_entry_and_file_agree_and_move_setup_s_in_every_cell(name):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    file = json.loads((ROOT / "benchmarks" / "metrics" / f"{name}.json").read_text())
    unit, source, what = SIX[name]
    assert entry == {"name": name, "unit": unit, "better": "lower", "source": source,
                     "layer": "set-up", "moves": "setup_s"}  # no `workloads`: every cell
    for key in entry:
        assert file[key] == entry[key], key
    assert (file["reducer"], file["args"]) == ("setup_span", {"what": what})


def synthetic_ring():
    """The process starts at 100 s and the window's first stamp is at 140 s."""
    spans = [
        ("jax_compile", None, None, 90.0, 95.0),  # before the process: out
        ("jax_trace", None, None, 99.0, 101.0),  # across its start: 1 s in
        ("init_state", None, None, 101.0, 111.0),
        ("jax_trace", None, None, 102.0, 104.0),
        ("jax_trace", None, None, 102.5, 103.0),  # nested: counted once
        ("jax_lower", None, None, 104.0, 105.0),
        ("jax_cache_miss", None, None, 106.0, 106.0),
        ("jax_compile", None, None, 105.0, 108.0),
        ("jax_trace", 1, None, 121.0, 122.5),  # the step's first call
        ("jax_lower", 1, None, 122.5, 123.0),
        ("jax_compile", 1, None, 123.0, 125.0),
        ("dispatch", 1, "step", 120.5, 125.5),
        ("step", 1, None, 120.0, 130.0),
        ("step", 2, None, 130.0, 141.0),  # across the window's edge: 10 s in
        ("jax_compile", 3, None, 150.0, 151.0),  # in the window: out
        ("jax_cache_miss", 3, None, 150.5, 150.5),
    ]
    stamps = [(129.0, 1, 2.0), (140.0, 2, 2.0), (150.0, 3, 2.0), (160.0, 4, 2.0)]
    return {"spans": spans, "stamps": stamps, "window": (1, 3), "slice": None, "trace": None,
            "process_start": 100.0}


EXPECTED = {
    "setup_init_s": 10.0,
    "setup_trace_s": 1.0 + 2.0 + 1.5,
    "setup_lower_s": 1.0 + 0.5,
    "setup_compile_s": 3.0 + 2.0,
    # traced: 99..111 (cut at 100) and 120..140: 11 + 20 of the 40 s of set-up
    "setup_untraced_s": 40.0 - 31.0,
    "setup_cache_misses": 1.0,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_setup_span_reads_unions_cut_to_the_set_up(name):
    assert reduce(name, synthetic_ring()) == pytest.approx(EXPECTED[name], rel=1e-12)


def test_no_miss_reads_zero_and_a_missing_kind_none():
    ctx = synthetic_ring()
    ctx["spans"] = [r for r in ctx["spans"] if r[0] not in ("jax_cache_miss", "init_state")]
    assert reduce("setup_cache_misses", ctx) == 0.0
    assert reduce("setup_init_s", ctx) is None
    # traced: 100..101, 102..108 (trace, lower, compile) and 120..140
    assert reduce("setup_untraced_s", ctx) == pytest.approx(40.0 - 1.0 - 6.0 - 20.0)


@pytest.mark.parametrize("name", sorted(SIX))
def test_a_ring_of_the_loops_alone_gives_none(name, monkeypatch):
    """The ring of a commit from before these records: its loops' spans and
    no set-up record; and a program without the ring at all."""
    ctx = synthetic_ring()
    ctx["spans"] = [r for r in ctx["spans"] if r[0] in ("dispatch", "step")]
    assert reduce(name, ctx) is None
    from atomo_tpu.utils import tracing

    monkeypatch.delattr(tracing, "spans")
    del ctx["spans"]
    assert reduce(name, ctx) is None


def test_an_unknown_reduction_raises():
    from benchmarks.reducers import setup_span

    with pytest.raises(ValueError, match="nope"):
        setup_span.reduce(synthetic_ring(), "nope")


@pytest.fixture(scope="module", params=["gpt2m-1chip-dense", "resnet18-1chip-dense"])
def rehearsal(request):
    """One rehearsal of the cell through run.py, in this process: the ring as
    it stands after it, and the window's stamps as run.py wrote them."""
    from atomo_tpu.utils import tracing
    from benchmarks import run

    cell = request.param
    result = run.run_cell(run.parse(["--workload", cell, "--seed", "38", "--seconds", "1.0",
                                     "--trace", "0", "--rehearse"]))
    assert result["correct"] is True and result["attempted"] > 0
    records = tracing.spans()
    stamps = json.loads(max((ROOT / "bench_out" / cell).glob("stamps-seed38-trace0-*.json"),
                            key=lambda p: p.stat().st_mtime).read_text())
    ctx = {"stamps": [(s["clock_s"], s["step"], s["loss"]) for s in stamps["stamps"]],
           "window": tuple(stamps["window"]), "slice": None, "trace": None,
           "process_start": stamps["process_start"], "spans": records}
    return cell, records, ctx


def test_a_rehearsal_reads_all_six_inside_its_setup_s(rehearsal):
    _, records, ctx = rehearsal
    setup_s = reduce("setup_s", ctx)
    got = {name: reduce(name, ctx) for name in SIX}
    assert all(value is not None and 0 <= value <= setup_s for value in got.values()), got
    assert got["setup_init_s"] > 0 and got["setup_trace_s"] > 0 and got["setup_compile_s"] > 0
    assert got["setup_cache_misses"] == 0  # the suite runs with the persistent cache off
    assert len(records) < 32768  # the ring held set-up and window together


def test_a_rehearsals_loop_metrics_read_what_they_read_without_set_up(rehearsal):
    """The records are never an iteration's child, so dispatch_ms and the
    rest read exactly what they read on a ring without them."""
    cell, records, ctx = rehearsal
    setup = ("init_state", "jax_trace", "jax_lower", "jax_compile", "jax_cache_miss")
    assert all(r[2] not in ("block", "step") for r in records if r[0] in setup)
    without = {**ctx, "spans": [r for r in records if r[0] not in setup]}
    assert len(without["spans"]) < len(records)
    for name in LOOP_METRICS:
        assert reduce(name, ctx) == reduce(name, without), name
    assert (reduce("feed_ms", ctx) is not None) == cell.startswith("resnet18")
    assert reduce("dispatch_ms", ctx) is not None
