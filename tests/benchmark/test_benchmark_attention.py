"""What PR 30 brings to the benchmark: `attn_score_mib`, the step's own count
of the exponentials causal attention keeps for the backward pass, read by the
reducer the other byte counters use."""

import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
LM_CELLS = ["gpt2m-1chip-dense", "olmohybrid-1chip-dense"]


def test_attn_score_mib_file_and_entry_agree_and_name_the_two_lm_cells():
    entry = BENCH["per_layer"][-1]
    file = json.loads((ROOT / "benchmarks/metrics/attn_score_mib.json").read_text())
    assert entry["name"] == file["name"] == "attn_score_mib"
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == file[key], key
    assert (entry["unit"], entry["better"], entry["source"]) == ("MiB", "lower", "program_counter")
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"][:-1]}  # a layer PERF.md already has
    assert (file["reducer"], file["args"]) == ("counter_mib", {"counter": "attn_score_bytes"})
    assert entry["workloads"] == LM_CELLS
    lm = {c["name"] for c in BENCH["configs"] if "lm" in json.loads((ROOT / c["file"]).read_text())["subcommand"]}
    assert [w["name"] for w in BENCH["workloads"] if w["config"] in lm] == LM_CELLS


@pytest.mark.parametrize("counters,want", [
    ({"attn_score_bytes": 1728.0 * 2**20}, 1728.0),
    ({"msg_bytes": 5.0}, None),  # the parent's step, which counts no such thing: left out, not raised
    ({}, None),
])
def test_attn_score_mib_reads_the_steps_counter_and_is_left_out_without_it(counters, want):
    from benchmarks.run import Data

    data = Data(ROOT / "BENCHMARK.json")
    metric = next(m for m in data.metrics("per_layer", data.cell(LM_CELLS[0])) if m["name"] == "attn_score_mib")
    reducer = data.module("reducers", metric["reducer"])
    assert reducer.reduce({"counters": counters}, **metric["args"]) == want
