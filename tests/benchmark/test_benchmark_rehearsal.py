"""Whole runs of run.py at the files' tiny sizes on the CPU: the result line,
a throw-away cell brought as new files only, the control, and the faults that
`correct` has to catch. No time and no memory reading is asserted: off the chip
the harness reports none."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_agrees_with_the_plain_reference(cell, rehearsal_args):
    """Each configuration's reference against the program at a tiny size,
    through the harness's own comparison and the cell's own limits."""
    from benchmarks import run

    result = run.run_cell(rehearsal_args(cell, seed=2**31 + 77))
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["compared"]) >= {"loss_gap", "change_gap"}
    assert result["device"]["platform"] == "cpu"
    assert not {"busy_s", "window_s", "memory_peak_bytes"} & set(result["device"])
    device_metrics = {m["name"] for k in ("end_to_end", "per_layer") for m in BENCH[k]
                      if m["source"] != "program_counter"}
    assert not device_metrics & set(result["metrics"])


def test_result_line_has_the_contracts_keys_and_comes_last():
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0], "--seed", "5",
         "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "compared" and line["device"]["platform"] == "cpu"
    assert done.stderr.strip().splitlines()[-1] == f"correct: {line['correct']}"
    assert "compared loss_gap:" in done.stderr
    newest = max((ROOT / "bench_out" / CELLS[0]).glob("stamps-seed5-trace1-*.json"),
                 key=lambda p: p.stat().st_mtime)
    stamps = json.loads(newest.read_text())
    first, last = stamps["window"]
    assert stamps["stamps"][last]["step"] - stamps["stamps"][first]["step"] == line["attempted"]


def test_off_the_chip_a_measuring_run_exits_nonzero_with_no_result():
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0], "--seed", "5",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode != 0 and "{" not in done.stdout


def test_a_throwaway_cell_runs_from_new_files_and_one_new_entry_each(tmp_path, rehearsal_args):
    """A later PR adds a configuration, a mix, a metric, a reducer and a cell
    without editing a file that is there."""
    from benchmarks import run

    base_cfg = json.loads((ROOT / BENCH["configs"][0]["file"]).read_text())
    base_cell = next(w for w in BENCH["workloads"] if w["config"] == BENCH["configs"][0]["name"])
    new = tmp_path / "benchmarks"
    for kind in ("configs", "traffic", "metrics", "reducers", "limits"):
        (new / kind).mkdir(parents=True)
    cfg = {**base_cfg, "tiny": {**base_cfg["tiny"], "n_layer": 1}}
    (new / "configs" / "throwaway.json").write_text(json.dumps(cfg))
    traffic = json.loads((ROOT / "benchmarks/traffic" / f"{base_cell['traffic']}.json").read_text())
    traffic["tiny"] = {**traffic.get("tiny", {}), "--batch-size": 3}
    (new / "traffic" / "three-rows.json").write_text(json.dumps(traffic))
    (new / "metrics" / "lines_seen.json").write_text(json.dumps({
        "name": "lines_seen", "layer": "training loop", "unit": "count", "better": "higher",
        "source": "program_counter", "moves": "step_ms", "reducer": "count_lines", "args": {},
    }))
    (new / "reducers" / "count_lines.py").write_text(
        "def reduce(ctx):\n    return float(len(ctx['stamps']))\n"
    )
    (new / "limits" / "throwaway-cell.json").write_text(
        (ROOT / "benchmarks/limits" / f"{base_cell['name']}.json").read_text()
    )
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "throwaway", "source": "none", "reduced": [], "why": "test",
                             "file": "benchmarks/configs/throwaway.json"})
    bench["workloads"].append({"name": "throwaway-cell", "config": "throwaway",
                               "traffic": "three-rows", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "lines_seen", "unit": "count", "better": "higher",
                               "source": "program_counter", "layer": "training loop",
                               "moves": "step_ms", "workloads": ["throwaway-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    result = run.run_cell(rehearsal_args("throwaway-cell", trace=1), benchmark=tmp_path / "BENCHMARK.json")
    assert result["correct"] is True, result["compared"]
    assert result["metrics"]["lines_seen"]["value"] > 3


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_a_lower_precision_is_not_correct(cell):
    """The reference, put in the program's place and computed in a precision
    below the configuration's, has to fail the cell's limits: here, in the
    CPU's true float32, each of the reference's controls (on the v5e float32
    at XLA's default precision is bfloat16 arithmetic, and the bfloat16
    control reads as a sound run: PERF.md §2). A cell whose numbers cannot
    show the backward pass (a randomised codec hides it) names the cell of its
    configuration that does."""
    import numpy as np

    from benchmarks import check, run

    data = run.Data(ROOT / "BENCHMARK.json")
    held_by = data.json("limits", cell).get("control_held_by")
    if held_by:
        assert data.cell(held_by)["config"] == data.cell(cell)["config"]
        assert "control_held_by" not in data.json("limits", held_by)
        cell = held_by
    entry = data.cell(cell)
    cfg, traffic = run.tiny(data.config(entry["config"]), data.json("traffic", entry["traffic"]))
    _, flags = run.program_argv(cfg, traffic, seed=3)
    reference = data.module("reference", cfg["reference"])
    batches = reference.example_batches(cfg, seed=3, calls=3, rows=4)
    assert all(isinstance(b, (np.ndarray, tuple)) for b in batches)
    follow = lambda **how: reference.train_steps(  # noqa: E731
        reference.init_params(cfg, 3), batches, cfg, flags=flags, **how)
    sound = follow()
    limits = data.json("limits", cell)["limits"]
    # in the program's place the first gradient is as hidden as the program's own
    hidden = data.module("adapters", cfg["adapter"]).ONE_STEP_PER_CALL is False
    seen = lambda side: {**side, "grad1_norms": None} if hidden else side  # noqa: E731
    assert check.judge(check.training_numbers(seen(sound), sound), limits)[0]
    for mode in reference.CONTROLS:
        control = follow(mode=mode, draws=1)
        lower, compared = check.judge(check.training_numbers(seen(control), sound), limits)
        assert not lower, (mode, compared)
