"""BENCHMARK.json against the contract's limits, and against the files it names."""

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
METRICS = [(kind, m) for kind in ("end_to_end", "per_layer") for m in BENCH[kind]]
METRIC_IDS = [m["name"] for _, m in METRICS]


def all_names():
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[kind]:
            yield f"{kind}:{entry['name']}", entry["name"]
    for cell in BENCH["workloads"]:
        yield f"traffic:{cell['traffic']}", cell["traffic"]
    for config in BENCH["configs"]:
        for key in config["reduced"]:
            yield f"reduced:{key}", key


NAMES = list(all_names())


def test_top_level_keys_are_exactly_the_contracts():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    }
    assert len(json.dumps(BENCH)) < 64 * 1024
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("label,name", NAMES, ids=[label for label, _ in NAMES])
def test_name_uses_only_allowed_characters(label, name):
    assert NAME.match(name), name


def test_names_are_unique_within_their_kind():
    for kind in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[kind]]
        assert len(names) == len(set(names))
    assert len(METRIC_IDS) == len(set(METRIC_IDS))
    pairs = [(c["config"], c["traffic"]) for c in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("kind,metric", METRICS, ids=METRIC_IDS)
def test_metric_entry_is_well_formed(kind, metric):
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"bound"} if kind == "end_to_end" else {"layer", "moves"}
    assert set(metric) <= allowed and allowed - {"workloads"} <= set(metric)
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    if kind == "end_to_end":
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        assert "\n" not in metric["layer"] and 1 <= len(metric["layer"]) <= 200
    for cell in metric.get("workloads", []):
        assert cell in {w["name"] for w in BENCH["workloads"]}


@pytest.mark.parametrize("kind,metric", METRICS, ids=METRIC_IDS)
def test_metric_has_its_file_and_its_reducer(kind, metric):
    described = json.loads((ROOT / "benchmarks" / "metrics" / f"{metric['name']}.json").read_text())
    for key in ("name", "unit", "source", "better"):
        assert described[key] == metric[key], key
    if kind == "per_layer":
        assert described["layer"] == metric["layer"] and described["moves"] == metric["moves"]
    assert (ROOT / "benchmarks" / "reducers" / f"{described['reducer']}.py").is_file()


def test_setup_s_is_an_end_to_end_metric_of_every_cell():
    setup = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0] and setup[0]["bound"] <= 0.1


def test_an_mfu_metric_bounds_the_step():
    assert any("mfu" in m["name"] and m["unit"] == "%" for m in BENCH["per_layer"])


@pytest.mark.parametrize("config", BENCH["configs"], ids=[c["name"] for c in BENCH["configs"]])
def test_configuration_file_holds_what_the_entry_says(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    held = json.loads((ROOT / config["file"]).read_text())
    assert held["reduced"] == config["reduced"] and len(config["reduced"]) <= 16
    assert 1 <= len(config["why"]) <= 200 and 1 <= len(config["source"]) <= 200
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])
    for kind, key in (("adapters", "adapter"), ("reference", "reference"), ("flops", "flops")):
        assert (ROOT / "benchmarks" / kind / f"{held[key]}.py").is_file(), key
    for key in config["reduced"]:
        assert not re.search(r"(_dim|_rank|hidden|intermediate|n_embd|head_dim)$", key)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=[w["name"] for w in BENCH["workloads"]])
def test_cell_names_files_that_exist(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    traffic = json.loads((ROOT / "benchmarks" / "traffic" / f"{cell['traffic']}.json").read_text())
    assert isinstance(traffic["flags"], dict)
    limits = json.loads((ROOT / "benchmarks" / "limits" / f"{cell['name']}.json").read_text())
    assert limits["limits"] and all(0 <= v < 1 for v in limits["limits"].values())


def test_four_chip_cells_keep_to_their_share():
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 4)


def test_command_and_paths():
    assert BENCH["command"][:2] == ["python3", "benchmarks/run.py"] and len(BENCH["command"]) <= 32
    assert 1 <= len(BENCH["paths"]) <= 16
    for path in BENCH["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", path) and (ROOT / path).is_dir()
        for file in (ROOT / path).rglob("*"):
            if file.is_file() and "__pycache__" not in file.parts:
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", str(file.relative_to(ROOT))), file


def test_every_cell_reports_a_per_layer_metric_and_two_end_to_end():
    for cell in BENCH["workloads"]:
        for kind, least in (("end_to_end", 2), ("per_layer", 1)):
            mine = [m for m in BENCH[kind] if cell["name"] in m.get("workloads", [cell["name"]])]
            assert len(mine) >= least, (cell["name"], kind)
