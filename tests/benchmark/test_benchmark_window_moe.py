"""What PR 35 brings to the benchmark for `mellum2-12b-a2.5b`: the FLOPs of a
decoder of windowed and full grouped-query attention over routed experts
counted by hand, the attention core's work and its reducer (on a recorded
trace), the configuration's file against the catalog's keys, the reference's
size, the limits between their readings, and the appended entries."""

import json
import math
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CONFIG = json.loads((ROOT / "benchmarks/configs/mellum2-12b-a2.5b.json").read_text())
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
FIXTURE = ROOT / "tests/benchmark/fixtures/tpu_v5e_window_moe_trace.json"
CELL = "mellum2-1chip-dense"
FLAGS = {"--batch-size": "2", "--seq-len": "8192", "--bf16": True}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

SMALL = {"hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 3,
         "moe_intermediate_size": 6, "num_hidden_layers": 3, "sliding_window": 4,
         "layer_types": ["sliding_attention", "full_attention", "sliding_attention", "full_attention"],
         "n_routed_experts": 2, "routed_experts_total": 8, "num_experts_per_tok": 4, "vocab_size": 32}


# ---- FLOPs and bytes from shapes ------------------------------------------------

def test_pairs_of_each_layer_kind_counted_by_enumeration():
    from benchmarks.flops import window_moe_lm as flops

    for seq, window in ((16, 4), (16, 16), (16, 40), (8192, 1024), (5, 1)):
        seen = sum(1 for p in range(seq) for t in range(seq) if 0 <= p - t < window) if seq < 100 else None
        if seen is not None:
            assert flops.pairs("sliding_attention", seq, window) == seen
        assert flops.pairs("full_attention", seq, window) == seq * (seq + 1) // 2
    assert flops.pairs("sliding_attention", 8192, 1024) == 7_864_832 == 1024 * 1025 // 2 + 7168 * 1024
    assert flops.pairs("full_attention", 8192, 1024) == 33_558_528
    assert flops.step_pairs(CONFIG, 8192) == 3 * 7_864_832 + 33_558_528 == 57_153_024
    assert flops.step_pairs(SMALL, 16) == 2 * (4 * 5 // 2 + 12 * 4) + 16 * 17 // 2  # sliding, full, sliding held


def _by_hand(batch, seq):
    d, h, hk, dh, fe, vocab, total = 8, 4, 2, 3, 6, 32, 8
    tokens = batch * seq
    projections = 2 * tokens * (d * (h + 2 * hk) * dh + h * dh * d)
    window_pairs, full_pairs = 4 * 5 // 2 + (seq - 4) * 4, seq * (seq + 1) // 2
    core = lambda pairs: 2 * batch * h * pairs * 2 * dh  # noqa: E731  q.k and p.v
    experts = 2 * tokens * d * total + 2 * (tokens * 4 * 2 / 8) * 3 * d * fe
    layers = 3 * (projections + experts) + 2 * core(window_pairs) + core(full_pairs)
    return layers + 2 * batch * (seq - 1) * d * vocab


def test_window_moe_flops_of_a_small_model_against_a_hand_count():
    from benchmarks.flops import window_moe_lm as flops

    assert flops.forward_flops(SMALL, 1, 16) == pytest.approx(_by_hand(1, 16), rel=1e-12)
    assert flops.train_flops_per_step(SMALL, {"--batch-size": "3", "--seq-len": "16"}) == pytest.approx(
        3 * _by_hand(3, 16), rel=1e-12)
    assert flops.expected_rows(SMALL, 16) == 16 * 4 * 2 / 8 and flops.expert_layers(SMALL) == 3


def test_the_cell_needs_24_46_teraflops_a_step_and_counts_the_head_once():
    from benchmarks.flops import window_moe_lm as flops

    step = flops.train_flops_per_step(CONFIG, FLAGS)
    assert step == pytest.approx(24.46e12, rel=1e-3)
    head = 3 * 2 * 2 * 8191 * 2304 * 24576
    core = 3 * 4 * 128 * 2 * 32 * 57_153_024
    projections = 3 * 4 * 2 * 16384 * 21_233_664
    experts = 3 * 4 * (2 * 16384 * 2304 * 64 + 2 * 32768 * 6_193_152)
    assert step == pytest.approx(head + core + projections + experts, rel=1e-12)
    assert 0.22 < core / step < 0.24 and 0.22 < head / step < 0.24  # ISSUE 35's shares: 23% each
    assert flops.expected_rows(CONFIG, 16384) == 32768 and flops.expert_layers(CONFIG) == 4
    # were the window a mask over the triangle, the core alone would be 2.35 times as much
    assert 4 * 33_558_528 / 57_153_024 == pytest.approx(2.349, rel=1e-3)


def test_attention_work_is_the_bands_pairs_three_and_a_half_times_and_what_the_core_must_move():
    from benchmarks.flops import window_moe_lm as flops

    work, moved = flops.attention_work(CONFIG, FLAGS)
    forward = 4 * 128 * 2 * 32 * 57_153_024
    assert work == 3.5 * forward == pytest.approx(6.55e12, rel=1e-3)
    # forward q, k, v in and o out; backward q, k, v, o, do in and dq, dk, dv out: six arrays at
    # the 32 query heads and six at the 4 key/value heads, bfloat16, in each of 4 layers
    array = lambda heads: 2 * heads * 8192 * 128 * 2  # noqa: E731
    assert moved == 4 * (6 * array(32) + 6 * array(4))
    assert work / 197e12 > 7 * moved / 819e9  # the MXU bounds it: 33.3 ms against 4.4
    # broadcast heads or dead tiles are not in the count: it does not follow what implements it
    assert flops.attention_work(CONFIG, {**FLAGS, "--bf16": False})[1] == 2 * moved


def test_expert_work_is_three_passes_over_the_steps_own_rows():
    from benchmarks.flops import window_moe_lm as flops

    work, moved = flops.expert_work(CONFIG, FLAGS, 131072)
    assert work == 3 * 2 * 131072 * 3 * 2304 * 896
    weights = 4 * 16 * 3 * 2304 * 896 * 2
    assert moved == 4 * weights + 4 * 131072 * 2304 * 2
    assert flops.row_bytes(CONFIG, FLAGS) == 4608 and flops.row_bytes(CONFIG, {}) == 9216


def test_the_moe_reducers_read_this_configurations_file_as_glms():
    """The keys `reducers/moe.py sizes_of` and `moe_counters.py` read are in
    the file under GLM's names, so that a later `benchmark` PR can widen the
    four `moe_*` metrics to this cell with data alone."""
    from benchmarks.reducers import moe, moe_counters

    ctx = {"config": CONFIG, "flags": FLAGS, "counters": {
        "moe_held_row_bytes": 131000 * 4608.0, "moe_max_expert_row_bytes": 2500 * 4608.0}}
    sizes = moe.sizes_of(ctx)
    assert (sizes["tokens"], sizes["per_token"], sizes["rows"], sizes["outputs"], sizes["width"]) == (
        16384, 8, 131072, 64, 2304)
    assert sizes["experts"] == {(16, 2304, 896), (16, 896, 2304)}
    assert moe_counters.reduce(ctx, "held_rows") == 131000
    assert moe_counters.reduce(ctx, "max_over_mean") == pytest.approx(2500 / (131000 / 64))


# ---- the configuration's file ----------------------------------------------------

def test_configuration_keeps_every_key_of_the_published_config_but_the_three_it_cuts():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    cut = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 98304 // 4}
    published_cut = {"num_hidden_layers": 28, "num_experts": 64, "vocab_size": 98304}
    if catalog.is_file():  # the catalog's row, where the guide is installed
        rows = [json.loads(line) for line in catalog.read_text().splitlines() if line.strip()]
        row = next(r for r in rows if r["name"] == "Mellum2-12B-A2.5B-Instruct")
        assert row["source_url"] == CONFIG["source"]
        assert {k: CONFIG[k] for k in row["config"]} == {**row["config"], **cut}
        assert {k: row["config"][k] for k in cut} == published_cut
    assert CONFIG["reduced"] == list(cut) and CONFIG["published"] == published_cut
    # no width is cut
    widths = {"hidden_size": 2304, "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
              "moe_intermediate_size": 896, "num_experts_per_tok": 8, "sliding_window": 1024,
              "intermediate_size": 7168, "rms_norm_eps": 1e-06}
    assert {k: CONFIG[k] for k in widths} == widths
    assert CONFIG["layer_types"][:4] == ["sliding_attention"] * 3 + ["full_attention"] and len(CONFIG["layer_types"]) == 28
    assert set(CONFIG["mlp_layer_types"]) == {"sparse"}
    assert CONFIG["rope_parameters"]["full_attention"] == {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16, "original_max_position_embeddings": 8192,
        "beta_fast": 32, "beta_slow": 1, "attention_factor": 1.2772588722239782}
    assert CONFIG["rope_parameters"]["sliding_attention"] == {"rope_type": "default", "rope_theta": 500000}
    # the flags' copies of the nested record say what the record says
    rule = CONFIG["rope_parameters"]["full_attention"]
    assert (CONFIG["rope_theta"], CONFIG["yarn_factor"], CONFIG["yarn_original_len"], CONFIG["yarn_beta_fast"],
            CONFIG["yarn_beta_slow"], CONFIG["yarn_attention_factor"]) == (
        rule["rope_theta"], rule["factor"], rule["original_max_position_embeddings"], rule["beta_fast"],
        rule["beta_slow"], rule["attention_factor"])
    assert (CONFIG["routed_experts_total"], CONFIG["first_expert_held"], CONFIG["n_routed_experts"]) == (64, 0, 16)
    assert "shared by 4 chips" in CONFIG["deployment"] and "595.2 M" in CONFIG["deployment"]
    for key in ("num_experts", "layer_types", "intermediate_size", "partial_rotary_factor", "rotary_pairing",
                "sliding_window", "yarn", "qk_norm", "mtp", "router", "aux_loss", "qkv_leaf", "init",
                "optimizer", "seq_len", "data", "remat"):
        assert len(CONFIG["assumed"][key]) > 40, key
    entry = next(c for c in BENCH["configs"] if c["name"] == "mellum2-12b-a2.5b")
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == CONFIG["reduced"]
    assert entry["file"] == "benchmarks/configs/mellum2-12b-a2.5b.json"


def test_reference_describes_595_million_parameters_and_imports_nothing_of_the_program():
    from benchmarks.reference import mellum2_12b_a2_5b

    shapes = mellum2_12b_a2_5b.param_shapes(CONFIG)
    layer = 21_233_664 + 4_608 + 147_456 + 16 * 6_193_152
    assert sum(math.prod(s) for s in shapes.values()) == 4 * layer + 2 * 24576 * 2304 + 2304 == 595_153_152
    assert shapes["block0/MultiHeadAttention_0/qkv/kernel"] == (2304, 4096 + 512 + 512)
    assert shapes["block3/MultiHeadAttention_0/proj/kernel"] == (4096, 2304)
    assert shapes["block1/moe/gate"] == (16, 2304, 896) and shapes["block1/moe/down"] == (16, 896, 2304)
    assert shapes["block1/moe/router"] == (2304, 64) and shapes["head/kernel"] == (2304, 24576)
    assert not [name for name in shapes if "bias" in name or "shared" in name or "mtp" in name]
    assert mellum2_12b_a2_5b.layer_kinds(CONFIG) == ["sliding_attention"] * 3 + ["full_attention"]
    source = (ROOT / "benchmarks/reference/mellum2_12b_a2_5b.py").read_text()
    assert "atomo_tpu" not in source.split('"""', 2)[2]  # named in the docstring only
    assert 'default_matmul_precision("highest")' in source
    assert "ragged" not in source and "argsort" not in source and "pallas" not in source


# ---- the entries ------------------------------------------------------------------

def test_the_cell_and_its_three_metrics_are_appended_after_what_was_there():
    names = [w["name"] for w in BENCH["workloads"]]
    assert names.index(CELL) > names.index("glm47flash-1chip-dense")
    cell = BENCH["workloads"][names.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("mellum2-12b-a2.5b", "1chip-dense-2xseq8192", 1)
    assert len(cell["why"]) <= 200 and "8192" in cell["why"]
    traffic = json.loads((ROOT / "benchmarks/traffic/1chip-dense-2xseq8192.json").read_text())
    assert (traffic["flags"]["--batch-size"], traffic["flags"]["--seq-len"]) == (2, 8192)
    metrics = [m["name"] for m in BENCH["per_layer"]]
    new = ["attention_ms", "attention_roofline_pct", "attn_tile_score_mib"]
    assert [metrics.index(n) for n in new] == sorted(metrics.index(n) for n in new)
    assert metrics.index(new[0]) > metrics.index("moe_rows_max_over_mean")
    for name in new:
        metric = BENCH["per_layer"][metrics.index(name)]
        file = json.loads((ROOT / f"benchmarks/metrics/{name}.json").read_text())
        assert metric["workloads"] == [CELL] and metric["layer"] == "attention core" and metric["moves"] == "step_ms"
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert metric[key] == file[key], (name, key)
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert [by_name[n]["source"] for n in new] == ["device_trace", "device_trace", "program_counter"]
    assert [by_name[n]["better"] for n in new] == ["lower", "higher", "lower"]
    file = json.loads((ROOT / "benchmarks/metrics/attn_tile_score_mib.json").read_text())
    assert (file["reducer"], file["args"]) == ("counter_mib", {"counter": "attn_tile_score_bytes"})
    assert "workloads" not in by_name["step_mfu_pct"]  # it applies to the new cell as to every other


# ---- the attention reducer on a recorded trace ------------------------------------------

def _ctx(trace=None, counters=None, config=CONFIG, flags=FLAGS, **more):
    stamps = [(50.0 + 0.5 * i, 10 + i, 9.0) for i in range(21)]
    return {"trace": trace, "config": config, "stamps": stamps, "window": (0, 20), "slice": (8, 12),
            "flags": flags, "peaks": PEAKS, "counters": counters or {}, **more}


@pytest.fixture(scope="module")
def recorded():
    return json.loads(FIXTURE.read_text())


def test_attention_ms_on_the_recorded_trace_is_the_scopes_time(recorded):
    """The fixture keeps, beside the operations, the time `report timeline`
    gave the `attention` scope on the chip: the rule that reads the kernels'
    names and `delta`'s shape has to find it to within 2% (it reads the same
    93.287 ms: the three kernels 91.85, `delta` 1.43)."""
    from benchmarks.reducers import attention

    got = attention.reduce(_ctx(recorded["trace"]), "ms")
    assert got == pytest.approx(recorded["scoped_attention_ms_per_step"], rel=0.02)
    assert got == pytest.approx(recorded["rule_ms_per_step"], rel=1e-9)


def test_attention_roofline_is_the_least_time_over_the_measured_and_under_100(recorded):
    from benchmarks.flops import window_moe_lm as flops
    from benchmarks.reducers import attention

    ctx = _ctx(recorded["trace"])
    ms = attention.reduce(ctx, "ms")
    work, moved = flops.attention_work(CONFIG, FLAGS)
    least_ms = 1e3 * max(work / 197e12, moved / 819e9)
    assert least_ms == pytest.approx(33.27, rel=1e-3)
    got = attention.reduce(ctx, "roofline_pct")
    assert got == pytest.approx(100 * least_ms / ms, rel=1e-9) and 10 < got < 100
    assert attention.reduce({**ctx, "peaks": None}, "roofline_pct") is None
    with pytest.raises(ValueError, match="unknown attention reduction"):
        attention.reduce(ctx, "nope")


def test_a_program_whose_core_is_not_the_kernels_reads_nothing(recorded):
    """The parent on an accepted cell, a trace without the kernels' names, a
    configuration whose flops file states no `attention_work`, no trace."""
    from benchmarks.reducers import attention

    assert attention.reduce(_ctx(None), "ms") is None
    other = json.loads((ROOT / "tests/benchmark/fixtures/tpu_v5e_tiny_trace.json").read_text())
    assert attention.reduce(_ctx(other.get("trace", other)), "ms") is None
    glm = json.loads((ROOT / "benchmarks/configs/glm-4.7-flash.json").read_text())
    flags = {"--batch-size": "2", "--seq-len": "4096", "--bf16": True}
    moe_trace = json.loads((ROOT / "tests/benchmark/fixtures/tpu_v5e_moe_trace.json").read_text())["trace"]
    assert attention.reduce(_ctx(moe_trace, config=glm, flags=flags), "roofline_pct") is None  # no attention_work there
    without = {"devices": {name: {**dev, "ops": [op for op in dev["ops"] if "fused_attention" not in op[0]]}
                           for name, dev in recorded["trace"]["devices"].items()}, "host": []}
    assert attention.reduce(_ctx(without), "ms") is None  # `delta`'s shape alone is no core


@pytest.mark.parametrize("line,mine", [
    ("%fused_attention_fwd.3 = (bf16[2,32,8192,128]{3,2,1,0}, f32[2,32,1,8192]{3,2,1,0}) custom-call(", True),
    ("%fused_attention_dkv.1 = (bf16[2,4,8192,128]{3,2,1,0}, bf16[2,4,8192,128]{3,2,1,0}) custom-call(", True),
    ("%fused_attention_dq.2 = bf16[2,32,8192,128]{3,2,1,0} custom-call(", True),
    ("%multiply_reduce_fusion.7 = f32[2,32,8192]{2,1,0:T(8,128)S(1)} fusion(", True),  # delta = rowsum(dO x O)
    ("%broadcast_in_dim.9 = f32[2,32,1,8192]{3,2,1,0:T(1,128)S(1)} reshape(", True),  # and its shape as the kernels read it
    ("%fusion.21 = f32[2,4,8192]{2,1,0} fusion(", False),  # another head count
    ("%fusion.12 = bf16[2,32,8192,128]{3,2,1,0} fusion(", False),  # the heads' layout, the rotation: outside the scope
    ("%fusion.13 = f32[2,8192,24576]{2,1,0} fusion(", False),
    ("%copy-start.4 = (f32[2,32,1,8192]{3,2,1,0}, u32[]) copy-start(", False),
    ("%ragged-dot-none.5 = bf16[131072,896]{1,0} custom-call(", False),
])
def test_the_rule_takes_the_kernels_names_and_deltas_shape_and_no_others(line, mine):
    from benchmarks.reducers import attention

    assert attention.is_core(line, attention.sizes_of(_ctx())) is mine


# ---- the limits ---------------------------------------------------------------------

@pytest.mark.parametrize("number,sound,fault", [
    ("loss_gap", "1.9e-5", "1.15e-3"),  # against half of the batch left out: the norms hold the float8 control
    ("grad1_gap", "0.0175", "0.314"),  # against the float8 control
    ("change_gap", "0.0126", "0.321"),  # against half of the batch left out, the lower of its two upper readings
])
def test_each_limit_lies_between_its_two_readings_with_room_on_both_sides(number, sound, fault):
    """The sound runs' largest over 19 seeds on the chip and the smallest
    reading of what the number is held against (PERF.md section 2): no limit
    nearer than 2.5 times to either."""
    limits = json.loads((ROOT / f"benchmarks/limits/{CELL}.json").read_text())
    assert 2.5 * float(sound) <= limits["limits"][number] <= float(fault) / 2.5
    assert sound in limits["set_from"][number] and fault in limits["set_from"][number]
    assert set(limits["limits"]) == {"loss_gap", "grad1_gap", "change_gap"}
