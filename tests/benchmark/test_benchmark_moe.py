"""What PR 33 brings to the benchmark for `glm-4.7-flash`: the FLOPs and bytes
of a latent-attention decoder with routed experts counted by hand, the two
reducers of the expert layer (the trace's on a recorded trace, the counters'
on the step's own counts), and the configuration's file against the published
widths and the catalog's keys."""

import json
import math
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CONFIG = json.loads((ROOT / "benchmarks/configs/glm-4.7-flash.json").read_text())
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
FIXTURE = ROOT / "tests/benchmark/fixtures/tpu_v5e_moe_trace.json"
CELL = "glm47flash-1chip-dense"
FLAGS = {"--batch-size": "2", "--seq-len": "4096", "--bf16": True}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

SMALL = {"hidden_size": 8, "intermediate_size": 20, "moe_intermediate_size": 6, "num_attention_heads": 2,
         "q_lora_rank": 5, "kv_lora_rank": 4, "qk_nope_head_dim": 3, "qk_rope_head_dim": 2, "v_head_dim": 4,
         "num_hidden_layers": 3, "first_k_dense_replace": 1, "num_nextn_predict_layers": 1,
         "n_routed_experts": 2, "routed_experts_total": 8, "num_experts_per_tok": 4, "n_shared_experts": 1,
         "vocab_size": 32}


# ---- FLOPs and bytes from shapes ------------------------------------------------

def _by_hand(seq, mtp_seq):
    d, h, f, fe, vocab = 8, 2, 20, 6, 32
    mla = lambda s: (2 * s * (d * 5 + 5 * h * (3 + 2) + d * (4 + 2) + 4 * h * (3 + 4) + h * 4 * d)  # noqa: E731
                     + 2 * h * (s * (s + 1) // 2) * ((3 + 2) + 4))  # scores over 5, values over 4
    experts = lambda t: 2 * t * d * 8 + 2 * t * 3 * d * fe + 2 * (t * 4 * 2 / 8) * 3 * d * fe  # noqa: E731
    main = 3 * mla(seq) + 2 * seq * 3 * d * f + 2 * experts(seq) + 2 * (seq - 1) * d * vocab
    module = 2 * mtp_seq * 2 * d * d + mla(mtp_seq) + experts(mtp_seq) + 2 * (seq - 2) * d * vocab
    return main + module


def test_moe_lm_flops_of_a_small_model_against_a_hand_count():
    from benchmarks.flops import moe_lm

    assert moe_lm.forward_flops(SMALL, 1, 16) == pytest.approx(_by_hand(16, 15), rel=1e-12)
    flags = {"--batch-size": "3", "--seq-len": "16"}
    assert moe_lm.train_flops_per_step(SMALL, flags) == pytest.approx(3 * 3 * _by_hand(16, 15), rel=1e-12)
    assert moe_lm.expected_rows(SMALL, 16) == 16 * 4 * 2 / 8 and moe_lm.expert_layers(SMALL) == 3


def test_the_cell_needs_2_87_gigaflops_a_token_and_counts_the_head_twice():
    from benchmarks.flops import moe_lm

    flops = moe_lm.train_flops_per_step(CONFIG, FLAGS)
    assert flops / 8192 == pytest.approx(2.870e9, rel=1e-3)
    without = moe_lm.train_flops_per_step({**CONFIG, "num_nextn_predict_layers": 0}, FLAGS)
    head = 3 * 2 * 2 * 4094 * 2048 * 19360
    module = flops - without
    assert module > head and module - head < 0.25 * flops  # the second head, the projection and one more block
    assert moe_lm.expected_rows(CONFIG, 8192) == 4096 and moe_lm.expert_layers(CONFIG) == 5


def test_expert_work_is_three_passes_over_the_steps_own_rows_and_what_they_must_move():
    from benchmarks.flops import moe_lm

    flops, moved = moe_lm.expert_work(CONFIG, FLAGS, 20480)
    assert flops == 3 * 2 * 20480 * 3 * 2048 * 1536
    weights = 5 * 8 * 3 * 2048 * 1536 * 2  # five expert layers' held experts in bfloat16
    assert moved == 4 * weights + 4 * 20480 * 2048 * 2  # read three times and their gradients written; rows in, out and their cotangents
    assert flops / 197e12 > moved / 819e9  # at 512 rows an expert the products are bound by the MXU, barely
    half, moved_half = moe_lm.expert_work(CONFIG, FLAGS, 10240)
    assert half == flops / 2 and half / 197e12 < moved_half / 819e9  # at 256 rows by the weights' bytes
    assert moe_lm.row_bytes(CONFIG, FLAGS) == 4096 and moe_lm.row_bytes(CONFIG, {}) == 8192


# ---- the configuration's file ----------------------------------------------------

def test_configuration_keeps_every_key_of_the_published_config_but_the_three_it_cuts():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    published = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 10240,
        "max_position_embeddings": 202752, "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
        "topk_method": "noaux_tc", "norm_topk_prob": True, "num_attention_heads": 20, "n_group": 1,
        "topk_group": 1, "n_routed_experts": 64, "n_shared_experts": 1, "routed_scaling_factor": 1.8,
        "num_experts_per_tok": 4, "first_k_dense_replace": 1, "num_hidden_layers": 47,
        "num_key_value_heads": 20, "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 1000000, "tie_word_embeddings": False,
        "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
        "v_head_dim": 256, "vocab_size": 154880,
    }
    if catalog.is_file():  # the catalog's row, where the guide is installed
        rows = [json.loads(line) for line in catalog.read_text().splitlines() if line.strip()]
        row = next(r for r in rows if r["name"] == "GLM-4.7-Flash")
        assert row["config"] == published and row["source_url"] == CONFIG["source"]
    cut = {"num_hidden_layers": 5, "n_routed_experts": 8, "vocab_size": 154880 // 8}
    assert CONFIG["reduced"] == list(cut)
    assert {k: CONFIG[k] for k in published} == {**published, **cut}
    assert CONFIG["published"] == {k: published[k] for k in cut}
    assert (CONFIG["routed_experts_total"], CONFIG["first_expert_held"]) == (64, 0)
    assert "shared by 8 chips" in CONFIG["deployment"] and "706.1 M" in CONFIG["deployment"]
    for key in ("n_routed_experts", "rotary_pairing", "mtp_concat_order", "mtp_loss_weight", "route_bias",
                "init", "optimizer", "seq_len", "data", "remat"):
        assert len(CONFIG["assumed"][key]) > 40, key
    entry = next(c for c in BENCH["configs"] if c["name"] == "glm-4.7-flash")
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == CONFIG["reduced"]


def test_reference_describes_706_million_parameters_and_imports_nothing_of_the_program():
    from benchmarks.reference import glm_4_7_flash

    shapes = glm_4_7_flash.param_shapes(CONFIG)
    assert sum(math.prod(s) for s in shapes.values()) == 706_518_848
    assert shapes["block1/moe/gate"] == (8, 2048, 1536) and shapes["block1/moe/down"] == (8, 1536, 2048)
    assert shapes["block1/moe/router"] == (2048, 64) and shapes["head/kernel"] == (2048, 19360)
    assert shapes["block0/gate/kernel"] == (2048, 10240) and "block0/moe/router" not in shapes
    source = (ROOT / "benchmarks/reference/glm_4_7_flash.py").read_text()
    assert "atomo_tpu" not in source.split('"""', 2)[2]  # named in the docstring only
    assert 'default_matmul_precision("highest")' in source
    assert "ragged" not in source and "argsort" not in source  # no grouped product, no sort


def test_the_cell_and_its_four_metrics_are_appended_entries():
    cell = BENCH["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "glm-4.7-flash", "1chip-dense-2xseq4096", 1)
    traffic = json.loads((ROOT / "benchmarks/traffic/1chip-dense-2xseq4096.json").read_text())
    assert (traffic["flags"]["--batch-size"], traffic["flags"]["--seq-len"]) == (2, 4096)
    new = BENCH["per_layer"][-4:]
    assert [m["name"] for m in new] == ["moe_ms", "moe_roofline_pct", "moe_held_rows", "moe_rows_max_over_mean"]
    for metric in new:
        assert metric["workloads"] == [CELL] and metric["layer"] == "expert layer" and metric["moves"] == "step_ms"
    assert [m["source"] for m in new] == ["device_trace"] * 2 + ["program_counter"] * 2
    # rows are work a step may not lose: fewer of them never reads as better
    assert [m["better"] for m in new] == ["lower", "higher", "higher", "lower"]
    mfu = next(m for m in BENCH["per_layer"] if m["name"] == "step_mfu_pct")
    assert "workloads" not in mfu  # it applies to the new cell as to every other


@pytest.mark.parametrize("number,sound,fault", [
    ("loss_gap", "8.4e-5", "8.5e-4"),  # against half of the batch left out: float8 does not move the loss
    ("grad1_gap", "0.0076", "0.317"),  # against the float8 control
    ("change_gap", "0.0072", "0.474"),
])
def test_each_limit_lies_between_its_two_readings_with_room_on_both_sides(number, sound, fault):
    """The sound runs' largest over 20 seeds and the smallest reading of what
    the number is held against (PERF.md section 2): no limit above its own
    upper reading, as `loss_gap` at the Olmo cell's 2e-3 was."""
    limits = json.loads((ROOT / f"benchmarks/limits/{CELL}.json").read_text())
    assert 2.5 * float(sound) <= limits["limits"][number] <= float(fault) / 2.5
    assert sound in limits["set_from"][number] and fault in limits["set_from"][number]


# ---- the counters' reducer -----------------------------------------------------------

def _ctx(trace=None, counters=None, config=CONFIG, **more):
    stamps = [(50.0 + 0.4 * i, 10 + i, 9.0) for i in range(21)]
    return {"trace": trace, "config": config, "stamps": stamps, "window": (0, 20), "slice": (8, 12),
            "flags": FLAGS, "peaks": PEAKS, "counters": counters or {}, **more}


@pytest.mark.parametrize("counters,rows,ratio", [
    ({"moe_held_row_bytes": 20480 * 4096.0, "moe_max_expert_row_bytes": 768 * 4096.0}, 20480, 1.5),
    ({"moe_held_row_bytes": 16384 * 4096.0, "moe_max_expert_row_bytes": 4096 * 4096.0}, 16384, 10.0),
    ({"moe_held_row_bytes": 20480 * 4096.0}, 20480, None),
    ({"attn_score_bytes": 1.0}, None, None),
    ({}, None, None),
])
def test_moe_counters_read_rows_from_the_steps_byte_counts_or_nothing(counters, rows, ratio):
    """20,480 rows over 5 layers of 8 experts are 512 a piece: 768 in the
    fullest is 1.5 times the mean. A step that counts nothing (another model,
    the parent's program) reads nothing and does not raise."""
    from benchmarks.reducers import moe_counters

    ctx = _ctx(counters=counters)
    assert moe_counters.reduce(ctx, "held_rows") == rows
    got = moe_counters.reduce(ctx, "max_over_mean")
    assert got == (pytest.approx(ratio) if ratio else None)
    if rows:
        with pytest.raises(ValueError):
            moe_counters.reduce(ctx, "nope")


# ---- the trace's reducer on a recorded trace -------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return json.loads(FIXTURE.read_text())


def test_moe_ms_on_the_recorded_trace_is_the_scopes_time(recorded):
    """The fixture keeps, beside each operation, the scope `report timeline`
    put it in on the chip: the rule that reads names and shapes has to find
    the routed experts' time to within 2% of the scopes' (it reads 1.2% over:
    a layout copy of the cast matrices that the scopes give to none)."""
    from benchmarks.reducers import moe

    got = moe.reduce(_ctx(recorded["trace"]), "ms")
    assert got == pytest.approx(recorded["scoped_moe_ms_per_step"], rel=0.02)
    assert got == pytest.approx(recorded["rule_ms_per_step"], rel=1e-9)


def test_moe_roofline_is_the_least_time_at_the_steps_rows_over_the_measured_and_under_100(recorded):
    from benchmarks.flops import moe_lm
    from benchmarks.reducers import moe

    counters = {"moe_held_row_bytes": recorded["held_rows"] * 4096.0}
    ctx = _ctx(recorded["trace"], counters)
    ms = moe.reduce(ctx, "ms")
    flops, moved = moe_lm.expert_work(CONFIG, FLAGS, recorded["held_rows"])
    least_ms = 1e3 * max(flops / 197e12, moved / 819e9)
    got = moe.reduce(ctx, "roofline_pct")
    assert got == pytest.approx(100 * least_ms / ms) and 0 < got < 100
    assert moe.reduce({**ctx, "peaks": None}, "roofline_pct") is None
    assert moe.reduce(_ctx(recorded["trace"]), "roofline_pct") is None  # no row count, no work to state


def test_a_program_without_the_expert_layer_reads_nothing(recorded):
    from benchmarks.reducers import moe

    other = json.loads((ROOT / "tests/benchmark/fixtures/tpu_v5e_tiny_trace.json").read_text())
    gpt2 = json.loads((ROOT / "benchmarks/configs/gpt2-medium.json").read_text())
    assert moe.reduce(_ctx(other, config=gpt2), "ms") is None
    assert moe.reduce(_ctx(other), "ms") is None  # no grouped product, no sort and no such shape in GPT-2's step
    hybrid = json.loads((ROOT / "tests/benchmark/fixtures/tpu_v5e_hybrid_trace.json").read_text())
    assert moe.reduce(_ctx(hybrid["trace"]), "ms") is None  # nor in the hybrid's
    assert moe.reduce(_ctx(None), "ms") is None
    with pytest.raises(ValueError):
        moe.reduce(_ctx(recorded["trace"]), "nope")


@pytest.mark.parametrize("line,mine", [
    ("%ragged-dot-none.3 = bf16[32768,1536]{1,0:T(8,128)(2,1)} custom-call(", True),
    ("%sort.12 = (s32[32768]{0:T(1024)}, s32[32768]{0:T(1024)}) sort(", True),
    ("%fusion.77 = bf16[32768,2048]{1,0:T(8,128)(2,1)} fusion(", True),
    ("%fusion.78 = f32[8192,4,2048]{2,1,0:T(4,128)} fusion(", True),
    ("%fusion.79 = f32[8192,64]{1,0:T(8,128)} fusion(", True),
    ("%select_reduce_fusion.2 = bf16[8192,2048]{1,0:T(8,128)(2,1)} fusion(", True),  # the rows brought back, tokens as rows
    ("%copy.31 = bf16[8,1536,2048]{2,1,0:T(8,128)(2,1)} copy(", True),  # the grouped product's layout
    ("%sort.3 = (s32[16384]{0:T(1024)}, s32[16384]{0:T(1024)S(1)}) sort(", False),  # the embedding gradient's
    ("%fusion.82 = f32[8192,2048]{1,0:T(8,128)} fusion(", False),
    ("%fusion.83 = bf16[2,4096,2048]{2,1,0:T(8,128)(2,1)} fusion(", False),  # any other layer's activations
    ("%fusion.80 = bf16[8192,1536]{1,0:T(8,128)(2,1)} fusion(", False),  # the shared expert, scope ffn
    ("%fusion.81 = bf16[2,4096,64]{2,1,0:T(8,128)(2,1)} fusion(", False),  # the rotated keys, scope mla
    ("%convert.5 = bf16[8,2048,1536]{2,1,0:T(8,128)(2,1)} convert(", False),  # the entry cast of the master weights
    ("%copy-start.4 = (bf16[32768,2048]{1,0}, bf16[32768,2048]{1,0}, u32[]) copy-start(", False),
])
def test_the_rule_takes_the_layers_own_names_and_shapes_and_no_others(line, mine):
    from benchmarks.reducers import moe

    sizes = moe.sizes_of(_ctx())
    assert sizes == {"tokens": 8192, "per_token": 4, "rows": 32768, "outputs": 64, "width": 2048,
                     "experts": {(8, 2048, 1536), (8, 1536, 2048)}}
    assert moe.is_moe(line, sizes) is mine
