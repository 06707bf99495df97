"""What PR 28 brings to the benchmark for `olmo-hybrid-7b`: the FLOPs and bytes
of a hybrid decoder counted by hand, the two reducers of the linear-attention
layer on a recorded trace, and the configuration's file against the published
widths."""

import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CONFIG = json.loads((ROOT / "benchmarks/configs/olmo-hybrid-7b.json").read_text())
FIXTURE = ROOT / "tests/benchmark/fixtures/tpu_v5e_hybrid_trace.json"


# ---- FLOPs and bytes from shapes ------------------------------------------------

ONE_PERIOD = {"hidden_size": 8, "intermediate_size": 12, "num_attention_heads": 2, "vocab_size": 32,
              "num_hidden_layers": 4, "linear_key_head_dim": 3, "linear_value_head_dim": 5,
              "linear_conv_kernel_dim": 4,
              "layer_types": ["linear_attention"] * 3 + ["full_attention"] + ["linear_attention"] * 4}


def test_hybrid_flops_of_a_one_period_model_against_a_hand_count():
    from benchmarks.flops import hybrid_lm

    seq, d, f, h, dk, dv = 128, 8, 12, 2, 3, 5
    ffn = 2 * seq * (3 * d * f)  # gate, up, down
    full = 2 * seq * (d * 3 * d + d * d)  # qkv and proj
    pairs = seq * (seq + 1) // 2  # a causal query sees itself and what came before
    full += 2 * 2 * pairs * d  # scores and values over all heads: 2 matmuls of width d
    projections = 2 * seq * d * (2 * h * dk + 3 * h * dv + 2 * h)  # q k, v z o, a b
    conv = 2 * 4 * seq * h * (2 * dk + dv)
    c = 64  # per chunk and head: K K^T, Q K^T, T (beta gamma K): 3 of c x c x dk; T (beta V), (Q K^T) U: 2 of c x c x dv
    chunk = 2 * c * c * (3 * dk + 2 * dv) + 2 * 3 * c * dk * dv  # and W H, (gamma Q) H, K^T U with the state
    delta = h * (seq // c) * chunk + conv
    head = 2 * (seq - 1) * d * 32
    want = 4 * ffn + full + 3 * (projections + delta) + head
    assert hybrid_lm.delta_rule_forward_flops(ONE_PERIOD, 1, seq) == delta
    assert hybrid_lm.forward_flops(ONE_PERIOD, 1, seq) == want
    flags = {"--batch-size": "3", "--seq-len": "128"}
    assert hybrid_lm.train_flops_per_step(ONE_PERIOD, flags) == 3 * 3 * want


def test_linear_attention_work_counts_three_passes_of_flops_and_what_the_core_must_move():
    from benchmarks.flops import hybrid_lm

    flags = {"--batch-size": "2", "--seq-len": "128"}
    flops, moved = hybrid_lm.linear_attention_work(ONE_PERIOD, flags)
    assert flops == 3 * 3 * hybrid_lm.delta_rule_forward_flops(ONE_PERIOD, 2, 128)
    inputs = 2 * 128 * 2 * (2 * (3 + 3 + 5 + 5) + 4 * 2)  # q, k, v, z in bfloat16, two float32 logits, per head
    output = 2 * 128 * 2 * 2 * 5
    assert moved == 3 * (3 * inputs + 2 * output)  # read, read again with dO, their gradients written


def test_the_cell_needs_about_six_flops_per_matrix_parameter_and_token():
    from benchmarks.flops import hybrid_lm

    flops = hybrid_lm.train_flops_per_step(CONFIG, {"--batch-size": "1", "--seq-len": "4096"})
    d, f, v = 3840, 11008, 12544
    linear = d * (2 * 2880 + 3 * 5760 + 60)
    matrices = 4 * 3 * d * f + 4 * d * d + 3 * linear + d * v
    assert 6 * matrices * 4096 < flops < 1.05 * 6 * matrices * 4096
    core, moved = hybrid_lm.linear_attention_work(CONFIG, {"--batch-size": "1", "--seq-len": "4096"})
    assert core < 0.02 * flops  # the chunks are a hundredth of the step's arithmetic
    assert moved / 819e9 > core / 197e12  # and by this count bound by memory, not by the MXU


# ---- the configuration's file ----------------------------------------------------

def test_configuration_keeps_every_published_width_and_states_its_cut():
    published = {"hidden_size": 3840, "intermediate_size": 11008, "num_attention_heads": 30,
                 "num_key_value_heads": 30, "linear_num_key_heads": 30, "linear_num_value_heads": 30,
                 "linear_key_head_dim": 96, "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
                 "max_position_embeddings": 65536, "rms_norm_eps": 1e-6, "linear_allow_neg_eigval": True,
                 "rope_parameters": {"rope_theta": None}, "tie_word_embeddings": False}
    assert {k: CONFIG[k] for k in published} == published
    assert CONFIG["layer_types"] == (["linear_attention"] * 3 + ["full_attention"]) * 8
    assert CONFIG["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert (CONFIG["num_hidden_layers"], CONFIG["vocab_size"]) == (4, 100352 // 8)
    assert CONFIG["published"] == {"num_hidden_layers": 32, "vocab_size": 100352}
    assert CONFIG["layer_pattern"] == "linear,linear,linear,full" and "8 pipeline stages" in CONFIG["deployment"]
    for key in ("norm_placement", "qk_norm", "no_rotary", "output_gate", "init", "optimizer", "data"):
        assert len(CONFIG["assumed"][key]) > 40, key


def test_reference_describes_929_million_parameters_and_imports_nothing_of_the_program():
    import math

    from benchmarks.reference import olmo_hybrid_7b

    shapes = olmo_hybrid_7b.param_shapes(CONFIG)
    assert sum(math.prod(s) for s in shapes.values()) == 928_862_196
    assert {len(s) for s in shapes.values()} == {1, 2, 3}
    source = (ROOT / "benchmarks/reference/olmo_hybrid_7b.py").read_text()
    assert "atomo_tpu" not in source.split('"""', 2)[2]  # named in the docstring only
    assert 'default_matmul_precision("highest")' in source and "lax.scan(token" in source


# ---- the two reducers on a recorded trace -------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return json.loads(FIXTURE.read_text())


def _ctx(trace, config=CONFIG, **more):
    stamps = [(50.0 + 0.3 * i, 10 + i, 9.0) for i in range(21)]
    return {"trace": trace, "config": config, "stamps": stamps, "window": (0, 20), "slice": (8, 12),
            "flags": {"--batch-size": "1", "--seq-len": "4096"},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, **more}


def test_linear_attn_ms_on_the_recorded_trace_is_the_scopes_time(recorded):
    """The fixture keeps, beside each operation, the scope `report timeline`
    put it in on the chip: the rule that reads shapes and loops has to find
    the core's time to within 5% of the scopes'."""
    from benchmarks.reducers import linear_attention

    got = linear_attention.reduce(_ctx(recorded["trace"]), "ms")
    assert got == pytest.approx(recorded["scoped_core_ms_per_step"], rel=0.05)
    assert got == pytest.approx(recorded["rule_ms_per_step"], rel=1e-9)


def test_linear_attn_roofline_is_the_least_time_over_the_measured_and_under_100(recorded):
    from benchmarks.flops import hybrid_lm
    from benchmarks.reducers import linear_attention

    ctx = _ctx(recorded["trace"])
    ms = linear_attention.reduce(ctx, "ms")
    flops, moved = hybrid_lm.linear_attention_work(CONFIG, ctx["flags"])
    least_ms = 1e3 * max(flops / 197e12, moved / 819e9)
    got = linear_attention.reduce(ctx, "roofline_pct")
    assert got == pytest.approx(100 * least_ms / ms) and 0 < got < 100
    assert linear_attention.reduce({**ctx, "peaks": None}, "roofline_pct") is None


def test_a_program_without_linear_layers_reads_nothing():
    from benchmarks.reducers import linear_attention

    other = json.loads((ROOT / "tests/benchmark/fixtures/tpu_v5e_tiny_trace.json").read_text())
    gpt2 = json.loads((ROOT / "benchmarks/configs/gpt2-medium.json").read_text())
    assert linear_attention.reduce(_ctx(other, config=gpt2), "ms") is None
    assert linear_attention.reduce(_ctx(other), "ms") is None  # no loop and no such shape in GPT-2's step
    assert linear_attention.reduce(_ctx(None), "ms") is None
    with pytest.raises(ValueError):
        linear_attention.reduce(_ctx(json.loads(FIXTURE.read_text())["trace"]), "nope")
