"""The hybrid block of `lm` (PR 28): the chunked gated delta rule against the
token-by-token recurrence of benchmarks/reference/olmo_hybrid_7b.py, the whole
model against that reference at the configuration's tiny sizes, GPT-2's block
unchanged where no new flag is given, the layouts that refuse the new block,
the codecs on the new tree, and the scopes and the counter."""

import json
import re
import sys
from pathlib import Path
from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.reference import olmo_hybrid_7b as reference  # noqa: E402
from benchmarks.run import leaf_name, tiny  # noqa: E402

HYBRID = ["--block", "olmo", "--layer-pattern", "linear,linear,linear,full", "--vocab-size", "64",
          "--seq-len", "128", "--width", "48", "--depth", "4", "--num-heads", "3", "--ffn-width", "80",
          "--linear-key-dim", "8", "--linear-value-dim", "16"]


def tiny_config():
    cfg = json.loads((ROOT / "benchmarks/configs/olmo-hybrid-7b.json").read_text())
    return tiny(cfg, {"flags": {}})[0]


# ---- the chunked rule against the recurrence ----------------------------------

def mixer_inputs(seed, s, decay, b=2, h=2, dk=8, dv=16):
    """q, k (unit), v, log alpha and beta in (0, 2). `decay` picks alpha:
    "mixed" as the reference initialises it, "near0" about exp(-12) a token,
    "near1" above 0.9999."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, s, h, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (b, s, h, dk)))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    lo, hi = {"mixed": (-1.6, -1e-3), "near0": (-14.0, -10.0), "near1": (-1e-4, -1e-6)}[decay]
    g = jax.random.uniform(ks[3], (b, s, h), minval=lo, maxval=hi)
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (b, s, h)))
    return q, k, v, g, beta


@pytest.mark.parametrize("decay", ["mixed", "near0", "near1"])
@pytest.mark.parametrize("s", [64, 128, 256])
def test_chunked_rule_equals_the_recurrence(s, decay):
    from atomo_tpu.models.linear_attention import chunked_gated_delta_rule

    q, k, v, g, beta = mixer_inputs(s, s, decay)
    assert float(beta.max()) > 1.5  # negative eigenvalues of the transition are in play
    got, state_bytes = chunked_gated_delta_rule(q, k, v, g, beta)
    want = reference.delta_rule_recurrent(q, k, v, jnp.exp(g), beta)
    # float32 both ways; the chunked form sums in another order and divides by
    # products of decays, so the gap is a few float32 roundings of the largest output
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.abs(want).max()), rtol=1e-4)
    assert state_bytes == (s // 64) * 2 * 2 * 8 * 16 * 4


def test_chunked_rule_holds_where_every_key_is_the_same_and_beta_is_2():
    """The transition's eigenvalue is -1 there; a power series of the chunk's
    triangular matrix would overflow float32, block substitution does not."""
    from atomo_tpu.models.linear_attention import chunked_gated_delta_rule

    q, k, v, g, _ = mixer_inputs(3, 128, "near1")
    k = jnp.broadcast_to(k[:, :1], k.shape)
    beta = jnp.full(g.shape, 1.99)
    got, _ = chunked_gated_delta_rule(q, k, v, g, beta)
    want = reference.delta_rule_recurrent(q, k, v, jnp.exp(g), beta)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=1e-3 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("wrt", ["q", "k", "v", "g", "beta"])
def test_gradient_of_the_chunked_rule_equals_the_recurrences(wrt):
    from atomo_tpu.models.linear_attention import chunked_gated_delta_rule

    inputs = dict(zip(("q", "k", "v", "g", "beta"), mixer_inputs(11, 128, "mixed")))
    probe = jax.random.normal(jax.random.PRNGKey(12), (2, 128, 2, 16))

    def through(rule):
        def scalar(x):
            args = {**inputs, wrt: x}
            return jnp.sum(rule(args["q"], args["k"], args["v"], args["g"], args["beta"]) * probe)
        return jax.grad(scalar)(inputs[wrt])

    got = through(lambda q, k, v, g, beta: chunked_gated_delta_rule(q, k, v, g, beta)[0])
    want = through(lambda q, k, v, g, beta: reference.delta_rule_recurrent(q, k, v, jnp.exp(g), beta))
    np.testing.assert_allclose(got, want, atol=5e-5 * float(jnp.abs(want).max()), rtol=1e-3)


def test_unit_lower_inverse_and_its_gradient():
    from atomo_tpu.models.linear_attention import unit_lower_inverse

    b = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 16, 16)), -1)
    want = jnp.linalg.inv(jnp.eye(16) + b)
    np.testing.assert_allclose(unit_lower_inverse(b), want, atol=1e-4 * float(jnp.abs(want).max()))
    probe = jax.random.normal(jax.random.PRNGKey(1), b.shape)
    got = jax.grad(lambda x: jnp.sum(unit_lower_inverse(jnp.tril(x, -1)) * probe))(b)
    ref = jax.grad(lambda x: jnp.sum(jnp.linalg.inv(jnp.eye(16) + jnp.tril(x, -1)) * probe))(b)
    np.testing.assert_allclose(got, ref, atol=1e-3 * float(jnp.abs(ref).max()))


def test_a_sequence_that_is_no_multiple_of_the_chunk_is_refused():
    from atomo_tpu.cli import main
    from atomo_tpu.models.linear_attention import chunked_gated_delta_rule

    q, k, v, g, beta = mixer_inputs(0, 96, "mixed")
    with pytest.raises(ValueError, match="whole chunks of 64"):
        chunked_gated_delta_rule(q, k, v, g, beta)
    argv = ["lm", "--layout", "dp", "--n-devices", "1", "--code", "sgd", *HYBRID]
    argv[argv.index("--seq-len") + 1] = "96"
    with pytest.raises(SystemExit, match="--seq-len 96 is no multiple of 64"):
        main(argv)


# ---- the whole model against the plain reference -------------------------------

def hybrid_model(cfg, **more):
    from atomo_tpu.models.transformer import BLOCK_RECIPES, TransformerLM

    return TransformerLM(
        vocab_size=cfg["vocab_size"], max_len=cfg["seq_len"], width=cfg["hidden_size"],
        depth=cfg["num_hidden_layers"], num_heads=cfg["num_attention_heads"],
        ffn_width=cfg["intermediate_size"], layer_pattern=tuple(cfg["layer_pattern"].split(",")),
        linear_key_dim=cfg["linear_key_head_dim"], linear_value_dim=cfg["linear_value_head_dim"],
        linear_conv_width=cfg["linear_conv_kernel_dim"], **BLOCK_RECIPES["olmo"], **more,
    )


@pytest.fixture(scope="module")
def both_sides():
    """The reference's loss and gradient at the tiny sizes, and the program's
    in float32 and in bfloat16 compute, from the same seeded weights."""
    import optax

    from atomo_tpu.training.trainer import cast_params

    cfg = tiny_config()
    flat = reference.init_params(cfg, 5)
    tokens = jnp.asarray(reference.example_batches(cfg, 5, 1, 2)[0])
    model = hybrid_model(cfg, remat="dots")
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tokens))["params"]
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    assert {leaf_name(p): tuple(x.shape) for p, x in paths} == reference.param_shapes(cfg)
    params = jax.tree_util.tree_unflatten(treedef, [flat[leaf_name(p)] for p, _ in paths])

    def side(dtype):
        def loss_fn(params):
            cast = params if dtype is None else cast_params(params, dtype)
            logits = model.apply({"params": cast}, tokens, train=True).astype(jnp.float32)
            return optax.softmax_cross_entropy_with_integer_labels(logits[:, :-1], tokens[:, 1:]).mean()
        value, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
        leaves, _ = jax.tree_util.tree_flatten_with_path(grads)
        return float(value), {leaf_name(p): g for p, g in leaves}

    want = reference.loss_and_grads(flat, tokens, cfg)
    return {"reference": (float(want[0]), want[1]), "float32": side(None), "bfloat16": side(jnp.bfloat16)}


LEAVES = sorted(reference.param_shapes(tiny_config()))


@pytest.mark.parametrize("leaf", LEAVES)
def test_first_gradient_of_every_leaf_follows_the_reference_in_float32(both_sides, leaf):
    """Both sides compute in float32 on the CPU, in another order (chunks
    against tokens, a fused qkv against none): a few 1e-6 of the leaf's norm."""
    (want_loss, want), (loss, got) = both_sides["reference"], both_sides["float32"]
    assert abs(loss - want_loss) <= 2e-6 * want_loss
    gap = float(jnp.linalg.norm(got[leaf] - want[leaf]) / jnp.linalg.norm(want[leaf]))
    assert gap < 1e-4, gap


@pytest.mark.parametrize("leaf", LEAVES)
def test_first_gradient_of_every_leaf_follows_the_reference_in_bfloat16(both_sides, leaf):
    """bfloat16 has 8 bits: each rounding is 2e-3, and at 48 units of width
    nothing averages out, so single entries are off by tens of percent. Held
    is what `correct` holds on the chip, the norm of each leaf's gradient, to
    a tenth of the larger of its own and the median leaf's."""
    import statistics

    (want_loss, want), (loss, got) = both_sides["reference"], both_sides["bfloat16"]
    assert abs(loss - want_loss) <= 2e-3 * want_loss
    norms = {k: float(jnp.linalg.norm(v)) for k, v in want.items()}
    floor = max(norms[leaf], statistics.median(norms.values()))
    assert abs(float(jnp.linalg.norm(got[leaf])) - norms[leaf]) / floor < 0.1


# ---- GPT-2's block, where no new flag is given ----------------------------------

class LegacyAttention(nn.Module):
    num_heads: int
    head_dim: int
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x):
        from atomo_tpu.parallel.ring import full_attention

        b, s, _ = x.shape
        h, d = self.num_heads, self.head_dim
        q, k, v = jnp.split(nn.Dense(3 * h * d, use_bias=False, name="qkv")(x), 3, axis=-1)
        heads = lambda t: t.reshape(b, s, h, d).transpose(0, 2, 1, 3)  # noqa: E731
        fn = self.attention_fn or (lambda q, k, v: full_attention(q, k, v, causal=True))
        out = fn(heads(q), heads(k), heads(v)).transpose(0, 2, 1, 3).reshape(b, s, h * d)
        # the one line since PR 27: the step counts the exponentials it keeps (PR 30)
        self.sow("counters", "attn_score_bytes", jnp.float32(b * h * s * s * q.dtype.itemsize))
        return nn.Dense(x.shape[-1], use_bias=False, name="proj")(out)


class LegacyBlock(nn.Module):
    num_heads: int
    head_dim: int
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x):
        width = x.shape[-1]
        y = nn.LayerNorm(use_bias=False, name="ln1")(x)
        x = x + LegacyAttention(self.num_heads, self.head_dim, self.attention_fn,
                                name="MultiHeadAttention_0")(y)
        y = nn.LayerNorm(use_bias=False, name="ln2")(x)
        y = nn.gelu(nn.Dense(4 * width, use_bias=False, name="up")(y))
        return x + nn.Dense(width, use_bias=False, name="down")(y)


class LegacyTransformerLM(nn.Module):
    """models/transformer.py's TransformerLM as PR 27 left it (dropout, which
    `lm` never sets, left out): the program `lm` built before the block took
    its choices as fields."""

    vocab_size: int = 256
    max_len: int = 1024
    width: int = 256
    depth: int = 4
    num_heads: int = 4
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, tokens, train=False, pos_offset=0):
        x = nn.Embed(self.vocab_size, self.width, name="tok_emb")(tokens)
        pos = nn.Embed(self.max_len, self.width, name="pos_emb")(pos_offset + jnp.arange(tokens.shape[1]))
        x = x + pos[None, :, :]
        for i in range(self.depth):
            x = LegacyBlock(self.num_heads, self.width // self.num_heads, self.attention_fn,
                            name=f"block{i}")(x)
        x = nn.LayerNorm(use_bias=False, name="ln_f")(x)
        return nn.Dense(self.vocab_size, use_bias=False, name="head")(x)


def run_lm(monkeypatch, argv, steps=3):
    """`cli.main(argv)` with the built program looked at: the tree's shapes,
    the step's lowered text, each step's metrics, the codec it was given."""
    import atomo_tpu.parallel.model_axes as model_axes
    from atomo_tpu.cli import main

    seen = {"metrics": []}
    real = model_axes.build_model_axis_program

    def build(spec, cfg, optimizer, rng, codec=None, **kwargs):
        prog = real(spec, cfg, optimizer, rng, codec, **kwargs)
        leaves, _ = jax.tree_util.tree_flatten_with_path(prog.state.params)
        seen["shapes"] = {leaf_name(p): tuple(x.shape) for p, x in leaves}
        seen["codec"] = codec

        def step(state, key, tokens):
            if "text" not in seen:
                seen["text"] = prog.step.lower(state, key, tokens).as_text()
            state, metrics = prog.step(state, key, tokens)
            seen["metrics"].append({k: float(v) for k, v in metrics.items()})
            return state, metrics

        return prog._replace(step=step)

    with monkeypatch.context() as patch:  # undone on return: a second run wraps the real one again
        patch.setattr(model_axes, "build_model_axis_program", build)
        main(["lm", *argv, "--max-steps", str(steps), "--log-interval", "1"])
    return seen


def test_lm_without_a_new_flag_builds_the_tree_program_and_losses_it_built_before(monkeypatch):
    import atomo_tpu.models.transformer as transformer
    from benchmarks.reference import gpt2_medium

    cfg = json.loads((ROOT / "benchmarks/configs/gpt2-medium.json").read_text())
    cfg = tiny(cfg, {"flags": {}})[0]
    argv = ["--layout", "dp", "--n-devices", "1", "--code", "sgd", "--aggregate", "psum",
            "--vocab-size", str(cfg["vocab_size"]), "--seq-len", str(cfg["n_positions"]),
            "--width", str(cfg["n_embd"]), "--depth", str(cfg["n_layer"]),
            "--num-heads", str(cfg["n_head"]), "--batch-size", "2", "--bf16", "--lr", "0.01", "--seed", "3"]
    now = run_lm(monkeypatch, argv)
    monkeypatch.setattr(transformer, "TransformerLM", LegacyTransformerLM)
    before = run_lm(monkeypatch, argv)
    assert now["shapes"] == before["shapes"] == gpt2_medium.param_shapes(cfg)
    assert now["text"] == before["text"]
    assert [m["loss"] for m in now["metrics"]] == [m["loss"] for m in before["metrics"]]
    assert len(now["metrics"]) == 3 and "lin_state_bytes" not in now["metrics"][0]


@pytest.mark.parametrize("layout", ["dp-sp", "dp-tp", "dp-ep", "dp-pp", "dp-tp-sp"])
@pytest.mark.parametrize("flags,named", [(HYBRID, "--block"), (["--ffn-width", "96"], "--ffn-width"),
                                         (["--remat", "dots"], "--remat")])
def test_a_block_other_than_gpt2s_is_refused_outside_layout_dp(layout, flags, named):
    from atomo_tpu.cli import main

    with pytest.raises(SystemExit) as refused:
        main(["lm", "--layout", layout, "--n-devices", "4", "--ways", "2", "--batch-size", "8",
              "--code", "sgd", "--aggregate", "psum", *flags])
    said = str(refused.value)
    assert said.startswith(f"{named} needs --layout dp") and "\n" not in said


def test_the_sp_ring_refuses_a_linear_layer():
    from atomo_tpu.mesh.spec import MeshSpec
    from atomo_tpu.parallel.lm import make_lm_train_step
    from atomo_tpu.training import make_optimizer

    mesh = MeshSpec.from_layout("dp-sp", 2, 2).build()
    cfg = dict(vocab_size=16, max_len=128, width=16, depth=1, num_heads=2,
               layer_pattern=("linear",), linear_key_dim=8, linear_value_dim=8)
    with pytest.raises(ValueError, match="sp=2 needs every layer"):
        make_lm_train_step(cfg, make_optimizer("sgd", lr=0.1), mesh)


# ---- the codecs on the new tree ---------------------------------------------------

@pytest.mark.parametrize("code", ["svd", "qsgd"])
def test_codecs_take_three_steps_on_the_hybrid_tree(monkeypatch, code):
    """Leaves of rank 1 (A_log, dt_bias, scales), 2 and 3 (the convolutions'
    taps): the loss stays finite and falls, and the step's message is the
    codec's count from the leaves' shapes."""
    from atomo_tpu.utils.comm_model import codec_leaf_payload_bytes

    seen = run_lm(monkeypatch, ["--layout", "dp", "--n-devices", "2", "--code", code,
                                "--aggregate", "gather", "--batch-size", "4", "--lr", "0.05",
                                "--seed", "1", *HYBRID])
    losses = [m["loss"] for m in seen["metrics"]]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
    ranks = {len(shape) for shape in seen["shapes"].values()}
    assert ranks == {1, 2, 3}
    counted = sum(codec_leaf_payload_bytes(seen["codec"], shape) for shape in seen["shapes"].values())
    assert seen["metrics"][0]["msg_bytes"] == counted


# ---- scopes and the counter -----------------------------------------------------------

def test_hybrid_step_lowers_with_its_scopes_and_counts_its_state(monkeypatch):
    from atomo_tpu.obs.timeline import MODEL_PHASES, phase_of

    seen = run_lm(monkeypatch, ["--layout", "dp", "--n-devices", "1", "--code", "sgd",
                                "--aggregate", "psum", "--batch-size", "2", "--remat", "dots", *HYBRID],
                  steps=1)
    # 3 linear layers x 2 chunks x 2 rows x 3 heads x (8, 16) float32
    assert seen["metrics"][0]["lin_state_bytes"] == 3 * 2 * 2 * 3 * 8 * 16 * 4
    new = {"linear_attention", "delta_chunk", "delta_scan", "ffn"}
    assert new <= set(MODEL_PHASES)
    assert phase_of("jit(step)/forward_backward/block0/linear_attention/delta_scan/while/body/dot") == "delta_scan"
    assert phase_of("jit(step)/transpose(jvp(block0))/linear_attention/mul") == "linear_attention"


def test_hybrid_step_carries_the_scopes_into_its_lowering():
    from atomo_tpu.mesh.spec import MeshSpec
    from atomo_tpu.parallel.model_axes import build_model_axis_program
    from atomo_tpu.training import make_optimizer

    cfg = dict(vocab_size=16, max_len=64, width=16, depth=2, num_heads=2, ffn="swiglu",
               layer_pattern=("linear", "full"), linear_key_dim=8, linear_value_dim=8)
    prog = build_model_axis_program(
        MeshSpec.from_layout("dp", 1, 1), cfg, make_optimizer("sgd", lr=0.01, momentum=0.9),
        jax.random.PRNGKey(0), None, aggregate="psum",
    )
    tokens = prog.shard_tokens(jnp.zeros((2, 64), jnp.int32))
    text = prog.step.lower(prog.state, jax.random.PRNGKey(1), tokens).as_text(debug_info=True)
    scopes = set(re.findall(r'["/(]([a-z_]+)(?=[/)])', text))  # the names between two `/` of a path
    assert {"linear_attention", "delta_chunk", "delta_scan", "attention", "ffn"} <= scopes
