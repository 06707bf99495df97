"""`lm --block mellum` (PR 35): grouped-query attention with rotary positions,
a window in three of four layers and YaRN-scaled full attention in the fourth,
over softmax-routed experts, against benchmarks/reference/mellum2_12b_a2_5b.py
at the configuration's tiny sizes: every leaf's first gradient and three
losses through the step `lm` builds, the shares of the experts adding up to
the uncut layer, the softmax router's weights and gradients, the codecs on
the new tree, the flags, the layouts that refuse the block, and the scopes
and counters. The attention core's own tests are tests/test_window_gqa.py."""

import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.reference import mellum2_12b_a2_5b as reference  # noqa: E402
from benchmarks.run import leaf_name, program_argv, tiny  # noqa: E402

HI = jax.lax.Precision.HIGHEST
CONFIG = json.loads((ROOT / "benchmarks/configs/mellum2-12b-a2.5b.json").read_text())
TRAFFIC = json.loads((ROOT / "benchmarks/traffic/1chip-dense-2xseq8192.json").read_text())


def tiny_config(**more):
    return {**tiny(CONFIG, {"flags": {}})[0], **more}


def expert_sizes(cfg, **more):
    from atomo_tpu.models.moe import ExpertSizes

    given = dict(expert_width=cfg["moe_intermediate_size"], experts=cfg["routed_experts_total"],
                 experts_held=cfg["num_experts"], first_expert=cfg["first_expert_held"],
                 per_token=cfg["num_experts_per_tok"], scoring="softmax")
    return ExpertSizes(**{**given, **more})


def lm_config(cfg, **more):
    """The model's fields as `lm` builds them from the cell's flags at these sizes."""
    from atomo_tpu.cli import _lm_block_config, build_parser

    argv, _ = program_argv(cfg, {"flags": {"--layout": "dp", "--seq-len": cfg["seq_len"]}}, seed=0)
    args = build_parser().parse_args(argv)
    return dict(vocab_size=args.vocab_size, max_len=args.seq_len, width=args.width, depth=args.depth,
                num_heads=args.num_heads, **{**_lm_block_config(args), **more})


def tree_of(flat, like):
    paths, treedef = jax.tree_util.tree_flatten_with_path(like)
    return jax.tree_util.tree_unflatten(treedef, [flat[leaf_name(p)] for p, _ in paths])


def flat_of(tree):
    return {leaf_name(p): x for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def program(cfg, lr, momentum, dtype, seed=5, dp=1, codec=None, aggregate="psum"):
    """`lm`'s own program for the configuration with the reference's seeded
    weights installed, as the benchmark's adapter does."""
    from atomo_tpu.mesh.spec import MeshSpec
    from atomo_tpu.parallel.model_axes import build_model_axis_program
    from atomo_tpu.training import make_optimizer

    prog = build_model_axis_program(
        MeshSpec.from_layout("dp", dp, 1), lm_config(cfg),
        make_optimizer("sgd", lr=lr, momentum=momentum), jax.random.PRNGKey(0), codec,
        aggregate=aggregate, compute_dtype=dtype,
    )
    flat = reference.init_params(cfg, seed)
    assert {k: tuple(v.shape) for k, v in flat_of(prog.state.params).items()} == reference.param_shapes(cfg)
    copies = {k: jnp.copy(v) for k, v in flat.items()}  # the step donates its state
    return prog._replace(state=prog.state.replace(params=tree_of(copies, prog.state.params))), flat


# ---- the whole model against the plain reference -------------------------------

@pytest.fixture(scope="module")
def both_sides():
    """The reference's loss and gradient at the tiny sizes, and what one step
    of plain SGD shows of the program's (at a learning rate of 2^16, so that
    the step is far larger than the weights' own rounding), in float32 and in
    bfloat16 compute, from the same seeded weights."""
    cfg = tiny_config()
    tokens = reference.example_batches(cfg, 5, 1, 2)[0]
    out, lr = {}, 65536.0
    for name, dtype in (("float32", None), ("bfloat16", jnp.bfloat16)):
        prog, flat = program(cfg, lr, 0.0, dtype)
        with jax.default_matmul_precision("highest"):
            state, metrics = prog.step(prog.state, jax.random.PRNGKey(1), prog.shard_tokens(tokens))
        moved = flat_of(state.params)
        out[name] = float(metrics["loss"]), {k: (flat[k] - moved[k]) / lr for k in flat}
    want = reference.loss_and_grads(flat, tokens, cfg)
    out["reference"] = float(want[0]), want[1]
    return out


LEAVES = sorted(reference.param_shapes(tiny_config()))


def test_the_tree_has_the_leaves_the_issue_names_and_no_bias():
    assert len(LEAVES) == 3 + 4 * 8 and not [leaf for leaf in LEAVES if "bias" in leaf]
    shapes = reference.param_shapes(tiny_config())
    assert shapes["block3/MultiHeadAttention_0/qkv/kernel"] == (48, (4 + 2 * 2) * 16)
    assert shapes["block0/moe/router"] == (48, 16) and shapes["block0/moe/down"] == (4, 24, 48)


@pytest.mark.parametrize("leaf", LEAVES)
def test_first_gradient_of_every_leaf_follows_the_reference_in_float32(both_sides, leaf):
    """Both sides compute in float32 on the CPU, in another order (sorted rows
    and grouped products against every expert on every row; a group's heads as
    rows of one product against repeated heads; the band in query blocks
    against an (S, S) mask): a few 1e-6 of the leaf's norm."""
    (want_loss, want), (loss, got) = both_sides["reference"], both_sides["float32"]
    assert abs(loss - want_loss) <= 2e-6 * want_loss
    gap = float(jnp.linalg.norm(got[leaf] - want[leaf]) / jnp.linalg.norm(want[leaf]))
    assert gap < 1e-4, gap


@pytest.mark.parametrize("leaf", LEAVES)
def test_first_gradient_of_every_leaf_follows_the_reference_in_bfloat16(both_sides, leaf):
    """bfloat16 has 8 bits, and a token whose eighth and ninth scores lie
    within the rounding of each other goes to another expert, whose gradient
    then differs by whole rows. Held here: the loss, and the norm of every
    leaf's gradient to a third; the benchmark holds the real sizes tighter."""
    (want_loss, want), (loss, got) = both_sides["reference"], both_sides["bfloat16"]
    assert abs(loss - want_loss) <= 2e-3 * want_loss
    a, b = float(jnp.linalg.norm(got[leaf])), float(jnp.linalg.norm(want[leaf]))
    assert abs(a - b) <= 0.33 * b, (a, b)


@pytest.mark.parametrize("dtype,loss_tol,change_tol", [(None, 1e-5, 1e-3), (jnp.bfloat16, 2e-3, 0.3)],
                         ids=["float32", "bfloat16"])
def test_three_steps_of_lm_follow_the_reference(dtype, loss_tol, change_tol):
    from benchmarks import check

    cfg = tiny_config()
    batches = reference.example_batches(cfg, 9, 3, 2)
    prog, flat = program(cfg, cfg["lr"], cfg["momentum"], dtype, seed=9)
    state, losses, rows = prog.state, [], []
    row = cfg["hidden_size"] * (2 if dtype is jnp.bfloat16 else 4)
    with jax.default_matmul_precision("highest"):
        for i, tokens in enumerate(batches):
            state, metrics = prog.step(state, jax.random.PRNGKey(i), prog.shard_tokens(tokens))
            losses.append(float(metrics["loss"]))
            rows.append(float(metrics["moe_held_row_bytes"]) / row)
    want = reference.train_steps(flat, batches, cfg)
    assert np.allclose(losses, want["losses"], rtol=loss_tol), (losses, want["losses"])
    # the assignments the step computed are those the reference counts for the held experts, but
    # for the few whose fourth and fifth scores the two orders of summation rank differently
    assert np.allclose(rows, want["held_rows"], rtol=0.005 if dtype is None else 0.05), (rows, want["held_rows"])
    moved = {k: float(jnp.linalg.norm(v - flat[k])) for k, v in flat_of(state.params).items()}
    gap, leaf = check.worst_leaf_gap(moved, want["change_norms"])
    assert gap < change_tol, (gap, leaf)


def test_a_window_that_were_left_out_or_one_key_wider_is_another_model():
    """The comparison can tell: the same weights through a model whose window
    layers see one key more, or every key, read another loss."""
    from atomo_tpu.models.transformer import TransformerLM

    cfg = tiny_config()
    flat = reference.init_params(cfg, 4)
    tokens = jnp.asarray(reference.example_batches(cfg, 4, 1, 2)[0])
    want = float(reference.loss_and_grads(flat, tokens, cfg)[0])

    def loss(**more):
        model = TransformerLM(**lm_config(cfg, **more))
        like = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tokens))["params"]
        with jax.default_matmul_precision("highest"):
            logits = model.apply({"params": tree_of(flat, like)}, tokens)
        return float(-jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(logits[:, :-1]), tokens[:, 1:, None], -1)))

    assert loss() == pytest.approx(want, rel=2e-6)
    assert abs(loss(window=cfg["sliding_window"] + 1) - want) > 1e-5 * want
    assert abs(loss(window=cfg["seq_len"]) - want) > 1e-5 * want


# ---- the chip's share of the experts ---------------------------------------------

def layer_inputs(cfg, seed, rows=96):
    """One expert layer's leaves with all the router's experts held, as the
    reference names them, and a batch of normalised rows."""
    d, fe, total = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["routed_experts_total"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    p = {"router": 0.3 * jax.random.normal(ks[0], (d, total)),
         "gate": 0.2 * jax.random.normal(ks[1], (total, d, fe)),
         "up": 0.2 * jax.random.normal(ks[2], (total, d, fe)),
         "down": 0.2 * jax.random.normal(ks[3], (total, fe, d))}
    return p, jax.random.normal(ks[4], (2, rows // 2, d))


def share_of(p, first, held):
    return {**p, **{k: p[k][first:first + held] for k in ("gate", "up", "down")}}


def routed(cfg, p, u, first, held):
    from atomo_tpu.models.moe import RoutedExperts

    layer = RoutedExperts(expert_sizes(cfg, first_expert=first, experts_held=held))
    with jax.default_matmul_precision("highest"):
        return layer.apply({"params": share_of(p, first, held)}, u, mutable=["counts", "counts_max"])


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """model-configs section 4's test: what each of the 4 shares of 4 experts
    computes for the tokens routed to its own, added up, is the uncut
    reference's layer (all 16 held); there is no shared expert to count once."""
    cfg = tiny_config()
    p, u = layer_inputs(cfg, 3)
    total, held = cfg["routed_experts_total"], cfg["num_experts"]
    mm = reference._matmul("float32")
    with jax.default_matmul_precision("highest"):
        want, every = reference.routed_experts(u, p, cfg, mm, first=0)
    parts, rows = [], 0.0
    for first in range(0, total, held):
        y, sown = routed(cfg, p, u, first, held)
        parts.append(y)
        counted = float(sown["counts"]["moe_held_row_bytes"][0]) / (cfg["hidden_size"] * 4)
        rows += counted
        with jax.default_matmul_precision("highest"):
            ref_part, ref_rows = reference.routed_experts(u, share_of(p, first, held), cfg, mm, first=first)
        assert counted == int(ref_rows)  # and computes the assignments the reference counts for it
        assert float(jnp.abs(y - ref_part).max()) < 1e-5 * float(jnp.abs(want).max())  # each share is the reference's share
    assert rows == int(every) == u.shape[0] * u.shape[1] * cfg["num_experts_per_tok"]  # every assignment computed once, by its holder
    assert float(jnp.abs(sum(parts) - want).max()) < 1e-5 * float(jnp.abs(want).max())
    assert float(jnp.abs(parts[0] - want).max()) > 0.1 * float(jnp.abs(want).max())  # and one share alone is not


def test_a_model_that_holds_every_expert_is_the_uncut_reference():
    from atomo_tpu.models.transformer import TransformerLM

    cfg = tiny_config(num_experts=16, n_routed_experts=16, first_expert_held=0)
    flat = reference.init_params(cfg, 4)
    tokens = jnp.asarray(reference.example_batches(cfg, 4, 1, 2)[0])
    model = TransformerLM(**lm_config(cfg))
    like = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tokens))["params"]
    assert flat_of(like)["block1/moe/gate"].shape == (16, 48, 24)
    with jax.default_matmul_precision("highest"):
        logits = model.apply({"params": tree_of(flat, like)}, tokens)
    loss = -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(logits[:, :-1]), tokens[:, 1:, None], -1))
    assert float(loss) == pytest.approx(float(reference.loss_and_grads(flat, tokens, cfg)[0]), rel=2e-6)


# ---- the softmax router -----------------------------------------------------------

def test_the_chosen_weights_sum_to_one_and_the_unchosen_get_no_gradient():
    """softmax over all 16 outputs, the 4 largest renormalised over their sum:
    the weights of a token sum to 1 whatever the scores; the router's matrix
    gets a gradient through every output (the softmax couples them) but a
    held expert that no token chose gets none, nor does an absent one's row."""
    cfg = tiny_config()
    p, u = layer_inputs(cfg, 8, rows=4)
    chosen, weights = reference.route(u, p["router"], cfg)
    assert np.allclose(weights.sum(-1), 1.0, rtol=1e-6) and chosen.shape == (2, 2, 4)
    scores = jax.nn.softmax(jnp.einsum("bsd,de->bse", u, p["router"], precision=HI), axis=-1)
    assert np.array_equal(np.sort(np.asarray(chosen), -1), np.sort(np.asarray(jnp.argsort(-scores, -1)[..., :4]), -1))
    picked = jnp.take_along_axis(scores, chosen, -1)
    assert np.allclose(weights, picked / picked.sum(-1, keepdims=True), rtol=1e-6)

    from atomo_tpu.models.moe import RoutedExperts

    layer = RoutedExperts(expert_sizes(cfg, first_expert=0, experts_held=16))

    def through_the_program(params):
        with jax.default_matmul_precision("highest"):
            return jnp.sum(layer.apply({"params": params}, u) ** 2)

    g = jax.grad(through_the_program)(p)
    never = sorted(set(range(16)) - set(np.asarray(chosen).reshape(-1).tolist()))
    assert never, "4 tokens x 4 choose every one of 16 experts: draw other rows"
    for name in ("gate", "up", "down"):
        assert float(jnp.abs(g[name][jnp.asarray(never)]).max()) == 0.0
        assert float(jnp.abs(g[name][int(chosen[0, 0, 0])]).max()) > 0
    # a softmax, not a score a token and expert on its own: the same vector added to every column
    # of the router adds one number to all of a token's logits, which moves nothing
    shifted = {**p, "router": p["router"] + 0.3 * jax.random.normal(jax.random.PRNGKey(1), (cfg["hidden_size"], 1))}
    assert float(through_the_program(shifted)) == pytest.approx(float(through_the_program(p)), rel=1e-5)


def test_the_router_has_no_bias_leaf_and_stays_in_float32_under_bfloat16_compute():
    from atomo_tpu.models.moe import FLOAT32_LEAVES, RoutedExperts
    from atomo_tpu.parallel.lm import keep_float32
    from atomo_tpu.training.trainer import cast_params

    cfg = tiny_config()
    u = jnp.zeros((1, 8, cfg["hidden_size"]))
    leaves = RoutedExperts(expert_sizes(cfg)).init(jax.random.PRNGKey(0), u)["params"]
    assert sorted(leaves) == ["down", "gate", "router", "up"]
    sigmoid = RoutedExperts(expert_sizes(cfg, scoring="sigmoid")).init(jax.random.PRNGKey(0), u)["params"]
    assert sorted(sigmoid) == ["down", "gate", "route_bias", "router", "up"]  # GLM's rule keeps its leaf
    tree = {"block1": {"moe": dict(leaves)}}
    kept = keep_float32(cast_params(tree, jnp.bfloat16), tree, FLOAT32_LEAVES)["block1"]["moe"]
    assert kept["router"].dtype == jnp.float32 and kept["gate"].dtype == jnp.bfloat16
    prog, _ = program(cfg, 0.01, 0.9, jnp.bfloat16)
    tokens = prog.shard_tokens(reference.example_batches(cfg, 3, 1, 2)[0])
    text = prog.step.lower(prog.state, jax.random.PRNGKey(1), tokens).as_text()
    assert re.search(r"stablehlo.dot_general.*tensor<256x48xf32>, tensor<48x16xf32>", text), "the router's product is float32"


@pytest.mark.parametrize("bad,said", [
    (dict(scoring="tanh"), "unknown router scoring 'tanh'"),
    (dict(first_expert=13), "experts [13, 17) are not among the router's 16"),
    (dict(per_token=17), "17 experts per token of 16"),
])
def test_expert_sizes_that_do_not_fit_are_refused(bad, said):
    with pytest.raises(ValueError, match=re.escape(said)):
        expert_sizes(tiny_config(), **bad)


# ---- codecs, flags, layouts, scopes ------------------------------------------------

@pytest.mark.parametrize("code", ["svd", "qsgd"])
def test_codecs_take_a_step_on_the_new_tree(code):
    """dp 2 with a compressed exchange over leaves of rank 1, 2 and 3 (norms,
    the one qkv matrix, the held experts): the loss stays finite, every leaf
    moves, and the counters are the replicas' sum and most."""
    from atomo_tpu.codecs import get_codec

    cfg = tiny_config()
    prog, flat = program(cfg, 0.05, 0.9, None, seed=2, dp=2, codec=get_codec(code, svd_rank=4), aggregate="gather")
    assert {len(x.shape) for x in jax.tree_util.tree_leaves(prog.state.params)} == {1, 2, 3}
    tokens = reference.example_batches(cfg, 2, 1, 4)[0]
    state, metrics = prog.step(prog.state, jax.random.PRNGKey(0), prog.shard_tokens(tokens))
    assert np.isfinite(float(metrics["loss"]))
    now = flat_of(state.params)
    for leaf in ("block0/MultiHeadAttention_0/qkv/kernel", "block3/moe/gate", "block2/moe/router", "head/kernel"):
        assert float(jnp.abs(now[leaf] - flat[leaf]).max()) > 0, leaf
    rows = float(metrics["moe_held_row_bytes"]) / (cfg["hidden_size"] * 4)
    assert rows == int(rows) and 0 < rows <= 4 * 4 * 128 * 4  # both replicas' tokens, four layers
    assert float(metrics["attn_tile_score_bytes"]) > 0


def test_the_cells_flags_build_the_configurations_sizes():
    from atomo_tpu.cli import _lm_block_config, build_parser
    from atomo_tpu.models.moe import ExpertSizes
    from atomo_tpu.models.rotary import Rotary, Yarn

    argv, _ = program_argv(CONFIG, TRAFFIC, seed=1)
    args = build_parser().parse_args(argv)
    block = _lm_block_config(args)
    assert (args.width, args.depth, args.num_heads, args.vocab_size, args.seq_len, args.batch_size) == (
        2304, 4, 32, 24576, 8192, 2)
    assert block["layer_pattern"] == ("window", "window", "window", "full")
    assert (block["kv_heads"], block["head_dim"], block["window"], block["remat"]) == (4, 128, 1024, "dots")
    assert block["experts"] == ExpertSizes(expert_width=896, experts=64, experts_held=16, first_expert=0,
                                           per_token=8, scoring="softmax")
    yarn = Yarn(16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    assert dict(block["rope"]) == {"window": Rotary(500000.0), "full": Rotary(500000.0, yarn)}
    assert (block["norm"], block["positions"], block["ffn"]) == ("rmsnorm", "rotary", "experts")
    assert "latent_moe" not in block and "ffn_width" not in block


MELLUM = ["--block", "mellum", "--layer-pattern", "window,full", "--window", "32", "--kv-heads", "2",
          "--head-dim", "16", "--router", "softmax", "--routed-experts", "16", "--experts-held", "4",
          "--expert-width", "24", "--shared-experts", "0", "--dense-layers", "0"]


@pytest.mark.parametrize("layout", ["dp-tp", "dp-pp", "dp-ep", "dp-sp", "dp-tp-sp"])
@pytest.mark.parametrize("flags,named", [(MELLUM, "--block"), (["--kv-heads", "2"], "--kv-heads"),
                                         (["--head-dim", "16"], "--head-dim"),
                                         (["--layer-pattern", "window,full", "--window", "32"], "--layer-pattern")])
def test_the_block_and_its_sizes_are_refused_outside_layout_dp(layout, flags, named):
    from atomo_tpu.cli import main

    with pytest.raises(SystemExit) as refused:
        main(["lm", "--layout", layout, "--n-devices", "4", "--ways", "2", "--batch-size", "8",
              "--code", "sgd", "--aggregate", "psum", *flags])
    said = str(refused.value)
    assert said.startswith(f"{named} needs --layout dp") and "\n" not in said


def _without(flags, *names):
    out, skip = [], False
    for item in flags:
        if skip:
            skip = False
        elif item in names:
            skip = True
        else:
            out.append(item)
    return out


@pytest.mark.parametrize("argv,said", [
    (["--block", "mellum"], "--block mellum needs its experts' sizes: --routed-experts --expert-width"),
    (_without(MELLUM, "--shared-experts"), "--block mellum: every layer is routed experts alone"),
    (_without(MELLUM, "--dense-layers"), "--block mellum: every layer is routed experts alone"),
    ([*MELLUM, "--first-expert", "13"], "--block mellum: experts [13, 17) are not among the router's 16"),
    (_without(MELLUM, "--window"), "--layer-pattern with a window layer needs --window"),
    ([*_without(MELLUM, "--kv-heads"), "--kv-heads", "3"], "--kv-heads 3 does not divide --num-heads 4"),
    ([*MELLUM, "--yarn-factor", "16"], "--yarn-factor scales past --yarn-original-len"),
    (["--block", "olmo", "--layer-pattern", "window"], "--layer-pattern with a window layer needs --window"),
])
def test_sizes_that_do_not_fit_are_refused_in_one_line(argv, said):
    from atomo_tpu.cli import main

    with pytest.raises(SystemExit) as refused:
        main(["lm", "--layout", "dp", "--n-devices", "1", "--batch-size", "2", "--code", "sgd",
              "--aggregate", "psum", *argv])
    assert str(refused.value).startswith(said) and "\n" not in str(refused.value), str(refused.value)


@pytest.mark.parametrize("more,said", [
    (dict(), "sp=2 needs experts unset"),
    (dict(experts=None, ffn="swiglu"), "sp=2 needs kv_heads and rope unset and no `window` layer"),
])
def test_the_sp_ring_refuses_the_block(more, said):
    from atomo_tpu.mesh.spec import MeshSpec
    from atomo_tpu.parallel.lm import make_lm_train_step
    from atomo_tpu.training import make_optimizer

    mesh = MeshSpec.from_layout("dp-sp", 2, 2).build()
    with pytest.raises(ValueError, match=said):
        make_lm_train_step(lm_config(tiny_config(), **more), make_optimizer("sgd", lr=0.1), mesh)


@pytest.mark.parametrize("bad,said", [
    (dict(positions="rotary", rope=()), "positions='rotary' and `rope`"),
    (dict(rope=(("full", None),)), "rope has no rule for the `window` layers"),
    (dict(window=0), "a `window` layer, and no other, takes a window"),
    (dict(experts=None), "the `experts` FFN needs its sizes"),
])
def test_a_model_whose_fields_do_not_go_together_is_refused(bad, said):
    from atomo_tpu.models.transformer import TransformerLM

    tokens = jnp.zeros((1, 128), jnp.int32)
    with pytest.raises(ValueError, match=re.escape(said)):
        jax.eval_shape(lambda: TransformerLM(**lm_config(tiny_config(), **bad)).init(jax.random.PRNGKey(0), tokens))


def test_the_step_lowers_with_its_scopes_and_reports_its_counters():
    from atomo_tpu.obs.timeline import MODEL_PHASES, phase_of

    cfg = tiny_config()
    prog, _ = program(cfg, 0.01, 0.9, jnp.bfloat16)
    tokens = prog.shard_tokens(reference.example_batches(cfg, 3, 1, 2)[0])
    text = prog.step.lower(prog.state, jax.random.PRNGKey(1), tokens).as_text(debug_info=True)
    scopes = set(re.findall(r'["/(]([a-z_]+)(?=[/)])', text))
    assert {"rope", "attention", "moe", "moe_route", "moe_dispatch", "moe_experts", "forward_backward", "update"} <= scopes
    assert "rope" in MODEL_PHASES and "ffn" not in scopes and "mla" not in scopes
    assert phase_of("jit(step)/forward_backward/block1/MultiHeadAttention_0/rope/mul") == "rope"
    assert phase_of("jit(step)/transpose(jvp(block3))/MultiHeadAttention_0/attention/dot_general") == "attention"
    _, metrics = prog.step(prog.state, jax.random.PRNGKey(1), tokens)
    row = cfg["hidden_size"] * 2  # bfloat16 rows
    rows, most = float(metrics["moe_held_row_bytes"]) / row, float(metrics["moe_max_expert_row_bytes"]) / row
    layers, held, assignments = 4, cfg["num_experts"], 2 * 128 * 4
    assert rows == int(rows) and 0 < rows <= layers * assignments
    assert rows / (layers * held) <= most <= 2 * 128  # at least the mean, at most every token
    # 128 positions are one block: every layer keeps its whole square of exponentials in bfloat16,
    # and computes it in float32, 4 layers of 2 sequences of 4 heads
    assert float(metrics["attn_score_bytes"]) == 4 * 2 * 4 * 128 * 128 * 2
    assert float(metrics["attn_tile_score_bytes"]) == 4 * 2 * 4 * 128 * 128 * 4
    assert "attn_fused_layers" not in metrics  # off the TPU
