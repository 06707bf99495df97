"""The latent-attention block with routed experts of `lm --block glm` (PR 33)
against benchmarks/reference/glm_4_7_flash.py at the configuration's tiny
sizes: every leaf's first gradient and three losses through the step `lm`
builds, the shares of the experts adding up to the uncut layer, the rotation
by hand, the selection bias, the worst case of the static row bound, the
prediction module's targets, weight and shared leaves, the codecs on the 3-D
expert leaves, the layouts that refuse the block, and the scopes and counters."""

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

from benchmarks.reference import glm_4_7_flash as reference  # noqa: E402
from benchmarks.run import leaf_name, program_argv, tiny  # noqa: E402

HI = jax.lax.Precision.HIGHEST


def tiny_config(**more):
    cfg = json.loads((ROOT / "benchmarks/configs/glm-4.7-flash.json").read_text())
    return {**tiny(cfg, {"flags": {}})[0], **more}


def sizes_of(cfg, **more):
    from atomo_tpu.models.moe import LatentMoeSizes

    given = dict(
        q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"], nope_dim=cfg["qk_nope_head_dim"],
        rope_dim=cfg["qk_rope_head_dim"], value_dim=cfg["v_head_dim"],
        expert_width=cfg["moe_intermediate_size"], experts=cfg["routed_experts_total"],
        experts_held=cfg["n_routed_experts"], first_expert=cfg["first_expert_held"],
        per_token=cfg["num_experts_per_tok"], shared_experts=cfg["n_shared_experts"],
        dense_layers=cfg["first_k_dense_replace"], route_scale=cfg["routed_scaling_factor"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        mtp_depth=cfg["num_nextn_predict_layers"], mtp_weight=cfg["mtp_loss_weight"],
    )
    return LatentMoeSizes(**{**given, **more})


def lm_config(cfg, **more):
    from atomo_tpu.models.transformer import BLOCK_RECIPES

    return dict(vocab_size=cfg["vocab_size"], max_len=cfg["seq_len"], width=cfg["hidden_size"],
                depth=cfg["num_hidden_layers"], num_heads=cfg["num_attention_heads"],
                ffn_width=cfg["intermediate_size"], latent_moe=sizes_of(cfg), remat="dots",
                **{**BLOCK_RECIPES["glm"], **more})


def tree_of(flat, like):
    paths, treedef = jax.tree_util.tree_flatten_with_path(like)
    return jax.tree_util.tree_unflatten(treedef, [flat[leaf_name(p)] for p, _ in paths])


def flat_of(tree):
    return {leaf_name(p): x for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def program(cfg, lr, momentum, dtype, seed=5):
    """`lm`'s own program for the configuration with the reference's seeded
    weights installed, as the benchmark's adapter does."""
    from atomo_tpu.mesh.spec import MeshSpec
    from atomo_tpu.parallel.model_axes import build_model_axis_program
    from atomo_tpu.training import make_optimizer

    prog = build_model_axis_program(
        MeshSpec.from_layout("dp", 1, 1), lm_config(cfg),
        make_optimizer("sgd", lr=lr, momentum=momentum), jax.random.PRNGKey(0), None,
        aggregate="psum", compute_dtype=dtype,
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
BIASES = [leaf for leaf in LEAVES if leaf.endswith("route_bias")]


@pytest.mark.parametrize("leaf", LEAVES)
def test_first_gradient_of_every_leaf_follows_the_reference_in_float32(both_sides, leaf):
    """Both sides compute in float32 on the CPU, in another order (sorted rows
    and grouped products against every expert on every row): a few 1e-6 of
    the leaf's norm. The selection bias has no gradient on either side."""
    (want_loss, want), (loss, got) = both_sides["reference"], both_sides["float32"]
    assert abs(loss - want_loss) <= 2e-6 * want_loss
    if leaf in BIASES:
        assert float(jnp.abs(got[leaf]).max()) == 0.0 == float(jnp.abs(want[leaf]).max())
        return
    gap = float(jnp.linalg.norm(got[leaf] - want[leaf]) / jnp.linalg.norm(want[leaf]))
    assert gap < 1e-4, gap


@pytest.mark.parametrize("leaf", LEAVES)
def test_first_gradient_of_every_leaf_follows_the_reference_in_bfloat16(both_sides, leaf):
    """bfloat16 has 8 bits, and a token whose fourth and fifth scores lie
    within the rounding of each other goes to another expert, whose gradient
    then differs by whole rows: at 64 units of width single leaves are off by
    tens of percent of their norm. Held here: the loss, and the norm of every
    leaf's gradient to a third; the benchmark holds the real sizes tighter."""
    (want_loss, want), (loss, got) = both_sides["reference"], both_sides["bfloat16"]
    assert abs(loss - want_loss) <= 2e-3 * want_loss
    if leaf in BIASES:
        assert float(jnp.abs(got[leaf]).max()) == 0.0
        return
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
    # (1 to 3 of 1000 in float32; bfloat16 routes a few percent elsewhere)
    assert np.allclose(rows, want["held_rows"], rtol=0.005 if dtype is None else 0.05), (rows, want["held_rows"])
    moved = {k: float(jnp.linalg.norm(v - flat[k])) for k, v in flat_of(state.params).items()}
    skip = [k for k in moved if k.endswith("route_bias")]
    assert all(moved[k] == 0.0 == want["change_norms"][k] for k in skip)
    gap, leaf = check.worst_leaf_gap(moved, want["change_norms"], skip)
    assert gap < change_tol, (gap, leaf)


# ---- the chip's share of the experts ---------------------------------------------

def layer_inputs(cfg, seed, rows=96):
    """One expert layer's leaves with all the router's experts held, as the
    reference names them, and a batch of normalised rows."""
    d, fe, total = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["routed_experts_total"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    p = {"router": 0.3 * jax.random.normal(ks[0], (d, total)),
         "route_bias": jax.random.uniform(ks[1], (total,), minval=-0.1, maxval=0.1),
         "gate": 0.2 * jax.random.normal(ks[2], (total, d, fe)),
         "up": 0.2 * jax.random.normal(ks[3], (total, d, fe)),
         "down": 0.2 * jax.random.normal(ks[4], (total, fe, d))}
    return p, jax.random.normal(ks[5], (2, rows // 2, d))


def share_of(p, first, held):
    return {**p, **{k: p[k][first:first + held] for k in ("gate", "up", "down")}}


def routed(cfg, p, u, first, held):
    from atomo_tpu.models.moe import RoutedExperts

    layer = RoutedExperts(sizes_of(cfg, first_expert=first, experts_held=held))
    with jax.default_matmul_precision("highest"):
        return layer.apply({"params": share_of(p, first, held)}, u, mutable=["counts", "counts_max"])


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """model-configs section 4's test: what each of the 4 shares of 4 experts
    computes for the tokens routed to its own, added up, is the uncut
    reference's routed part (all 16 held); the shared expert, which every chip
    computes alike, is counted once and is the same on both sides."""
    cfg = tiny_config()
    p, u = layer_inputs(cfg, 3)
    total, held = cfg["routed_experts_total"], cfg["n_routed_experts"]
    uncut = {**cfg, "first_expert_held": 0}
    with jax.default_matmul_precision("highest"):
        want, every = reference.routed_experts(u, p, uncut, reference._matmul("float32"))
    parts, rows = [], 0.0
    for first in range(0, total, held):
        y, sown = routed(cfg, p, u, first, held)
        parts.append(y)
        counted = float(sown["counts"]["moe_held_row_bytes"][0]) / (cfg["hidden_size"] * 4)
        rows += counted
        ref_part, ref_rows = reference.routed_experts(u, share_of(p, first, held), {**cfg, "first_expert_held": first},
                                                      reference._matmul("float32"))
        assert counted == int(ref_rows)  # and computes the assignments the reference counts for it
        assert float(jnp.abs(y - ref_part).max()) < 1e-5 * float(jnp.abs(want).max())  # each share is the reference's share
    assert rows == int(every) == u.shape[0] * u.shape[1] * cfg["num_experts_per_tok"]  # every assignment computed once, by its holder
    assert float(jnp.abs(sum(parts) - want).max()) < 1e-5 * float(jnp.abs(want).max())
    assert float(jnp.abs(parts[0] - want).max()) > 0.1 * float(jnp.abs(want).max())  # and one share alone is not


def test_a_model_that_holds_every_expert_is_the_uncut_reference():
    from atomo_tpu.models.transformer import TransformerLM

    cfg = tiny_config(n_routed_experts=16, first_expert_held=0)
    flat = reference.init_params(cfg, 4)
    tokens = jnp.asarray(reference.example_batches(cfg, 4, 1, 2)[0])
    model = TransformerLM(**lm_config(cfg))
    like = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tokens))["params"]
    assert flat_of(like)["block1/moe/gate"].shape == (16, 64, 48)
    with jax.default_matmul_precision("highest"):
        logits, mtp_logits = model.apply({"params": tree_of(flat, like)}, tokens)
    main = -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(logits[:, :-1]), tokens[:, 1:, None], -1))
    second = -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(mtp_logits[:, :-2]), tokens[:, 2:, None], -1))
    want, _ = reference.loss_and_grads(flat, tokens, cfg)
    assert float(main + cfg["mtp_loss_weight"] * second) == pytest.approx(float(want), rel=2e-6)


def test_no_assignment_is_dropped_when_every_token_chooses_held_experts():
    """The worst case of the static bound: a router that sends every token's
    four assignments to the four held experts fills all T x 4 rows."""
    cfg = tiny_config()
    p, u = layer_inputs(cfg, 6)
    first, held = cfg["first_expert_held"], cfg["n_routed_experts"]
    bias = jnp.full_like(p["route_bias"], -10.0).at[first:first + held].set(10.0)
    p = {**p, "route_bias": bias}
    y, sown = routed(cfg, p, u, first, held)
    tokens = u.shape[0] * u.shape[1]
    assert float(sown["counts"]["moe_held_row_bytes"][0]) == tokens * 4 * cfg["hidden_size"] * 4
    assert float(sown["counts_max"]["moe_max_expert_row_bytes"][0]) == tokens * cfg["hidden_size"] * 4
    with jax.default_matmul_precision("highest"):
        want, ref_rows = reference.routed_experts(u, share_of(p, first, held), cfg, reference._matmul("float32"))
    assert int(ref_rows) == tokens * 4
    assert float(jnp.abs(y - want).max()) < 1e-5 * float(jnp.abs(want).max())
    nowhere = {**p, "route_bias": -bias}  # and a router that sends none here computes nothing
    y, sown = routed(cfg, nowhere, u, first, held)
    assert float(jnp.abs(y).max()) == 0.0 and float(sown["counts"]["moe_held_row_bytes"][0]) == 0.0


def test_the_bias_changes_the_choice_and_not_the_weights_and_takes_no_gradient():
    cfg = tiny_config()
    p, u = layer_inputs(cfg, 8)
    chosen, weights = reference.route(u, p["router"], p["route_bias"], cfg)
    lifted = p["route_bias"].at[9].add(5.0)  # expert 9 now wins everywhere
    chosen_l, weights_l = reference.route(u, p["router"], lifted, cfg)
    assert bool((chosen_l == 9).any(-1).all()) and not bool((chosen == 9).any(-1).all())
    scores = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", u, p["router"], precision=HI))
    picked = jnp.take_along_axis(scores, chosen_l, -1)  # the weights are of the scores alone
    assert np.allclose(weights_l, cfg["routed_scaling_factor"] * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    assert np.allclose(weights_l.sum(-1), cfg["routed_scaling_factor"], rtol=1e-6)

    def through_the_program(bias):
        y, _ = routed(cfg, {**p, "route_bias": bias}, u, 4, 4)
        return jnp.sum(y * y)

    assert float(jnp.abs(jax.grad(through_the_program)(p["route_bias"])).max()) == 0.0
    assert float(through_the_program(lifted)) != float(through_the_program(p["route_bias"]))


# ---- the rotation ------------------------------------------------------------------

@pytest.mark.parametrize("position", [0, 1, 127])
def test_rotary_against_a_rotation_by_hand(position):
    """Pair j of the 8 rotated dimensions is (x_j, x_{j+4}), turned by
    position * theta^(-2j/8); program and reference alike."""
    from atomo_tpu.models.moe import rotary, rotary_angles

    theta, dim, s = 1e6, 8, 128
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (1, s, 3, dim)), np.float64)
    want = np.empty(dim)
    for j in range(dim // 2):
        angle = position * theta ** (-2 * j / dim)
        a, b = x[0, position, 1, j], x[0, position, 1, j + dim // 2]
        want[j] = a * np.cos(angle) - b * np.sin(angle)
        want[j + dim // 2] = b * np.cos(angle) + a * np.sin(angle)
    cos, sin = rotary_angles(jnp.arange(s), dim, theta)
    got = rotary(jnp.asarray(x, jnp.float32), cos[:, None, :], sin[:, None, :])
    assert np.allclose(got[0, position, 1], want, atol=2e-5)
    assert np.allclose(reference.rotate(jnp.asarray(x, jnp.float32), theta)[0, position, 1], want, atol=2e-5)
    if position == 0:
        assert np.array_equal(np.asarray(got[0, 0]), x[0, 0].astype(np.float32))


# ---- the prediction module ----------------------------------------------------------

def test_the_prediction_module_shares_head_and_embedding_and_predicts_two_ahead():
    from atomo_tpu.models.transformer import TransformerLM

    cfg = tiny_config()
    names = reference.param_shapes(cfg)
    assert sum(k.endswith("embedding") for k in names) == 1 and sum(k.startswith("head/") for k in names) == 1
    assert names["mtp_proj/kernel"] == (2 * cfg["hidden_size"], cfg["hidden_size"])
    flat = reference.init_params(cfg, 12)
    tokens = jnp.asarray(reference.example_batches(cfg, 12, 1, 2)[0])
    model = TransformerLM(**lm_config(cfg))
    like = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tokens))["params"]
    with jax.default_matmul_precision("highest"):
        logits, mtp_logits = model.apply({"params": tree_of(flat, like)}, tokens)
        changed = tokens.at[:, 60].set((tokens[:, 60] + 1) % cfg["vocab_size"])
        logits_c, mtp_c = model.apply({"params": tree_of(flat, like)}, changed)
    # token 60 enters the main logits from position 60 on, the module's from 59 on (it reads token t+1)
    assert np.array_equal(np.asarray(logits[:, :60]), np.asarray(logits_c[:, :60]))
    assert not np.allclose(logits[:, 60], logits_c[:, 60])
    assert np.array_equal(np.asarray(mtp_logits[:, :59]), np.asarray(mtp_c[:, :59]))
    assert not np.allclose(mtp_logits[:, 59], mtp_c[:, 59])


@pytest.mark.parametrize("weight", [0.0, 0.3, 1.0])
def test_the_steps_loss_is_main_plus_weighted_prediction_loss(weight):
    from atomo_tpu.models.transformer import TransformerLM

    cfg = tiny_config(mtp_loss_weight=weight)
    tokens = reference.example_batches(cfg, 7, 1, 2)[0]
    prog, flat = program(cfg, 0.0, 0.0, None, seed=7)
    with jax.default_matmul_precision("highest"):
        _, metrics = prog.step(prog.state, jax.random.PRNGKey(0), prog.shard_tokens(tokens))
        model = TransformerLM(**lm_config(cfg))
        like = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.asarray(tokens)))["params"]
        logits, mtp_logits = model.apply({"params": tree_of(flat, like)}, jnp.asarray(tokens))
    ce = lambda lg, tg: -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(lg), tg[..., None], -1))  # noqa: E731
    main, second = ce(logits[:, :-1], tokens[:, 1:]), ce(mtp_logits[:, :-2], tokens[:, 2:])
    assert float(metrics["loss"]) == pytest.approx(float(main + weight * second), rel=1e-6)
    assert float(second) > 0 and float(reference.loss_and_grads(flat, tokens, cfg)[0]) == pytest.approx(
        float(metrics["loss"]), rel=2e-6)


def test_the_router_stays_in_float32_under_bfloat16_compute():
    from atomo_tpu.models.moe import FLOAT32_LEAVES
    from atomo_tpu.parallel.lm import keep_float32
    from atomo_tpu.training.trainer import cast_params

    flat = reference.init_params(tiny_config(), 1)
    tree = {"block1": {"moe": {k: flat[f"block1/moe/{k}"] for k in ("router", "route_bias", "gate")}}}
    kept = keep_float32(cast_params(tree, jnp.bfloat16), tree, FLOAT32_LEAVES)["block1"]["moe"]
    assert kept["router"].dtype == kept["route_bias"].dtype == jnp.float32
    assert kept["gate"].dtype == jnp.bfloat16


# ---- codecs, layouts, scopes ----------------------------------------------------------

@pytest.mark.parametrize("shape", [(4, 64, 48), (4, 48, 64)], ids=["gate", "down"])
@pytest.mark.parametrize("code", ["svd", "qsgd"])
def test_codecs_round_trip_the_three_dimensional_expert_leaves(code, shape):
    """What the expert layer adds to the tree: leaves of (held, d, f) and
    (held, f, d). A codec encodes and decodes them to their own shape, and
    the mean over draws comes back to the leaf (both estimators are unbiased)."""
    from atomo_tpu.codecs import get_codec

    codec = get_codec(code, svd_rank=4, quantization_level=4)
    leaf = jax.random.normal(jax.random.PRNGKey(0), shape)

    def once(key):
        return codec.decode(codec.encode(key, leaf), shape)

    got = jax.vmap(once)(jax.random.split(jax.random.PRNGKey(1), 512))
    assert got.shape == (512, *shape) and bool(jnp.isfinite(got).all())
    off = lambda x: float(jnp.linalg.norm(x - leaf) / jnp.linalg.norm(leaf))  # noqa: E731
    assert off(got.mean(0)) < 0.2 * off(got[0])  # 512 draws: a 23rd of one draw's error, were there no bias


@pytest.mark.parametrize("code", ["svd", "qsgd"])
def test_codecs_take_two_steps_on_the_tree_and_leave_the_bias_where_it_was(code):
    """dp 2 with a compressed exchange: leaves of rank 1, 2 and 3; the loss
    stays finite, the counters are the replicas' sum and most, and the
    selection bias, whose gradient is zero, rides through the codec and the
    momentum update unchanged."""
    from atomo_tpu.codecs import get_codec
    from atomo_tpu.mesh.spec import MeshSpec
    from atomo_tpu.parallel.model_axes import build_model_axis_program
    from atomo_tpu.training import make_optimizer

    cfg = tiny_config()
    prog = build_model_axis_program(
        MeshSpec.from_layout("dp", 2, 1), lm_config(cfg), make_optimizer("sgd", lr=0.05, momentum=0.9),
        jax.random.PRNGKey(0), get_codec(code, svd_rank=4), aggregate="gather",
    )
    assert {len(x.shape) for x in jax.tree_util.tree_leaves(prog.state.params)} == {1, 2, 3}
    flat = reference.init_params(cfg, 2)
    state = prog.state.replace(params=tree_of({k: jnp.copy(v) for k, v in flat.items()}, prog.state.params))
    for i, tokens in enumerate(reference.example_batches(cfg, 2, 2, 4)):
        state, metrics = prog.step(state, jax.random.PRNGKey(i), prog.shard_tokens(tokens))
        assert np.isfinite(float(metrics["loss"]))
    now = flat_of(state.params)
    for leaf in BIASES:
        assert np.array_equal(np.asarray(now[leaf]), np.asarray(flat[leaf])), leaf
    assert float(jnp.abs(now["block1/moe/gate"] - flat["block1/moe/gate"]).max()) > 0
    rows = float(metrics["moe_held_row_bytes"]) / (cfg["hidden_size"] * 4)
    assert rows == int(rows) and 0 < rows <= 3 * 4 * 128 * 4  # both replicas' tokens, three expert blocks


GLM = ["--block", "glm", "--q-rank", "24", "--kv-rank", "16", "--nope-dim", "12", "--rope-dim", "8",
       "--value-dim", "16", "--routed-experts", "16", "--experts-held", "4", "--expert-width", "48"]


@pytest.mark.parametrize("layout", ["dp-tp", "dp-pp", "dp-ep", "dp-sp", "dp-tp-sp"])
def test_the_block_is_refused_outside_layout_dp(layout):
    from atomo_tpu.cli import main

    with pytest.raises(SystemExit) as refused:
        main(["lm", "--layout", layout, "--n-devices", "4", "--ways", "2", "--batch-size", "8",
              "--code", "sgd", "--aggregate", "psum", *GLM])
    said = str(refused.value)
    assert said.startswith("--block needs --layout dp") and "\n" not in said


@pytest.mark.parametrize("argv,said", [
    (["--block", "glm"], "--block glm needs its sizes: --q-rank"),
    (["--block", "olmo", "--layer-pattern", "mla"], "--layer-pattern: an mla layer comes with --block glm"),
    ([*GLM, "--first-expert", "13"], "--block glm: experts [13, 17) are not among the router's 16"),
    ([*GLM, "--mtp-depth", "2"], "--block glm: mtp_depth 2"),
])
def test_sizes_that_do_not_fit_are_refused_in_one_line(argv, said):
    from atomo_tpu.cli import main

    with pytest.raises(SystemExit) as refused:
        main(["lm", "--layout", "dp", "--n-devices", "1", "--batch-size", "2", "--code", "sgd",
              "--aggregate", "psum", *argv])
    assert str(refused.value).startswith(said) and "\n" not in str(refused.value)


def test_the_sp_ring_refuses_the_block():
    from atomo_tpu.mesh.spec import MeshSpec
    from atomo_tpu.parallel.lm import make_lm_train_step
    from atomo_tpu.training import make_optimizer

    mesh = MeshSpec.from_layout("dp-sp", 2, 2).build()
    with pytest.raises(ValueError, match="sp=2 needs latent_moe unset"):
        make_lm_train_step(lm_config(tiny_config()), make_optimizer("sgd", lr=0.1), mesh)


def test_the_cells_flags_build_the_configurations_sizes():
    from atomo_tpu.cli import _lm_block_config, build_parser

    cfg = json.loads((ROOT / "benchmarks/configs/glm-4.7-flash.json").read_text())
    traffic = json.loads((ROOT / "benchmarks/traffic/1chip-dense-2xseq4096.json").read_text())
    argv, _ = program_argv(cfg, traffic, seed=1)
    block = _lm_block_config(build_parser().parse_args(argv))
    assert block["latent_moe"] == sizes_of(cfg) and block["layer_pattern"] == ("mla",)
    assert (block["latent_moe"].experts, block["latent_moe"].held, block["ffn_width"]) == (64, 8, 10240)


def test_the_step_lowers_with_its_scopes_and_reports_both_counters():
    from atomo_tpu.obs.timeline import MODEL_PHASES, phase_of

    cfg = tiny_config()
    prog, _ = program(cfg, 0.01, 0.9, jnp.bfloat16)
    tokens = prog.shard_tokens(reference.example_batches(cfg, 3, 1, 2)[0])
    text = prog.step.lower(prog.state, jax.random.PRNGKey(1), tokens).as_text(debug_info=True)
    scopes = set(re.findall(r'["/(]([a-z_]+)(?=[/)])', text))
    new = {"mla", "moe", "moe_route", "moe_dispatch", "moe_experts", "mtp"}
    assert new | {"attention", "ffn", "forward_backward", "update"} <= scopes
    assert new <= set(MODEL_PHASES)
    assert phase_of("jit(step)/forward_backward/block1/moe/moe/moe_experts/ragged_dot") == "moe_experts"
    assert phase_of("jit(step)/transpose(jvp(block1))/moe/moe/moe_dispatch/gather") == "moe_dispatch"
    assert phase_of("jit(step)/forward_backward/block1/mla/mla/dot_general") == "mla"
    _, metrics = prog.step(prog.state, jax.random.PRNGKey(1), tokens)
    row = cfg["hidden_size"] * 2  # bfloat16 rows
    rows, most = float(metrics["moe_held_row_bytes"]) / row, float(metrics["moe_max_expert_row_bytes"]) / row
    layers, held, assignments = 3, cfg["n_routed_experts"], 2 * 128 * 4
    assert rows == int(rows) and 0 < rows <= layers * assignments
    assert rows / (layers * held) <= most <= 2 * 128  # at least the mean, at most every token
    # four blocks of 4 heads over 128 positions, the exponentials in bfloat16
    assert float(metrics["attn_score_bytes"]) == 4 * 2 * 4 * 128 * 128 * 2
