"""The one-device causal attention core with a window and grouped key/value
heads (PR 35): the jnp query blocks and the fused kernels (under the TPU
interpreter, at head size 128) against a float32 one-block oracle with an
explicit mask and repeated heads; the live-tile tables; the two rotary rules
by hand; the refusals of the multi-block paths; and the step's
`attn_tile_score_bytes` and `attn_fused_layers` at the cell's real shapes."""

import json
import math
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from atomo_tpu.parallel import ring  # noqa: E402
from atomo_tpu.parallel.ring import Blocks, full_attention, ring_attention  # noqa: E402

CONFIG = json.loads((ROOT / "benchmarks/configs/mellum2-12b-a2.5b.json").read_text())


def oracle(q, k, v, window):
    """One block, float32 at `highest`, an explicit (S, S) mask, every query
    head with its own copy of the key/value head it reads."""
    group, (s, d) = q.shape[1] // k.shape[1], q.shape[-2:]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") / d**0.5
    behind = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    seen = (behind >= 0) & ((behind < window) if window else True)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v, precision="highest")


def operands(shape, kv_heads, seed=0):
    b, h, s, d = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(keys[0], shape), jax.random.normal(keys[1], (b, kv_heads, s, d)),
            jax.random.normal(keys[2], (b, kv_heads, s, d)), jax.random.normal(keys[3], shape))


def forward_and_gradients(fn, w, q, k, v):
    out, pull = jax.vjp(fn, q, k, v)
    return [out, *pull(w.astype(out.dtype))]


def rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# ---- the jnp path ---------------------------------------------------------------------

JNP_CASES = {
    "window-crosses-a-block": ((2, 4, 512, 16), 2, 100),  # 4 blocks of 128; keys from the lane boundary below
    "window-is-a-block": ((1, 4, 512, 16), 1, 128),
    "window-is-two-blocks-and-a-bit": ((1, 4, 1024, 8), 2, 300),  # 8 blocks of 128
    "window-past-the-sequence": ((1, 4, 512, 16), 2, 1000),  # equals full attention
    "no-window-grouped": ((2, 6, 512, 16), 3, 0),
    "uncut-one-block": ((2, 4, 200, 8), 2, 50),  # no multiple of 128: the one-block program
    "equal-heads-window": ((1, 2, 512, 16), 2, 77),
}


@pytest.mark.parametrize("impl", ["full", "ring1"])
@pytest.mark.parametrize("case", list(JNP_CASES))
def test_the_jnp_path_matches_the_masked_oracle_forward_and_in_all_three_gradients(case, impl):
    shape, kv_heads, window = JNP_CASES[case]
    q, k, v, w = operands(shape, kv_heads)
    fn = (partial(full_attention, causal=True, window=window) if impl == "full" else
          partial(ring_attention, axis_name="sp", axis_size=1, causal=True, window=window))
    got = forward_and_gradients(fn, w, q, k, v)
    want = forward_and_gradients(partial(oracle, window=window), w, q, k, v)
    for g, ref in zip(got, want, strict=True):
        assert g.shape == ref.shape and rel(g, ref) < 2e-6, (case, rel(g, ref))
    if window >= shape[2]:  # a window that holds every key is no window
        plain = forward_and_gradients(partial(full_attention, causal=True), w, q, k, v)
        for g, ref in zip(got, plain, strict=True):
            assert rel(g, ref) < 1e-6


def test_bfloat16_operands_give_bfloat16_results_near_the_oracle():
    q, k, v, w = (x.astype(jnp.bfloat16) for x in operands((1, 4, 512, 16), 2, seed=3))
    got = forward_and_gradients(partial(full_attention, causal=True, window=100), w, q, k, v)
    want = forward_and_gradients(partial(oracle, window=100), w.astype(jnp.float32),
                                 *(x.astype(jnp.float32) for x in (q, k, v)))
    for g, ref in zip(got, want, strict=True):
        assert g.dtype == jnp.bfloat16 and rel(g, ref) < 1e-2


@pytest.mark.parametrize("s,n,window,want", [
    (512, 4, 0, [(0, 0, 128), (128, 0, 256), (256, 0, 384), (384, 0, 512)]),
    (512, 4, 100, [(0, 0, 128), (128, 0, 256), (256, 128, 384), (384, 256, 512)]),  # 256 - 99 = 157 -> 128
    (512, 4, 128, [(0, 0, 128), (128, 0, 256), (256, 128, 384), (384, 256, 512)]),  # 129 -> 128
    (512, 4, 129, [(0, 0, 128), (128, 0, 256), (256, 128, 384), (384, 256, 512)]),
    (512, 4, 130, [(0, 0, 128), (128, 0, 256), (256, 0, 384), (384, 128, 512)]),  # 256 - 129 = 127 -> 0
    (8192, 8, 1024, [(i * 1024, max(i - 1, 0) * 1024, (i + 1) * 1024) for i in range(8)]),
])
def test_a_query_block_runs_from_its_windows_start_to_its_own_end(s, n, window, want):
    assert ring.block_key_ranges(s, n, window) == want


def test_the_counters_count_the_band_and_not_the_triangle():
    """At the cell's shape off the TPU: 8 query blocks of 1024, the first
    against 1024 keys and the others against 2048 under the window, against
    1024 ... 8192 without: 15 and 36 squares of 1024, exponentials kept in the
    operands' type and scores computed in float32."""
    q = jax.ShapeDtypeStruct((2, 32, 8192, 128), jnp.bfloat16)
    window = partial(full_attention, causal=True, window=1024)
    full = partial(ring_attention, axis_name="sp", axis_size=1, causal=True)
    assert ring.kept_score_bytes(window, q) == 2 * 32 * 15 * 1024 * 1024 * 2
    assert ring.kept_score_bytes(full, q) == 2 * 32 * 36 * 1024 * 1024 * 2
    assert ring.tile_score_bytes(window, q) == 2 * 32 * 15 * 1024 * 1024 * 4
    assert ring.tile_score_bytes(full, q) == 2 * 32 * 36 * 1024 * 1024 * 4
    assert ring.tile_score_bytes(lambda q, k, v: q, q) == 0  # a callable it cannot read
    assert ring.tile_score_bytes(partial(ring_attention, axis_name="sp", axis_size=2, causal=True), q) == 0


# ---- the multi-block paths refuse ---------------------------------------------------------

def test_the_multi_block_paths_refuse_a_window_and_unequal_heads_in_one_line():
    from atomo_tpu.parallel.ring import blockwise_attention, ulysses_attention

    q, k, v, _ = operands((1, 4, 256, 8), 2)
    for fn, said in (
        (partial(ring_attention, axis_name="sp", axis_size=2, causal=True), "the ring over sp=2 runs the sequence in several"),
        (partial(blockwise_attention, causal=True), "blockwise_attention runs the sequence in several"),
        (partial(ulysses_attention, axis_name="sp", axis_size=2, causal=True), "ulysses_attention runs the sequence in several"),
    ):
        with pytest.raises(ValueError, match=said) as refused:
            fn(q, k, v)
        assert "\n" not in str(refused.value)
    with pytest.raises(ValueError, match="the ring over sp=2 runs the sequence in several"):
        ring_attention(q, q, q, axis_name="sp", axis_size=2, causal=True, window=64)
    with pytest.raises(ValueError, match="a window is a causal band"):
        full_attention(q, q, q, causal=False, window=64)
    with pytest.raises(ValueError, match="4 query heads are no whole number of 3"):
        full_attention(q, q[:, :3], q[:, :3], causal=True)


# ---- the kernels under the interpreter, head size 128 ------------------------------------

KERNEL_CASES = {
    # shape, key/value heads, window, (query rows, key rows) of the forward / dK,dV / dQ tiles
    "window-crosses-a-tile": ((1, 4, 512, 128), 2, 200, Blocks((128, 128), (128, 128), (128, 128))),
    "window-is-a-tile": ((1, 4, 512, 128), 1, 128, Blocks((128, 128), (128, 128), (128, 128))),
    "window-past-the-sequence": ((1, 4, 512, 128), 2, 600, Blocks((128, 128), (128, 128), (128, 128))),
    "one-tile": ((1, 4, 128, 128), 2, 64, Blocks((128, 128), (128, 128), (128, 128))),
    "no-window-grouped": ((1, 8, 256, 128), 2, 0, Blocks((128, 128), (128, 128), (128, 128))),
    "tiles-of-two-shapes": ((1, 4, 512, 128), 2, 300, Blocks((256, 128), (128, 256), (256, 256))),
    "two-sequences-equal-heads": ((2, 2, 256, 128), 2, 100, Blocks((128, 128), (128, 128), (128, 128))),
}


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_the_kernels_match_the_masked_oracle_forward_and_in_all_three_gradients(case):
    """dK and dV come out of the kernel at the key/value heads' count, summed
    over a group's query heads, as the oracle's repeated heads' cotangents
    sum when `jnp.repeat` is transposed."""
    from atomo_tpu.ops.attention_kernels import fused_attention

    shape, kv_heads, window, blocks = KERNEL_CASES[case]
    q, k, v, w = operands(shape, kv_heads, seed=1)
    fn = lambda q, k, v: fused_attention(q, k, v, True, 1 / math.sqrt(shape[-1]), blocks, True, window)  # noqa: E731
    got = forward_and_gradients(fn, w, q, k, v)
    want = forward_and_gradients(partial(oracle, window=window), w, q, k, v)
    for g, ref in zip(got, want, strict=True):
        assert g.shape == ref.shape and rel(g, ref) < 2e-6, (case, rel(g, ref))
    if window >= shape[2]:
        plain = forward_and_gradients(
            lambda q, k, v: fused_attention(q, k, v, True, 1 / math.sqrt(shape[-1]), blocks, True), w, q, k, v)
        for g, ref in zip(got, plain, strict=True):
            assert np.array_equal(np.asarray(g), np.asarray(ref))  # the same tiles, no mask on the lower edge


def test_the_dispatch_takes_the_kernels_for_a_window_and_grouped_heads(monkeypatch):
    """`full_attention` on a TPU (the platform patched; the interpreter runs
    the kernels here) in bfloat16 at head size 128: the result is the
    kernels', near the oracle, and the layer counts itself fused."""
    monkeypatch.setattr(ring, "_on_tpu", lambda: True)
    q, k, v, w = (x.astype(jnp.bfloat16) for x in operands((1, 4, 512, 128), 2, seed=2))
    fn = partial(full_attention, causal=True, window=200)
    assert ring.fused_blocks(q.shape, k.shape, q.dtype) == Blocks((512, 512), (512, 512), (512, 512))
    assert ring.fused_layers(fn, q) == 1 and ring.kept_score_bytes(fn, q) == 0
    assert ring.tile_score_bytes(fn, q) == 1 * 4 * 512 * 512 * 4  # one tile a head
    got = forward_and_gradients(fn, w, q, k, v)
    want = forward_and_gradients(partial(oracle, window=200), w.astype(jnp.float32),
                                 *(x.astype(jnp.float32) for x in (q, k, v)))
    for g, ref in zip(got, want, strict=True):
        assert g.dtype == jnp.bfloat16 and rel(g, ref) < 1e-2


@pytest.mark.parametrize("window,block,tiles", [
    (1024, (512, 512), 45), (0, (512, 512), 136),  # the forward kernel's at the cell's shape
    (1024, (1024, 1024), 15), (0, (1024, 1024), 36),  # the backward kernels'
    (1, (512, 512), 16), (513, (512, 512), 31), (512, (512, 512), 16 + 15), (8192, (512, 512), 136),
])
def test_the_live_tiles_are_those_the_band_touches(window, block, tiles):
    """8192 positions. Under a window of 1024 a forward query block of 512
    sees its own key block and the two before it (the key at distance 1024 is
    out, the one at 1023 in the block two back is in): 1 + 2 + 14 x 3 = 45 of
    the triangle's 136."""
    from atomo_tpu.ops.attention_kernels import _live_tiles, forward_tiles

    i_tab, j_tab = _live_tiles(8192, *block, True, False, window)
    assert len(i_tab) == len(j_tab) == tiles == forward_tiles(8192, block, window)
    bq, bk = block
    for i, j in zip(i_tab.tolist(), j_tab.tolist()):
        newest_query, oldest_query = (i + 1) * bq - 1, i * bq
        assert j * bk <= newest_query  # a key at or below a query
        assert not window or (j + 1) * bk - 1 > oldest_query - window  # and one inside its window
    # walked by key block, each for every query head of its group in turn
    i_tab, j_tab, g_tab = _live_tiles(8192, *block, True, True, window, group=8)
    assert len(i_tab) == 8 * tiles and sorted(zip(j_tab.tolist(), g_tab.tolist(), i_tab.tolist())) == list(
        zip(j_tab.tolist(), g_tab.tolist(), i_tab.tolist()))


# ---- the two rotary rules ---------------------------------------------------------------

FULL_RULE = CONFIG["rope_parameters"]["full_attention"]


def test_yarns_ramp_runs_from_pair_18_to_pair_35_with_the_published_factor():
    from atomo_tpu.models.rotary import Yarn
    from benchmarks.reference import mellum2_12b_a2_5b as reference

    yarn = Yarn(FULL_RULE["factor"], FULL_RULE["original_max_position_embeddings"], FULL_RULE["beta_fast"],
                FULL_RULE["beta_slow"])
    by_hand = lambda turns: 128 * math.log(8192 / (2 * math.pi * turns)) / (2 * math.log(500000))  # noqa: E731
    assert (math.floor(by_hand(32)), math.ceil(by_hand(1))) == (18, 35)
    assert yarn.ramp_bounds(128, 500000.0) == (18, 35) == reference.yarn_ramp_bounds(FULL_RULE, 128)
    assert yarn.scale == pytest.approx(0.1 * math.log(16) + 1) == pytest.approx(FULL_RULE["attention_factor"], rel=1e-12)
    assert Yarn(16, 8192, attention_factor=1.5).scale == 1.5
    assert Yarn(16, 64).ramp_bounds(16, 500000.0) == (0, 2)  # clipped below at pair 0


@pytest.mark.parametrize("position", [0, 1, 8191])
@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_both_rotary_rules_against_a_rotation_by_hand(kind, position):
    """Pair j of the 128 dimensions is (x_j, x_{j+64}). A sliding layer turns
    it by position x 500000^(-2j/128); a full layer by YaRN's frequency (the
    pairs below 18 keep theirs, those above 35 have it divided by 16, a ramp
    between) and multiplies cos and sin by 1.2772588722239782; program and
    reference alike, in float64 by hand."""
    from atomo_tpu.models.rotary import Rotary, Yarn, rotary, rotary_angles
    from benchmarks.reference import mellum2_12b_a2_5b as reference

    rule, dim, s = CONFIG["rope_parameters"][kind], 128, 8192
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (1, s, 2, dim)), np.float64)
    want = np.empty(dim)
    for j in range(dim // 2):
        freq, factor = 500000.0 ** (-2 * j / dim), 1.0
        if kind == "full_attention":
            ramp = min(max((j - 18) / (35 - 18), 0.0), 1.0)
            freq, factor = freq * (1 - ramp) + freq / 16 * ramp, 1.2772588722239782
        a, b = x[0, position, 1, j], x[0, position, 1, j + dim // 2]
        want[j] = factor * (a * np.cos(position * freq) - b * np.sin(position * freq))
        want[j + dim // 2] = factor * (b * np.cos(position * freq) + a * np.sin(position * freq))
    yarn = Yarn(16, 8192, 32, 1, 1.2772588722239782) if kind == "full_attention" else None
    mine = Rotary(500000.0, yarn)
    cos, sin = rotary_angles(jnp.arange(s), dim, mine.theta, mine.yarn)
    got = rotary(jnp.asarray(x, jnp.float32), cos[:, None, :], sin[:, None, :])
    # float32 angles at position 8191 carry 8191 x 6e-8 of their size: 5e-4 rad on the fastest pair
    tolerance = 2e-5 if position < 2 else 4e-3
    assert np.allclose(got[0, position, 1], want, atol=tolerance)
    assert np.allclose(reference.rotate(jnp.asarray(x, jnp.float32), rule)[0, position, 1], want, atol=tolerance)
    if position == 0:
        scale = 1.0 if yarn is None else yarn.scale
        assert np.allclose(np.asarray(got[0, 0]), scale * x[0, 0], rtol=1e-6)


def test_the_latent_attentions_rotation_is_the_one_it_was():
    """`rotary_angles` without a YaRN record is models/moe.py's function of
    PR 33, importable from there as before."""
    from atomo_tpu.models import moe, rotary

    assert moe.rotary is rotary.rotary and moe.rotary_angles is rotary.rotary_angles
    cos, sin = rotary.rotary_angles(jnp.arange(5), 8, 1e6)
    freq = 1e6 ** (-jnp.arange(0, 8, 2, dtype=jnp.float32) / 8)
    assert np.array_equal(np.asarray(cos), np.asarray(jnp.cos(jnp.arange(5, dtype=jnp.float32)[:, None] * freq)))
    assert np.array_equal(np.asarray(sin), np.asarray(jnp.sin(jnp.arange(5, dtype=jnp.float32)[:, None] * freq)))


# ---- the step's counters at the cell's real shapes -----------------------------------------

def _cell_step_metrics():
    """The lm step of mellum2-1chip-dense traced on shapes alone (no array of
    the model's size is made): the names of its metrics, and the value of the
    constant ones, read from the jaxpr pruned to that output."""
    from jax.interpreters import partial_eval as pe

    from atomo_tpu.cli import _lm_block_config, build_parser
    from atomo_tpu.models.transformer import TransformerLM
    from atomo_tpu.parallel.lm import make_lm_train_step
    from atomo_tpu.parallel.mesh import make_mesh
    from atomo_tpu.training import create_state, make_optimizer
    from benchmarks.run import Data, program_argv

    data = Data(ROOT / "BENCHMARK.json")
    entry = data.cell("mellum2-1chip-dense")
    config, traffic = data.config(entry["config"]), data.json("traffic", entry["traffic"])
    args = build_parser().parse_args(program_argv(config, traffic, 0)[0])
    cfg = dict(vocab_size=args.vocab_size, max_len=args.seq_len, width=args.width,
               depth=args.depth, num_heads=args.num_heads, **_lm_block_config(args))
    mesh = make_mesh(1, axes=(("dp", 1), ("sp", 1)))
    opt = make_optimizer("sgd", lr=args.lr, momentum=args.momentum)
    step = make_lm_train_step(cfg, opt, mesh, compute_dtype=jnp.bfloat16 if args.bf16 else None)
    sample = jnp.zeros((1, args.seq_len), jnp.int32)
    state = jax.eval_shape(
        lambda key: create_state(TransformerLM(**cfg), opt, key, sample), jax.random.PRNGKey(0)
    )
    tokens = jax.ShapeDtypeStruct((args.batch_size, args.seq_len), jnp.int32)
    closed, out = jax.make_jaxpr(step, return_shape=True)(state, jax.random.PRNGKey(0), tokens)
    names = [jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_flatten_with_path(out)[0]]

    def constant(name):
        pruned, used = pe.dce_jaxpr(closed.jaxpr, [n == f"[1]['{name}']" for n in names])
        assert not any(used), f"{name} depends on the step's inputs"
        return float(jax.core.eval_jaxpr(pruned, closed.consts)[0])

    return {n[5:-2] for n in names if n.startswith("[1]")}, constant


def test_on_a_tpu_the_cells_step_computes_17344_mib_of_score_tiles_in_4_fused_layers(monkeypatch):
    """The platform patched, the cell's step traced on shapes. Forward tiles
    of 512: 45 a (sequence, head) in each of the three window layers and 136
    in the full one, (3 x 45 + 136) x 1 MiB x 64 = 17,344 MiB. **A window
    computed as a mask over every causal tile would read 4 x 136 x 64 =
    34,816 and fail here.** All four layers run the kernels and keep no
    exponentials."""
    from atomo_tpu.ops import attention_kernels

    monkeypatch.setattr(ring, "_on_tpu", lambda: True)
    # traced as the chip's compiler gets them (the interpreter's calls carry
    # an effect, which no pruning removes); nothing is lowered here
    monkeypatch.setattr(attention_kernels, "interpret_requested", lambda: False)
    names, constant = _cell_step_metrics()
    assert constant("attn_tile_score_bytes") == (3 * 45 + 136) * 64 * 2**20 == 17344 * 2**20
    assert constant("attn_tile_score_bytes") != 4 * 136 * 64 * 2**20
    assert constant("attn_fused_layers") == 4 and "attn_score_bytes" not in names
    assert {"moe_held_row_bytes", "moe_max_expert_row_bytes"} <= names


def test_off_the_tpu_the_cells_step_counts_the_query_blocks_band():
    """The jnp path: 8 query blocks of 1024 against 2048 keys (the first
    1024) in a window layer, against their prefixes in the full one:
    (3 x 15 + 36) squares of 1024 x 64 (sequence, head) x 4 B = 20,736 MiB
    computed, half of it kept as bfloat16 exponentials."""
    names, constant = _cell_step_metrics()
    assert constant("attn_tile_score_bytes") == (3 * 15 + 36) * 64 * 4 * 2**20
    assert constant("attn_score_bytes") == (3 * 15 + 36) * 64 * 2 * 2**20
    assert "attn_fused_layers" not in names
