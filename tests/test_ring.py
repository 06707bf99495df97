"""Ring attention + sequence-parallel LM tests on the CPU-simulated mesh."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from atomo_tpu.codecs import SvdCodec
from atomo_tpu.models.transformer import TransformerLM, lm_loss
from atomo_tpu.parallel import make_mesh
from atomo_tpu.parallel.lm import make_lm_train_step, shard_tokens
from atomo_tpu.parallel.ring import (
    full_attention,
    make_sequence_parallel_attention,
    ring_attention,
)
from atomo_tpu.training import create_state, make_optimizer


slow = pytest.mark.slow  # heavy multi-device compile/parity runs; deselect with -m "not slow"


@slow
@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_full_attention(causal):
    """Exactness: ring attention over 4 sequence shards == full attention."""
    mesh = make_mesh(4, axes=(("sp", 4),))
    b, h, s, d = 2, 3, 32, 8
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, h, s, d), jnp.float32)
    k = jax.random.normal(kk, (b, h, s, d), jnp.float32)
    v = jax.random.normal(kv, (b, h, s, d), jnp.float32)

    expected = full_attention(q, k, v, causal=causal)
    ring = make_sequence_parallel_attention(mesh, "sp", causal=causal)
    got = ring(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)


@slow
def test_ring_attention_single_shard_degenerates():
    """axis_size=1: ring == full attention trivially (no ppermute traffic)."""
    mesh = make_mesh(1, axes=(("sp", 1),))
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 16, 4))
    out = make_sequence_parallel_attention(mesh, "sp", causal=True)(q, q, q)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(full_attention(q, q, q, causal=True)), atol=2e-5
    )


def _lm_cfg(max_len=64):
    return dict(vocab_size=32, max_len=max_len, width=32, depth=2, num_heads=2)


@slow
def test_transformer_forward_shapes():
    model = TransformerLM(**_lm_cfg())
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = model.init({"params": jax.random.PRNGKey(0)}, tokens)["params"]
    logits = model.apply({"params": params}, tokens)
    assert logits.shape == (2, 16, 32)
    assert np.isfinite(float(lm_loss(logits, tokens)))


@slow
def test_lm_dp_sp_step_runs_and_compresses():
    """2x4 mesh: dp-compressed + sp-ring training step executes and the
    payload bytes beat dense."""
    mesh = make_mesh(8, axes=(("dp", 2), ("sp", 4)))
    cfg = _lm_cfg(max_len=64)
    opt = make_optimizer("sgd", lr=0.1, momentum=0.9)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (4, 64), 0, 32)

    model = TransformerLM(**cfg)
    state = create_state(model, opt, jax.random.PRNGKey(1), tokens)
    step = make_lm_train_step(cfg, opt, mesh, SvdCodec(rank=2))
    st = shard_tokens(mesh, tokens)
    state2, metrics = step(state, jax.random.PRNGKey(2), st)
    assert int(state2.step) == 1
    assert np.isfinite(float(metrics["loss"]))
    assert int(metrics["msg_bytes"]) < int(metrics["dense_bytes"])


@slow
def test_lm_sharded_loss_matches_unsharded():
    """The dp x sp dense step computes the same loss as a single-device
    forward on the full batch (boundary-token handling is exact)."""
    mesh = make_mesh(8, axes=(("dp", 2), ("sp", 4)))
    cfg = _lm_cfg(max_len=64)
    opt = make_optimizer("sgd", lr=0.0)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 64), 0, 32)
    model = TransformerLM(**cfg)
    state = create_state(model, opt, jax.random.PRNGKey(1), tokens)

    logits = model.apply({"params": state.params}, tokens)
    expected = float(lm_loss(logits, tokens))

    step = make_lm_train_step(cfg, opt, mesh, codec=None)
    _, metrics = step(state, jax.random.PRNGKey(4), shard_tokens(mesh, tokens))
    assert abs(float(metrics["loss"]) - expected) < 2e-3, (
        float(metrics["loss"]),
        expected,
    )


@slow
def test_lm_training_learns():
    """A few compressed dp x sp steps reduce loss on a repeating pattern."""
    mesh = make_mesh(8, axes=(("dp", 2), ("sp", 4)))
    cfg = _lm_cfg(max_len=64)
    opt = make_optimizer("adam", lr=0.01)
    base = jnp.tile(jnp.arange(8, dtype=jnp.int32), 8)[None, :]
    tokens = jnp.tile(base, (4, 1))  # (4, 64) periodic sequence
    model = TransformerLM(**cfg)
    state = create_state(model, opt, jax.random.PRNGKey(1), tokens)
    step = make_lm_train_step(cfg, opt, mesh, SvdCodec(rank=2))
    st = shard_tokens(mesh, tokens)
    losses = []
    for i in range(10):
        state, m = step(state, jax.random.PRNGKey(5), st)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.8, losses


@slow
@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full_attention(causal):
    """Exactness of the all-to-all strategy: ulysses over 4 sequence shards
    == full attention (heads divisible by the axis)."""
    mesh = make_mesh(4, axes=(("sp", 4),))
    b, h, s, d = 2, 4, 32, 8
    key = jax.random.PRNGKey(7)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, h, s, d), jnp.float32)
    k = jax.random.normal(kk, (b, h, s, d), jnp.float32)
    v = jax.random.normal(kv, (b, h, s, d), jnp.float32)

    expected = full_attention(q, k, v, causal=causal)
    uly = make_sequence_parallel_attention(mesh, "sp", causal=causal, impl="ulysses")
    np.testing.assert_allclose(np.asarray(uly(q, k, v)), np.asarray(expected), atol=2e-5)


@slow
def test_ulysses_rejects_indivisible_heads():
    from atomo_tpu.parallel.ring import ulysses_attention

    mesh = make_mesh(4, axes=(("sp", 4),))
    q = jax.random.normal(jax.random.PRNGKey(8), (1, 3, 32, 4))  # 3 heads, 4 chips
    fn = make_sequence_parallel_attention(mesh, "sp", impl="ulysses")
    with pytest.raises(ValueError, match="divisible"):
        fn(q, q, q)


@slow
def test_lm_ulysses_step_matches_ring_loss():
    """The dp x sp LM step computes the same loss under either
    sequence-parallel strategy (both are exact attention)."""
    mesh = make_mesh(8, axes=(("dp", 2), ("sp", 4)))
    cfg = dict(_lm_cfg(max_len=64), num_heads=4)  # ulysses: heads % sp == 0
    opt = make_optimizer("sgd", lr=0.0)
    tokens = jax.random.randint(jax.random.PRNGKey(9), (4, 64), 0, 32)
    model = TransformerLM(**cfg)
    st = shard_tokens(mesh, tokens)
    losses = {}
    for impl in ("ring", "ulysses"):
        # fresh state per impl: the step donates its input state buffers
        state = create_state(model, opt, jax.random.PRNGKey(1), tokens)
        step = make_lm_train_step(cfg, opt, mesh, codec=None, attn_impl=impl)
        _, m = step(state, jax.random.PRNGKey(10), st)
        losses[impl] = float(m["loss"])
    assert abs(losses["ring"] - losses["ulysses"]) < 2e-4, losses


@slow
def test_blockwise_matches_full_attention():
    """The local blockwise kernel (ulysses' inner loop) never builds the
    S x S matrix yet must equal full attention, incl. causal + a block
    size that does not divide S."""
    from atomo_tpu.parallel.ring import blockwise_attention

    q = jax.random.normal(jax.random.PRNGKey(11), (2, 2, 50, 8))
    k = jax.random.normal(jax.random.PRNGKey(12), (2, 2, 50, 8))
    v = jax.random.normal(jax.random.PRNGKey(13), (2, 2, 50, 8))
    for causal in (False, True):
        expected = full_attention(q, k, v, causal=causal)
        got = blockwise_attention(q, k, v, causal=causal, block_size=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)


@slow
def test_lm_ulysses_gradients_match_ring():
    """GRADIENT parity between the strategies: one real (lr > 0) training
    step from identical state must land on (numerically) identical params —
    a wrong transpose in the all_to_all backward would diverge here."""
    mesh = make_mesh(8, axes=(("dp", 2), ("sp", 4)))
    cfg = dict(_lm_cfg(max_len=64), num_heads=4)
    opt = make_optimizer("sgd", lr=0.1)
    tokens = jax.random.randint(jax.random.PRNGKey(14), (4, 64), 0, 32)
    model = TransformerLM(**cfg)
    st = shard_tokens(mesh, tokens)
    results = {}
    for impl in ("ring", "ulysses"):
        state = create_state(model, opt, jax.random.PRNGKey(1), tokens)
        step = make_lm_train_step(cfg, opt, mesh, codec=None, attn_impl=impl)
        new_state, _ = step(state, jax.random.PRNGKey(15), st)
        results[impl] = jax.device_get(new_state.params)
    ring_leaves = jax.tree_util.tree_leaves(results["ring"])
    uly_leaves = jax.tree_util.tree_leaves(results["ulysses"])
    for a, b in zip(ring_leaves, uly_leaves):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


@slow
def test_make_lm_train_step_rejects_unknown_impl():
    mesh = make_mesh(8, axes=(("dp", 2), ("sp", 4)))
    with pytest.raises(ValueError, match="attn_impl"):
        make_lm_train_step(_lm_cfg(), make_optimizer("sgd", lr=0.1), mesh,
                           attn_impl="ulises")


@slow
def test_lm_bf16_step_runs_and_keeps_f32_state():
    """Mixed precision on the dp x sp LM path: bf16 compute, f32 master."""
    mesh = make_mesh(8, axes=(("dp", 2), ("sp", 4)))
    cfg = _lm_cfg(max_len=64)
    opt = make_optimizer("sgd", lr=0.1)
    tokens = jax.random.randint(jax.random.PRNGKey(20), (4, 64), 0, 32)
    model = TransformerLM(**cfg)
    state = create_state(model, opt, jax.random.PRNGKey(1), tokens)
    step = make_lm_train_step(
        cfg, opt, mesh, SvdCodec(rank=2), compute_dtype=jnp.bfloat16
    )
    state, m = step(state, jax.random.PRNGKey(21), shard_tokens(mesh, tokens))
    assert np.isfinite(float(m["loss"]))
    for leaf in jax.tree_util.tree_leaves(state.params):
        assert leaf.dtype == jnp.float32


@slow
def test_lm_sharded_grads_match_unsharded_oracle():
    """Regression: one dense dp=1 x sp=4 update step lands on the same params
    as single-device AD + SGD. Catches the sp-axis gradient inflation class
    of bug (grads psum'd over sp where the psum-transposes-to-psum rule
    demands a pmean: the sharded step would silently train with an
    effective LR of n_sp x the configured one)."""
    import optax

    cfg = _lm_cfg(max_len=16)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 16), 0, 32)
    model = TransformerLM(**cfg)
    opt = optax.sgd(0.1)
    params0 = model.init(jax.random.PRNGKey(0), tokens)["params"]

    def loss_fn(p):
        return lm_loss(model.apply({"params": p}, tokens), tokens)

    grads = jax.grad(loss_fn)(params0)
    want = jax.device_get(
        optax.apply_updates(params0, opt.update(grads, opt.init(params0), params0)[0])
    )

    mesh = make_mesh(4, axes=(("dp", 1), ("sp", 4)))
    state = create_state(model, opt, jax.random.PRNGKey(0), tokens)
    step = make_lm_train_step(cfg, opt, mesh, codec=None)
    state2, _ = step(state, jax.random.PRNGKey(1), shard_tokens(mesh, tokens))
    got = jax.device_get(state2.params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4
        ),
        got,
        want,
    )


# --- bfloat16 inputs: operands in bf16, softmax and accumulation in float32


def _attention_impl(impl, causal, scale):
    """(q, k, v) -> out for one of the five ways into the shared block."""
    from atomo_tpu.parallel.ring import ATTENTION_IMPLS, blockwise_attention

    if impl == "full":
        return partial(full_attention, causal=causal, scale=scale)
    if impl == "blockwise":  # 32 positions in blocks of 12: a padded last block
        return partial(blockwise_attention, causal=causal, scale=scale, block_size=12)
    name, n = {"ring1": ("ring", 1), "ring4": ("ring", 4), "ulysses": ("ulysses", 4)}[impl]
    mesh = make_mesh(n, axes=(("sp", n),))
    fn = partial(ATTENTION_IMPLS[name], axis_name="sp", axis_size=n, causal=causal, scale=scale)
    spec = P(None, None, "sp", None)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False)


def _forward_and_grads(fn, q, k, v, w):
    out = jax.jit(fn)(q, k, v)
    grads = jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w), argnums=(0, 1, 2)))(q, k, v)
    return {"forward": [out], "grads": list(grads)}


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# Readings on the CPU over three seeds at this shape, relative to the norm of
# the float32 answer: forward 1.7e-3 to 1.8e-3 (the output's own rounding to
# bf16), gradients 2.8e-3 to 4.8e-3; a scale 1.25x off reads 0.14 to 0.33.
_BF16_TOL = {"forward": 1e-2, "grads": 2e-2}


@pytest.mark.parametrize("what", ["forward", "grads"])
@pytest.mark.parametrize("impl", ["full", "ring1", "ring4", "blockwise", "ulysses"])
def test_bf16_attention_matches_float32_oracle(impl, what):
    """bfloat16 q, k, v through every entry point against full_attention on
    the same values in float32, forward and the gradients of a scalar loss;
    a deliberately wrong scale must fail the same comparison."""
    b, h, s, d = 2, 4, 32, 8
    q, k, v, w = (
        jax.random.normal(key, (b, h, s, d), jnp.float32)
        for key in jax.random.split(jax.random.PRNGKey(0), 4)
    )
    lo = [x.astype(jnp.bfloat16) for x in (q, k, v)]
    want = _forward_and_grads(
        _attention_impl("full", True, None), *(x.astype(jnp.float32) for x in lo), w
    )[what]
    got = _forward_and_grads(_attention_impl(impl, True, None), *lo, w)[what]
    wrong = _forward_and_grads(_attention_impl(impl, True, 1.25 / d**0.5), *lo, w)[what]
    for g, x, ref in zip(got, wrong, want):
        assert g.dtype == jnp.bfloat16 and g.shape == ref.shape
        assert _rel(g, ref) < _BF16_TOL[what], (impl, what, _rel(g, ref))
        assert _rel(x, ref) > 5 * _BF16_TOL[what], (impl, what, _rel(x, ref))


def _kept_for_backward(jaxpr, shape, dtype):
    """Variables of ``shape`` and ``dtype`` that an equation of the forward
    pass writes and an equation of the backward pass (autodiff brackets it
    ``transpose(...)`` in the name stack) reads: the residuals of that size.
    Walks into sub-jaxprs (pjit, shard_map, scan)."""
    kept, forward = [], {}
    for eqn in jaxpr.eqns:
        backward = "transpose(" in str(eqn.source_info.name_stack)
        for var in eqn.invars:
            if backward and id(var) in forward:
                kept.append((forward[id(var)], str(eqn.source_info.name_stack)))
        for var in eqn.outvars:
            aval = var.aval
            if not backward and getattr(aval, "shape", None) == shape and aval.dtype == dtype:
                forward[id(var)] = eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            kept += _kept_for_backward(sub, shape, dtype)
    return kept


def _attention_dots(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and "attention" in str(eqn.source_info.name_stack):
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _attention_dots(sub)


def _lm_step_jaxpr(compute_dtype, attention_fn=None):
    """The dp=1 x sp=1 lm step (the one-chip cell's path) at a tiny size
    whose head size (8) differs from its sequence (16)."""
    cfg = dict(vocab_size=32, max_len=16, width=16, depth=2, num_heads=2)
    mesh = make_mesh(1, axes=(("dp", 1), ("sp", 1)))
    opt = make_optimizer("sgd", lr=0.1)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (3, 16), 0, 32)
    state = create_state(TransformerLM(**cfg), opt, jax.random.PRNGKey(1), tokens)
    step = make_lm_train_step(cfg, opt, mesh, codec=None, compute_dtype=compute_dtype)
    jaxpr = jax.make_jaxpr(step)(state, jax.random.PRNGKey(2), shard_tokens(mesh, tokens))
    return jaxpr.jaxpr, (3, 2, 16, 16)


def test_bf16_lm_step_attention_engages():
    """What stands in for a counter: in the lowered --bf16 step every
    contraction inside the attention scope, forward and backward, takes
    bfloat16 operands and accumulates in float32, and the backward pass
    reads no float32 (B, H, S, S) array that the forward pass wrote."""
    jaxpr, scores = _lm_step_jaxpr(jnp.bfloat16)
    dots = list(_attention_dots(jaxpr))
    assert len(dots) == 2 * 6  # two layers: two contractions, four in the backward pass
    for eqn in dots:
        assert [v.aval.dtype for v in eqn.invars] == [jnp.bfloat16] * 2, eqn
        assert eqn.params["preferred_element_type"] == jnp.float32, eqn
        assert eqn.params["precision"] is None, eqn  # one MXU pass
    assert _kept_for_backward(jaxpr, scores, jnp.float32) == []
    kept = _kept_for_backward(jaxpr, scores, jnp.bfloat16)
    assert kept and all(name == "convert_element_type" for name, _ in kept), kept


def test_float32_lm_step_keeps_float32_attention():
    """The rule reads the dtype: without --bf16 the same step contracts
    float32 operands at the highest precision and keeps float32
    exponentials, so the check above has something to tell apart."""
    jaxpr, scores = _lm_step_jaxpr(None)
    dots = list(_attention_dots(jaxpr))
    assert len(dots) == 2 * 6
    for eqn in dots:
        assert [v.aval.dtype for v in eqn.invars] == [jnp.float32] * 2, eqn
        assert eqn.params["precision"] == (jax.lax.Precision.HIGHEST,) * 2, eqn
    assert _kept_for_backward(jaxpr, scores, jnp.float32) != []


# --- causal attention in query blocks: each block against its own key prefix


def _parent_attention(impl, causal):
    """PR 27's one-block entry points, frozen: what the cut path must equal in
    value, and what it must still lower to where nothing is cut."""
    from atomo_tpu.parallel import ring

    def attention(q, k, v):
        scale = 1.0 / (q.shape[-1] ** 0.5)
        q, k, v = ring._common_dtype(q, k, v)
        if impl == "ring1":
            pos = jnp.arange(q.shape[-2])
            bias = ring._causal_bias(pos, pos) if causal else None
        else:
            bias = ring._causal_bias(jnp.arange(q.shape[-2]), jnp.arange(k.shape[-2])) if causal else None
        return ring._one_block_attention(q, k, v, bias, scale)

    return attention


def _this_attention(impl, causal):
    def attention(q, k, v):
        if impl == "ring1":
            return ring_attention(q, k, v, axis_name="sp", axis_size=1, causal=causal)
        return full_attention(q, k, v, causal=causal)

    return attention


@pytest.mark.parametrize("seq,blocks", [
    (64, 1), (128, 1), (200, 1), (255, 1), (256, 2), (384, 3), (512, 4), (1000, 1),
    (1024, 8), (1152, 3), (2048, 8), (4096, 8), (65536, 8),
])
def test_the_number_of_query_blocks_is_capped_whatever_the_length(seq, blocks):
    from atomo_tpu.parallel.ring import MAX_QUERY_BLOCKS, causal_query_blocks

    n = causal_query_blocks(seq, seq)
    assert n == blocks <= MAX_QUERY_BLOCKS == 8
    assert n == 1 or (seq // n) % 128 == 0
    assert causal_query_blocks(seq, 2 * seq) == 1  # queries against a longer memory: not cut


@pytest.mark.parametrize("impl", ["full", "ring1"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("seq", [256, 1024, 4096])
def test_causal_blocks_match_the_one_block_oracle(seq, dtype, impl):
    """2 blocks of 128 at 256, 8 of 128 at 1024, 8 of 512 at 4096, forward
    and the three gradients, against the one-block program on the same
    values in float32: float32 differs by the order of float32 sums alone,
    bfloat16 within the tolerances of the one-block bfloat16 path."""
    from atomo_tpu.parallel.ring import causal_query_blocks

    assert causal_query_blocks(seq, seq) > 1
    q, k, v, w = (
        jax.random.normal(key, (1, 2, seq, 8), jnp.float32)
        for key in jax.random.split(jax.random.PRNGKey(seq), 4)
    )
    lo = [x.astype(dtype) for x in (q, k, v)]
    want = _forward_and_grads(_parent_attention(impl, True), *(x.astype(jnp.float32) for x in lo), w)
    got = _forward_and_grads(_this_attention(impl, True), *lo, w)
    tol = {"forward": 1e-5, "grads": 1e-5} if dtype == jnp.float32 else _BF16_TOL
    for what in ("forward", "grads"):
        for g, ref in zip(got[what], want[what], strict=True):
            assert g.dtype == dtype and g.shape == ref.shape
            assert _rel(g, ref) < tol[what], (seq, impl, what, _rel(g, ref))


def _lowered(fn, *shapes, dtype=jnp.bfloat16):
    args = [jax.ShapeDtypeStruct(shape, dtype) for shape in shapes]
    return jax.jit(fn).lower(*args).as_text()


@pytest.mark.parametrize("impl,causal,sq,sk", [
    ("full", True, 128, 128), ("ring1", True, 128, 128),  # shorter than two blocks
    ("full", True, 200, 200), ("ring1", True, 1000, 1000),  # no multiple of 128
    ("full", False, 1024, 1024), ("ring1", False, 1024, 1024),  # nothing masked
    ("full", True, 256, 512), ("full", True, 1024, 256),  # queries and keys of different lengths
])
def test_what_is_not_cut_lowers_to_the_parents_text(impl, causal, sq, sk):
    shapes = ((2, 2, sq, 8), (2, 2, sk, 8), (2, 2, sk, 8))
    assert _lowered(_this_attention(impl, causal), *shapes) == _lowered(_parent_attention(impl, causal), *shapes)
    # and the comparison can tell: the same entry point where it is cut
    cut = ((2, 2, 256, 8),) * 3
    assert _lowered(_this_attention(impl, True), *cut) != _lowered(_parent_attention(impl, True), *cut)


@pytest.mark.parametrize("impl", ["full", "ring1"])
def test_the_attention_program_is_as_large_at_4096_as_at_1024(impl):
    """Guards the step's set-up off the chip: every query block is a set of
    contractions of its own shapes, so their number must not follow the
    sequence (one block with its gradient: 6 dot_general)."""

    def dots(fn, seq):
        grad = jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)), argnums=(0, 1, 2))
        return _lowered(grad, *((1, 2, seq, 8),) * 3).count("stablehlo.dot_general")

    assert dots(_parent_attention(impl, True), 1024) == 6
    at_1024, at_4096 = (dots(_this_attention(impl, True), seq) for seq in (1024, 4096))
    assert at_1024 == at_4096 == 6 * 8 <= 48


# --- attn_score_bytes: what the blocks left of the score square, counted by the step


def _cell_step_metrics(cell, **overrides):
    """The lm step of a benchmark cell traced on shapes alone (no array of
    the model's size is made): the names of its metrics, and the value of
    the constant ones, read from the jaxpr pruned to that output."""
    from pathlib import Path

    from jax.interpreters import partial_eval as pe

    from atomo_tpu.cli import _lm_block_config, build_parser
    from benchmarks.run import Data, program_argv

    data = Data(Path(__file__).resolve().parents[1] / "BENCHMARK.json")
    entry = data.cell(cell)
    config, traffic = data.config(entry["config"]), data.json("traffic", entry["traffic"])
    args = build_parser().parse_args(program_argv({**config, **overrides}, traffic, 0)[0])
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


@pytest.mark.parametrize("cell,mib", [("gpt2m-1chip-dense", 1728.0), ("olmohybrid-1chip-dense", 540.0)])
def test_attn_score_bytes_at_the_cells_shapes(cell, mib):
    """24 layers x 4 x 16 x 1024 x 1024 x 2 B x 9/16 = 1728 MiB, and one layer
    of 30 x 4096 x 4096 x 2 B x 9/16 = 540 MiB: 8 blocks keep (8+1)/16 of
    the square in both cells."""
    names, constant = _cell_step_metrics(cell)
    assert "attn_score_bytes" in names
    assert constant("attn_score_bytes") == mib * 2**20


@pytest.mark.parametrize("cell,layers", [
    ("glm47flash-1chip-dense", 6), ("olmohybrid-1chip-dense", 1), ("gpt2m-1chip-dense", 24),
])
def test_on_a_tpu_the_cells_steps_count_their_fused_layers_and_keep_no_exponentials(cell, layers, monkeypatch):
    """The platform patched, the cell's step traced on shapes: every causal
    softmax layer whose head size ``FUSED_BLOCKS`` lists (5 layers and the
    prediction module of GLM, Olmo's one full layer; GPT-2's 24 once 64-wide
    heads are listed) runs the kernels and keeps no exponentials, and a
    head size left out keeps the jnp blocks and their count. Off the TPU
    (the tests around this one) the same steps count no fused layer."""
    from atomo_tpu.ops import attention_kernels
    from atomo_tpu.parallel import ring as ring_mod

    head = {"glm47flash-1chip-dense": 256, "olmohybrid-1chip-dense": 128, "gpt2m-1chip-dense": 64}[cell]
    assert "attn_fused_layers" not in _cell_step_metrics(cell)[0]
    monkeypatch.setattr(ring_mod, "_on_tpu", lambda: True)
    # traced as the chip's compiler gets them (the interpreter's calls carry
    # an effect, which no pruning removes); nothing is lowered here
    monkeypatch.setattr(attention_kernels, "interpret_requested", lambda: False)
    names, constant = _cell_step_metrics(cell)
    if head in ring_mod.FUSED_BLOCKS:
        assert constant("attn_fused_layers") == layers and "attn_score_bytes" not in names
    else:
        assert "attn_fused_layers" not in names and "attn_score_bytes" in names


def test_attn_score_bytes_is_absent_where_no_full_layer_runs():
    names, _ = _cell_step_metrics("olmohybrid-1chip-dense", layer_pattern="linear,linear,linear,linear")
    assert "lin_state_bytes" in names and "attn_score_bytes" not in names


@pytest.mark.parametrize("fn,want", [
    (partial(full_attention, causal=True), 2 * 3 * 128 * 128 * 3 * 2),
    (partial(ring_attention, axis_name="sp", axis_size=1, causal=True), 2 * 3 * 128 * 128 * 3 * 2),
    (partial(full_attention, causal=False), 2 * 3 * 256 * 256 * 2),
    (partial(ring_attention, axis_name="sp", axis_size=2, causal=True), 0),  # the ring's loop: not counted here
    (lambda q, k, v: full_attention(q, k, v, causal=True), 0),  # a callable it cannot read
])
def test_kept_score_bytes_counts_the_one_block_paths_it_knows(fn, want):
    from atomo_tpu.parallel.ring import kept_score_bytes

    q = jax.ShapeDtypeStruct((2, 3, 256, 8), jnp.bfloat16)
    assert kept_score_bytes(fn, q) == want
    if want:  # and that is what autodiff keeps: the residuals of score size, from the vjp's own closure
        kept = jax.tree_util.tree_leaves(jax.eval_shape(lambda *a: jax.vjp(fn, *a)[1], q, q, q))
        assert sum(x.size * x.dtype.itemsize for x in kept if x.shape[-1] >= 128) == want
