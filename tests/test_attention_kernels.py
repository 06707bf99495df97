"""The fused attention kernels, forward and backward, vs the jnp oracles (TPU
interpreter on CPU), and the rule by which ``parallel.ring`` takes them."""

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from atomo_tpu.ops.attention_kernels import flash_attention, fused_attention
from atomo_tpu.parallel import ring
from atomo_tpu.parallel.ring import (
    Blocks, blockwise_attention, full_attention, fused_blocks, ring_attention,
)


def _qkv(key, b=2, h=3, s=64, d=16):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(key), 3)
    return (
        jax.random.normal(kq, (b, h, s, d), jnp.float32),
        jax.random.normal(kk, (b, h, s, d), jnp.float32),
        jax.random.normal(kv, (b, h, s, d), jnp.float32),
    )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("blocks", [(16, 16), (32, 16), (64, 64)])
def test_flash_matches_full_attention(causal, blocks):
    q, k, v = _qkv(0)
    bq, bk = blocks
    got = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    want = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_non_tiling_falls_back():
    q, k, v = _qkv(1, s=50)  # 50 % 16 != 0 -> blockwise fallback
    got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_gradients_match_full_attention():
    q, k, v = _qkv(2, b=1, h=2, s=32, d=8)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, block_q=16, block_k=16) ** 2
        )

    def loss_full(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_flash_bf16_inputs():
    q, k, v = _qkv(3, s=32, d=8)
    q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    want = blockwise_attention(q, k, v, causal=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=3e-2
    )


def test_ulysses_with_flash_local_attention_matches_full():
    """sp=4 Ulysses with the Pallas flash kernel as its local attention ==
    unsharded full attention (collective swap + fused kernel compose)."""
    from jax.sharding import PartitionSpec as P

    from atomo_tpu.parallel.mesh import make_mesh
    from atomo_tpu.parallel.ring import ulysses_attention

    mesh = make_mesh(4, axes=(("sp", 4),))
    q, k, v = _qkv(4, b=2, h=4, s=64, d=16)
    want = full_attention(q, k, v, causal=True)
    fn = jax.jit(
        jax.shard_map(
            lambda q, k, v: ulysses_attention(
                q, k, v, axis_name="sp", axis_size=4, causal=True,
                block_size=16, local_impl="flash",
            ),
            mesh=mesh,
            in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None),
            check_vma=False,
        )
    )
    got = fn(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_ulysses_rejects_unknown_local_impl():
    from atomo_tpu.parallel.ring import ulysses_attention

    q, k, v = _qkv(5, h=4, s=16, d=8)
    with pytest.raises(ValueError, match="local_impl"):
        # axis-free path never reached: validation precedes collectives
        ulysses_attention(
            q, k, v, axis_name="sp", axis_size=1, causal=True,
            local_impl="nope",
        )


# --- the three kernels against the float32 one-block oracle (PR 34)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _oracle(q, k, v):
    """ring._one_block_attention on the same values in float32."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    pos = jnp.arange(q.shape[-2])
    return ring._one_block_attention(q, k, v, ring._causal_bias(pos, pos), 1.0 / q.shape[-1] ** 0.5)


@lru_cache(maxsize=None)
def _kernels_and_oracle(d, block, s=384):
    """(out, dq, dk, dv) of the kernels on bfloat16 operands, causal, and of
    the oracle: three blocks a side at (128, 128), so a tile above the
    diagonal (left out), one on it (masked) and one below (whole) all occur."""
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(d, b=1, h=2, s=s, d=d))
    w = jax.random.normal(jax.random.PRNGKey(6), q.shape, jnp.float32)
    blocks = Blocks(block, block, block)

    def both(fn):
        grads = jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w), argnums=(0, 1, 2))(q, k, v)
        return [fn(q, k, v), *grads]

    got = both(lambda q, k, v: fused_attention(q, k, v, True, 1.0 / d**0.5, blocks, True))
    return got, both(_oracle), v


@pytest.mark.parametrize("which", ["out", "dq", "dk", "dv"])
@pytest.mark.parametrize("d", [64, 128, 256])
def test_fused_kernels_match_the_float32_oracle(d, which):
    """bfloat16 operands against float32: the output differs by its own
    rounding (2e-3 of the norm), a gradient by 3.5e-3, as the blocked jnp
    path reads against the same oracle; a scale 1.25x off reads 0.1."""
    at = ["out", "dq", "dk", "dv"].index(which)
    got, want, _ = _kernels_and_oracle(d, (128, 128))
    assert got[at].dtype == jnp.bfloat16 and got[at].shape == want[at].shape
    assert _rel(got[at], want[at]) < (4e-3 if at == 0 else 6e-3)


@pytest.mark.parametrize("block", [(256, 128), (128, 256)])
def test_fused_kernels_with_unequal_blocks(block):
    """Query blocks longer than key blocks (rows with no live key in a tile)
    and shorter (a query block that ends inside a key block)."""
    got, want, _ = _kernels_and_oracle(64, block, s=512)
    for g, ref, limit in zip(got, want, [4e-3, 6e-3, 6e-3, 6e-3]):
        assert _rel(g, ref) < limit


def test_the_first_row_sees_one_key_and_returns_its_value():
    got, _, v = _kernels_and_oracle(64, (128, 128))
    np.testing.assert_array_equal(np.asarray(got[0][:, :, 0], np.float32), np.asarray(v[:, :, 0], np.float32))


def test_fused_kernels_not_causal_match_full_attention_with_gradients():
    q, k, v = _qkv(7, b=1, h=2, s=64, d=16)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) ** 2)

    flash = partial(flash_attention, causal=False, block_q=32, block_k=16)
    g1 = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(partial(full_attention, causal=False)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


# --- the dispatch rule: what the code can see, no flag

CELL_SHAPES = {"gpt2m": (4, 16, 1024, 64), "olmohybrid": (1, 30, 4096, 128), "glm47flash": (2, 20, 4096, 256)}


@pytest.mark.parametrize("shape,k_shape,dtype,on_tpu,engages", [
    *((shape, shape, jnp.bfloat16, True, shape[-1] in ring.FUSED_BLOCKS)
      for shape in CELL_SHAPES.values()),
    *((shape, shape, jnp.bfloat16, False, False) for shape in CELL_SHAPES.values()),  # off the TPU
    ((2, 20, 4096, 256), (2, 20, 4096, 256), jnp.float32, True, False),  # float32 operands
    ((2, 20, 4096, 256), (2, 20, 2048, 256), jnp.bfloat16, True, False),  # keys shorter than queries
    ((2, 20, 4160, 256), (2, 20, 4160, 256), jnp.bfloat16, True, False),  # no whole number of 128
    ((2, 20, 4224, 256), (2, 20, 4224, 256), jnp.bfloat16, True, False),  # 33 x 128: no whole number of a block
    ((2, 20, 4096, 96), (2, 20, 4096, 96), jnp.bfloat16, True, False),  # a head size never measured
    ((2, 20, 256, 256), (2, 20, 256, 256), jnp.bfloat16, True, True),  # shorter than a block: one tile
])
def test_fused_blocks_is_the_rule(shape, k_shape, dtype, on_tpu, engages):
    blocks = fused_blocks(shape, k_shape, dtype, on_tpu=on_tpu)
    assert (blocks is not None) == engages
    if blocks is not None:
        assert all(shape[-2] % b == 0 and b <= shape[-2] for pair in blocks for b in pair)


def test_full_attention_on_the_cpu_is_the_jnp_path_bit_for_bit():
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(8, b=1, h=2, s=256, d=64))
    assert fused_blocks(q.shape, k.shape, q.dtype) is None
    n = ring.causal_query_blocks(256, 256)
    want = ring._causal_blocks_attention(q, k, v, n, 1.0 / 8.0)
    for fn in (partial(full_attention, causal=True), partial(ring_attention, axis_name="sp", axis_size=1, causal=True)):
        assert "pallas_call" not in str(jax.make_jaxpr(fn)(q, k, v))
        np.testing.assert_array_equal(np.asarray(fn(q, k, v), np.float32), np.asarray(want, np.float32))


ONE_DEVICE = {
    "full": partial(full_attention, causal=True),
    "ring1": partial(ring_attention, axis_name="sp", axis_size=1, causal=True),
}


@pytest.mark.parametrize("name", list(ONE_DEVICE))
def test_on_a_tpu_the_one_device_paths_take_the_kernels(name, monkeypatch):
    """The platform patched: the same call runs the kernels (interpreted
    here), keeps no exponentials and counts one fused layer; forward and
    gradients agree with the jnp path on the same bfloat16 operands."""
    fn = ONE_DEVICE[name]
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(9, b=1, h=2, s=256, d=64))
    w = jax.random.normal(jax.random.PRNGKey(10), q.shape, jnp.float32)

    def both():
        grads = jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w), argnums=(0, 1, 2))(q, k, v)
        return [fn(q, k, v), *grads]

    want = both()
    assert ring.kept_score_bytes(fn, q) == 2 * 128 * 128 * 3 * 2 and ring.fused_layers(fn, q) == 0
    monkeypatch.setattr(ring, "_on_tpu", lambda: True)
    monkeypatch.setitem(ring.FUSED_BLOCKS, 64, Blocks((128, 128), (128, 128), (128, 128)))
    assert "pallas_call" in str(jax.make_jaxpr(fn)(q, k, v))
    assert ring.kept_score_bytes(fn, q) == 0 and ring.fused_layers(fn, q) == 1
    for g, ref in zip(both(), want):
        assert _rel(g, ref) < 6e-3


@pytest.mark.parametrize("fn", [
    partial(full_attention, causal=False),  # the kernels are taken for causal attention only
    partial(ring_attention, axis_name="sp", axis_size=2, causal=True),  # the ring's loop keeps the jnp block
    lambda q, k, v: full_attention(q, k, v, causal=True),  # a callable it cannot read
])
def test_fused_layers_is_zero_for_what_the_rule_leaves_out(fn, monkeypatch):
    monkeypatch.setattr(ring, "_on_tpu", lambda: True)
    q = jax.ShapeDtypeStruct((2, 20, 4096, 256), jnp.bfloat16)
    assert ring.fused_layers(fn, q) == 0
