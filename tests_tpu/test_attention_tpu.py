"""Flash-attention Pallas kernel compiled by Mosaic on the real chip.

The CPU suite (tests/test_attention_kernels.py) runs the same comparisons
under the TPU-semantics interpreter; this file is the hardware half of the
round-2 discipline: Mosaic-only lowering (dot_general shapes, iota layouts,
the dynamic-bound fori_loop) has no CPU path, so only an on-chip compile
can catch its regressions.
"""

import jax
import jax.numpy as jnp
import numpy as np


def _qkv(key, b=2, h=4, s=256, d=64):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(key), 3)
    return (
        jax.random.normal(kq, (b, h, s, d), jnp.float32),
        jax.random.normal(kk, (b, h, s, d), jnp.float32),
        jax.random.normal(kv, (b, h, s, d), jnp.float32),
    )


def test_flash_compiles_and_matches_on_tpu():
    from atomo_tpu.ops.attention_kernels import flash_attention
    from atomo_tpu.parallel.ring import full_attention

    q, k, v = _qkv(0)
    got = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal=True)
    )(q, k, v)
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-2, rtol=2e-2
    )


def test_flash_grad_compiles_on_tpu():
    from atomo_tpu.ops.attention_kernels import flash_attention

    q, k, v = _qkv(1, s=128)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    for g in grads:
        assert np.all(np.isfinite(np.asarray(g)))


# --- the jnp core at the one-chip cell's shape (PR 27)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def test_bf16_core_matches_float32_oracle_at_cell_shape_on_tpu():
    """(4, 16, 1024, 64) bfloat16, causal: the one-block core, as
    full_attention and as ring_attention with one shard (the path of
    `lm --layout dp --n-devices 1 --bf16`), forward and the gradients of a
    scalar loss, against the same values through float32 operands at
    Precision.HIGHEST. Read on the v5e (PR 27): forward 2.0e-3 (the
    output's own rounding), gradients 3.4e-3 to 4.0e-3 of the oracle's norm;
    a scale 1.25x off has to fail the same limits."""
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from atomo_tpu.parallel.ring import full_attention, ring_attention

    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(2, b=4, h=16, s=1024, d=64))
    w = jax.random.normal(jax.random.PRNGKey(3), q.shape, jnp.float32)

    def both(fn, *args):
        out = jax.jit(fn)(*args)
        grads = jax.jit(jax.grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w), argnums=(0, 1, 2)
        ))(*args)
        return [out, *grads]

    def ring1(scale=None):
        mesh = jax.make_mesh((1,), ("sp",))
        spec = P(None, None, "sp", None)
        return jax.shard_map(
            partial(ring_attention, axis_name="sp", axis_size=1, causal=True, scale=scale),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False,
        )

    want = both(partial(full_attention, causal=True), *(x.astype(jnp.float32) for x in (q, k, v)))
    limits = [1e-2, 2e-2, 2e-2, 2e-2]
    for fn in (partial(full_attention, causal=True), ring1()):
        got = both(fn, q, k, v)
        for g, ref, limit in zip(got, want, limits):
            assert g.dtype == jnp.bfloat16
            assert _rel(g, ref) < limit, (_rel(g, ref), limit)
    wrong = both(ring1(scale=1.25 / 8.0), q, k, v)
    for g, ref, limit in zip(wrong, want, limits):
        assert _rel(g, ref) > 2 * limit, (_rel(g, ref), limit)
