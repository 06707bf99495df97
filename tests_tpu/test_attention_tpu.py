"""Flash-attention Pallas kernel compiled by Mosaic on the real chip.

The CPU suite (tests/test_attention_kernels.py) runs the same comparisons
under the TPU-semantics interpreter; this file is the hardware half of the
round-2 discipline: Mosaic-only lowering (dot_general shapes, iota layouts,
the dynamic-bound fori_loop) has no CPU path, so only an on-chip compile
can catch its regressions.
"""

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest


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


# --- the jnp core at the one-chip cells' shapes (PR 27, PR 30)

CELL_SHAPES = {"gpt2m": (4, 16, 1024, 64), "olmohybrid": (1, 30, 4096, 128)}


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _one_block(q, k, v, scale=None):
    """The uncut causal program (PR 27's `full_attention`): the oracle."""
    from atomo_tpu.parallel import ring

    scale = 1.0 / q.shape[-1] ** 0.5 if scale is None else scale
    bias = ring._causal_bias(jnp.arange(q.shape[-2]), jnp.arange(k.shape[-2]))
    return ring._one_block_attention(q, k, v, bias, scale)


def _ring1(scale=None):
    from jax.sharding import PartitionSpec as P

    from atomo_tpu.parallel.ring import ring_attention

    mesh = jax.make_mesh((1,), ("sp",))
    spec = P(None, None, "sp", None)
    return jax.shard_map(
        partial(ring_attention, axis_name="sp", axis_size=1, causal=True, scale=scale),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False,
    )


@pytest.mark.parametrize("cell", list(CELL_SHAPES))
def test_bf16_core_matches_float32_oracle_at_cell_shape_on_tpu(cell):
    """(4, 16, 1024, 64) and (1, 30, 4096, 128) bfloat16, causal, in 8 query
    blocks: the core as full_attention and as ring_attention with one shard
    (the path of `lm --layout dp --n-devices 1 --bf16`), forward and the
    gradients of a scalar loss, against the uncut program on the same values
    through float32 operands at Precision.HIGHEST. Read on the v5e (PR 27,
    one block at the first shape): forward 2.0e-3 (the output's own
    rounding), gradients 3.4e-3 to 4.0e-3 of the oracle's norm; a scale
    1.25x off has to fail the same limits."""
    from atomo_tpu.parallel.ring import causal_query_blocks, full_attention

    b, h, s, d = CELL_SHAPES[cell]
    assert causal_query_blocks(s, s) == 8
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(2, b=b, h=h, s=s, d=d))
    w = jax.random.normal(jax.random.PRNGKey(3), q.shape, jnp.float32)

    def both(fn, *args):
        out = jax.jit(fn)(*args)
        grads = jax.jit(jax.grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w), argnums=(0, 1, 2)
        ))(*args)
        return [out, *grads]

    want = both(_one_block, *(x.astype(jnp.float32) for x in (q, k, v)))
    limits = [1e-2, 2e-2, 2e-2, 2e-2]
    for name, fn in (("full", partial(full_attention, causal=True)), ("ring1", _ring1())):
        got = both(fn, q, k, v)
        read = [_rel(g, ref) for g, ref in zip(got, want)]
        print(f"\n{cell} {name}: forward and dq, dk, dv against the float32 oracle: {read}")
        for g, gap, limit in zip(got, read, limits):
            assert g.dtype == jnp.bfloat16
            assert gap < limit, (gap, limit)
    wrong = both(_ring1(scale=1.25 / d**0.5), q, k, v)
    for g, ref, limit in zip(wrong, want, limits):
        assert _rel(g, ref) > 2 * limit, (_rel(g, ref), limit)


@pytest.mark.parametrize("cell", list(CELL_SHAPES))
def test_blocked_core_is_faster_than_one_block_on_tpu(cell, monkeypatch):
    """The core alone, forward and backward, ms a layer (printed for PERF.md;
    PR 27 read 2.28 for one block at (4, 16, 1024, 64)): uncut, and cut into
    at most 2, 4, 8 (the cap), 16 and 32 query blocks."""
    from atomo_tpu.parallel import ring

    b, h, s, d = CELL_SHAPES[cell]
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(4, b=b, h=h, s=s, d=d))
    w = jax.random.normal(jax.random.PRNGKey(5), q.shape, jnp.bfloat16)

    def ms_a_layer(fn, calls=30):
        step = jax.jit(jax.grad(lambda *a: jnp.sum((fn(*a) * w).astype(jnp.float32)), argnums=(0, 1, 2)))
        started = time.perf_counter()
        jax.block_until_ready(step(q, k, v))
        first = time.perf_counter() - started
        started = time.perf_counter()
        for _ in range(calls):
            out = step(q, k, v)
        jax.block_until_ready(out)
        return (time.perf_counter() - started) / calls * 1e3, first

    read = {"one block": ms_a_layer(_one_block)}
    for cap in (2, 4, 8, 16, 32):
        monkeypatch.setattr(ring, "MAX_QUERY_BLOCKS", cap)
        read[f"{ring.causal_query_blocks(s, s)} blocks"] = ms_a_layer(partial(ring.full_attention, causal=True))
    monkeypatch.undo()
    print(f"\n{cell} {(b, h, s, d)} core, ms a layer (first call with its compile, s): "
          + ", ".join(f"{name} {ms:.3f} ({first:.2f})" for name, (ms, first) in read.items()))
    assert read["8 blocks"][0] < 0.85 * read["one block"][0], read
