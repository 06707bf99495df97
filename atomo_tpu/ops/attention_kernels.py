"""Pallas TPU kernel for fused (flash) attention — the LM forward hot path.

The jnp attention paths (parallel.ring.full_attention / blockwise_attention)
leave the softmax chain to XLA: scores, max, exp, sum and the PV matmul are
separate HBM-visible ops unless XLA fuses them. This kernel is the classic
flash-attention schedule: the grid walks (batch, head, q-block, k-block)
with the k-block axis innermost, K/V arrive one (block_k, D) tile at a time
(Pallas double-buffers the HBM→VMEM DMA), and an online-softmax accumulator
lives in VMEM scratch across the k sweep. The S×S score matrix never
exists, VMEM residency is O(block·D) — independent of S, so sequence
length is NOT bounded by VMEM (ADVICE r3 #1: the round-3 kernel kept the
full (S, D) K/V resident per program, capping S at ~16k for D=64 f32 on a
16 MB-VMEM core). For causal masks, k-blocks strictly above the diagonal
skip their FLOPs via `pl.when` (the static grid still walks — and
prefetches — those blocks, so causal saves compute but not bandwidth).

Scope discipline (round-2 lesson: TPU-only code paths must stay testable):
  * forward = Pallas kernel, bit-compared against full_attention in the
    TPU-semantics interpreter on CPU (tests/) and compiled on the chip
    (tests_tpu/);
  * backward = jax.vjp of the jnp blockwise oracle (identical math), so
    training through ``flash_attention`` is exact and needs no hand-written
    transpose kernel; the fused win applies to the forward pass.
  * shapes that don't tile (S % block) fall back to blockwise_attention —
    no silent padding semantics.

No reference analogue: the reference has no attention at all (SURVEY.md
§5.7); this is TPU-first capability the framework adds on top of parity.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from atomo_tpu.ops.qsgd_kernels import _interpret_mode, interpret_requested

NEG_INF = float("-inf")


def _fa_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
    scale: float, causal: bool,
):
    """One (batch, head, q-block, k-block) grid step. Blocks: q/o
    (1, 1, Bq, D) pinned across the k sweep; k/v (1, 1, Bk, D) — one tile
    per step, streamed from HBM. The online-softmax state (m, l, acc)
    lives in VMEM scratch, initialized at k-block 0 and folded into o_ref
    at the last k-block."""
    iq = pl.program_id(2)
    jk = pl.program_id(3)
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    # causal: a k-block whose first position is past this q-block's last
    # position is fully masked — skip its FLOPs (the DMA still happened;
    # see module docstring)
    live = (jk * bk <= (iq + 1) * bq - 1) if causal else (jk >= 0)

    @pl.when(live)
    def _accumulate():
        q = q_ref[0, 0].astype(jnp.float32)  # (Bq, D)
        k_blk = k_ref[0, 0].astype(jnp.float32)  # (Bk, D)
        v_blk = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (Bq, Bk)
        if causal:
            q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
            k_pos = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m, l, acc = m_ref[...], l_ref[...], acc_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe), 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        m_ref[...] = m_new
        l_ref[...] = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc * alpha + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(jk == pl.num_programs(3) - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], jnp.finfo(jnp.float32).tiny)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def _flash_forward(
    q, k, v, *, causal: bool, scale: float, block_q: int, block_k: int,
    interpret: bool,
):
    b, h, s, d = q.shape
    grid = (b, h, s // block_q, s // block_k)
    kernel = partial(_fa_kernel, scale=scale, causal=causal)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bb, hh, i, j: (bb, hh, i, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bb, hh, i, j: (bb, hh, j, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bb, hh, i, j: (bb, hh, j, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda bb, hh, i, j: (bb, hh, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),  # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),  # running denom l
            pltpu.VMEM((block_q, d), jnp.float32),  # unnormalized acc
        ],
        interpret=_interpret_mode(interpret),
    )(q, k, v)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret):
    return _flash_forward(
        q, k, v, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, interpret=interpret,
    )


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out = _flash_forward(
        q, k, v, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, interpret=interpret,
    )
    return out, (q, k, v)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, do):
    # exact gradients via the jnp blockwise oracle (same online-softmax
    # math, same O(S·block) memory); the fused kernel accelerates forward
    from atomo_tpu.parallel.ring import blockwise_attention

    q, k, v = res
    _, vjp = jax.vjp(
        lambda qq, kk, vv: blockwise_attention(
            qq, kk, vv, causal=causal, scale=scale, block_size=block_k
        ),
        q, k, v,
    )
    return vjp(do)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused exact attention (B, H, S, D) -> (B, H, S, D).

    Forward runs the Pallas flash kernel, compiled by Mosaic for the
    device it is on (``interpret=None`` interprets only when
    ops.qsgd_kernels.interpret_requested says so — tests and CPU dry
    runs); backward is the jnp blockwise oracle's VJP. Falls back to
    blockwise_attention when S doesn't tile by the blocks — identical
    results either way (tested)."""
    from atomo_tpu.parallel.ring import blockwise_attention

    b, h, s, d = q.shape
    if scale is None:
        scale = 1.0 / (d**0.5)
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if s % block_q or s % block_k:
        return blockwise_attention(
            q, k, v, causal=causal, scale=scale, block_size=block_k
        )
    if interpret is None:
        interpret = interpret_requested()
    return _flash(q, k, v, causal, scale, block_q, block_k, interpret)
