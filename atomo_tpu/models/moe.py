"""Latent attention and a routed expert layer: the block of the ``mla`` layers
of a :class:`~atomo_tpu.models.transformer.TransformerLM`, and the
multi-token-prediction module that follows the last of them. The routed
experts (:class:`RoutedExperts`, sized by :class:`ExpertSizes`) are also the
``experts`` FFN of the plain :class:`~atomo_tpu.models.transformer.Block`,
there with a softmax router and nothing beside them.

Block, pre-norm, no biases (u the block's input after RMSNorm):

    h = x + MLA(RMSNorm(x));  y = h + F(RMSNorm(h))

**MLA** (multi-head latent attention). Queries and keys/values come through
low-rank latents, each with an RMSNorm of its own:
``q = RMSNorm(u W_qa) W_qb`` split per head into ``q_nope | q_pe``;
``[c_kv | k_pe] = u W_kva``, ``[k_nope | v] = RMSNorm(c_kv) W_kvb`` per head,
``k_pe`` one vector shared by the heads. ``q_pe`` and ``k_pe`` are rotated
(:func:`rotary`); a head attends with ``[q_nope | rot q_pe]`` over
``[k_nope | rot k_pe]``, causal softmax at one over the root of their common
width, through the same attention core as the ``full`` layers
(parallel/ring.py). Training materialises k and v per head.

**F** is a SiLU-gated FFN in the leading ``dense_layers`` blocks and the
expert layer in every other: one shared expert (a gated FFN, scope ``ffn``)
plus the routed experts (:class:`RoutedExperts`, scope ``moe``). The router
scores every token over all ``experts`` with a sigmoid in float32, chooses the
``per_token`` largest of score + bias (the bias enters the choice alone and
takes no gradient), and weights a chosen expert by ``route_scale`` times its
score over the sum of the chosen scores. That is the router's ``sigmoid``
scoring; under ``softmax`` the scores are a softmax over all ``experts``
outputs, the ``per_token`` largest are chosen with no bias (the layer then has
no such leaf) and weighted by their score over the chosen scores' sum. No
capacity, no dropped token, no auxiliary loss.

**This chip's share.** The layer holds experts ``[first, first + held)`` of
the router's ``experts``. It routes over all of them, computes its own, and
leaves out what the absent ones would add: the partial result goes on to the
next layer. The T x per_token assignments are sorted by expert with those of
absent experts last, the tokens' rows gathered in that order, the three
matmuls run as grouped products over the held experts' row counts
(``jax.lax.ragged_dot``), and the rows brought back by the inverse
permutation and summed under the weights. The buffer has T x per_token rows,
the most that can ever be held, so every assignment to a held expert is
computed whatever the routing; rows past the groups are masked wherever they
are read. Both passes of the gather are gathers (:func:`take_rows`,
:func:`bring_back`): the transpose of a gather is a scatter-add, which the
permutation makes unnecessary. The two backward passes are written by hand
for two reasons, both read on the v5e (PERF.md section 6, PR 33). The TPU's
grouped product writes only the tiles its groups cover, so rows past the
groups hold whatever the memory held, in the rows' gradient too: plain
indexing under autodiff adds them into the tokens' gradient and the first
update is NaN (tests_tpu/test_moe_tpu.py; on the CPU those rows are zero).
And its scatter-adds cost 87 ms of a 490 ms step that takes 403 ms this way.

Scopes: ``mla`` around the mixer outside ``attention``; ``moe`` with
``moe_route``, ``moe_dispatch`` and ``moe_experts`` inside it; ``mtp`` around
the prediction module's projection. Counted from the routing as the step
runs, in bytes of token rows as the experts read them (rows x width x
itemsize): ``moe_held_row_bytes`` (collection ``counts``: summed over layers
and replicas), of the assignments this layer computed, and
``moe_max_expert_row_bytes`` (collection ``counts_max``: the most over layers
and replicas), of the most one held expert got.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from atomo_tpu.models.rotary import rotary, rotary_angles
from atomo_tpu.parallel.ring import full_attention, fused_layers, kept_score_bytes
from atomo_tpu.utils.tracing import named_phase

INIT = nn.initializers.normal(0.02)
FLOAT32_LEAVES = ("router", "route_bias")  # kept out of the bf16 cast: the gate computes in float32


SCORINGS = ("sigmoid", "softmax")


def _check_experts(z) -> None:
    held = z.experts_held or z.experts
    if not 0 <= z.first_expert <= z.experts - held:
        raise ValueError(
            f"experts [{z.first_expert}, {z.first_expert + held}) are not "
            f"among the router's {z.experts}"
        )
    if z.per_token > z.experts:
        raise ValueError(f"{z.per_token} experts per token of {z.experts}")
    if z.scoring not in SCORINGS:
        raise ValueError(f"unknown router scoring {z.scoring!r}; expected {' | '.join(SCORINGS)}")


@dataclasses.dataclass(frozen=True)
class ExpertSizes:
    """What :class:`RoutedExperts` needs: the router's outputs, this chip's
    share of them, and the router's rule. ``scoring`` is ``sigmoid`` (a
    score a token and expert on its own, a selection bias in the choice, the
    chosen scores over their sum times ``route_scale``) or ``softmax`` (over
    all the router's outputs, the chosen ones renormalised over their sum; no
    bias leaf)."""

    expert_width: int
    experts: int  # the router's outputs
    experts_held: int = 0  # 0: all of them
    first_expert: int = 0
    per_token: int = 4
    scoring: str = "sigmoid"
    route_scale: float = 1.0

    def __post_init__(self):
        _check_experts(self)

    @property
    def held(self) -> int:
        return self.experts_held or self.experts


@dataclasses.dataclass(frozen=True)
class LatentMoeSizes:
    """What a configuration of this family carries beside width, depth, heads
    and the dense FFN's width; one hashable field of the model. Its expert
    layer reads the fields it shares with :class:`ExpertSizes`."""

    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    value_dim: int
    expert_width: int
    experts: int  # the router's outputs
    experts_held: int = 0  # 0: all of them
    first_expert: int = 0
    per_token: int = 4
    shared_experts: int = 1
    dense_layers: int = 1
    route_scale: float = 1.0
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    mtp_depth: int = 0
    mtp_weight: float = 0.3
    scoring: str = "sigmoid"

    def __post_init__(self):
        _check_experts(self)
        if self.rope_dim % 2:
            raise ValueError(f"rotary pairs need an even rope_dim, not {self.rope_dim}")
        if self.mtp_depth not in (0, 1):
            raise ValueError(f"mtp_depth {self.mtp_depth}: one prediction module or none")

    @property
    def held(self) -> int:
        return self.experts_held or self.experts


def gated_ffn(y: jax.Array, hidden: int, prefix: str = "") -> jax.Array:
    """down(silu(gate(y)) * up(y)), its three matrices named after ``prefix``
    in the module that calls this."""
    width = y.shape[-1]
    with named_phase("ffn"):
        gate = nn.Dense(hidden, use_bias=False, name=f"{prefix}gate")(y)
        y = nn.silu(gate) * nn.Dense(hidden, use_bias=False, name=f"{prefix}up")(y)
        return nn.Dense(width, use_bias=False, name=f"{prefix}down")(y)


class LatentAttention(nn.Module):
    num_heads: int
    sizes: LatentMoeSizes
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, u: jax.Array, pos_offset=0) -> jax.Array:
        z, h = self.sizes, self.num_heads
        b, s, width = u.shape
        dense = partial(nn.Dense, use_bias=False)
        norm = partial(nn.RMSNorm, epsilon=z.norm_eps)
        with named_phase("mla"):
            q = dense(h * (z.nope_dim + z.rope_dim), name="q_b")(
                norm(name="q_a_norm")(dense(z.q_rank, name="q_a")(u))
            ).reshape(b, s, h, z.nope_dim + z.rope_dim)
            latent = dense(z.kv_rank + z.rope_dim, name="kv_a")(u)
            kv = dense(h * (z.nope_dim + z.value_dim), name="kv_b")(
                norm(name="kv_a_norm")(latent[..., : z.kv_rank])
            ).reshape(b, s, h, z.nope_dim + z.value_dim)
            cos, sin = rotary_angles(pos_offset + jnp.arange(s), z.rope_dim, z.rope_theta)
            q_pe = rotary(q[..., z.nope_dim :], cos[:, None, :], sin[:, None, :])
            k_pe = rotary(latent[..., z.kv_rank :], cos, sin)
            k_pe = jnp.broadcast_to(k_pe[:, :, None, :], (b, s, h, z.rope_dim))
            q = jnp.concatenate([q[..., : z.nope_dim], q_pe], axis=-1)
            k = jnp.concatenate([kv[..., : z.nope_dim], k_pe], axis=-1)
            q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, kv[..., z.nope_dim :]))
        fn = self.attention_fn or partial(full_attention, causal=True)
        out = fn(q, k, v)  # (B, H, S, value_dim)
        if kept := kept_score_bytes(fn, q):
            self.sow("counters", "attn_score_bytes", jnp.float32(kept))
        if fused := fused_layers(fn, q):
            self.sow("counters", "attn_fused_layers", jnp.float32(fused))
        with named_phase("mla"):
            out = out.transpose(0, 2, 1, 3).reshape(b, s, h * z.value_dim)
            return dense(width, name="o")(out)


@jax.custom_vjp
def take_rows(x, order, back, live):
    """x (T, d) -> the row of each of the T x k assignments in sorted order,
    (T k, d). ``order`` (T k,) is the sort's permutation of the assignments,
    ``back`` (T, k) its inverse and ``live`` (T, k) whether an assignment's
    expert is held: a cotangent's rows come back by ``back`` and are summed
    over a token's live assignments, which is the scatter-add that the
    transpose of this gather would be."""
    return x[order // back.shape[1]]


def _take_rows_fwd(x, order, back, live):
    return take_rows(x, order, back, live), (back, live)


def _take_rows_bwd(res, g):
    back, live = res
    picked = jnp.where(live[..., None], g[back], 0)
    return picked.astype(jnp.float32).sum(axis=1).astype(g.dtype), None, None, None


take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@jax.custom_vjp
def bring_back(ys, weights, order, back, live):
    """The experts' rows ys (T k, d) back at their tokens under the float32
    ``weights`` (T, k), (T, d): sum over a token's live assignments of weight
    times row. Dead rows (an absent expert's, past the groups) are masked and
    not multiplied, whatever they hold."""
    picked = jnp.where(live[..., None], ys[back], 0).astype(jnp.float32)
    return jnp.einsum("tkd,tk->td", picked, weights).astype(ys.dtype)


def _bring_back_fwd(ys, weights, order, back, live):
    return bring_back(ys, weights, order, back, live), (ys, weights, order, back, live)


def _bring_back_bwd(res, g):
    ys, weights, order, back, live = res
    k = back.shape[1]
    picked = jnp.where(live[..., None], ys[back], 0).astype(jnp.float32)
    d_weights = jnp.einsum("tkd,td->tk", picked, g.astype(jnp.float32))
    scale = jnp.where(live, weights, 0).reshape(-1)[order]  # of each sorted row
    d_ys = (g[order // k].astype(jnp.float32) * scale[:, None]).astype(ys.dtype)
    return d_ys, d_weights, None, None, None


bring_back.defvjp(_bring_back_fwd, _bring_back_bwd)


class RoutedExperts(nn.Module):
    """The routed part of the expert layer on this chip's share of the
    experts; the shared expert is its caller's."""

    sizes: "ExpertSizes | LatentMoeSizes"

    @nn.compact
    def __call__(self, u: jax.Array) -> jax.Array:
        z = self.sizes
        width, k, held = u.shape[-1], z.per_token, z.held
        x = u.reshape(-1, width)
        router = self.param("router", INIT, (width, z.experts), jnp.float32)
        if z.scoring == "sigmoid":
            bias = self.param("route_bias", nn.initializers.zeros, (z.experts,), jnp.float32)
        rows = lambda name, a, b: self.param(name, INIT, (held, a, b))  # noqa: E731
        gate, up = rows("gate", width, z.expert_width), rows("up", width, z.expert_width)
        down = rows("down", z.expert_width, width)
        with named_phase("moe"):
            with named_phase("moe_route"):
                logits = jnp.dot(
                    x.astype(jnp.float32), router.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST,
                )
                if z.scoring == "sigmoid":
                    scores = jax.nn.sigmoid(logits)
                    _, chosen = jax.lax.top_k(
                        jax.lax.stop_gradient(scores + bias.astype(jnp.float32)), k
                    )
                else:
                    scores = jax.nn.softmax(logits, axis=-1)
                    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(scores), k)
                self.sow("intermediates", "chosen", chosen)  # kept only where a caller asks for it
                picked = jnp.take_along_axis(scores, chosen, axis=-1)
                weights = z.route_scale * picked / (picked.sum(-1, keepdims=True) + 1e-20)
            with named_phase("moe_dispatch"):
                live = (chosen >= z.first_expert) & (chosen < z.first_expert + held)
                group = jnp.where(live, chosen - z.first_expert, held).reshape(-1)
                order = jnp.argsort(group, stable=True)
                back = jnp.argsort(order).reshape(-1, k)
                counts = jnp.sum(
                    group[:, None] == jnp.arange(held, dtype=group.dtype)[None, :],
                    axis=0, dtype=jnp.int32,
                )
                xs = take_rows(x, order, back, live)
            with named_phase("moe_experts"):
                dot = partial(jax.lax.ragged_dot, group_sizes=counts)
                ys = dot(nn.silu(dot(xs, gate)) * dot(xs, up), down)
            with named_phase("moe_dispatch"):
                y = bring_back(ys, weights, order, back, live)
        # byte counts, as the step's other counters are: rows times a row's bytes
        row_bytes = jnp.float32(width * x.dtype.itemsize)
        self.sow("counts", "moe_held_row_bytes", counts.sum() * row_bytes)
        self.sow("counts_max", "moe_max_expert_row_bytes", counts.max() * row_bytes)
        return y.reshape(u.shape)


class LatentMoeBlock(nn.Module):
    num_heads: int
    sizes: LatentMoeSizes
    ffn_width: int
    experts: bool  # the expert layer, or the dense FFN of the leading blocks
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False, pos_offset=0) -> jax.Array:
        z = self.sizes
        norm = partial(nn.RMSNorm, epsilon=z.norm_eps)
        x = x + LatentAttention(self.num_heads, z, self.attention_fn, name="mla")(
            norm(name="ln1")(x), pos_offset
        )
        u = norm(name="ln2")(x)
        if not self.experts:
            return x + gated_ffn(u, self.ffn_width)
        y = RoutedExperts(z, name="moe")(u)
        if z.shared_experts:
            y = y + gated_ffn(u, z.shared_experts * z.expert_width, "shared_")
        return x + y


def mtp_input(z: LatentMoeSizes, embedded_next: jax.Array, hidden: jax.Array) -> jax.Array:
    """[RMSNorm(Emb(x_{t+1})) | RMSNorm(z_t)] W, the prediction module's input
    (its three leaves are named in the model that calls this)."""
    norm = partial(nn.RMSNorm, epsilon=z.norm_eps)
    with named_phase("mtp"):
        both = jnp.concatenate(
            [norm(name="mtp_enorm")(embedded_next), norm(name="mtp_hnorm")(hidden)], axis=-1
        )
        return nn.Dense(hidden.shape[-1], use_bias=False, name="mtp_proj")(both)
