"""Decoder-only transformer LM — the long-context model family.

The reference's zoo is CV-only (SURVEY.md §2 model row); this family extends
the framework to sequence models so the sequence/context-parallel machinery
(atomo_tpu.parallel.ring) has a first-class consumer. Design is TPU-first:
bias-free linears feeding the MXU, all static shapes. The defaults are GPT-2's
block: pre-LN, GELU MLP at 4x width, learned positional embeddings, full
attention in every layer. The block's other choices are fields of
:class:`TransformerLM` (``BLOCK_RECIPES`` names the sets that go together):
RMSNorm, the norm on each sublayer's output, no positional embedding, a
SiLU-gated FFN of its own width, an RMSNorm over the projected queries and
keys, a per-layer mixer from ``layer_pattern`` (``full`` attention, the same
over a ``window`` of the last positions, or the ``linear`` gated delta rule
of models/linear_attention.py), fewer key/value heads than query heads and a
head size of its own, rotary positions with a rule a mixer kind (``rope``;
models/rotary.py), the routed experts of models/moe.py in place of the FFN
(``ffn="experts"``), and ``remat``,
which has a block keep only its weight matmuls for the backward pass. A
pattern of ``mla`` layers takes its block whole from models/moe.py (latent
attention with rotary keys, a dense FFN in the leading layers and routed
experts beside a shared one after, sized by ``latent_moe``), and with
``latent_moe.mtp_depth`` the model returns a second set of logits, from the
multi-token-prediction module, beside the first.

The attention callable is injectable: ``attention_fn(q, k, v)`` receives
(B, H, S, D). Default is the single-device exact softmax
(parallel.ring.full_attention); under a mesh with an 'sp' axis pass the
shard_map-wrapped ring attention (make_sequence_parallel_attention) and the
same module runs with the sequence dimension sharded.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from atomo_tpu.models.linear_attention import GatedDeltaNet
from atomo_tpu.models.moe import (
    ExpertSizes, LatentMoeBlock, LatentMoeSizes, RoutedExperts, gated_ffn, mtp_input,
)
from atomo_tpu.models.rotary import Rotary, rotary, rotary_angles
from atomo_tpu.parallel.ring import full_attention, fused_layers, kept_score_bytes, tile_score_bytes
from atomo_tpu.utils.tracing import named_phase

AttentionFn = Callable[[jax.Array, jax.Array, jax.Array], jax.Array]
MIXERS = ("full", "window", "linear", "mla")
# the block choices that go together, as `lm --block` names them; "olmo" is
# the OLMo 2/3 family's: the norm reordered onto the sublayers' outputs, q/k
# norm, SwiGLU, and (Olmo-Hybrid's `rope_theta: null`) no positions at all;
# "glm" is the GLM-4.7-Flash / DeepSeek-V3 family's: pre-norm RMSNorm, no
# positional table (the `mla` layers rotate their keys), SwiGLU, and every
# layer latent attention over a sigmoid-gated expert layer (models/moe.py)
BLOCK_RECIPES = {
    "gpt2": {},
    "olmo": dict(norm="rmsnorm", norm_placement="post", positions="none",
                 ffn="swiglu", qk_norm=True),
    "glm": dict(norm="rmsnorm", positions="none", ffn="swiglu", layer_pattern=("mla",)),
    # "mellum" is Mellum 2's: pre-norm RMSNorm, rotary positions in every
    # attention layer (a rule a mixer kind, `rope`), grouped key/value heads
    # of their own size, and in place of the FFN the routed experts of
    # models/moe.py with a softmax router and no shared expert
    "mellum": dict(norm="rmsnorm", positions="rotary", ffn="experts"),
}


# the choices a TransformerLM hands to every one of its blocks unchanged
BLOCK_FIELDS = ("dropout", "attention_fn", "norm", "norm_placement", "ffn", "ffn_width",
                "qk_norm", "linear_key_dim", "linear_value_dim", "linear_conv_width",
                "kv_heads", "experts")


def _norm(kind: str, name: str) -> nn.Module:
    if kind == "layernorm":
        return nn.LayerNorm(use_bias=False, name=name)
    if kind == "rmsnorm":
        return nn.RMSNorm(name=name)
    raise ValueError(f"unknown norm {kind!r}; expected layernorm | rmsnorm")


class MultiHeadAttention(nn.Module):
    """Softmax attention of ``num_heads`` query heads over ``kv_heads``
    key/value heads (0: as many), all of ``head_dim``: query head i reads
    key/value head i // (num_heads / kv_heads). ``window`` keeps a query to
    the keys less than that many positions behind it (0: all before it).
    ``rope`` rotates queries and keys over the whole head by position."""

    num_heads: int
    head_dim: int
    attention_fn: Optional[AttentionFn] = None
    qk_norm: bool = False
    kv_heads: int = 0
    window: int = 0
    rope: Optional[Rotary] = None

    @nn.compact
    def __call__(self, x: jax.Array, pos_offset=0) -> jax.Array:
        b, s, _ = x.shape
        h, d = self.num_heads, self.head_dim
        hk = self.kv_heads or h
        qkv = nn.Dense((h + 2 * hk) * d, use_bias=False, name="qkv")(x)
        q, k, v = jnp.split(qkv, [h * d, (h + hk) * d], axis=-1)
        if self.qk_norm:  # over the whole projection, before the split into heads
            q, k = nn.RMSNorm(name="q_norm")(q), nn.RMSNorm(name="k_norm")(k)

        def heads(t, n):  # (B, S, n*D) -> (B, n, S, D)
            return t.reshape(b, s, n, d).transpose(0, 2, 1, 3)

        q, k, v = heads(q, h), heads(k, hk), heads(v, hk)
        if self.rope is not None:
            with named_phase("rope"):
                cos, sin = rotary_angles(pos_offset + jnp.arange(s), d, self.rope.theta, self.rope.yarn)
                q, k = rotary(q, cos, sin), rotary(k, cos, sin)
        fn = self.attention_fn or partial(full_attention, causal=True)
        if self.window:
            fn = partial(fn, window=self.window)
        out = fn(q, k, v)
        # read by the lm step into its metrics, summed over the full layers
        if kept := kept_score_bytes(fn, q):
            self.sow("counters", "attn_score_bytes", jnp.float32(kept))
        if fused := fused_layers(fn, q):
            self.sow("counters", "attn_fused_layers", jnp.float32(fused))
        # of the layers that the band or the groups shape: the others' steps
        # report what they reported
        if (self.window or hk != h) and (tiles := tile_score_bytes(fn, q)):
            self.sow("counters", "attn_tile_score_bytes", jnp.float32(tiles))
        out = out.transpose(0, 2, 1, 3).reshape(b, s, h * d)
        return nn.Dense(x.shape[-1], use_bias=False, name="proj")(out)


class Block(nn.Module):
    num_heads: int
    head_dim: int
    mlp_ratio: int = 4
    dropout: float = 0.0
    attention_fn: Optional[AttentionFn] = None
    mixer: str = "full"
    norm: str = "layernorm"
    norm_placement: str = "pre"  # pre: x + f(norm(x)); post: x + norm(f(x))
    ffn: str = "gelu"
    ffn_width: int = 0  # 0: mlp_ratio x width
    qk_norm: bool = False
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    linear_conv_width: int = 4
    kv_heads: int = 0  # 0: num_heads
    window: int = 0  # of a `window` mixer
    rope: Optional[Rotary] = None  # this mixer kind's rule
    experts: Optional[ExpertSizes] = None  # of the `experts` FFN

    def _ffn(self, y: jax.Array) -> jax.Array:
        width = y.shape[-1]
        hidden = self.ffn_width or self.mlp_ratio * width
        if self.ffn == "gelu":
            y = nn.Dense(hidden, use_bias=False, name="up")(y)
            y = nn.gelu(y)
            return nn.Dense(width, use_bias=False, name="down")(y)
        if self.ffn == "experts":
            if self.experts is None:
                raise ValueError("the `experts` FFN needs its sizes: experts=ExpertSizes(...)")
            return RoutedExperts(self.experts, name="moe")(y)
        if self.ffn != "swiglu":
            raise ValueError(f"unknown ffn {self.ffn!r}; expected gelu | swiglu | experts")
        return gated_ffn(y, hidden)

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False, pos_offset=0) -> jax.Array:
        if self.norm_placement not in ("pre", "post"):
            raise ValueError(
                f"unknown norm_placement {self.norm_placement!r}; expected pre | post"
            )
        pre = self.norm_placement == "pre"
        if self.mixer in ("full", "window"):
            if (self.mixer == "window") != (self.window > 0):
                raise ValueError("a `window` layer, and no other, takes a window: window=N")
            mixer = partial(
                MultiHeadAttention(
                    self.num_heads, self.head_dim, self.attention_fn, self.qk_norm,
                    self.kv_heads, self.window, self.rope,
                ),
                pos_offset=pos_offset,
            )
        elif self.mixer == "linear":
            mixer = GatedDeltaNet(
                self.num_heads, self.linear_key_dim, self.linear_value_dim,
                self.linear_conv_width,
            )
        else:
            raise ValueError(f"unknown mixer {self.mixer!r}; expected one of {MIXERS}")
        ln1, ln2 = _norm(self.norm, "ln1"), _norm(self.norm, "ln2")
        y = mixer(ln1(x)) if pre else ln1(mixer(x))
        if self.dropout:
            y = nn.Dropout(self.dropout, deterministic=not train)(y)
        x = x + y
        y = self._ffn(ln2(x)) if pre else ln2(self._ffn(x))
        if self.dropout:
            y = nn.Dropout(self.dropout, deterministic=not train)(y)
        return x + y


class TransformerLM(nn.Module):
    """Causal LM: int32 tokens (B, S) -> logits (B, S, vocab)."""

    vocab_size: int = 256
    max_len: int = 1024
    width: int = 256
    depth: int = 4
    num_heads: int = 4
    dropout: float = 0.0
    attention_fn: Optional[AttentionFn] = None  # of the `full` layers
    norm: str = "layernorm"  # layernorm | rmsnorm
    norm_placement: str = "pre"  # pre | post
    positions: str = "learned"  # learned | none | rotary: by `rope`, in the attention layers
    ffn: str = "gelu"  # gelu | swiglu | experts
    ffn_width: int = 0  # 0: 4 x width
    qk_norm: bool = False
    layer_pattern: tuple = ("full",)  # mixer kinds, repeated over the depth
    linear_key_dim: int = 0  # per head, of the `linear` layers
    linear_value_dim: int = 0
    linear_conv_width: int = 4
    remat: str = "none"  # none | dots: what a block keeps for the backward pass of a training step
    latent_moe: Optional[LatentMoeSizes] = None  # of the `mla` layers, which then are all the layers
    kv_heads: int = 0  # key/value heads of the `full` and `window` layers; 0: num_heads
    head_dim: int = 0  # 0: width / num_heads
    window: int = 0  # of the `window` layers
    rope: tuple = ()  # ((mixer kind, Rotary), ...): the rule of each kind under positions="rotary"
    experts: Optional[ExpertSizes] = None  # of the `experts` FFN

    @nn.compact
    def __call__(
        self, tokens: jax.Array, train: bool = False, pos_offset=0
    ) -> jax.Array:
        """``pos_offset`` is the global position of tokens[:, 0] — pass
        axis_index(sp) * S_local when the sequence dim is sharded, so every
        shard embeds its true positions (not local 0..S/n)."""
        b, s = tokens.shape
        head_dim = self.head_dim or self.width // self.num_heads
        embed = nn.Embed(self.vocab_size, self.width, name="tok_emb")
        x = embed(tokens)
        if self.positions == "learned":
            pos = nn.Embed(self.max_len, self.width, name="pos_emb")(
                pos_offset + jnp.arange(s)
            )
            x = x + pos[None, :, :]
        elif self.positions not in ("none", "rotary"):
            raise ValueError(
                f"unknown positions {self.positions!r}; expected learned | none | rotary"
            )
        rope = dict(self.rope)
        if (self.positions == "rotary") != bool(rope):
            raise ValueError("positions='rotary' and `rope`, a rule a mixer kind, come together")
        if self.remat not in ("none", "dots"):
            raise ValueError(f"unknown remat {self.remat!r}; expected none | dots")
        if self.remat == "none" or not train:
            # also where nothing is trained (initialisation, evaluation):
            # flax's lifted remat keeps the scope of a call on concrete
            # arrays alive, and with it a copy of the parameters
            remat = lambda block: block  # noqa: E731
        else:
            # keep the matmuls against weights, rebuild the rest of a block
            # (elementwise passes, attention, the chunks and their scan)
            # inside the backward pass
            remat = partial(
                nn.remat, static_argnums=(2,),
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            )
        if "mla" in self.layer_pattern:
            return self._latent_moe(remat(LatentMoeBlock), embed, x, tokens, train, pos_offset)
        block = remat(Block)
        shared = {f: getattr(self, f) for f in BLOCK_FIELDS}
        for i in range(self.depth):
            kind = self.layer_pattern[i % len(self.layer_pattern)]
            if rope and kind in ("full", "window") and kind not in rope:
                raise ValueError(f"rope has no rule for the `{kind}` layers: {sorted(rope)}")
            # what only a layer with a window or a rotation takes; the others
            # are built and called as they were
            own = {"window": self.window} if kind == "window" else {}
            if kind in rope:
                own["rope"] = rope[kind]
            layer = block(self.num_heads, head_dim, mixer=kind, name=f"block{i}", **shared, **own)
            x = layer(x, train, pos_offset) if own else layer(x, train)
        x = _norm(self.norm, "ln_f")(x)
        return nn.Dense(self.vocab_size, use_bias=False, name="head")(x)

    def _latent_moe(self, block, embed, x, tokens, train, pos_offset):
        """The layers of a model whose every layer is ``mla``, the last norm
        and the head; with the prediction module, (logits, its logits): the
        module sees the last block's output at t beside the embedding of
        token t+1, runs one more expert block, and predicts token t+2 through
        the same embedding and head. All S positions are computed (the last,
        whose next token is the first, predicts nothing and is causal's last,
        so it reaches no other) and the loss leaves the last two out."""
        z = self.latent_moe
        if z is None or set(self.layer_pattern) != {"mla"}:
            raise ValueError(
                "an `mla` layer needs latent_moe's sizes and every layer of "
                f"layer_pattern to be `mla`, not {self.layer_pattern}"
            )
        if self.norm != "rmsnorm" or self.positions != "none" or self.ffn != "swiglu":
            raise ValueError("`mla` layers are pre-norm RMSNorm blocks with a gated FFN and no positional table")
        make = partial(block, self.num_heads, z, self.ffn_width or 4 * self.width,
                       attention_fn=self.attention_fn)
        for i in range(self.depth):
            x = make(experts=i >= z.dense_layers, name=f"block{i}")(x, train, pos_offset)
        norm = partial(nn.RMSNorm, epsilon=z.norm_eps)
        head = nn.Dense(self.vocab_size, use_bias=False, name="head")
        logits = head(norm(name="ln_f")(x))
        if not z.mtp_depth:
            return logits
        m = mtp_input(z, embed(jnp.roll(tokens, -1, axis=1)), x)
        m = make(experts=True, name="mtp_block")(m, train, pos_offset)
        return logits, head(norm(name="mtp_norm")(m))


def lm_loss(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Next-token cross-entropy: predict tokens[:, 1:] from logits[:, :-1]."""
    import optax

    return optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], tokens[:, 1:]
    ).mean()
