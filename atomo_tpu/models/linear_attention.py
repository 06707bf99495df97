"""Gated delta rule linear attention (arXiv:2412.06464): the mixer of the
``linear`` layers of a hybrid :class:`~atomo_tpu.models.transformer.TransformerLM`.

Per head, with keys of ``dk`` and values of ``dv`` features, a state ``H`` of
(dk, dv) follows

    H_t = alpha_t (I - beta_t k_t k_t^T) H_{t-1} + beta_t k_t v_t^T,   o_t = H_t^T q_t

with a decay ``alpha_t`` in (0, 1) and a write strength ``beta_t`` in (0, 2)
(above 1 the transition has a negative eigenvalue). The token-by-token
recurrence is the definition (benchmarks/reference/olmo_hybrid_7b.py runs
it); this module computes the same thing in chunks of ``CHUNK`` tokens, the
WY representation of arXiv:2406.06484 with the decay folded in. With
``u_t = beta_t (v_t - alpha_t H_{t-1}^T k_t)`` the state is a decayed sum of
``k_t u_t^T``, and inside a chunk (rows are tokens, ``gamma`` the running
product of alpha, ``H_0`` the state the chunk starts from)

    (I + B) U = diag(beta) V - diag(beta gamma) K H_0,
        B_ij = beta_i (gamma_i / gamma_j) (k_i . k_j)  for j < i
    O   = diag(gamma) Q H_0 + (Q K^T * gamma_i / gamma_j, j <= i) U
    H_C = gamma_C H_0 + (diag(gamma_C / gamma) K)^T U

so everything but ``H_0`` is matrix products inside the chunk (scope
``delta_chunk``), and one ``lax.scan`` over the chunks carries ``H`` (scope
``delta_scan``); both lie inside ``linear_attention``, which runs from the
projections' results to the output projection's operand. Matmul operands are in the dtype q, k, v arrive in,
accumulation, gates, the triangular inverse and the state are float32. The
backward pass is autodiff through this form; the inverse alone has a rule of
its own, so that only the inverse is kept for it.
"""

from __future__ import annotations

from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp

from atomo_tpu.parallel.ring import _dot
from atomo_tpu.utils.tracing import named_phase

CHUNK = 64
BETA_RANGE = 2.0  # beta = 2 sigmoid(.): the published `linear_allow_neg_eigval`
L2_EPS = 1e-6


def causal_depthwise_conv(x: jax.Array, kernel: jax.Array) -> jax.Array:
    """x (B, S, C), kernel (W, 1, C), the layout of a grouped ``lax.conv``:
    y_t = sum_w kernel[w] x_{t-(W-1)+w}, zeros before the sequence starts."""
    width, s = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(padded[:, w : w + s] * kernel[w, 0] for w in range(width))


def l2_normalise(x: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


@jax.custom_vjp
def unit_lower_inverse(b: jax.Array) -> jax.Array:
    """(I + b)^-1 for strictly lower triangular b (..., C, C), C a power of
    two, by block forward substitution: with the diagonal blocks of size s
    inverted, the blocks of size 2s are [[X, 0], [-Z M21 X, Z]]. Matrix
    products only, log2(C) rounds, and no entry grows past the inverse's own
    (a power series of b would, where keys repeat and beta is near 2)."""
    c = b.shape[-1]
    index = jnp.arange(c)
    t = jnp.broadcast_to(jnp.eye(c, dtype=b.dtype), b.shape)
    s = 1
    while s < c:
        pair, second = index // (2 * s), (index // s) % 2 == 1
        below = (pair[:, None] == pair[None, :]) & second[:, None] & ~second[None, :]
        t = t - _dot("...ij,...jk->...ik", t, _dot("...ij,...jk->...ik", b * below, t))
        s *= 2
    return t


def _unit_lower_inverse_fwd(b):
    t = unit_lower_inverse(b)
    return t, t


def _unit_lower_inverse_bwd(t, dt):
    # d(T) = -T d(b) T
    db = -_dot("...ji,...jk->...ik", t, _dot("...ij,...kj->...ik", dt, t))
    return (jnp.tril(db, -1),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def chunked_gated_delta_rule(q, k, v, g, beta, chunk: int = CHUNK):
    """q, k (B, S, H, dk), v (B, S, H, dv) of one dtype; g = log alpha and
    beta (B, S, H), float32. Returns o (B, S, H, dv) in v's dtype, and the
    bytes of state the scan keeps for the backward pass: one float32
    (dk, dv) per chunk, head and row."""
    b, s, h, dk = q.shape
    dv, n, dtype = v.shape[-1], s // chunk, v.dtype
    if s % chunk or chunk & (chunk - 1):
        raise ValueError(
            f"the chunked delta rule needs a sequence of whole chunks of "
            f"{chunk} tokens (a power of two), not {s}"
        )

    def chunks(x):  # (B, S, H, ...) -> (N, B, H, C, ...): the scan runs over N
        x = x.reshape(b, n, chunk, h, *x.shape[3:])
        return jnp.moveaxis(x, (1, 3), (0, 2))

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    with named_phase("delta_chunk"):
        gc = jnp.cumsum(g, axis=-1)  # log gamma, within the chunk
        lower = jnp.tril(jnp.ones((chunk, chunk), bool))
        # gamma_i / gamma_j for j <= i, else 0: masked before exp, a ratio above the diagonal overflows
        decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :], -jnp.inf))
        kk = _dot("nbhik,nbhjk->nbhij", k, k)
        t = unit_lower_inverse(jnp.tril(beta[..., None] * kk * decay, -1)).astype(dtype)
        gamma = jnp.exp(gc)
        w = _dot("nbhij,nbhjk->nbhik", t, (k * (beta * gamma)[..., None]).astype(dtype)).astype(dtype)
        u0 = _dot("nbhij,nbhjv->nbhiv", t, (v * beta[..., None]).astype(dtype))
        attn = (_dot("nbhik,nbhjk->nbhij", q, k) * decay).astype(dtype)
        k_out = (k * jnp.exp(gc[..., -1:] - gc)[..., None]).astype(dtype)
        q_in = (q * gamma[..., None]).astype(dtype)
        gamma_c = gamma[..., -1]

    def step(state, xs):
        w_c, u0_c, k_c, gamma_cc = xs
        entering = state.astype(dtype)
        u_c = (u0_c - _dot("bhik,bhkv->bhiv", w_c, entering)).astype(dtype)
        state = state * gamma_cc[..., None, None] + _dot("bhik,bhiv->bhkv", k_c, u_c)
        return state, (entering, u_c)

    with named_phase("delta_scan"):
        state0 = jnp.zeros((b, h, dk, dv), jnp.float32)
        _, (entering, u) = jax.lax.scan(step, state0, (w, u0, k_out, gamma_c))
    with named_phase("delta_chunk"):
        o = _dot("nbhik,nbhkv->nbhiv", q_in, entering) + _dot("nbhij,nbhjv->nbhiv", attn, u)
        o = jnp.moveaxis(o.astype(dtype), (0, 2), (1, 3)).reshape(b, s, h, dv)
    return o, n * state0.size * state0.dtype.itemsize


def _decay_rate_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, jnp.log(1e-3), jnp.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1(dt)


class GatedDeltaNet(nn.Module):
    """One linear-attention sublayer: x (B, S, width) -> (B, S, width)."""

    num_heads: int
    key_dim: int
    value_dim: int
    conv_width: int = 4

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, s, width = x.shape
        h, dk, dv = self.num_heads, self.key_dim, self.value_dim
        dense = partial(nn.Dense, use_bias=False)
        q, k = dense(h * dk, name="q")(x), dense(h * dk, name="k")(x)
        v, z = dense(h * dv, name="v")(x), dense(h * dv, name="z")(x)
        a, write = dense(h, name="a")(x), dense(h, name="b")(x)
        a_log = self.param("A_log", _decay_rate_init, (h,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (h,))
        kernels = [
            self.param(f"{name}_conv", nn.initializers.lecun_normal(),
                       (self.conv_width, 1, t.shape[-1]))
            for name, t in (("q", q), ("k", k), ("v", v))
        ]
        with named_phase("linear_attention"):  # the device scope `report timeline` reads
            # float32 from the projections to the matmuls' operands: the
            # gradient through a normalised vector is a difference of
            # near-equal terms, and bfloat16 loses it
            q, k, v = (
                nn.silu(causal_depthwise_conv(t.astype(jnp.float32), kernel)).reshape(b, s, h, -1)
                for t, kernel in zip((q, k, v), kernels)
            )
            q, k = l2_normalise(q) * dk**-0.5, l2_normalise(k)
            q, k, v = (t.astype(x.dtype) for t in (q, k, v))
            beta = BETA_RANGE * jax.nn.sigmoid(write.astype(jnp.float32))
            g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
                a.astype(jnp.float32) + dt_bias.astype(jnp.float32)
            )
            o, state_bytes = chunked_gated_delta_rule(q, k, v, g, beta)
            o = nn.RMSNorm(name="o_norm")(o) * nn.silu(z.reshape(b, s, h, dv))
        # read by the lm step into its metrics, summed over the linear layers
        self.sow("counters", "lin_state_bytes", jnp.float32(state_bytes))
        return dense(width, name="o")(o.reshape(b, s, h * dv))
