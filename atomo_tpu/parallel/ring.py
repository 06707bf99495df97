"""Ring attention: exact attention over a sequence-sharded axis.

The reference is CV-only and has no sequence dimension (SURVEY.md §5.7), but
this framework treats long-context as first-class: a sequence of length S is
sharded over the mesh axis ``sp`` (S/n per chip), and attention runs exactly
— not approximately — by rotating key/value blocks around the ring with
``jax.lax.ppermute`` while accumulating a streaming (online-softmax) partial
result. Compute for block t overlaps the transfer of block t+1 on the ICI
torus, which is the TPU-native analogue of the reference's comm/compute
overlap idea (the split-backward models, resnet_split.py:259-361 — there,
per-layer Isend under manual backward; here, XLA pipelines the ppermute).

Memory per chip is O(S/n) for activations and O((S/n)^2) for one score block
— never the full S×S matrix; with n chips the max context grows n× at equal
per-chip HBM.

All shapes are static; the rotation loop is a ``lax.fori_loop`` (compiler-
friendly control flow, no Python unrolling at large n).

Precision follows the dtype q, k and v arrive in: the contractions, forward
and backward, take operands of that dtype and accumulate in float32 (one MXU
pass for bfloat16, ``Precision.HIGHEST`` for float32); scores, row maximum,
exponentials and row sums are float32 always. Of score size the backward
pass keeps the exponentials alone, rounded to that dtype (``_softmax_block``).

Causal attention over one block (``full_attention`` and a ring of one shard)
runs as the fused Pallas kernels of ``ops.attention_kernels``, forward and
backward, where the code can see that they apply (``fused_blocks``: the
arrays on a TPU, bfloat16, queries and keys of one length that is a whole
number of the kernels' blocks, a head size they were measured to win at): a
score tile then never leaves VMEM, the residuals are q, k, v, the output and
a log-sum-exp a row, and ``kept_score_bytes`` is 0. The precision is the one
stated above. Everywhere else (the CPU, float32, other shapes) it is the
``jax.numpy`` block below, which is also the oracle the kernels are tested
against and the block every multi-block path (the ring over n > 1,
``blockwise_attention``, ``ulysses_attention``) shares. That path
skips most of the masked half: the queries are cut into at most
``MAX_QUERY_BLOCKS`` equal blocks (``causal_query_blocks``), and each block
runs against the keys up to its own end only, its softmax taken once over
exactly the keys it saw before, so nothing is rescaled or merged. With n
blocks (n+1)/2n of the score square is computed and kept. The number of
blocks is capped whatever the sequence length: every block is a set of
contractions of shapes no other block has, and the step program's set-up
pays for each distinct shape (PERF.md, PR 30). ``kept_score_bytes`` counts
what is left of the square, for the step's ``attn_score_bytes``.

That one-device causal core also takes a **window** (a query sees the keys
less than ``window`` positions behind it) and **grouped key/value heads** (k
and v with H / group heads; query head i reads head i // group). A window is
not a mask over the triangle: a query block of the jnp path runs against the
keys from its window's start to its own end (``block_key_ranges``), the
kernels' grids walk only the tiles the band touches, and
``tile_score_bytes`` counts what either computes. A group's query heads are
rows of one contraction against their shared head in the jnp path and share
its blocks in the kernels, which sum dK and dV over the group themselves:
neither broadcasts k or v. The multi-block paths (the ring over n > 1,
``blockwise_attention``, ``ulysses_attention``) refuse both.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from atomo_tpu.utils.tracing import named_phase


# The attention core's three contractions as ``dot_general`` takes them: the
# contracted axis of each operand, over the batch axes (0, 1). ``jnp.einsum``
# builds the same operation, but parses the spec and searches a contraction
# path on every trace, and the query blocks trace each one once per block.
_CONTRACTED = {"bhqd,bhkd->bhqk": (3, 3), "bhqk,bhkd->bhqd": (3, 2), "bhqk,bhqd->bhkd": (2, 2)}


def _dot(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """One contraction of the core, operands as they are, float32 result:
    a single MXU pass for bfloat16, ``Precision.HIGHEST`` for float32."""
    precision = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    if spec not in _CONTRACTED:  # the linear layers' contractions
        return jnp.einsum(
            spec, a, b, precision=precision, preferred_element_type=jnp.float32
        )
    in_a, in_b = _CONTRACTED[spec]
    return jax.lax.dot_general(
        a, b, (((in_a,), (in_b,)), ((0, 1), (0, 1))),
        precision=precision, preferred_element_type=jnp.float32,
    )


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _softmax_block(q, k_blk, v_blk, bias, m_prev, scale):
    """Scores, row maximum and exponentials of one K/V block, and the
    exponentials times the values: returns (m, l, o), unnormalised.

    q: (B, H, Sq, D); k_blk/v_blk: (B, H, Sk, D), all of one dtype; bias:
    (Sq, Sk) additive mask (-inf for masked) or None; m_prev: (B, H, Sq)
    running maximum to take the exponentials against (online form, with the
    guards for rows that are fully masked so far), or None for a first and
    only block. m and l are float32, o is float32 of shape (B, H, Sq, D).

    The maximum is a constant to autodiff (the attention built from
    (m, l, o) does not depend on it), so the backward pass needs q, k, v
    and the exponentials alone, kept in the operands' dtype.
    """
    return _softmax_block_fwd(q, k_blk, v_blk, bias, m_prev, scale)[0]


def _softmax_block_fwd(q, k_blk, v_blk, bias, m_prev, scale):
    s = _dot("bhqd,bhkd->bhqk", q, k_blk) * scale
    if bias is not None:
        s = s + bias[None, None, :, :]
    m = jnp.max(s, axis=-1)
    if m_prev is None:
        p = jnp.exp(s - m[..., None])
    else:
        m = jnp.maximum(m_prev, m)
        # guard -inf (fully masked rows) against NaN in exp(-inf - -inf)
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
    l = jnp.sum(p, axis=-1)
    p = p.astype(v_blk.dtype)  # rounded once, where it becomes an operand
    o = _dot("bhqk,bhkd->bhqd", p, v_blk)
    return (m, l, o), (q, k_blk, v_blk, p)


def _softmax_block_grads(scale, res, dl, do):
    """The gradients of q, k_blk and v_blk from the cotangents of l and o,
    in float32 as the contractions accumulate them."""
    q, k_blk, v_blk, p = res
    do = do.astype(v_blk.dtype)
    dv = _dot("bhqk,bhqd->bhkd", p, do)
    dp = _dot("bhqd,bhkd->bhqk", do, v_blk)
    ds = (p * (dp + dl[..., None]) * scale).astype(q.dtype)
    dq = _dot("bhqk,bhkd->bhqd", ds, k_blk)
    dk = _dot("bhqk,bhqd->bhkd", ds, q)
    return dq, dk, dv


def _softmax_block_bwd(scale, res, cts):
    q, k_blk, v_blk, _ = res
    _, dl, do = cts  # m carries no gradient
    dq, dk, dv = _softmax_block_grads(scale, res, dl, do)
    return (
        dq.astype(q.dtype), dk.astype(k_blk.dtype), dv.astype(v_blk.dtype),
        None, None,
    )


_softmax_block.defvjp(_softmax_block_fwd, _softmax_block_bwd)


def _online_softmax_block(q, k_blk, v_blk, bias, m_prev, l_prev, o_prev, scale):
    """One streaming-softmax update: fold a new K/V block into (m, l, o),
    all three float32."""
    m_new, l_blk, o_blk = _softmax_block(q, k_blk, v_blk, bias, m_prev, scale)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    return m_new, l_prev * alpha + l_blk, o_prev * alpha[..., None] + o_blk


def _one_block_attention(q, k, v, bias, scale):
    """Plain masked softmax attention: one block, nothing to rescale."""
    _, l, o = _softmax_block(q, k, v, bias, None, scale)
    return (o / l[..., None]).astype(q.dtype)


def _causal_bias(q_pos, k_pos, window: int = 0):
    """0 where a key is at or below its query and, under a window, less than
    ``window`` positions behind it; -inf elsewhere."""
    seen = q_pos[:, None] >= k_pos[None, :]
    if window:
        seen = seen & (q_pos[:, None] - k_pos[None, :] < window)
    return jnp.where(seen, 0.0, jnp.float32(-jnp.inf))


QUERY_BLOCK_MULTIPLE = 128  # a block's keys end on a lane boundary of the scores
MAX_QUERY_BLOCKS = 8  # a constant: the program is as large at 4096 as at 1024


def causal_query_blocks(sq: int, sk: int) -> int:
    """How many query blocks one-block causal attention is cut into, from the
    static shape alone: the most, up to ``MAX_QUERY_BLOCKS``, equal blocks
    whose size is a multiple of 128 (8 of 128 at 1024, 8 of 512 at 4096);
    1, the uncut program, where the sequence is shorter than 256, is no
    multiple of 128, or queries and keys differ in length."""
    if sq != sk:
        return 1
    return max(
        (n for n in range(1, MAX_QUERY_BLOCKS + 1) if sq % (n * QUERY_BLOCK_MULTIPLE) == 0),
        default=1,
    )


def block_key_ranges(s: int, n: int, window: int = 0) -> list[tuple[int, int, int]]:
    """(first query, first key, end) of each of the n query blocks of S
    positions: a block's keys run to its own end, from 0 or, under a window,
    from the lane boundary at or below the first key its first query sees."""
    blk = s // n
    out = []
    for end in range(blk, s + 1, blk):
        start = max(end - blk - window + 1, 0) if window else 0
        out.append((end - blk, start - start % QUERY_BLOCK_MULTIPLE, end))
    return out


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _causal_blocks(q, k, v, n, scale, window=0):
    """Causal softmax of S queries over the same S keys in n query blocks,
    each against its own key prefix (under a window: the keys from its
    window's start to its own end, ``block_key_ranges``) with the mask on
    the columns the diagonal and the window's edge cross: (l, o) as
    ``_softmax_block`` returns them, rows concatenated.
    A row's softmax is the one-block path's (the keys left out contributed
    exp(-inf) = 0). One differentiation rule over all blocks, so that dK and
    dV are summed over the blocks in float32 and rounded once, as the one
    contraction over all queries rounds them.

    q may have a whole number of times k's heads (grouped queries): the
    group's heads are then rows of one contraction against their shared key
    and value head, so dK and dV come out summed over the group."""
    return _causal_blocks_fwd(q, k, v, n, scale, window)[0]


def _group_rows(t, group: int):
    """(B, H, rows, D) -> (B, H / group, group x rows, D): a group's query
    heads as rows over their one key/value head; as it is for a group of 1."""
    if group == 1:
        return t
    b, h, rows = t.shape[:3]
    return t.reshape(b, h // group, group * rows, *t.shape[3:])


def _ungroup_rows(t, group: int):
    if group == 1:
        return t
    b, hk, rows = t.shape[:3]
    return t.reshape(b, hk * group, rows // group, *t.shape[3:])


def _block_bias(first, start, end, window, group):
    bias = _causal_bias(jnp.arange(first, end), jnp.arange(start, end), window)
    return bias if group == 1 else jnp.tile(bias, (group, 1))


def _causal_blocks_fwd(q, k, v, n, scale, window=0):
    group = q.shape[1] // k.shape[1]
    ls, os, ps = [], [], []
    for first, start, end in block_key_ranges(q.shape[-2], n, window):
        bias = _block_bias(first, start, end, window, group)
        (_, l, o), (_, _, _, p) = _softmax_block_fwd(
            _group_rows(q[:, :, first:end], group), k[:, :, start:end], v[:, :, start:end], bias, None, scale
        )
        ls.append(_ungroup_rows(l, group)), os.append(_ungroup_rows(o, group)), ps.append(p)
    return (jnp.concatenate(ls, axis=2), jnp.concatenate(os, axis=2)), (q, k, v, ps)


def _causal_blocks_bwd(n, scale, window, res, cts):
    q, k, v, ps = res
    dl, do = cts
    group = q.shape[1] // k.shape[1]
    dqs = []
    dk, dv = jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32)
    for (first, start, end), p in zip(block_key_ranges(q.shape[-2], n, window), ps, strict=True):
        rows = slice(first, end)
        dq_blk, dk_blk, dv_blk = _softmax_block_grads(
            scale, (_group_rows(q[:, :, rows], group), k[:, :, start:end], v[:, :, start:end], p),
            _group_rows(dl[:, :, rows], group), _group_rows(do[:, :, rows], group),
        )
        dqs.append(_ungroup_rows(dq_blk, group).astype(q.dtype))
        dk, dv = dk.at[:, :, start:end].add(dk_blk), dv.at[:, :, start:end].add(dv_blk)
    return jnp.concatenate(dqs, axis=2), dk.astype(k.dtype), dv.astype(v.dtype)


_causal_blocks.defvjp(_causal_blocks_fwd, _causal_blocks_bwd)


@partial(jax.jit, static_argnums=(3, 4, 5))  # traced once per shape, not once per layer
def _causal_blocks_attention(q, k, v, n, scale, window=0):
    l, o = _causal_blocks(q, k, v, n, scale, window)
    return (o / l[..., None]).astype(q.dtype)


class Blocks(NamedTuple):
    """(query rows, key rows) of a tile in each of the fused kernels."""

    fwd: tuple[int, int]
    dkv: tuple[int, int]
    dq: tuple[int, int]


# Head size -> the fused kernels' block sizes, clipped to the sequence: one
# algorithm that wants other parameters at other shapes. A head size is
# listed where the kernels beat ``_causal_blocks_attention`` on the chip,
# forward and backward (tests_tpu/test_attention_tpu.py; the readings:
# PERF.md §6, PR 34): at (2, 20, 4096, 256) 11.05 ms a layer against 19.56,
# at (1, 30, 4096, 128) 4.48 against 10.65, every block size from 512 to 1024
# within 5% of the best. 64-wide heads are left out: at (4, 16, 1024, 64) the
# kernels read 1.19 ms a layer at their best blocks where the jnp blocks read
# 0.60 (a 64-wide contraction fills half of a 128 x 128 MXU and half of
# every lane). The table lives here and not with the kernels because their
# module imports Pallas, a second of set-up that a run which takes no kernel
# (`train`, or `lm` at a head size left out) does not pay.
FUSED_BLOCKS: dict[int, Blocks] = {
    256: Blocks(fwd=(512, 512), dkv=(512, 512), dq=(512, 512)),
    128: Blocks(fwd=(512, 512), dkv=(1024, 1024), dq=(1024, 1024)),
}


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def fused_blocks(q_shape, k_shape, dtype, on_tpu: Optional[bool] = None) -> Optional[Blocks]:
    """The block sizes the fused kernels take for causal attention of these
    shapes, or None where the jnp path stays: off the TPU, operands other
    than bfloat16, queries and keys of different lengths, a head size
    without a measured win, or a sequence that is no whole number of 128 or
    of a block."""
    s, d = q_shape[-2:]
    table = FUSED_BLOCKS.get(d)
    if table is None or dtype != jnp.bfloat16 or tuple(k_shape[-2:]) != (s, d) or s % 128:
        return None
    if not (_on_tpu() if on_tpu is None else on_tpu):
        return None
    blocks = Blocks(*((min(bq, s), min(bk, s)) for bq, bk in table))
    if any(s % b for pair in blocks for b in pair):
        return None
    return blocks


def _cut_causal_attention(q, k, v, scale, window=0):
    """Causal attention of one K/V block on one device, nothing to rescale:
    through the fused kernels where ``fused_blocks`` says so (a TPU,
    bfloat16, a head size and a sequence they were measured at), else in
    query blocks; None where the sequence is not cut (the one-block program).
    Both take a window and grouped queries as they are: neither computes a
    tile or a block that the band does not touch."""
    if (blocks := fused_blocks(q.shape, k.shape, q.dtype)) is not None:
        from atomo_tpu.ops.attention_kernels import fused_attention, interpret_requested

        return fused_attention(q, k, v, True, float(scale), blocks, interpret_requested(), window)
    if (n := causal_query_blocks(q.shape[-2], k.shape[-2])) > 1:
        return _causal_blocks_attention(q, k, v, n, scale, window)
    return None


def _one_block_grouped(q, k, v, bias, scale):
    """The one-block program, a group's query heads as rows over their one
    key/value head."""
    group = q.shape[1] // k.shape[1]
    if group > 1 and bias is not None:
        bias = jnp.tile(bias, (group, 1))
    return _ungroup_rows(_one_block_attention(_group_rows(q, group), k, v, bias, scale), group)


def _check_core(q, k, window: int, causal: bool, multi_block: str = "") -> None:
    """What the one-device causal core takes and no other path does: a
    window, and fewer key/value heads than query heads. ``multi_block``
    names a path that runs the sequence in several key/value blocks."""
    if q.shape[1] % k.shape[1]:
        raise ValueError(f"{q.shape[1]} query heads are no whole number of {k.shape[1]} key/value heads")
    if window and not causal:
        raise ValueError("a window is a causal band: causal=True")
    if multi_block and (window or q.shape[1] != k.shape[1]):
        raise ValueError(
            f"{multi_block} runs the sequence in several key/value blocks: a window "
            "and grouped key/value heads are the one-device causal core's alone"
        )


def _common_dtype(q, k, v):
    dtype = jnp.result_type(q, k, v)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype)


@named_phase("attention")  # the device scope `report timeline` reads
def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    axis_size: int,
    causal: bool = False,
    scale: Optional[float] = None,
    window: int = 0,
) -> jax.Array:
    """Exact multi-head attention with sequence sharded over ``axis_name``.

    Call inside shard_map with q/k/v of per-chip shape (B, H, S/n, D); the
    global sequence order is shard-major (chip r holds positions
    [r*S/n, (r+1)*S/n)). Returns the per-chip output block (B, H, S/n, D).
    Over an axis of one, ``window`` keeps each query to the keys less than
    that many positions behind it, and k and v may have fewer heads than q
    (query head i reads key/value head i // group).
    """
    b, h, s_local, d = q.shape
    if scale is None:
        scale = 1.0 / (d**0.5)
    _check_core(q, k, window, causal, f"the ring over {axis_name}={axis_size}" if axis_size > 1 else "")
    q, k, v = _common_dtype(q, k, v)
    if axis_size == 1:  # one K/V block: no rotation, nothing to rescale
        if causal and (out := _cut_causal_attention(q, k, v, scale, window)) is not None:
            return out
        pos = jnp.arange(s_local)
        return _one_block_grouped(
            q, k, v, _causal_bias(pos, pos, window) if causal else None, scale
        )
    my = jax.lax.axis_index(axis_name)

    neg = jnp.float32(-jnp.inf)
    q_pos = my * s_local + jnp.arange(s_local)  # global query positions

    def body(t, carry):
        k_blk, v_blk, m, l, o = carry
        # block t came from chip (my + t) mod n  → its global offset
        src = (my + t) % axis_size
        k_pos = src * s_local + jnp.arange(s_local)
        if causal:
            bias = _causal_bias(q_pos, k_pos)
        else:
            bias = jnp.zeros((s_local, s_local), jnp.float32)
        m, l, o = _online_softmax_block(q, k_blk, v_blk, bias, m, l, o, scale)
        # rotate K/V one step around the ring (chip r receives from r+1, so
        # after t rotations we hold the block that started at (my + t) mod n)
        perm = [(i, (i - 1) % axis_size) for i in range(axis_size)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, m, l, o

    m0 = jnp.full((b, h, s_local), neg, jnp.float32)
    l0 = jnp.zeros((b, h, s_local), jnp.float32)
    o0 = jnp.zeros((b, h, s_local, d), jnp.float32)
    _, _, m, l, o = jax.lax.fori_loop(0, axis_size, body, (k, v, m0, l0, o0))
    out = o / jnp.maximum(l, jnp.finfo(jnp.float32).tiny)[..., None]
    return out.astype(q.dtype)


@named_phase("attention")
def full_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = False,
    scale: Optional[float] = None, window: int = 0,
) -> jax.Array:
    """Single-device exact attention (B, H, S, D) — the oracle ring_attention
    must match, and the path used when no 'sp' axis is in play. ``window``
    and fewer key/value heads as :func:`ring_attention` takes them."""
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d**0.5)
    _check_core(q, k, window, causal)
    q, k, v = _common_dtype(q, k, v)
    if causal and (out := _cut_causal_attention(q, k, v, scale, window)) is not None:
        return out
    bias = None
    if causal:
        bias = _causal_bias(jnp.arange(q.shape[-2]), jnp.arange(k.shape[-2]), window)
    return _one_block_grouped(q, k, v, bias, scale)


def _one_device_keywords(attention_fn) -> Optional[dict]:
    """The keywords of ``attention_fn`` where it is this module's one-device
    path: a ``partial`` of :func:`full_attention`, or of
    :func:`ring_attention` over an axis of one. None for any other callable."""
    func = getattr(attention_fn, "func", attention_fn)
    given = getattr(attention_fn, "keywords", {})
    if func is full_attention or (func is ring_attention and given.get("axis_size") == 1):
        return given
    return None


def fused_layers(attention_fn, q: jax.Array) -> int:
    """1 where ``attention_fn(q, k, v)``, over keys as long as the queries,
    runs the fused kernels (``_cut_causal_attention``'s rule), else 0: the
    step's ``attn_fused_layers``, summed over the layers."""
    given = _one_device_keywords(attention_fn)
    if given is None or not given.get("causal"):
        return 0
    return int(fused_blocks(q.shape, q.shape, q.dtype) is not None)


def _block_entries(given: dict, s: int) -> int:
    """Score entries a (sequence, head) the jnp path computes: its query
    blocks against the keys each sees."""
    n = causal_query_blocks(s, s) if given.get("causal") else 1
    return sum(
        (end - first) * (end - start)
        for first, start, end in block_key_ranges(s, n, given.get("window", 0))
    )


def kept_score_bytes(attention_fn, q: jax.Array) -> int:
    """Bytes of exponentials ``attention_fn(q, k, v)`` keeps for the backward
    pass, from shapes, where it is this module's one-device path over keys as
    long as the queries. 0 where the fused kernels run (they keep a
    log-sum-exp a row and no exponential), and for any other callable, whose
    residuals are not counted here."""
    given = _one_device_keywords(attention_fn)
    if given is None or fused_layers(attention_fn, q):
        return 0
    b, h, s, _ = q.shape
    return b * h * _block_entries(given, s) * q.dtype.itemsize


def tile_score_bytes(attention_fn, q: jax.Array) -> int:
    """Bytes of the score entries ``attention_fn(q, k, v)`` computes in its
    forward pass, at 4 B each, from shapes: the tiles the fused forward
    kernel walks, or the jnp path's query blocks against the keys each sees.
    A window that were a mask over the causal tiles would read as no window
    here. 0 for a callable that is not this module's one-device path."""
    given = _one_device_keywords(attention_fn)
    if given is None:
        return 0
    b, h, s, _ = q.shape
    entries = _block_entries(given, s)
    if fused_layers(attention_fn, q):
        from atomo_tpu.ops.attention_kernels import forward_tiles

        bq, bk = fused_blocks(q.shape, q.shape, q.dtype).fwd
        entries = forward_tiles(s, (bq, bk), given.get("window", 0)) * bq * bk
    return b * h * entries * 4


@named_phase("attention")
def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_size: int = 512,
) -> jax.Array:
    """Single-device exact attention that never materializes the S×S score
    matrix: streams K/V blocks through the same online-softmax update the
    ring uses, O(Sq·block) score memory. Equals full_attention (tested)."""
    b, h, s, d = q.shape
    if scale is None:
        scale = 1.0 / (d**0.5)
    _check_core(q, k, 0, causal, "blockwise_attention")
    q, k, v = _common_dtype(q, k, v)
    blk = min(block_size, s)
    n_blocks = -(-s // blk)
    pad = n_blocks * blk - s
    neg = jnp.float32(-jnp.inf)
    if pad:  # pad keys with fully-masked positions
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    q_pos = jnp.arange(s)

    def body(t, carry):
        m, l, o = carry
        k_blk = jax.lax.dynamic_slice_in_dim(k, t * blk, blk, axis=2)
        v_blk = jax.lax.dynamic_slice_in_dim(v, t * blk, blk, axis=2)
        k_pos = t * blk + jnp.arange(blk)
        valid = k_pos[None, :] < s
        if causal:
            valid = valid & (q_pos[:, None] >= k_pos[None, :])
        bias = jnp.where(valid, 0.0, neg)
        return _online_softmax_block(q, k_blk, v_blk, bias, m, l, o, scale)

    m0 = jnp.full((b, h, s), neg, jnp.float32)
    l0 = jnp.zeros((b, h, s), jnp.float32)
    o0 = jnp.zeros((b, h, s, d), jnp.float32)
    m, l, o = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, o0))
    out = o / jnp.maximum(l, jnp.finfo(jnp.float32).tiny)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    axis_size: int,
    causal: bool = False,
    scale: Optional[float] = None,
    block_size: int = 512,
    local_impl: str = "blockwise",
) -> jax.Array:
    """All-to-all (DeepSpeed-Ulysses-style) sequence parallelism: swap the
    sequence sharding for a *head* sharding with one ``all_to_all``, run
    blockwise exact attention on whole sequences for H/n local heads, and
    swap back. ``local_impl`` picks the per-chip attention after the swap:
    "blockwise" (jnp online-softmax scan) or "flash" (the fused Pallas
    kernel, ops.attention_kernels — Mosaic on TPU, interpreter on CPU).
    The second first-class long-context strategy next to
    :func:`ring_attention`:

      * ring — n ppermute hops of K/V around the ICI torus, O(S/n)
        sequence activations per chip; best when S is huge and H is small.
      * ulysses — TWO all_to_all collectives total (q/k/v ride one stacked
        collective in, the output one out — vs n hops), and the local
        attention is blockwise (no S×S matrix; O(S·block) score memory,
        O(S/n · H) activations after the swap); needs H divisible by n.

    Same contract as ring_attention: call inside shard_map with per-chip
    (B, H, S/n, D), shard-major global sequence order; returns the per-chip
    (B, H, S/n, D) output block. Exactness is tested against
    full_attention, and gradient parity against ring
    (tests/test_ring.py).
    """
    b, h, s_local, d = q.shape
    _check_core(q, k, 0, causal, "ulysses_attention")
    if local_impl not in ("blockwise", "flash"):
        raise ValueError(
            f"unknown local_impl {local_impl!r}; expected blockwise|flash"
        )
    if h % axis_size != 0:
        raise ValueError(
            f"ulysses needs heads ({h}) divisible by the {axis_name!r} "
            f"axis ({axis_size}); use ring_attention otherwise"
        )

    # ONE collective for all three operands: stack -> (3, B, H, S/n, D),
    # split heads (axis 2), concat sequence (axis 3)
    qkv = jnp.stack([q, k, v])
    qkv = jax.lax.all_to_all(qkv, axis_name, split_axis=2, concat_axis=3, tiled=True)
    q_g, k_g, v_g = qkv[0], qkv[1], qkv[2]  # (B, H/n, S, D)
    if local_impl == "flash":
        from atomo_tpu.ops.attention_kernels import flash_attention

        out = flash_attention(
            q_g, k_g, v_g, causal=causal, scale=scale,
            block_q=block_size, block_k=block_size,
        )
    else:
        out = blockwise_attention(
            q_g, k_g, v_g, causal=causal, scale=scale, block_size=block_size
        )
    # (B, H/n, S, D) -> (B, H, S/n, D): split the sequence, regather heads
    return jax.lax.all_to_all(
        out, axis_name, split_axis=2, concat_axis=1, tiled=True
    )


ATTENTION_IMPLS = {
    "ring": ring_attention,
    "ulysses": ulysses_attention,
    # Ulysses with the fused Pallas kernel as its local attention — the
    # flash forward IS reachable from training (make_lm_train_step /
    # `lm --attn-impl ulysses-flash`)
    "ulysses-flash": partial(ulysses_attention, local_impl="flash"),
}


def make_sequence_parallel_attention(
    mesh: Mesh, axis: str = "sp", causal: bool = True, impl: str = "ring"
):
    """shard_map-wrapped sequence-parallel attention: (B, H, S, D) arrays
    sharded over ``axis`` on the sequence dim; drop-in for full_attention
    at S too large for one chip. ``impl`` picks the strategy ("ring" |
    "ulysses" — see ulysses_attention for the tradeoff)."""
    if impl not in ATTENTION_IMPLS:
        raise ValueError(
            f"unknown attention impl {impl!r}; expected one of "
            f"{sorted(ATTENTION_IMPLS)}"
        )
    n = mesh.shape[axis]

    fn = partial(ATTENTION_IMPLS[impl], axis_name=axis, axis_size=n, causal=causal)
    return jax.jit(
        jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(P(None, None, axis, None),) * 3,
            out_specs=P(None, None, axis, None),
            check_vma=False,
        )
    )
