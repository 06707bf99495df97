"""Pallas TPU kernels for fused softmax attention, forward and backward.

The jnp attention paths (parallel.ring) leave the softmax chain to XLA: per
query block the float32 scores go to HBM, come back for the row maximum and
for the exponential, go out again as ``p`` and come back for ``p·v``; the
backward pass does the same with ``dp`` and ``ds`` and sums dK and dV over
the blocks in a float32 array of K's size. At 256-wide heads over 4096
positions that traffic, not the products, sets the time (PERF.md §6, PR 34).
Here a score tile never leaves VMEM.

Three kernels, each over a grid of (batch, head, live tile): the live
(query block, key block) pairs are listed once from the static shape and
handed to the kernel as scalar-prefetched tables, so a key block wholly
above the diagonal is neither computed nor fetched, nor does the grid step
over it.

  forward   walks a query block's key blocks, innermost, with the running
            maximum, row sum and unnormalised output in VMEM scratch;
            writes the output and the rows' log-sum-exp, the one residual
            beside q, k, v and o.
  dK, dV    walks a key block's query blocks and accumulates both in
            float32 VMEM scratch. It works on the transposed tile (keys as
            rows), so the rows' statistics lie along the lanes as they are
            stored and no tile is transposed.
  dQ        walks a query block's key blocks and accumulates dQ.

Both backward kernels rebuild ``p`` from q, k and the log-sum-exp;
``delta = rowsum(dO * O)`` is computed once outside them.

Under a ``window`` the live pairs are those the band touches (a key at or
below a query and less than ``window`` behind one), and a tile that the
window's lower edge crosses is masked as one the diagonal crosses is. With
fewer key/value heads than query heads (H = group x Hkv) the forward and dQ
kernels fetch K and V blocks of head ``h // group``; the dK/dV kernel's grid
walks the key/value heads, and a key block's tiles run over each query head
of its group in turn (a third table), so both are summed over the group in
the float32 scratch and written once: K and V are never broadcast.

Precision is the jnp path's (parallel/ring.py): operands enter the
products in the dtype they arrive in and accumulate in float32
(``Precision.HIGHEST`` for float32 operands); scores, maximum, exponentials,
sums and accumulators are float32; ``p`` and ``ds`` are rounded to the
operands' dtype once, where they become operands; dQ, dK and dV are rounded
once, from float32.

``parallel.ring`` takes ``fused_attention`` for its one-device causal path
where ``ring.fused_blocks`` says so; the rule and its table of block sizes
by head size (``ring.FUSED_BLOCKS``, from chip timings) live there, beside
the dispatch, because importing this module imports Pallas (a second of
every process's set-up) and a run that takes no kernel must not pay it.
``flash_attention`` is the explicit entry (`lm --attn-impl ulysses-flash`),
causal or not, with the caller's block sizes; a sequence that does not tile
falls back to ``blockwise_attention``.

Tested in the TPU-semantics interpreter on CPU (tests/) against the
float32 one-block oracle, and compiled by Mosaic, compared and timed on
the chip (tests_tpu/).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from atomo_tpu.ops.qsgd_kernels import _interpret_mode, interpret_requested

LANES = 128
# a masked score: far below any real one, and finite, so that a row whose
# keys in a tile are all masked never meets inf - inf
MASKED = -0.7 * float(np.finfo(np.float32).max)
# the tiles' temporaries at blocks of 1024 pass the compiler's default of 16 MiB
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _lanes(x, n: int):
    """x (rows, 128) with every lane alike -> (rows, n)."""
    if n % LANES == 0:
        return jnp.tile(x, (1, n // LANES))
    if n < LANES:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _dot(a, b, contract_b: int):
    """a (m, c) times b, contracted over b's axis ``contract_b``: operands as
    they are, float32 result."""
    precision = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(
        a, b, (((1,), (contract_b,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32,
    )


def _first_key_block(i, bq: int, bk: int, window: int):
    """The first key block query block i sees."""
    return jnp.maximum(i * bq - window + 1, 0) // bk if window else 0


def _last_key_block(i, bq: int, bk: int, nk: int, causal: bool):
    """The last key block query block i sees."""
    return jnp.minimum(((i + 1) * bq - 1) // bk, nk - 1) if causal else nk - 1


def _first_query_block(j, bq: int, bk: int, causal: bool):
    """The first query block that sees key block j."""
    return (j * bk) // bq if causal else 0


def _last_query_block(j, bq: int, bk: int, nq: int, window: int):
    """The last query block that sees key block j."""
    return jnp.minimum(((j + 1) * bk + window - 2) // bq, nq - 1) if window else nq - 1


def _live_tiles(s: int, bq: int, bk: int, causal: bool, keys_outermost: bool,
                window: int = 0, group: int = 1):
    """The (query block, key block) pairs with a key at or below a query
    and, under a window, less than ``window`` behind one, as int32 tables in
    the order a kernel walks them. With ``keys_outermost`` a third table
    gives the query head within its group: a key block's tiles are walked
    for each of the ``group`` query heads that share it in turn."""
    pairs = [
        (i, j) for i in range(s // bq) for j in range(s // bk)
        if (not causal or j * bk <= (i + 1) * bq - 1)
        and (not window or (j + 1) * bk - 1 > i * bq - window)
    ]
    if not keys_outermost:
        return tuple(np.asarray(t, np.int32) for t in zip(*pairs))
    walked = sorted((j, g, i) for i, j in pairs for g in range(group))
    j_tab, g_tab, i_tab = (np.asarray(t, np.int32) for t in zip(*walked))
    return i_tab, j_tab, g_tab


def _seen_tile(i, j, bq: int, bk: int, keys_as_rows: bool, window: int):
    """Where a tile's key is at or below its query and inside its window."""
    shape = (bk, bq) if keys_as_rows else (bq, bk)
    q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, shape, 1 if keys_as_rows else 0)
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, shape, 0 if keys_as_rows else 1)
    if not window:
        return k_pos <= q_pos
    return (k_pos <= q_pos) & (q_pos - k_pos < window)


def _masked_or_not(step, i, j, bq: int, bk: int, causal: bool, window: int):
    """Run ``step(masked)``: with the mask only on a tile that the diagonal
    or the window's lower edge crosses."""
    if not causal:
        step(False)
        return
    crosses = (j + 1) * bk - 1 > i * bq
    if window:
        crosses = crosses | ((i + 1) * bq - 1 - j * bk >= window)
    pl.when(crosses)(partial(step, True))
    pl.when(jnp.logical_not(crosses))(partial(step, False))


def _fwd_kernel(
    i_tab, j_tab, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
    scale: float, causal: bool, nk: int, window: int,
):
    bq, bk, dv = q_ref.shape[2], k_ref.shape[2], v_ref.shape[3]
    t = pl.program_id(2)
    i, j = i_tab[t], j_tab[t]

    @pl.when(j == _first_key_block(i, bq, bk, window))
    def _first_key_block_of_the_rows():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def step(masked: bool):
        v = v_ref[0, 0]
        s = _dot(q_ref[0, 0], k_ref[0, 0], 1) * scale  # (bq, bk)
        if masked:
            s = jnp.where(_seen_tile(i, j, bq, bk, False, window), s, MASKED)
        m_prev = m_scr[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - _lanes(m_next, bk))
        m_scr[...] = m_next
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        # p rounded once, where it becomes an operand
        acc_scr[...] = acc_scr[...] * _lanes(alpha, dv) + _dot(p.astype(v.dtype), v, 0)

    _masked_or_not(step, i, j, bq, bk, causal, window)

    @pl.when(j == _last_key_block(i, bq, bk, nk, causal))
    def _last_key_block_of_the_rows():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / _lanes(l, dv)).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[...] + jnp.log(l)).T[:1]  # rows along the lanes


def _dkv_kernel(
    i_tab, j_tab, g_tab, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr, *, scale: float, causal: bool, nq: int, window: int, group: int,
):
    bq, bk = q_ref.shape[2], k_ref.shape[2]
    t = pl.program_id(2)
    i, j, g = i_tab[t], j_tab[t], g_tab[t]

    @pl.when((i == _first_query_block(j, bq, bk, causal)) & (g == 0))
    def _first_tile_of_the_keys():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    def step(masked: bool):
        q, do = q_ref[0, 0], do_ref[0, 0]
        s = _dot(k_ref[0, 0], q, 1) * scale  # (bk, bq): keys as rows
        if masked:
            s = jnp.where(_seen_tile(i, j, bq, bk, True, window), s, MASKED)
        p = jnp.exp(s - lse_ref[0, 0])
        dv_scr[...] += _dot(p.astype(do.dtype), do, 0)
        dp = _dot(v_ref[0, 0], do, 1)
        ds = p * (dp - delta_ref[0, 0]) * scale
        dk_scr[...] += _dot(ds.astype(q.dtype), q, 0)

    _masked_or_not(step, i, j, bq, bk, causal, window)

    @pl.when((i == _last_query_block(j, bq, bk, nq, window)) & (g == group - 1))
    def _last_tile_of_the_keys():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(
    i_tab, j_tab, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
    dq_scr, lse_scr, delta_scr, *, scale: float, causal: bool, nk: int, window: int,
):
    bq, bk = q_ref.shape[2], k_ref.shape[2]
    t = pl.program_id(2)
    i, j = i_tab[t], j_tab[t]

    @pl.when(j == _first_key_block(i, bq, bk, window))
    def _first_key_block_of_the_rows():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)
        # the rows' statistics from along the lanes to one a row, lanes alike
        lse_scr[...] = jnp.broadcast_to(lse_ref[0, 0], (LANES, bq)).T
        delta_scr[...] = jnp.broadcast_to(delta_ref[0, 0], (LANES, bq)).T

    def step(masked: bool):
        k = k_ref[0, 0]
        s = _dot(q_ref[0, 0], k, 1) * scale  # (bq, bk)
        if masked:
            s = jnp.where(_seen_tile(i, j, bq, bk, False, window), s, MASKED)
        p = jnp.exp(s - _lanes(lse_scr[...], bk))
        dp = _dot(do_ref[0, 0], v_ref[0, 0], 1)
        ds = p * (dp - _lanes(delta_scr[...], bk)) * scale
        dq_scr[...] += _dot(ds.astype(k.dtype), k, 0)

    _masked_or_not(step, i, j, bq, bk, causal, window)

    @pl.when(j == _last_key_block(i, bq, bk, nk, causal))
    def _last_key_block_of_the_rows():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _call(kernel, name, heads, tables, in_specs, out_specs, out_shape, scratch, interpret, operands):
    return pl.pallas_call(
        kernel,
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=(operands[0].shape[0], heads, len(tables[0])),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=_interpret_mode(interpret),
    )(*tables, *operands)


def _same_head(h, t, tabs):
    return h


def _shared_head(group: int):
    """The key/value head a query head reads."""
    return _same_head if group == 1 else lambda h, t, tabs: h // group


def _rows_of(block: int, width: int, head=_same_head):
    """A tile of query rows: (1, 1, block, width) at query block i of the
    head that ``head(grid head, grid step, tables)`` gives."""
    return pl.BlockSpec((1, 1, block, width), lambda b, h, t, *tabs: (b, head(h, t, tabs), tabs[0][t], 0))


def _keys_of(block: int, width: int, head=_same_head):
    return pl.BlockSpec((1, 1, block, width), lambda b, h, t, *tabs: (b, head(h, t, tabs), tabs[1][t], 0))


def _stats_of(block: int, head=_same_head):
    """A query block's statistics, (B, H, 1, S) float32: rows along the lanes."""
    return pl.BlockSpec((1, 1, 1, block), lambda b, h, t, *tabs: (b, head(h, t, tabs), 0, tabs[0][t]))


def _forward(q, k, v, causal, scale, block, interpret, window=0):
    (b, h, s, d), dv, (bq, bk) = q.shape, v.shape[-1], block
    group = h // k.shape[1]
    shared = _shared_head(group)
    return _call(
        partial(_fwd_kernel, scale=scale, causal=causal, nk=s // bk, window=window),
        "fused_attention_fwd", h,
        _live_tiles(s, bq, bk, causal, False, window),
        [_rows_of(bq, d), _keys_of(bk, d, shared), _keys_of(bk, dv, shared)],
        [_rows_of(bq, dv), _stats_of(bq)],
        [jax.ShapeDtypeStruct((b, h, s, dv), q.dtype), jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32)],
        [pltpu.VMEM((bq, LANES), jnp.float32), pltpu.VMEM((bq, LANES), jnp.float32),
         pltpu.VMEM((bq, dv), jnp.float32)],
        interpret, (q, k, v),
    )


def _backward(q, k, v, o, lse, do, causal, scale, dkv_block, dq_block, interpret, window=0):
    (b, h, s, d), dv = q.shape, v.shape[-1]
    group = h // k.shape[1]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)[:, :, None, :]
    operands = (q, k, v, do, lse, delta)

    def specs(bq, bk, rows, keys):
        return [_rows_of(bq, d, rows), _keys_of(bk, d, keys), _keys_of(bk, dv, keys), _rows_of(bq, dv, rows),
                _stats_of(bq, rows), _stats_of(bq, rows)]

    # dK and dV: the grid walks the key/value heads, and a key block's tiles
    # run over every query head of its group, so both are summed in the kernel
    bq, bk = dkv_block
    member = _same_head if group == 1 else lambda h, t, tabs: h * group + tabs[2][t]  # noqa: E731
    dk, dv_ = _call(
        partial(_dkv_kernel, scale=scale, causal=causal, nq=s // bq, window=window, group=group),
        "fused_attention_dkv", h // group,
        _live_tiles(s, bq, bk, causal, True, window, group),
        specs(bq, bk, member, _same_head), [_keys_of(bk, d), _keys_of(bk, dv)],
        [jax.ShapeDtypeStruct(k.shape, k.dtype), jax.ShapeDtypeStruct(v.shape, v.dtype)],
        [pltpu.VMEM((bk, d), jnp.float32), pltpu.VMEM((bk, dv), jnp.float32)],
        interpret, operands,
    )
    bq, bk = dq_block
    shared = _shared_head(group)
    dq = _call(
        partial(_dq_kernel, scale=scale, causal=causal, nk=s // bk, window=window),
        "fused_attention_dq", h,
        _live_tiles(s, bq, bk, causal, False, window),
        specs(bq, bk, _same_head, shared), _rows_of(bq, d), jax.ShapeDtypeStruct(q.shape, q.dtype),
        [pltpu.VMEM((bq, d), jnp.float32), pltpu.VMEM((bq, LANES), jnp.float32),
         pltpu.VMEM((bq, LANES), jnp.float32)],
        interpret, operands,
    )
    return dq, dk, dv_


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fused(q, k, v, causal, scale, blocks, interpret, window):
    return _forward(q, k, v, causal, scale, blocks[0], interpret, window)[0]


def _fused_fwd(q, k, v, causal, scale, blocks, interpret, window):
    o, lse = _forward(q, k, v, causal, scale, blocks[0], interpret, window)
    return o, (q, k, v, o, lse)


def _fused_bwd(causal, scale, blocks, interpret, window, res, do):
    return _backward(*res, do, causal, scale, blocks[1], blocks[2], interpret, window)


_fused.defvjp(_fused_fwd, _fused_bwd)


@partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))  # traced and lowered once a shape, not once a layer
def fused_attention(q, k, v, causal: bool, scale: float, blocks: tuple, interpret: bool = False,
                    window: int = 0):
    """Softmax attention (B, H, S, D) x (B, Hkv, S, D) x (B, Hkv, S, Dv) ->
    (B, H, S, Dv) through the three kernels. ``blocks``: the (query rows, key
    rows) of a tile in the forward, the dK/dV and the dQ kernel; S a whole
    number of each. H a whole number of times Hkv: query head i reads
    key/value head i // (H / Hkv). ``window`` (causal only): a query sees the
    keys less than that many positions behind it, and the grids walk only
    the tiles that band touches."""
    return _fused(q, k, v, causal, scale, blocks, interpret, window)


def forward_tiles(s: int, block: tuple, window: int = 0) -> int:
    """How many score tiles the causal forward kernel computes a (sequence,
    head): what the step's ``attn_tile_score_bytes`` counts."""
    return len(_live_tiles(s, *block, True, False, window)[0])


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
    """Fused exact attention (B, H, S, D) -> (B, H, S, D), forward and
    backward through the kernels with the caller's block sizes, compiled by
    Mosaic for the device it is on (``interpret=None`` interprets only when
    ops.qsgd_kernels.interpret_requested says so: tests and CPU dry runs).
    Falls back to blockwise_attention when S doesn't tile by the blocks:
    the same result either way (tested)."""
    from atomo_tpu.parallel.ring import blockwise_attention

    s, d = q.shape[-2:]
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
    block = (block_q, block_k)
    return fused_attention(q, k, v, causal, float(scale), (block, block, block), interpret)
