"""Lossless sparse-row codec: (row-index, row-value) pairs on the wire.

The embedding workloads the ROADMAP's "millions of users" framing points
at (recommendation/retrieval towers) produce table gradients that are
naturally ROW-sparse: a step touches only the rows its batch looked up,
so a dense — or even compressed-dense — exchange ships almost all zeros.
Parallax (1808.02621) is the blueprint: sparse layers exchange as row
updates while dense layers keep their existing path. This module is the
wire format for the sparse half.

Design rules, in the house order of importance:

  * STATIC SHAPES. The nonzero-row count varies per step, so the payload
    carries a fixed worst-case ``max_rows`` budget (rows beyond the
    budget would be dropped — see the overflow contract below), keeping
    every shape a trace-time constant under jit/scan exactly like the
    fixed-budget samplers of codecs/svd.py.
  * LOSSLESS, bit for bit up to the sign of zero. Unlike every other
    codec here, the row codec is NOT a stochastic estimator:
    ``decode(encode(key, g)) == g`` exactly whenever the gradient's
    nonzero rows fit the budget. Padding slots point at row 0 with
    exactly-zero values, and ``x + 0.0`` is exact in IEEE, so a
    scatter-ADD decode reproduces the dense gradient bit for bit (the
    elastic.shrink "zero row is an exact identity" argument, applied per
    scatter slot) — with ONE stated corner: a ``-0.0`` entry in a
    shipped row 0 decodes as ``+0.0`` ((-0.0) + (+0.0) = +0.0 in
    round-to-nearest), and an all ``-0.0`` row classifies as empty, so
    signed zeros normalize to ``+0.0`` (value-equal; autodiff's
    untouched-row cotangents are ``+0.0`` already, and every parity gate
    treats -0.0 == +0.0). Duplicate rows — within one payload or across
    replicas' payloads summed after decode — sum exactly, which is what
    makes the hybrid aggregation operator bit-identical to the canonical
    dense exchange (sparse/hybrid.py).
  * HONEST OVERFLOW. A gradient with more nonzero rows than the budget
    cannot be shipped losslessly; the codec keeps the FIRST ``max_rows``
    nonzero rows (ascending row order — deterministic) and reports the
    dropped count in ``payload.overflow``. Callers that claim
    losslessness (the hybrid plan) must size the budget from a true
    worst-case bound (``sparse.hybrid.infer_row_bounds``: a lookup
    touches at most batch x slots rows), and the tests gate on
    ``overflow == 0`` rather than trusting the claim.

Wire accounting: ``max_rows x (ncols x itemsize + 4)`` bytes + the 4-byte
overflow counter — ``payload_nbytes`` prices it like any other payload
(the Msg(MB) honesty rule), and comm_model's per-leaf pricing uses
:func:`row_payload_bytes` so prediction and execution cannot disagree.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from atomo_tpu.codecs.base import PRNGKey


class RowPayload(NamedTuple):
    rows: jax.Array  # (max_rows,) int32 row indices; padding slots = 0
    values: jax.Array  # (max_rows, ncols) row values; padding slots = 0.0
    overflow: jax.Array  # () int32: nonzero rows DROPPED (budget exceeded)


def row_payload_bytes(max_rows: int, ncols: int, itemsize: int = 4) -> int:
    """Static wire bytes of one :class:`RowPayload` — THE formula the
    comm model prices sparse-assigned leaves with (kept next to the
    format so the two cannot drift): values + int32 indices + the int32
    overflow counter."""
    return int(max_rows) * (int(ncols) * int(itemsize) + 4) + 4


@dataclasses.dataclass(frozen=True)
class RowCodec:
    """Codec-protocol adapter for the sparse-row wire format over one 2-D
    ``(rows, ncols)`` leaf. ``max_rows`` is the static per-step budget;
    one instance serves one leaf shape (the hybrid plan builds one per
    sparse-assigned leaf). Implements ``encode``/``decode`` with the
    standard signatures, so it also rides the generic tree machinery —
    ``decode_mean_tree`` and the ring's ``_ring_stream_mean`` — unchanged
    (the "ring-staged form" of the lossless drill)."""

    max_rows: int
    name: str = "rows"

    def encode(self, key: PRNGKey, grad: jax.Array) -> RowPayload:
        del key  # deterministic: nothing is sampled, nothing is lost
        if grad.ndim != 2:
            raise ValueError(
                f"RowCodec encodes 2-D (rows, ncols) leaves; got shape "
                f"{tuple(grad.shape)} — the hybrid plan assigns only "
                "row-sparse table leaves here"
            )
        n_rows = grad.shape[0]
        k = min(int(self.max_rows), int(n_rows))
        nz = jnp.any(grad != 0, axis=1)
        # ascending row order, nonzero rows first: a deterministic,
        # shape-static selection (argsort of a two-band key)
        idx = jnp.arange(n_rows)
        order = jnp.argsort(jnp.where(nz, idx, n_rows + idx))
        sel = order[:k]
        live = nz[sel]
        rows = jnp.where(live, sel, 0).astype(jnp.int32)
        values = jnp.where(live[:, None], grad[sel], jnp.zeros((), grad.dtype))
        overflow = (
            jnp.sum(nz.astype(jnp.int32)) - jnp.sum(live.astype(jnp.int32))
        )
        return RowPayload(rows=rows, values=values, overflow=overflow)

    def decode(
        self, payload: RowPayload, grad_shape, dtype=jnp.float32
    ) -> jax.Array:
        # scatter-ADD, not set: padding slots add an exact 0.0 at row 0
        # (an IEEE identity), and duplicate indices sum exactly — the two
        # properties the lossless and exact-collision contracts rest on
        out = jnp.zeros(grad_shape, dtype)
        return out.at[payload.rows].add(payload.values.astype(dtype))
