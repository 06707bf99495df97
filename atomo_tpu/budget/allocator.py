"""Per-layer spectra + the ATOMO water-filling byte allocator.

THE VARIANCE MODEL (stated, tested): the repo's default sampler is
``fixed_k`` importance sampling with replacement — k atoms drawn with
q_i = s_i / sum(s), coefficients s_i / (k q_i). Its estimator error has

    E ||ghat - g||_F^2  =  ( (sum_i s_i)^2 - sum_i s_i^2 ) / k  =  A / k

(the cross terms vanish by unbiasedness; A is a property of the layer's
singular-value spectrum alone). So the total variance of a per-layer
allocation {k_l} is sum_l A_l / k_l, and minimizing it under a wire-byte
budget sum_l bytes_l(k_l) <= B is the paper's water-filling problem with
diminishing returns per atom — solved here by an exact greedy: give the
next atom slot to the layer with the best marginal variance reduction
per byte, tie-broken by leaf index so the allocation is a PURE
deterministic function of (spectra, budget).

Degenerate points of the same dial (tested as identities):

  * ``uniform``: every adaptive layer at the base rank — byte-for-byte
    today's fixed-budget behavior (the wrapper with uniform ranks
    produces bit-identical payloads to the plain codec).
  * spend-everything: an unbounded budget drives every layer to full
    rank, where the codec's dense-fallback rule (payload >= dense)
    ships the exact DensePayload — i.e. ``--on-diverge densify``'s
    remedy, reached as the limit of the budget dial.

Byte pricing is the codec's OWN static accounting
(``SvdCodec.leaf_payload_bytes`` — the clamped actual, pinned equal to
``jax.eval_shape`` over the real encode in tests/test_comm_model.py),
so a predicted allocation total and the executed program's
``msg_bytes`` agree to the byte (tests/test_budget.py's wire-match).

THE QSGD BIT LAW (the second water-filling target, same machinery,
different pricing/variance pair): stochastic rounding of |x|/s onto
L(b) = 2^b - 1 levels has per-value error (s/L)^2 f(1-f) with f the
fractional level position. Under the uniform-residual model
(E f(1-f) = 1/6 — exact in the fine-grid limit L >> |x| sqrt(n)/s,
the regime where QSGD's own variance bound is tight), a bucketed leaf
obeys

    E ||ghat - g||_F^2  =  B_l / (2^b - 1)^2,
    B_l = (1/6) sum_buckets n_b * s_b^2

(n_b = real values in the bucket, s_b = its L2 scale; B_l is a
property of the gradient's bucket norms alone, and the 1/6 constant
cancels in every allocation ratio, so the greedy ordering does not
depend on the residual model). The knob is the leaf's bit width b,
priced by the codec's own packed-word accounting
(``QsgdCodec.leaf_payload_bytes``); unlike SVD there is NO dense
fallback in the wire format, so the solver never claims an exact-wire
zero-variance point — it simply refuses to buy bits whose payload
would meet or exceed the dense bytes. The uniform degenerate point is
every leaf at the codec's configured ``bits`` — byte-for-byte the
plain codec. TernGrad's max-norm scale + sigma clip has a DIFFERENT
error law (not stated here) and stays rejected.

Scope (honest): the solver allocates SVD ranks for the ``fixed_k``
sampler and QSGD bit widths for the L2-scale ``qsgd`` scheme — the
two families whose variance laws are stated above. Every other
codec/sampler pair is rejected at the CLI until its law is stated too.
"""

from __future__ import annotations

import dataclasses
import heapq
import math
from typing import Optional, Sequence


@dataclasses.dataclass(frozen=True)
class LayerSpectrum:
    """One leaf's allocation inputs, canonical flatten order.

    ``a`` is the variance numerator — A = (sum s)^2 - sum s^2 of the
    leaf's matricized spectrum for SVD ranks, or B = (1/6) sum n_b s_b^2
    of its bucket norms for QSGD bits; ``r_full`` caps the useful knob
    (full rank, or the last bit width whose payload still beats dense);
    ``adaptive`` is False for leaves with no knob — SVD leaves shipped
    dense at ANY rank (zero variance, fixed payload) and QSGD leaves
    whose 1-bit payload already meets dense (they still ship quantized
    at the base bits and contribute variance there, but the solver
    never moves them)."""

    index: int
    name: str
    shape: tuple
    dense_bytes: int
    r_full: int
    a: float
    base_k: int
    adaptive: bool


@dataclasses.dataclass(frozen=True)
class Allocation:
    """A solved per-layer budget split (the artifact's epoch body)."""

    mode: str  # "uniform" | "variance"
    ks: tuple  # per-leaf knob (SVD rank or QSGD bits), flatten order
    payload_bytes: int  # predicted total wire bytes (clamped actual)
    budget_bytes: int  # the budget the solver was given
    predicted_variance: float  # sum of the stated per-leaf law
    epoch: int = 0

    def describe(self) -> str:
        return (
            f"budget allocation ({self.mode}, epoch {self.epoch}): "
            f"{self.payload_bytes / 1e6:.4f} MB/replica predicted wire "
            f"of a {self.budget_bytes / 1e6:.4f} MB budget, predicted "
            f"variance {self.predicted_variance:.6g}"
        )


def knob_name(codec) -> str:
    """Which field the allocator waters: ``rank`` (SVD fixed_k) or
    ``bits`` (QSGD). The dispatch key for pricing AND variance law."""
    return "rank" if hasattr(codec, "rank") else "bits"


def _with_knob(codec, k: int):
    import dataclasses as _dc

    return _dc.replace(codec, **{knob_name(codec): int(k)})


def variance_at(codec, a: float, k: int) -> float:
    """The stated per-leaf law at knob value ``k``: A/k for SVD ranks,
    B/(2^b - 1)^2 for QSGD bits (module docstring)."""
    if knob_name(codec) == "bits":
        lv = float((1 << int(k)) - 1)
        return a / (lv * lv)
    return a / k


def _leaf_bytes(codec, spectrum: LayerSpectrum, k: int) -> int:
    """Wire bytes of this leaf at knob ``k`` — the codec's own clamped
    static pricing (dense fallback included, where the format has one)."""
    return int(_with_knob(codec, k).leaf_payload_bytes(spectrum.shape))


def measure_spectra(codec, grads) -> list:
    """Per-leaf :class:`LayerSpectrum` from a PROBE gradient tree.

    ``grads`` is a host (or device) gradient pytree — one backward pass
    over a fixed batch (``sparse.hybrid.probe_gradient``; callers must
    feed a batch that does not advance the training stream's shuffle
    RNG, the --aggregate auto precedent). Each leaf is matricized with
    the CODEC's own resize policy and its full singular-value spectrum
    taken host-side (numpy — probe-time only, never traced; the
    matrices are capped at ``max_min_dim`` on the small side, so this
    is cheap). Pure given the gradient: same probe, same spectra.

    A ``bits`` codec (QSGD) dispatches to the bucket-norm measurement —
    same LayerSpectrum container, the B_l numerator of the module
    docstring's bit law instead of the SVD A_l."""
    if knob_name(codec) == "bits":
        return _measure_bit_spectra(codec, grads)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from atomo_tpu.codecs.svd import resize_to_2d

    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    out = []
    for i, (path, leaf) in enumerate(flat):
        name = jax.tree_util.keystr(path)
        shape = tuple(int(d) for d in leaf.shape)
        arr = np.asarray(jax.device_get(leaf), dtype=np.float32)
        dense_b = int(arr.size) * 4
        mat, _, _pad = resize_to_2d(
            jnp.asarray(arr),
            policy=codec.reshape,
            max_min_dim=codec.max_min_dim,
        )
        mat = np.asarray(jax.device_get(mat))
        r_full = int(min(mat.shape))
        s = np.linalg.svd(mat, compute_uv=False)
        a = float(np.sum(s)) ** 2 - float(np.sum(s * s))
        base_k = max(min(int(codec.rank), r_full), 1)
        # adaptive iff rank 1 already beats dense — otherwise the codec
        # ships this leaf dense at EVERY rank and there is no knob
        adaptive = not _always_dense(codec, shape)
        out.append(
            LayerSpectrum(
                index=i, name=name, shape=shape, dense_bytes=dense_b,
                r_full=r_full, a=max(a, 0.0), base_k=base_k,
                adaptive=adaptive,
            )
        )
    return out


#: Bit widths past this point buy nothing: float32 inputs carry 24
#: significand bits, and the packed (1+b)-bit layout needs b+1 <= 32.
MAX_BITS = 16


def _measure_bit_spectra(codec, grads) -> list:
    """Per-leaf :class:`LayerSpectrum` for QSGD bit allocation.

    The numerator is the bit law's B_l = (1/6) sum_b n_b s_b^2 over the
    leaf's REAL (unpadded) bucket contents — n_b values and L2 scale
    s_b per bucket, exactly the bucketing :meth:`QsgdCodec.encode`
    performs, measured host-side from the probe gradient (no extra
    device work). ``r_full`` is the last bit width (<= MAX_BITS) whose
    payload still beats the leaf's dense bytes; ``base_k`` is the
    codec's configured ``bits`` UNCLAMPED — the uniform point must be
    byte-for-byte the plain codec, which never falls back to dense.
    TernGrad is refused: its max-norm scale + sigma clip follows a
    different error law that the module docstring does not state."""
    import jax
    import numpy as np

    if getattr(codec, "scheme", "qsgd") != "qsgd":
        raise ValueError(
            f"bit allocation needs the L2-scale qsgd scheme, got "
            f"{codec.scheme!r}: the terngrad max-norm law is not stated"
        )
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    out = []
    for i, (path, leaf) in enumerate(flat):
        name = jax.tree_util.keystr(path)
        shape = tuple(int(d) for d in leaf.shape)
        arr = np.asarray(jax.device_get(leaf), dtype=np.float32).reshape(-1)
        dense_b = int(arr.size) * 4
        bs = int(codec.bucket_size)
        b_num = 0.0
        for start in range(0, arr.size, bs):
            chunk = arr[start:start + bs]
            s_b = float(np.linalg.norm(chunk))
            b_num += chunk.size * s_b * s_b
        b_num /= 6.0
        adaptive = not _always_dense(codec, shape)
        r_full = 1
        for b in range(1, MAX_BITS + 1):
            if _with_knob(codec, b).leaf_payload_bytes(shape) < dense_b:
                r_full = b
        base_k = int(codec.bits)
        if not adaptive:
            r_full = base_k
        out.append(
            LayerSpectrum(
                index=i, name=name, shape=shape, dense_bytes=dense_b,
                r_full=r_full, a=max(b_num, 0.0), base_k=base_k,
                adaptive=adaptive,
            )
        )
    return out


def _always_dense(codec, shape) -> bool:
    """Is this leaf knob-less? SVD: dense-fallback already at rank 1
    (i.e. at every rank). QSGD: the 1-bit payload already meets the
    dense bytes, so no bit width can beat dense wire."""
    shape = tuple(shape)
    if knob_name(codec) == "bits":
        dense = 4
        for d in shape:
            dense *= int(d)
        return _with_knob(codec, 1).leaf_payload_bytes(shape) >= dense
    return bool(_with_knob(codec, 1)._dense_fallback(shape))


def spectra_from_qerr2(
    spectra: Sequence[LayerSpectrum],
    qerr2_mean: Sequence[float],
    current_ks: Sequence[int],
    codec=None,
) -> list:
    """Fold an observed per-layer q_err2 series into fresh spectra.

    Under the stated law E q_err2_l = A_l / k_l (SVD ranks; for QSGD
    bits the same inversion reads B_l ~= mean(q_err2_l) * (2^b - 1)^2
    when ``codec`` is a bits codec), the mean of the recorded
    ``--obs-quality`` series at the CURRENT allocation is an unbiased
    online estimate of the numerator — no extra
    SVDs, the streamed-encode leaf visits already paid for the signal.
    Non-adaptive leaves keep their measured A (they have no knob and a
    lossless/dense leaf reads q_err2 = 0 anyway); an unusable sample
    (non-finite, negative) keeps the prior A — a gap is not a sample,
    the drift-detector convention.

    A leaf whose CURRENT payload sits at the exact dense fallback also
    keeps its prior A (pass ``codec`` to enable the check — the
    retuner does): its observed q_err2 is exactly 0 because the wire
    is exact, NOT because its spectrum mass vanished, and folding that
    0 into A = 0 would let the re-solve strip the leaf back to rank 1
    "for free" while the hysteresis sees no predicted regression —
    the demote/re-promote oscillation the boundary re-solve must not
    exhibit (mirrors predicted_variance's zero-variance special
    case)."""
    out = []
    for l in spectra:
        a = l.a
        if l.adaptive and l.index < len(qerr2_mean):
            q = qerr2_mean[l.index]
            k = max(int(current_ks[l.index]), 1)
            at_dense = (
                codec is not None
                and _leaf_bytes(codec, l, k) >= l.dense_bytes
            )
            if (
                not at_dense
                and q is not None
                and math.isfinite(float(q))
                and float(q) >= 0
            ):
                if codec is not None and knob_name(codec) == "bits":
                    # invert the bit law: B = q_err2 * (2^b - 1)^2
                    a = float(q) / variance_at(codec, 1.0, k)
                else:
                    a = float(q) * k
        out.append(dataclasses.replace(l, a=a))
    return out


def uniform_ks(spectra: Sequence[LayerSpectrum]) -> tuple:
    """The degenerate uniform point: every leaf at its (clamped) base
    rank — today's fixed-budget behavior, byte for byte."""
    return tuple(l.base_k for l in spectra)


def predicted_variance(
    spectra: Sequence[LayerSpectrum], ks: Sequence[int], codec=None
) -> float:
    """Total predicted estimator variance under the stated per-leaf
    law. SVD ranks: sum_l A_l / k_l over adaptive leaves (a leaf whose
    payload at k_l reaches the dense fallback is exact — variance 0 —
    when ``codec`` is given to price it; non-adaptive leaves ship dense,
    zero variance). QSGD bits: sum_l B_l / (2^b - 1)^2 over EVERY leaf —
    the wire format has no exact point, and a knob-less leaf still
    quantizes at its base bits."""
    bits = codec is not None and knob_name(codec) == "bits"
    total = 0.0
    for l in spectra:
        k = max(int(ks[l.index]), 1)
        if bits:
            total += variance_at(codec, l.a, k)
            continue
        if not l.adaptive:
            continue
        if codec is not None and _leaf_bytes(codec, l, k) >= l.dense_bytes:
            continue  # dense fallback ships exact: zero variance
        total += l.a / k
    return total


def allocation_payload_bytes(
    codec, spectra: Sequence[LayerSpectrum], ks: Sequence[int]
) -> int:
    """Predicted total wire bytes of an allocation — the clamped-actual
    per-leaf pricing summed (what tests/test_budget.py's wire-match
    compares against the executed program's msg_bytes)."""
    return int(
        sum(_leaf_bytes(codec, l, ks[l.index]) for l in spectra)
    )


def allocation_leaf_budgets(
    codec, spectra: Sequence[LayerSpectrum], ks: Sequence[int]
) -> list:
    """Per-leaf ``(dense_bytes, payload_bytes)`` pairs in canonical
    order — ``comm_model.leaf_budget_totals`` input, so the ``+ab``
    autopilot candidates are priced from the SAME per-leaf sums the
    executed program reports (the PR-12 honest-accounting invariant)."""
    return [
        (int(l.dense_bytes), _leaf_bytes(codec, l, ks[l.index]))
        for l in spectra
    ]


def solve_allocation(
    codec,
    spectra: Sequence[LayerSpectrum],
    budget_bytes: Optional[int] = None,
    mode: str = "variance",
    epoch: int = 0,
) -> Allocation:
    """Distribute ``budget_bytes`` of wire across layers to minimize
    total estimator variance (module docstring). PURE and deterministic:
    the greedy's priority queue breaks ties by leaf index, so the same
    spectra and budget always yield the same allocation (tested).

    ``budget_bytes=None`` (or <= 0) spends exactly the uniform
    allocation's total — the equal-total-wire-bytes comparison.
    ``mode="uniform"`` skips the solve and returns
    the degenerate point. A budget at or past every layer's dense cost
    returns the spend-everything point (all-dense fallback — the
    densify remedy as the dial's limit)."""
    n = len(spectra)
    base = uniform_ks(spectra)
    uniform_total = allocation_payload_bytes(codec, spectra, base)
    if budget_bytes is None or int(budget_bytes) <= 0:
        budget_bytes = uniform_total
    budget_bytes = int(budget_bytes)
    if mode == "uniform":
        return Allocation(
            mode="uniform", ks=base, payload_bytes=uniform_total,
            budget_bytes=budget_bytes,
            predicted_variance=predicted_variance(spectra, base, codec),
            epoch=epoch,
        )
    if mode != "variance":
        raise ValueError(
            f"unknown allocation mode {mode!r}: expected uniform | variance"
        )
    ks = [1] * n
    spent = 0
    for l in spectra:
        if not l.adaptive:
            ks[l.index] = l.base_k  # fixed leaves: priced, never re-ranked
        spent += _leaf_bytes(codec, l, ks[l.index])
    # The greedy: each move raises one adaptive leaf's knob by one; its
    # gain is the stated law's marginal drop — SVD ranks:
    # A (1/k - 1/(k+1)), or the FULL remaining A/k when the next rank
    # crosses into the dense fallback (exact: variance drops to zero);
    # QSGD bits: B (1/L(b)^2 - 1/L(b+1)^2) with NO dense-crossing move
    # (the format has no exact point — a bit width whose payload meets
    # dense is simply never bought) — per delta-byte. heapq is a
    # min-heap: push -gain/byte.
    bits_knob = knob_name(codec) == "bits"
    heap: list = []

    def push_move(l: LayerSpectrum, k: int):
        if k >= l.r_full:
            return
        here = _leaf_bytes(codec, l, k)
        if here >= l.dense_bytes:
            return  # already at the exact dense fallback: nothing to buy
        nxt = _leaf_bytes(codec, l, k + 1)
        d_bytes = nxt - here
        if bits_knob:
            if nxt >= l.dense_bytes:
                return  # never pay dense wire for a lossy payload
            gain = variance_at(codec, l.a, k) - variance_at(
                codec, l.a, k + 1
            )
        elif nxt >= l.dense_bytes:
            gain = l.a / k  # crossing into the exact dense fallback
        else:
            gain = l.a * (1.0 / k - 1.0 / (k + 1))
        if d_bytes <= 0:
            # a free (or byte-saving) rank raise — take it greedily with
            # an infinite ratio; ties still break by index
            ratio = math.inf
        else:
            ratio = gain / d_bytes
        heapq.heappush(heap, (-ratio, l.index, k, d_bytes))

    by_index = {l.index: l for l in spectra}
    for l in spectra:
        if l.adaptive:
            push_move(l, ks[l.index])
    while heap:
        neg_ratio, idx, k, d_bytes = heapq.heappop(heap)
        if ks[idx] != k:
            continue  # stale move (the leaf advanced past it)
        if spent + d_bytes > budget_bytes:
            continue  # unaffordable; cheaper moves may still fit
        ks[idx] = k + 1
        spent += d_bytes
        push_move(by_index[idx], k + 1)
    ks_t = tuple(ks)
    return Allocation(
        mode="variance", ks=ks_t,
        payload_bytes=allocation_payload_bytes(codec, spectra, ks_t),
        budget_bytes=budget_bytes,
        predicted_variance=predicted_variance(spectra, ks_t, codec),
        epoch=epoch,
    )
