"""Pallas TPU kernels for the QSGD quantize→bit-pack hot path.

Reference equivalent: the per-value uint64 shifting loops of
src/codings/qsgd.py:52-79 (pack) and :126-139 (unpack), run in numpy on the
host CPU. Here the whole encode — per-bucket scale (L2 for qsgd, max-norm
for terngrad), stochastic rounding (on-core PRNG, no key streams from HBM),
sign/magnitude coding, and uint32 word packing — is one fused VMEM-resident
kernel: the gradient is read from HBM exactly once and only the ~(1+b)/32-
sized words go back out, so encode bandwidth ≈ the payload size rather than
2x the dense gradient.

Wire format (round 3, *planar*): words have shape
(n_buckets, words_per_bucket) uint32. Within a bucket padded to
bucket_p = vpw * n_words values (vpw = floor(32/(1+b)) values per word),
the value at bucket position p = j*n_words + w sits in word w at bit
j*(1+b). This planar layout (vs round 2's interleaved p = w*vpw + j) is
what real-TPU Mosaic can express: packing is a Python loop of middle-axis
slices over a (block, vpw, n_words) tile — the interleaved layout needed a
lane-dim-splitting reshape, which Mosaic rejects ("infer-vector-layout:
unsupported shape cast", hardware-verified this round). ``QsgdCodec`` emits
and accepts this exact layout from both its jnp path and these kernels.
The jnp path is the default everywhere and the bit-parity oracle; the
kernels are opt-in (``use_pallas=True`` / ``pack_kernel=True``).

Mosaic dtype discipline (all hardware-verified failures): no uint32
reductions, no u32<->f32 or bool->u32 casts — the kernels therefore compute
codes entirely in int32 (bit-identical for these small non-negative
fields) and bitcast to uint32 only at the output boundary.

RNG: passing ``u`` (external jax.random uniforms) makes the kernel
bit-identical to the jnp oracle; ``u=None`` draws from the on-core PRNG —
the zero-extra-bandwidth TPU hot path (per-block seeds: the block index is
folded into the seed so stochastic-rounding noise is independent across
blocks — round-1 ADVICE finding). A kernel compiles for the device it is
on, or raises. The TPU-semantics interpreter runs only on request
(:func:`interpret_requested`; its prng_random_bits is a zero stub, so
interpreter runs must pass explicit ``u``).

The grid tiles buckets; bucket_size is padded to the word boundary, so any
bucket_size works (the default 512 = reference --bucket-size).
"""

from __future__ import annotations

import os
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INTERPRET_ENV = "ATOMO_PALLAS_INTERPRET"


def is_tpu() -> bool:
    """A backend that fails to come up raises here; it is never read as
    "not a TPU"."""
    return jax.devices()[0].platform == "tpu"


def interpret_requested() -> bool:
    """Interpret mode is for tests and CPU dry runs, and only when asked
    for: ``ATOMO_PALLAS_INTERPRET=1`` (tests/conftest.py sets it; child
    processes inherit it). Nothing infers it from the backend — a kernel
    asked for on a machine whose accelerator did not come up must fail to
    compile, not run in the interpreter without a word."""
    return os.environ.get(INTERPRET_ENV) == "1"


# ---------------------------------------------------------------------------
# Pack-kernel default decision record (the use_pallas precedent, codified)
# ---------------------------------------------------------------------------
#
# The round-4 rule for every hand kernel in this repo: NO kernel
# auto-selects without a measured hardware win on record (the fused
# use_pallas quantize kernel measured SLOWER than XLA's fusion on v5e —
# encode 2.68/2.79 ms pallas vs 2.52/2.59 ms jnp, 8.4M values — and its
# auto-selection was flipped OFF with those numbers quoted). This table
# makes the rule a MECHANISM instead of a docstring: ``pack_kernel=None``
# resolves default-ON exactly for the device kinds listed here with a
# measured win, and to the jnp oracle everywhere else — including every
# non-TPU backend, which stays the automatic fallback unconditionally.
# A PERF_LEDGER.jsonl line that records a pack-kernel win on real hardware
# graduates the kernel by adding one entry with its evidence pointer; no
# code-path change, and the decision is auditable in-place.
PACK_KERNEL_MEASURED_WINS: dict = {
    # device-kind substring (lowercase) -> {"win": bool, "evidence": str}
    #
    # No entry yet: the bucketed pack/unpack kernels (PR 10) have no
    # real-TPU measurement on record (no benchmark cell runs them,
    # ROADMAP W3); the first recorded win lands here with its ledger line.
}


def pack_kernel_default(
    device_kind: Optional[str] = None, on_tpu: Optional[bool] = None
) -> bool:
    """Resolve ``QsgdCodec.pack_kernel=None``: True only on a real TPU
    whose device kind has a measured win recorded in
    :data:`PACK_KERNEL_MEASURED_WINS`; False (the jnp oracle) everywhere
    else — off-TPU backends fall back automatically by construction.

    ``device_kind``/``on_tpu`` default to the live backend; passing them
    explicitly is the graduation DRILL (tests and the controller's
    pack-kernel pricing): a synthetic win recorded for a device-kind
    substring must flip this default for that kind — and only that kind
    — without any code-path change. The measurement procedure that earns
    a real entry is documented in README "Graduating the pack kernel"."""
    if on_tpu is None:
        on_tpu = is_tpu()
    if not on_tpu:
        return False
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    kind = str(device_kind).lower()
    for tag, rec in PACK_KERNEL_MEASURED_WINS.items():
        if tag in kind and rec.get("win"):
            return True
    return False


def _interpret_mode(interpret: bool):
    """Value for ``pl.pallas_call(interpret=...)``: the TPU-semantics
    interpreter (generic interpret mode has no CPU lowering for the
    pltpu.prng_* primitives), or False to compile."""
    return pltpu.InterpretParams() if interpret else False


def _finish_quantize(x, u, words_ref, scales_ref, *, bits, levels, vpw, scheme):
    """x, u: (B_blk, vpw, n_words) planar bucket tiles → packed words.

    int32 throughout (Mosaic has no unsigned reductions / u32 casts); the
    field values are small and non-negative so the detour is exact.
    """
    # per-bucket scale: reduce the (vpw, n_words) tile in two supported
    # stages (middle axis, then lane axis with keepdims)
    if scheme == "terngrad":
        scale = jnp.max(jnp.max(jnp.abs(x), axis=1), axis=1, keepdims=True)
    else:
        scale = jnp.sqrt(jnp.sum(jnp.sum(x * x, axis=1), axis=1, keepdims=True))
    safe = jnp.maximum(scale, jnp.finfo(jnp.float32).tiny)  # (B_blk, 1)
    y = jnp.abs(x) / safe[:, :, None] * levels
    lo = jnp.floor(y)
    frac = y - lo
    level = jnp.clip(lo + (u < frac), 0, levels).astype(jnp.int32)
    sign = (x < 0).astype(jnp.int32)
    codes = (sign << bits) | level  # (B_blk, vpw, n_words) int32
    bpv = bits + 1
    acc = codes[:, 0, :]
    for j in range(1, vpw):
        acc = acc | (codes[:, j, :] << (j * bpv))
    words_ref[:] = jax.lax.bitcast_convert_type(acc, jnp.uint32)
    scales_ref[:] = scale


def _quantize_pack_kernel(
    x_ref, seed_ref, words_ref, scales_ref, *, bits, levels, vpw, scheme
):
    """One grid step: a block of buckets (B_blk, vpw, n_words) → packed
    words. Stochastic-rounding uniforms come from the on-core PRNG (no HBM
    key stream). The block index is folded into the seed so each block
    draws an independent stream (ADVICE r1: a shared scalar seed correlated
    the rounding noise across blocks)."""
    pltpu.prng_seed(seed_ref[0] + pl.program_id(0))
    x = x_ref[:]  # (B_blk, vpw, n_words)
    rbits = pltpu.bitcast(pltpu.prng_random_bits(x.shape), jnp.uint32)
    # uniform in [0,1) from the top 24 bits (exact float32 representability).
    # Mosaic has no u32->f32 cast; the top-24-bit values fit in int32, so
    # route the cast through int32 (VERDICT r2 finding 1).
    u = (rbits >> 8).astype(jnp.int32).astype(jnp.float32) * (1.0 / (1 << 24))
    _finish_quantize(
        x, u, words_ref, scales_ref, bits=bits, levels=levels, vpw=vpw, scheme=scheme
    )


def _quantize_pack_kernel_ext(
    x_ref, u_ref, words_ref, scales_ref, *, bits, levels, vpw, scheme
):
    """External-uniform variant: u in [0,1) supplied as a second input —
    bit-identical to the jnp oracle when fed the same uniforms."""
    _finish_quantize(
        x_ref[:], u_ref[:], words_ref, scales_ref,
        bits=bits, levels=levels, vpw=vpw, scheme=scheme,
    )


def _unpack_dequantize_kernel(
    words_ref, scales_ref, out_ref, *, bits: int, levels: int, vpw: int
):
    bpv = bits + 1
    words = jax.lax.bitcast_convert_type(words_ref[:], jnp.int32)  # (B_blk, n_words)
    scales = scales_ref[:]  # (B_blk, 1)
    mask = (1 << bpv) - 1
    inv = 1.0 / levels
    for j in range(vpw):
        # arithmetic >> then & mask == logical shift for these fields
        codes = (words >> (j * bpv)) & mask
        level = (codes & levels).astype(jnp.float32)
        sign = 1.0 - 2.0 * ((codes >> bits) & 1).astype(jnp.float32)
        out_ref[:, j, :] = sign * level * inv * scales


def padded_bucket(bucket_size: int, bits: int) -> int:
    """Bucket size rounded up to a whole number of uint32 words."""
    vpw = 32 // (bits + 1)
    return -(-bucket_size // vpw) * vpw


def words_per_bucket(bucket_size: int, bits: int) -> int:
    vpw = 32 // (bits + 1)
    return padded_bucket(bucket_size, bits) // vpw


@partial(
    jax.jit,
    static_argnames=("bits", "bucket_size", "scheme", "interpret", "block"),
)
def pallas_quantize_pack(
    x: jax.Array,
    seed: jax.Array,
    u: Optional[jax.Array] = None,
    *,
    bits: int,
    bucket_size: int = 512,
    scheme: str = "qsgd",
    interpret: bool = False,
    block: int = 8,
):
    """Fused QSGD encode. x: flat float32; returns (words, scales) with
    words (n_buckets, words_per_bucket) uint32, scales (n_buckets,) f32 —
    the codec wire format (planar field layout, see module docstring).

    ``u=None`` draws stochastic-rounding uniforms from the on-core PRNG
    seeded per-block from ``seed`` (TPU hot path, zero extra bandwidth);
    passing ``u`` of shape (n_buckets, bucket_size) uses those uniforms
    (oracle-checkable; required under the interpreter, whose
    prng_random_bits is a zero stub)."""
    vpw = 32 // (bits + 1)
    n = x.shape[0]
    n_buckets = -(-n // bucket_size)
    blocks = -(-n_buckets // block)
    pad_buckets = blocks * block
    bucket_p = padded_bucket(bucket_size, bits)
    n_words = bucket_p // vpw

    def to_planar(flat, fill_rows):
        """(rows, bucket_size) values → (pad_buckets, vpw, n_words) planar."""
        g = jnp.zeros((pad_buckets, bucket_p), jnp.float32)
        g = g.at[:fill_rows, :bucket_size].set(flat)
        return g.reshape(pad_buckets, vpw, n_words)

    x_rows = jnp.zeros((n_buckets * bucket_size,), jnp.float32).at[:n].set(x)
    grid_x = to_planar(x_rows.reshape(n_buckets, bucket_size), n_buckets)

    out_shape = (
        jax.ShapeDtypeStruct((pad_buckets, n_words), jnp.uint32),
        jax.ShapeDtypeStruct((pad_buckets, 1), jnp.float32),
    )
    out_specs = (
        pl.BlockSpec((block, n_words), lambda i: (i, 0)),
        pl.BlockSpec((block, 1), lambda i: (i, 0)),
    )
    levels = (1 << bits) - 1
    if u is None:
        seeds = jnp.asarray(seed, jnp.int32).reshape(1)
        words, scales = pl.pallas_call(
            partial(
                _quantize_pack_kernel,
                bits=bits, levels=levels, vpw=vpw, scheme=scheme,
            ),
            out_shape=out_shape,
            grid=(blocks,),
            in_specs=[
                pl.BlockSpec((block, vpw, n_words), lambda i: (i, 0, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=out_specs,
            interpret=_interpret_mode(interpret),
        )(grid_x, seeds)
    else:
        grid_u = to_planar(u, n_buckets)
        words, scales = pl.pallas_call(
            partial(
                _quantize_pack_kernel_ext,
                bits=bits, levels=levels, vpw=vpw, scheme=scheme,
            ),
            out_shape=out_shape,
            grid=(blocks,),
            in_specs=[
                pl.BlockSpec((block, vpw, n_words), lambda i: (i, 0, 0)),
                pl.BlockSpec((block, vpw, n_words), lambda i: (i, 0, 0)),
            ],
            out_specs=out_specs,
            interpret=_interpret_mode(interpret),
        )(grid_x, grid_u)
    return words[:n_buckets], scales[:n_buckets, 0]


def _pack_codes_kernel(codes_ref, words_ref, *, bits: int, vpw: int):
    """One grid step: a block of planar code tiles (B_blk, vpw, n_words)
    int32 -> packed uint32 words (B_blk, n_words). The bare bit-pack stage
    of _finish_quantize, split out so the BUCKETED pack/unpack behind
    ``--stream-encode``'s layer-bucket boundary can run fused without the
    quantizer (the codec's jnp ``pack_bucketed`` is the bit-parity
    oracle). Same Mosaic dtype discipline: int32 fields (small,
    non-negative — exact), bitcast to uint32 only at the output."""
    bpv = bits + 1
    codes = codes_ref[:]
    acc = codes[:, 0, :]
    for j in range(1, vpw):
        acc = acc | (codes[:, j, :] << (j * bpv))
    words_ref[:] = jax.lax.bitcast_convert_type(acc, jnp.uint32)


def _unpack_codes_kernel(words_ref, out_ref, *, bits: int, vpw: int):
    """Inverse of :func:`_pack_codes_kernel`: words -> planar int32 codes
    (arithmetic >> then & mask == logical shift for these fields)."""
    bpv = bits + 1
    words = jax.lax.bitcast_convert_type(words_ref[:], jnp.int32)
    mask = (1 << bpv) - 1
    for j in range(vpw):
        out_ref[:, j, :] = (words >> (j * bpv)) & mask


@partial(jax.jit, static_argnames=("bits", "interpret", "block"))
def pallas_pack_bucketed(
    codes: jax.Array, *, bits: int, interpret: bool = False, block: int = 8
):
    """Fused bucketed bit-pack: (n_buckets, bucket_p) codes ->
    (n_buckets, bucket_p/vpw) uint32 words, bit-identical to the jnp
    ``codecs.qsgd.pack_bucketed`` (the oracle; planar field layout —
    bucket position p = j*n_words + w sits in word w at bit j*(1+bits)).
    ``bucket_p`` must be a whole number of vals-per-word, exactly as the
    jnp path requires. One VMEM-resident pass: the codes are read from
    HBM once and only the ~1/vpw-sized words go back out."""
    vpw = 32 // (bits + 1)
    nb, bucket_p = codes.shape
    if bucket_p % vpw:
        raise ValueError(
            f"bucket_p {bucket_p} must be a multiple of vals-per-word "
            f"{vpw} (pad with zero codes first — the pack_bucketed "
            "contract)"
        )
    n_words = bucket_p // vpw
    blocks = -(-nb // block)
    pad_b = blocks * block
    # int32 in-kernel (Mosaic has no u32 ops); code fields are < 2^(1+bits)
    planar = (
        jnp.zeros((pad_b, bucket_p), jnp.int32)
        .at[:nb]
        .set(codes.astype(jnp.int32))
        .reshape(pad_b, vpw, n_words)
    )
    words = pl.pallas_call(
        partial(_pack_codes_kernel, bits=bits, vpw=vpw),
        out_shape=jax.ShapeDtypeStruct((pad_b, n_words), jnp.uint32),
        grid=(blocks,),
        in_specs=[pl.BlockSpec((block, vpw, n_words), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((block, n_words), lambda i: (i, 0)),
        interpret=_interpret_mode(interpret),
    )(planar)
    return words[:nb]


@partial(jax.jit, static_argnames=("bits", "interpret", "block"))
def pallas_unpack_bucketed(
    words: jax.Array, *, bits: int, interpret: bool = False, block: int = 8
):
    """Fused inverse of :func:`pallas_pack_bucketed`: (nb, wpb) uint32 ->
    (nb, wpb*vpw) uint32 codes, bit-identical to the jnp
    ``codecs.qsgd.unpack_bucketed`` oracle."""
    vpw = 32 // (bits + 1)
    nb, n_words = words.shape
    blocks = -(-nb // block)
    pad_b = blocks * block
    w = jnp.zeros((pad_b, n_words), jnp.uint32).at[:nb].set(words)
    codes = pl.pallas_call(
        partial(_unpack_codes_kernel, bits=bits, vpw=vpw),
        out_shape=jax.ShapeDtypeStruct((pad_b, vpw, n_words), jnp.int32),
        grid=(blocks,),
        in_specs=[pl.BlockSpec((block, n_words), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block, vpw, n_words), lambda i: (i, 0, 0)),
        interpret=_interpret_mode(interpret),
    )(w)
    # fields are < 2^(1+bits): the int32 detour is exact (module docstring)
    return codes.reshape(pad_b, vpw * n_words)[:nb].astype(jnp.uint32)


@partial(jax.jit, static_argnames=("bits", "bucket_size", "n", "interpret", "block"))
def pallas_unpack_dequantize(
    words: jax.Array,
    scales: jax.Array,
    *,
    bits: int,
    bucket_size: int = 512,
    n: int,
    interpret: bool = False,
    block: int = 8,
):
    """Fused QSGD decode: (words, scales) → flat float32 of length n."""
    vpw = 32 // (bits + 1)
    n_buckets = scales.shape[0]
    blocks = -(-n_buckets // block)
    pad_buckets = blocks * block
    bucket_p = padded_bucket(bucket_size, bits)
    n_words = bucket_p // vpw

    w = jnp.zeros((pad_buckets, n_words), jnp.uint32).at[:n_buckets].set(words)
    s = jnp.zeros((pad_buckets, 1), jnp.float32).at[:n_buckets, 0].set(scales)

    vals = pl.pallas_call(
        partial(
            _unpack_dequantize_kernel, bits=bits, levels=(1 << bits) - 1, vpw=vpw
        ),
        out_shape=jax.ShapeDtypeStruct((pad_buckets, vpw, n_words), jnp.float32),
        grid=(blocks,),
        in_specs=[
            pl.BlockSpec((block, n_words), lambda i: (i, 0)),
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block, vpw, n_words), lambda i: (i, 0, 0)),
        interpret=_interpret_mode(interpret),
    )(w, s)
    vals = vals.reshape(pad_buckets, bucket_p)
    return vals[:n_buckets, :bucket_size].reshape(-1)[:n]
