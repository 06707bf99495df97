"""Real-TPU compile + correctness coverage for the SVD codec hot path and
the distributed step program.

The CPU suite proves semantics; these prove the SAME programs lower through
XLA:TPU — the class of gap round 2 exposed for QSGD (code that only runs on
hardware had zero hardware coverage). Without a TPU the directory errors
(tests_tpu/conftest.py).
"""

import jax
import jax.numpy as jnp
import numpy as np

from atomo_tpu.codecs import SvdCodec, encode_tree, decode_tree
from atomo_tpu.models import get_model
from atomo_tpu.training import create_state, make_optimizer, make_train_step


def test_default_svd_codec_roundtrip_on_chip():
    """The default codec config (auto sketch + residual probes) on a
    conv-sized gradient: encode → decode on the chip, sane output."""
    codec = SvdCodec(rank=3)
    g = jax.random.normal(jax.random.PRNGKey(0), (512, 512), jnp.float32)
    rt = jax.jit(
        lambda k, x: codec.decode(codec.encode(k, x), (512, 512))
    )
    out = np.asarray(rt(jax.random.PRNGKey(1), g))
    assert np.isfinite(out).all()
    # rank-3+2probes of a noise matrix: reconstruction is sparse in energy
    # but must correlate positively in expectation over keys
    acc = np.zeros_like(out)
    for i in range(16):
        acc += np.asarray(rt(jax.random.PRNGKey(10 + i), g))
    corr = np.corrcoef(acc.ravel(), np.asarray(g).ravel())[0, 1]
    assert corr > 0.1, f"mean decode uncorrelated with input: {corr}"


def test_resnet18_compressed_train_step_on_chip():
    """One full compressed train step (fwd/bwd + encode_tree + decode_tree +
    update) compiles and runs on the chip with finite loss."""
    model = get_model("resnet18", 10)
    opt = make_optimizer("sgd", lr=0.01, momentum=0.9)
    rng = jax.random.PRNGKey(0)
    images = jax.random.uniform(rng, (16, 32, 32, 3), jnp.float32)
    labels = jax.random.randint(rng, (16,), 0, 10)
    state = create_state(model, opt, rng, images)
    step = make_train_step(model, opt, codec=SvdCodec(rank=3))
    state, m = step(state, jax.random.PRNGKey(1), images, labels)
    assert np.isfinite(float(m["loss"]))
    assert int(m["msg_bytes"]) > 0


def test_bf16_train_step_on_chip():
    """The --bf16 step (bf16 MXU compute, f32 master state) on hardware."""
    model = get_model("resnet18", 10)
    opt = make_optimizer("sgd", lr=0.01, momentum=0.9)
    rng = jax.random.PRNGKey(0)
    images = jax.random.uniform(rng, (16, 32, 32, 3), jnp.float32)
    labels = jax.random.randint(rng, (16,), 0, 10)
    state = create_state(model, opt, rng, images)
    step = make_train_step(
        model, opt, codec=SvdCodec(rank=3), compute_dtype=jnp.bfloat16
    )
    state, m = step(state, jax.random.PRNGKey(1), images, labels)
    assert np.isfinite(float(m["loss"]))
    for leaf in jax.tree_util.tree_leaves(state.params):
        assert leaf.dtype == jnp.float32


def test_encode_tree_bucketed_on_chip():
    """The production bucketed/vmapped encode over a small pytree."""
    rng = jax.random.PRNGKey(5)
    params = {
        "a": jax.random.normal(rng, (64, 64)),
        "b": jax.random.normal(jax.random.fold_in(rng, 1), (64, 64)),
        "c": jax.random.normal(jax.random.fold_in(rng, 2), (40,)),
    }
    codec = SvdCodec(rank=2)
    payloads, stats = encode_tree(codec, rng, params)
    decoded = decode_tree(codec, payloads, params)
    for leaf in jax.tree_util.tree_leaves(decoded):
        assert np.isfinite(np.asarray(leaf)).all()
    assert stats.payload_bytes < stats.dense_bytes


# ----------------------------------------------------- round-4 codec paths


def test_gram_svd_on_chip():
    """The gram factorization (eigh of the small-side Gram — the round-4
    replacement for iterative SVD on small matrices and the Bernoulli
    modes) compiles and reconstructs on hardware, both orientations."""
    for shape in [(32, 54), (54, 32)]:
        mat = jax.random.normal(jax.random.PRNGKey(2), shape) * 0.3
        u, s, vt = jax.jit(SvdCodec._gram_svd)(mat)
        # reconstruct on the HOST in f64: a device matmul at the TPU's
        # default precision is bf16 passes (~3e-3 abs error here — what
        # failed this assert on the v5e), which measures the check, not
        # the factorization. The codec's own decode pins HIGHEST.
        u, s, vt = (np.asarray(a, np.float64) for a in (u, s, vt))
        np.testing.assert_allclose(
            (u * s[None, :]) @ vt, np.asarray(mat), atol=5e-4
        )


def test_cholesky_qr_zero_block_on_chip():
    """TPU flushes subnormals to zero: the CholeskyQR jitter must survive
    that (code-review r4 finding — 10*eps*tiny would flush and revive the
    cholesky(0) NaN). A zero matrix through the full randomized encode
    must produce a finite all-zero decode ON HARDWARE."""
    q = jax.jit(SvdCodec._orthonormalize)(jnp.zeros((128, 8)))
    assert np.isfinite(np.asarray(q)).all()
    codec = SvdCodec(rank=3, algorithm="randomized")
    rt = jax.jit(lambda k, x: codec.decode(codec.encode(k, x), (128, 128)))
    out = np.asarray(rt(jax.random.PRNGKey(0), jnp.zeros((128, 128))))
    np.testing.assert_allclose(out, 0.0, atol=1e-6)


def test_bf16_wire_on_chip():
    """wire_dtype=bfloat16: the stochastic-round bitcast chain
    (bitcast_convert_type + random.bits uint16 + mask) must lower through
    Mosaic/XLA:TPU, halve the payload, and decode finite."""
    from atomo_tpu.codecs import payload_nbytes

    codec32 = SvdCodec(rank=3)
    codec16 = SvdCodec(rank=3, wire_dtype="bfloat16")
    g = jax.random.normal(jax.random.PRNGKey(3), (256, 256), jnp.float32)
    p32 = jax.jit(codec32.encode)(jax.random.PRNGKey(4), g)
    p16 = jax.jit(codec16.encode)(jax.random.PRNGKey(4), g)
    assert p16.u.dtype == jnp.bfloat16
    assert payload_nbytes(p16) < 0.6 * payload_nbytes(p32)
    out = np.asarray(
        jax.jit(lambda p: codec16.decode(p, (256, 256)))(p16)
    )
    assert np.isfinite(out).all() and (out != 0).any()


def test_stochastic_round_unbiased_on_chip():
    """E[stochastic_round(x)] == x must hold for the HARDWARE rounding
    path (bit arithmetic on the chip), not just the CPU interpreter."""
    from atomo_tpu.codecs.svd import stochastic_round

    x = jax.random.normal(jax.random.PRNGKey(5), (2048,)) * 2.3
    keys = jax.random.split(jax.random.PRNGKey(6), 512)
    rounded = jax.jit(
        jax.vmap(lambda k: stochastic_round(k, x).astype(jnp.float32))
    )(keys)
    mean = np.asarray(jnp.mean(rounded, axis=0))
    np.testing.assert_allclose(mean, np.asarray(x), rtol=2e-3, atol=1e-5)


def test_bernoulli_budget_gram_on_chip():
    """Config 5's sampler (bernoulli_budget, now on the gram path) on a
    resnet110-sized conv matricization: static payload, finite decode."""
    codec = SvdCodec(rank=3, sample="bernoulli_budget")
    g = jax.random.normal(jax.random.PRNGKey(7), (3, 3, 64, 64))
    p = jax.jit(codec.encode)(jax.random.PRNGKey(8), g)
    assert p.coeff.shape == (7,)
    out = np.asarray(
        jax.jit(lambda q: codec.decode(q, (3, 3, 64, 64)))(p)
    )
    assert np.isfinite(out).all()


# ------------------------------------------- the distributed step, 4 chips


def test_resnet18_compressed_step_spreads_over_four_chips():
    """The dp4 compressed step (ResNet-18 b128, svd rank 3, gather — the
    shape chip_smoke.py drives through the CLI) on four real chips: finite
    loss, state and batch shards addressable on four DISTINCT devices, and
    bytes in use on each."""
    import pytest

    from atomo_tpu.parallel.mesh import make_mesh, shard_devices
    from atomo_tpu.parallel.replicated import (
        make_distributed_train_step,
        replicate_state,
        shard_batch,
    )

    n = 4
    if len(jax.devices()) < n:
        reason = f"needs {n} chips, {len(jax.devices())} visible"
        print(f"SKIP: {reason}")
        pytest.skip(reason)
    mesh = make_mesh(n)
    model = get_model("resnet18", 10)
    opt = make_optimizer("sgd", lr=0.01, momentum=0.0)
    rng = jax.random.PRNGKey(0)
    images = np.asarray(jax.random.uniform(rng, (128, 32, 32, 3), jnp.float32))
    labels = np.asarray(jax.random.randint(rng, (128,), 0, 10))
    state = replicate_state(
        mesh, create_state(model, opt, rng, jnp.asarray(images[:8]))
    )
    step = make_distributed_train_step(
        model, opt, mesh, codec=SvdCodec(rank=3), aggregate="gather"
    )
    si, sl = shard_batch(mesh, images, labels)
    state, m = step(state, jax.random.PRNGKey(1), si, sl)
    assert np.isfinite(float(m["loss"]))
    assert 0 < int(m["msg_bytes"]) < int(m["dense_bytes"])

    assert len(shard_devices(state.params)) == n
    assert len(shard_devices(si)) == n
    assert {s.index[0].start for s in si.addressable_shards} == {0, 32, 64, 96}
    for d in mesh.devices.flat:
        assert d.memory_stats()["bytes_in_use"] > 0, d
