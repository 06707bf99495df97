"""Distributed runtime tests on an 8-device CPU-simulated mesh.

These cover the replicated-PS equivalence contract (SURVEY.md §7 hard-part
4): replicas must stay bit-identical; gather- and psum-aggregation must
agree; compressed-DP must actually train.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from atomo_tpu.codecs import QsgdCodec, SvdCodec
from atomo_tpu.data import SPECS, BatchIterator, synthetic_dataset
from atomo_tpu.models import get_model
from atomo_tpu.parallel import (
    make_distributed_eval_step,
    make_distributed_train_step,
    make_mesh,
    replicate_state,
    shard_batch,
)
from atomo_tpu.training import create_state, make_optimizer


def _setup(model_name="lenet", dataset="mnist", batch=16, n_dev=8):
    mesh = make_mesh(n_dev)
    model = get_model(model_name, 10)
    opt = make_optimizer("sgd", lr=0.01, momentum=0.9)
    ds = synthetic_dataset(SPECS[dataset], True, size=256)
    it = BatchIterator(ds, batch, seed=0)
    images, labels = next(iter(it.epoch()))
    state = create_state(model, opt, jax.random.PRNGKey(0), jnp.asarray(images))
    state = replicate_state(mesh, state)
    return mesh, model, opt, it, state


def test_mesh_has_8_devices():
    mesh = make_mesh()
    assert mesh.shape["dp"] == 8


@pytest.mark.parametrize("codec_name", ["svd", "qsgd", "dense"])
@pytest.mark.slow
def test_distributed_step_runs(codec_name):
    mesh, model, opt, it, state = _setup()
    codec = {
        "svd": SvdCodec(rank=2),
        "qsgd": QsgdCodec(bits=2, bucket_size=128),
        "dense": None,
    }[codec_name]
    step = make_distributed_train_step(model, opt, mesh, codec)
    key = jax.random.PRNGKey(1)
    images, labels = next(iter(it.epoch()))
    images, labels = shard_batch(mesh, images, labels)
    state2, metrics = step(state, key, images, labels)
    assert int(state2.step) == 1
    assert np.isfinite(float(metrics["loss"]))
    if codec is not None:
        assert int(metrics["msg_bytes"]) < int(metrics["dense_bytes"])


@pytest.mark.slow
def test_svd_gather_bytes_reduction_at_rank3():
    """North star: >=8x gradient-volume reduction at svd-rank 3 on ResNet-18
    (BASELINE.md). Checked on the exact payload sizes the gather moves."""
    mesh = make_mesh(2)
    model = get_model("resnet18", 10)
    opt = make_optimizer("sgd", lr=0.01)
    ds = synthetic_dataset(SPECS["cifar10"], True, size=8)
    it = BatchIterator(ds, 2, seed=0)
    images, labels = next(iter(it.epoch()))
    state = create_state(model, opt, jax.random.PRNGKey(0), jnp.asarray(images))
    state = replicate_state(mesh, state)
    step = make_distributed_train_step(model, opt, mesh, SvdCodec(rank=3))
    images, labels = shard_batch(mesh, images, labels)
    _, metrics = step(state, jax.random.PRNGKey(1), images, labels)
    reduction = int(metrics["dense_bytes"]) / int(metrics["msg_bytes"])
    assert reduction >= 8.0, f"only {reduction:.1f}x"


@pytest.mark.slow
def test_replicas_stay_identical():
    """After several compressed steps, params must be exactly replicated."""
    mesh, model, opt, it, state = _setup()
    step = make_distributed_train_step(model, opt, mesh, SvdCodec(rank=2))
    key = jax.random.PRNGKey(3)
    stream = it.forever()
    for _ in range(3):
        images, labels = next(stream)
        images, labels = shard_batch(mesh, images, labels)
        state, _ = step(state, key, images, labels)
    # pull each device's copy of one param and compare
    leaf = jax.tree_util.tree_leaves(state.params)[0]
    shards = [np.asarray(s.data) for s in leaf.addressable_shards]
    for s in shards[1:]:
        np.testing.assert_array_equal(shards[0], s)


@pytest.mark.slow
def test_gather_and_psum_agree():
    """gather (factors on the wire) and psum (dense on the wire) produce the
    same update given the same sampling keys."""
    mesh, model, opt, it, state = _setup()
    codec = SvdCodec(rank=2)
    step_g = make_distributed_train_step(model, opt, mesh, codec, aggregate="gather")
    step_p = make_distributed_train_step(model, opt, mesh, codec, aggregate="psum")
    key = jax.random.PRNGKey(5)
    images, labels = next(iter(it.epoch()))
    si, sl = shard_batch(mesh, images, labels)
    # donate_argnums: re-replicate state for each call
    sg, _ = step_g(jax.tree.map(jnp.copy, state), key, si, sl)
    sp, _ = step_p(jax.tree.map(jnp.copy, state), key, si, sl)
    for a, b in zip(
        jax.tree_util.tree_leaves(sg.params), jax.tree_util.tree_leaves(sp.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_distributed_matches_single_when_dense():
    """Dense pmean over the mesh == single-host step on the full batch."""
    from atomo_tpu.training import make_train_step

    mesh, model, opt, it, state = _setup()
    images, labels = next(iter(it.epoch()))
    # single-host reference on the same full batch
    sstate = jax.tree.map(jnp.copy, jax.device_get(state))
    single = make_train_step(model, opt, codec=None)
    dstep = make_distributed_train_step(model, opt, mesh, None)
    key = jax.random.PRNGKey(7)
    si, sl = shard_batch(mesh, images, labels)
    dstate, _ = dstep(state, key, si, sl)
    sstate2, _ = single(sstate, key, jnp.asarray(images), jnp.asarray(labels))
    for a, b in zip(
        jax.tree_util.tree_leaves(dstate.params),
        jax.tree_util.tree_leaves(sstate2.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.slow
def test_distributed_training_learns():
    mesh, model, opt, it, state = _setup()
    step = make_distributed_train_step(model, opt, mesh, QsgdCodec(bits=2, bucket_size=128))
    ev = make_distributed_eval_step(model, mesh)
    key = jax.random.PRNGKey(11)
    stream = it.forever()
    losses = []
    for _ in range(40):
        images, labels = next(stream)
        si, sl = shard_batch(mesh, images, labels)
        state, m = step(state, key, si, sl)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.8, (losses[0], losses[-1])


@pytest.mark.slow
def test_num_aggregate_subset():
    """Honest --num-aggregate: K-of-N rotating subset aggregation keeps
    replicas identical and still trains (SURVEY.md §2.1 'vestigial flag')."""
    mesh, model, opt, it, state = _setup()
    step = make_distributed_train_step(
        model, opt, mesh, SvdCodec(rank=2), num_aggregate=3
    )
    key = jax.random.PRNGKey(13)
    stream = it.forever()
    for _ in range(2):
        images, labels = next(stream)
        si, sl = shard_batch(mesh, images, labels)
        state, m = step(state, key, si, sl)
    assert np.isfinite(float(m["loss"]))
    leaf = jax.tree_util.tree_leaves(state.params)[0]
    shards = [np.asarray(s.data) for s in leaf.addressable_shards]
    for s in shards[1:]:
        np.testing.assert_array_equal(shards[0], s)


def test_num_aggregate_requires_gather():
    mesh, model, opt, it, state = _setup()
    with pytest.raises(ValueError, match="gather"):
        make_distributed_train_step(
            model, opt, mesh, SvdCodec(rank=2), aggregate="psum", num_aggregate=3
        )


@pytest.mark.slow
def test_bf16_distributed_replicas_stay_identical():
    """Mixed precision under SPMD: the bf16 step must keep the replicated-PS
    equivalence contract (f32 master state bit-identical across replicas)."""
    mesh, model, opt, it, state = _setup()
    step = make_distributed_train_step(
        model, opt, mesh, SvdCodec(rank=2), compute_dtype=jnp.bfloat16
    )
    images, labels = next(iter(it.epoch()))
    si, sl = shard_batch(mesh, images, labels)
    for k in range(3):
        state, metrics = step(state, jax.random.PRNGKey(7), si, sl)
    for leaf in jax.tree_util.tree_leaves(state.params):
        assert leaf.dtype == jnp.float32
        per_dev = np.stack([np.asarray(s.data) for s in leaf.addressable_shards])
        for r in range(1, per_dev.shape[0]):
            np.testing.assert_array_equal(per_dev[0], per_dev[r])
    assert np.isfinite(float(metrics["loss"]))
