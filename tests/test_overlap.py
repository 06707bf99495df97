"""Stale-by-one overlapped aggregation (PR-4 tentpole, ``--overlap delayed``).

Contract being pinned (parallel/replicated.make_distributed_train_step +
make_delayed_oracle_steps):

  * ``overlap='off'`` IS the blocking program — explicitly passing it is
    bit-identical to the default (and the rest of the suite pins that
    program against its own oracles).
  * The fused ``superstep=1`` delayed program matches the TWO-PROGRAM
    EAGER ORACLE (produce / apply, separately jitted from the same
    closures, optimization_barrier pinning the consume boundary in both)
    bit-for-bit, for gather and ring, with and without the guard.
  * Step 0 applies a zero (skipped) update: params/opt state/BN stats
    hold, metrics report skipped=1, dropped=0.
  * Staleness semantics: the first real update (delayed step 2) equals
    blocking step 1 — same gradient, applied one step late.
  * Within the superstep scan family, trajectories are bit-identical for
    any block partition (the PR-2 invariance, carry included).
  * The guard flag TRAVELS with the payload: a NaN produced at step t is
    masked at step t+1 (dropped=1 there, not at t), and the whole
    trajectory still matches the oracle bitwise.
  * Composes with ZeRO-1, num_aggregate, chaos, resume — resume restores
    the in-flight payload, so kill->restart->resume across a block
    boundary reproduces the uninterrupted delayed run exactly
    (tests/_overlap_worker.py drill).
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from atomo_tpu.codecs import QsgdCodec, SvdCodec
from atomo_tpu.data import BatchIterator, SPECS, synthetic_dataset
from atomo_tpu.models import get_model
from atomo_tpu.parallel import (
    init_delayed_state,
    make_delayed_oracle_steps,
    make_distributed_train_step,
    make_mesh,
    replicate_state,
    shard_batch,
    shard_superbatch,
)
from atomo_tpu.parallel.replicated import _zero_carry_host
from atomo_tpu.training import (
    GuardConfig,
    create_state,
    make_optimizer,
    snapshot_state,
)
from atomo_tpu.utils.chaos import ChaosConfig, ChaosInjector

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO_ROOT = os.path.dirname(_HERE)
_WORKER = os.path.join(_HERE, "_overlap_worker.py")

QSGD = QsgdCodec(bits=4, bucket_size=128)


def _setup(n_dev=2, batch=8, momentum=0.9):
    mesh = make_mesh(n_dev)
    model = get_model("lenet", 10)
    opt = make_optimizer("sgd", lr=0.01, momentum=momentum)
    r = np.random.default_rng(0)
    batches = [
        (r.standard_normal((batch, 28, 28, 1)).astype(np.float32),
         r.integers(0, 10, batch).astype(np.int32))
        for _ in range(5)
    ]
    host0 = snapshot_state(
        create_state(model, opt, jax.random.PRNGKey(0),
                     jnp.asarray(batches[0][0]))
    )
    return mesh, model, opt, host0, batches


def _fresh_train(mesh, host0):
    return replicate_state(mesh, jax.tree_util.tree_map(jnp.asarray, host0))


def _eq(a, b):
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))
    )


def _drive_oracle(oracle, st, carry, batches, key, mesh):
    """The eager delayed schedule: apply consumes step t-1's payload while
    produce emits step t's — each phase its own dispatch."""
    px, okx, valid = carry.payload, carry.ok, carry.valid
    ms = []
    for im, lb in batches:
        si, sl = shard_batch(mesh, im, lb)
        npx, nok, stats_x, pm = oracle["produce"](st, key, si, sl)
        st, am = oracle["apply"](st, px, okx, valid, stats_x, nok)
        px, okx, valid = npx, nok, jnp.float32(1.0)
        ms.append({**jax.device_get(pm), **jax.device_get(am)})
    return st, ms


# ------------------------------------------------ off-mode regression


def test_overlap_off_is_bit_identical_to_default():
    """`--overlap off` must BE the blocking program: two separately-built
    steps (default args vs explicit off) produce identical bits."""
    mesh, model, opt, host0, batches = _setup()
    key = jax.random.PRNGKey(1)
    s_def = make_distributed_train_step(model, opt, mesh, QSGD,
                                        aggregate="gather")
    s_off = make_distributed_train_step(model, opt, mesh, QSGD,
                                        aggregate="gather", overlap="off")
    a, b = _fresh_train(mesh, host0), _fresh_train(mesh, host0)
    si, sl = shard_batch(mesh, *batches[0])
    a, ma = s_def(a, key, si, sl)
    b, mb = s_off(b, key, si, sl)
    assert _eq(jax.device_get(a.params), jax.device_get(b.params))
    assert float(ma["loss"]) == float(mb["loss"])


# ---------------------------------------- the two-program eager oracle


def test_delayed_matches_two_program_oracle_bitwise_and_step0_skips():
    """The tentpole contract: the fused superstep=1 delayed program equals
    the produce/apply oracle pair bit-for-bit over a 5-step trajectory
    (params AND optimizer state), and step 0 applies a zero update."""
    mesh, model, opt, host0, batches = _setup()
    key = jax.random.PRNGKey(1)
    n_dev = mesh.shape["dp"]
    step = make_distributed_train_step(
        model, opt, mesh, QSGD, aggregate="gather", overlap="delayed"
    )
    oracle = make_delayed_oracle_steps(model, opt, mesh, QSGD,
                                       aggregate="gather")

    d = init_delayed_state(mesh, _fresh_train(mesh, host0), QSGD)
    st = _fresh_train(mesh, host0)
    carry0 = _zero_carry_host(QSGD, host0.params, n_dev)

    fused_ms = []
    for im, lb in batches:
        si, sl = shard_batch(mesh, im, lb)
        d, m = step(d, key, si, sl)
        fused_ms.append(jax.device_get(m))
    st, oracle_ms = _drive_oracle(oracle, st, carry0, batches, key, mesh)

    assert _eq(jax.device_get(d.train.params), jax.device_get(st.params))
    assert _eq(jax.device_get(d.train.opt_state),
               jax.device_get(st.opt_state))
    # step-0 semantics: zero (skipped) update, nothing dropped
    assert float(fused_ms[0]["skipped"]) == 1.0
    assert float(fused_ms[0]["dropped"]) == 0.0
    assert float(fused_ms[1]["skipped"]) == 0.0
    assert float(oracle_ms[0]["skipped"]) == 1.0
    # wire honesty unchanged: the produced payload is the message
    assert float(fused_ms[0]["msg_bytes"]) < float(fused_ms[0]["dense_bytes"])


def test_delayed_step0_holds_all_state():
    """After the first delayed step: params, opt state and BN stats are
    bit-equal to the initial state (the zero update), step advanced."""
    mesh, model, opt, host0, batches = _setup()
    step = make_distributed_train_step(
        model, opt, mesh, QSGD, aggregate="gather", overlap="delayed"
    )
    d = init_delayed_state(mesh, _fresh_train(mesh, host0), QSGD)
    si, sl = shard_batch(mesh, *batches[0])
    d, _ = step(d, jax.random.PRNGKey(1), si, sl)
    assert _eq(jax.device_get(d.train.params), host0.params)
    assert _eq(jax.device_get(d.train.opt_state), host0.opt_state)
    assert int(jax.device_get(d.train.step)) == 1
    assert float(jax.device_get(d.carry.valid)) == 1.0


def test_delayed_staleness_semantics():
    """Delayed applies step t's gradient at step t+1: after two delayed
    steps the params equal blocking's after ONE step on the same first
    batch (cross-program comparison — allclose at fp32 rounding)."""
    mesh, model, opt, host0, batches = _setup()
    key = jax.random.PRNGKey(1)
    delayed = make_distributed_train_step(
        model, opt, mesh, QSGD, aggregate="gather", overlap="delayed"
    )
    blocking = make_distributed_train_step(model, opt, mesh, QSGD,
                                           aggregate="gather")
    d = init_delayed_state(mesh, _fresh_train(mesh, host0), QSGD)
    for im, lb in batches[:2]:
        si, sl = shard_batch(mesh, im, lb)
        d, _ = delayed(d, key, si, sl)
    sb = _fresh_train(mesh, host0)
    si, sl = shard_batch(mesh, *batches[0])
    sb, _ = blocking(sb, key, si, sl)
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(d.train.params)),
                    jax.tree_util.tree_leaves(jax.device_get(sb.params))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


# --------------------------------------------- scan family invariance


def test_delayed_superstep_partition_invariant():
    """The delayed scan program fed [4], [1]*4 and [2,2] block partitions
    produces bit-identical per-step losses and final params — the carry
    (payload included) rides the scan exactly like the rest of the state."""
    mesh, model, opt, host0, batches = _setup()
    key = jax.random.PRNGKey(1)
    stepK = make_distributed_train_step(
        model, opt, mesh, QSGD, aggregate="gather", overlap="delayed",
        superstep=4,
    )

    def run(sizes):
        d = init_delayed_state(mesh, _fresh_train(mesh, host0), QSGD)
        i, losses = 0, []
        for k in sizes:
            im = np.stack([b[0] for b in batches[i:i + k]])
            lb = np.stack([b[1] for b in batches[i:i + k]])
            si, sl = shard_superbatch(mesh, im, lb)
            d, m = stepK(d, key, si, sl)
            losses.append(np.atleast_1d(jax.device_get(m["loss"])))
            i += k
        return jax.device_get(d), np.concatenate(losses)

    da, la = run([4])
    db, lb_ = run([1, 1, 1, 1])
    dc, lc = run([2, 2])
    np.testing.assert_array_equal(la, lb_)
    np.testing.assert_array_equal(la, lc)
    assert _eq(da.train.params, db.train.params)
    assert _eq(da.train.params, dc.train.params)
    # the carried payload itself is partition-invariant (it is state)
    assert _eq(da.carry.payload, db.carry.payload)


# ---------------------------------------------------- guard semantics


def test_delayed_guard_poisons_the_consuming_step():
    """A NaN confined to replica 0 at producing step 1 must be masked at
    CONSUMING step 2 (dropped=1 there, nothing dropped at step 1), the
    step is rescaled, params stay finite — and the whole guarded
    trajectory still matches the oracle bitwise (the flags travel in both
    representations)."""
    mesh, model, opt, host0, batches = _setup()
    key = jax.random.PRNGKey(1)

    def mk_chaos():
        return ChaosInjector(ChaosConfig.from_spec("nan@1"))

    step = make_distributed_train_step(
        model, opt, mesh, QSGD, aggregate="gather", overlap="delayed",
        guard=GuardConfig(), chaos=mk_chaos(),
    )
    oracle = make_delayed_oracle_steps(
        model, opt, mesh, QSGD, aggregate="gather",
        guard=GuardConfig(), chaos=mk_chaos(),
    )
    d = init_delayed_state(mesh, _fresh_train(mesh, host0), QSGD)
    ms = []
    for im, lb in batches[:3]:
        si, sl = shard_batch(mesh, im, lb)
        d, m = step(d, key, si, sl)
        ms.append(jax.device_get(m))
    st, _ = _drive_oracle(
        oracle, _fresh_train(mesh, host0),
        _zero_carry_host(QSGD, host0.params, mesh.shape["dp"]),
        batches[:3], key, mesh,
    )
    assert float(ms[0]["dropped"]) == 0.0 and float(ms[0]["skipped"]) == 1.0
    assert float(ms[1]["dropped"]) == 1.0 and float(ms[1]["skipped"]) == 0.0
    assert float(ms[2]["dropped"]) == 0.0
    assert all(
        np.all(np.isfinite(np.asarray(l)))
        for l in jax.tree_util.tree_leaves(jax.device_get(d.train.params))
    )
    assert _eq(jax.device_get(d.train.params), jax.device_get(st.params))


def test_delayed_sample_skipped_gates_the_detector_on_all_bad_forward():
    """metrics['skipped'] follows the CONSUMED step-(t-1) payload, so a
    step whose every forward gradient the guard rejected reports
    skipped=0 while _healthy_mean collapses its loss to 0.0 — an invalid
    sample the detector would fold as clean. 'sample_skipped' is the
    produce-aligned gate RecoveryRig.observe prefers."""
    mesh, model, opt, host0, batches = _setup()
    key = jax.random.PRNGKey(1)
    step = make_distributed_train_step(
        model, opt, mesh, QSGD, aggregate="gather", overlap="delayed",
        guard=GuardConfig(),
        chaos=ChaosInjector(ChaosConfig.from_spec("nan@2*")),
        track_grad_norm=True,
    )
    d = init_delayed_state(mesh, _fresh_train(mesh, host0), QSGD)
    ms = []
    for im, lb in batches[:3]:
        si, sl = shard_batch(mesh, im, lb)
        d, m = step(d, key, si, sl)
        ms.append(jax.device_get(m))
    # step 1: clean forward, consumes the empty step-0 carry
    assert float(ms[0]["sample_skipped"]) == 0.0
    assert float(ms[0]["skipped"]) == 1.0
    # step 2: every forward rejected (sample gated) — but the consumed
    # step-1 payload is healthy, so the update applies and skipped=0
    assert float(ms[1]["sample_skipped"]) == 1.0
    assert float(ms[1]["skipped"]) == 0.0
    # step 3: consumes the all-bad step-2 payload (skipped); its own
    # forward is healthy again
    assert float(ms[2]["sample_skipped"]) == 0.0
    assert float(ms[2]["skipped"]) == 1.0


# ------------------------------------------------------- validations


def test_delayed_construction_validations():
    mesh = make_mesh(2)
    model = get_model("lenet", 10)
    opt = make_optimizer("sgd", lr=0.01)
    with pytest.raises(ValueError, match="compressing codec"):
        make_distributed_train_step(model, opt, mesh, None,
                                    aggregate="gather", overlap="delayed")
    with pytest.raises(ValueError, match="delayed"):
        make_distributed_train_step(model, opt, mesh, QSGD,
                                    aggregate="psum", overlap="delayed")
    with pytest.raises(ValueError, match="overlap"):
        make_distributed_train_step(model, opt, mesh, QSGD,
                                    overlap="lazy")
    with pytest.raises(ValueError, match="_oracle_parts"):
        make_distributed_train_step(model, opt, mesh, QSGD,
                                    _oracle_parts=True)


def test_delayed_loop_validations():
    from atomo_tpu.parallel import distributed_train_loop

    mesh = make_mesh(2)
    model = get_model("lenet", 10)
    opt = make_optimizer("sgd", lr=0.01)
    it = BatchIterator(
        synthetic_dataset(SPECS["mnist"], True, size=32), 8, seed=0
    )
    with pytest.raises(ValueError, match="compressing codec"):
        distributed_train_loop(model, opt, mesh, it, codec=None,
                               aggregate="psum", overlap="delayed",
                               max_steps=1)
    with pytest.raises(ValueError, match="zero1"):
        distributed_train_loop(model, opt, mesh, it, codec=QSGD,
                               aggregate="gather", overlap="delayed",
                               zero1=True, resume=True, max_steps=1)


# ------------------------------------------------------- slow lane


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["gather", "ring"])
def test_delayed_oracle_bitwise_svd(mode):
    """Oracle bit-parity holds for the factor-payload family too, in both
    exchange modes (SVD's fused decode_mean rides the gather consume; the
    ring consume is the canonical segment-owner fold)."""
    mesh, model, opt, host0, batches = _setup(momentum=0.0)
    codec = SvdCodec(rank=2)
    key = jax.random.PRNGKey(1)
    step = make_distributed_train_step(
        model, opt, mesh, codec, aggregate=mode, overlap="delayed"
    )
    oracle = make_delayed_oracle_steps(model, opt, mesh, codec,
                                       aggregate=mode)
    d = init_delayed_state(mesh, _fresh_train(mesh, host0), codec)
    for im, lb in batches[:4]:
        si, sl = shard_batch(mesh, im, lb)
        d, m = step(d, key, si, sl)
    st, _ = _drive_oracle(
        oracle, _fresh_train(mesh, host0),
        _zero_carry_host(codec, host0.params, mesh.shape["dp"]),
        batches[:4], key, mesh,
    )
    assert np.isfinite(float(jax.device_get(m["loss"])))
    assert _eq(jax.device_get(d.train.params), jax.device_get(st.params))
    assert _eq(jax.device_get(d.train.opt_state), jax.device_get(st.opt_state))


@pytest.mark.slow
def test_delayed_ring_partition_invariant_and_replicated():
    """Ring consume under the scan: partition invariance plus the
    replicated-PS invariant (every chip holds identical params)."""
    mesh, model, opt, host0, batches = _setup()
    key = jax.random.PRNGKey(1)
    stepK = make_distributed_train_step(
        model, opt, mesh, QSGD, aggregate="ring", overlap="delayed",
        superstep=4,
    )

    def run(sizes):
        d = init_delayed_state(mesh, _fresh_train(mesh, host0), QSGD)
        i = 0
        for k in sizes:
            im = np.stack([b[0] for b in batches[i:i + k]])
            lb = np.stack([b[1] for b in batches[i:i + k]])
            si, sl = shard_superbatch(mesh, im, lb)
            d, _ = stepK(d, key, si, sl)
            i += k
        return d

    da = run([4])
    db = run([1, 1, 2])
    assert _eq(jax.device_get(da.train.params), jax.device_get(db.train.params))
    leaf = jax.tree_util.tree_leaves(da.train.params)[0]
    shards = [np.asarray(s.data) for s in leaf.addressable_shards]
    for s in shards[1:]:
        np.testing.assert_array_equal(shards[0], s)


@pytest.mark.slow
def test_delayed_composes_with_zero1():
    """ZeRO-1 consumes the delayed mean exactly as the blocking one:
    sliced update on the carried payload's decode, replicated params,
    finite loss, and the step-0 skip still holds the sharded opt state."""
    from atomo_tpu.parallel.replicated import DelayedState, zero1_state

    mesh, model, opt, host0, batches = _setup()
    z_state, specs = zero1_state(
        mesh, jax.tree_util.tree_map(jnp.asarray, host0), opt
    )
    step = make_distributed_train_step(
        model, opt, mesh, QSGD, aggregate="gather", overlap="delayed",
        zero1_specs=specs,
    )
    d = init_delayed_state(mesh, z_state, QSGD)
    key = jax.random.PRNGKey(1)
    for im, lb in batches[:2]:
        si, sl = shard_batch(mesh, im, lb)
        d, m = step(d, key, si, sl)
    assert np.isfinite(float(jax.device_get(m["loss"])))
    leaf = jax.tree_util.tree_leaves(d.train.params)[0]
    shards = [np.asarray(s.data) for s in leaf.addressable_shards]
    for s in shards[1:]:
        np.testing.assert_array_equal(shards[0], s)
    assert isinstance(jax.device_get(d), DelayedState)


@pytest.mark.slow
def test_delayed_num_aggregate_matches_oracle():
    """K-of-N subsetting composes: the subset rotation follows the
    PRODUCING step's counter, identically in the fused program and the
    oracle (bitwise)."""
    mesh, model, opt, host0, batches = _setup(n_dev=4, batch=8)
    key = jax.random.PRNGKey(1)
    step = make_distributed_train_step(
        model, opt, mesh, QSGD, aggregate="gather", overlap="delayed",
        num_aggregate=2,
    )
    oracle = make_delayed_oracle_steps(
        model, opt, mesh, QSGD, aggregate="gather", num_aggregate=2
    )
    d = init_delayed_state(mesh, _fresh_train(mesh, host0), QSGD)
    for im, lb in batches[:3]:
        si, sl = shard_batch(mesh, im, lb)
        d, m = step(d, key, si, sl)
    st, _ = _drive_oracle(
        oracle, _fresh_train(mesh, host0),
        _zero_carry_host(QSGD, host0.params, 4), batches[:3], key, mesh,
    )
    assert np.isfinite(float(jax.device_get(m["loss"])))
    assert _eq(jax.device_get(d.train.params), jax.device_get(st.params))


@pytest.mark.slow
def test_delayed_resume_across_block_boundary(tmp_path):
    """In-process resume drill: run K=2 to step 4 with checkpoints, resume
    with a DIFFERENT K=3 to step 6; the final params must be bit-identical
    to an uninterrupted delayed K=2 run — the checkpoint carried the
    in-flight payload, so no step was consumed twice or skipped."""
    from atomo_tpu.parallel import distributed_train_loop

    mesh, model, opt, _host0, _batches = _setup()

    def make_iter():
        return BatchIterator(
            synthetic_dataset(SPECS["mnist"], True, size=64), 16, seed=0
        )

    oracle = distributed_train_loop(
        model, opt, mesh, make_iter(), codec=QSGD, aggregate="gather",
        overlap="delayed", max_steps=6, log_every=0, eval_freq=0, seed=0,
        superstep=2,
    )
    distributed_train_loop(
        model, opt, mesh, make_iter(), codec=QSGD, aggregate="gather",
        overlap="delayed", max_steps=4, log_every=0, eval_freq=0, seed=0,
        superstep=2, train_dir=str(tmp_path), save_freq=2,
    )
    logs = []
    resumed = distributed_train_loop(
        model, opt, mesh, make_iter(), codec=QSGD, aggregate="gather",
        overlap="delayed", max_steps=6, log_every=0, eval_freq=0, seed=0,
        superstep=3, train_dir=str(tmp_path), resume=True,
        log_fn=logs.append,
    )
    assert any("Resumed" in l and "step 4" in l for l in logs), logs
    assert _eq(jax.device_get(resumed.params), jax.device_get(oracle.params))
    assert int(jax.device_get(resumed.step)) == 6


def _run_drill(train_dir, chaos="", resume=False, superstep=2, timeout=420):
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "ATOMO_OVL_DIR": str(train_dir),
        "ATOMO_OVL_RESUME": "1" if resume else "0",
        "ATOMO_OVL_SUPERSTEP": str(superstep),
        "ATOMO_CHAOS": chaos,
        "PYTHONPATH": _REPO_ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
    }
    proc = subprocess.run(
        [sys.executable, _WORKER],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    final = None
    for line in proc.stdout.splitlines():
        if line.startswith("OVLFINAL "):
            final = line.split()[1]
    return proc, final


@pytest.mark.slow
def test_blocking_resume_of_delayed_checkpoint_restores_train_state(
    tmp_path, recwarn
):
    """Resuming a delayed-mode checkpoint WITHOUT --overlap delayed must
    not die on flax's opaque key-mismatch: the nested train state is
    restored, the in-flight payload discarded, and a warning names the
    cause (code-review finding on the cross-format resume path)."""
    from atomo_tpu.parallel import distributed_train_loop

    mesh, model, opt, _host0, _batches = _setup()

    def make_iter():
        return BatchIterator(
            synthetic_dataset(SPECS["mnist"], True, size=64), 16, seed=0
        )

    distributed_train_loop(
        model, opt, mesh, make_iter(), codec=QSGD, aggregate="gather",
        overlap="delayed", max_steps=2, log_every=0, eval_freq=0, seed=0,
        train_dir=str(tmp_path), save_freq=2,
    )
    logs = []
    state = distributed_train_loop(
        model, opt, mesh, make_iter(), codec=QSGD, aggregate="gather",
        max_steps=3, log_every=0, eval_freq=0, seed=0,
        train_dir=str(tmp_path), resume=True, log_fn=logs.append,
    )
    assert any("Resumed" in l and "step 2" in l for l in logs), logs
    assert int(jax.device_get(state.step)) == 3
    assert any(
        "overlap delayed" in str(w.message) for w in recwarn.list
    ), [str(w.message) for w in recwarn.list]


@pytest.mark.slow
def test_delayed_kill_restart_resume_across_block_boundary(tmp_path):
    """The overlap fault-tolerance drill (acceptance criterion):

    oracle:  K=2, nan@3 (guard masks it at CONSUMING step 4), 8 steps
    crash:   K=2 + kill@5 — dies at the (4,6] block start; newest valid
             checkpoint is the boundary 4, in-flight payload included
    resume:  K=4 from step 4 — the restored carry is consumed at step 5,
             and the final params hash must equal the oracle's exactly
    """
    from atomo_tpu.training.checkpoint import latest_valid_step
    from atomo_tpu.utils.chaos import CHAOS_EXIT_CODE

    oracle_dir = tmp_path / "oracle"
    crash_dir = tmp_path / "crash"

    p_oracle, final_oracle = _run_drill(oracle_dir, chaos="nan@3", superstep=2)
    assert p_oracle.returncode == 0, p_oracle.stderr[-3000:]
    assert final_oracle is not None
    # the guard masked the poisoned payload at the CONSUMING step (4)
    assert any(
        line.startswith("Guard: Step: 4")
        for line in p_oracle.stdout.splitlines()
    ), p_oracle.stdout

    p_crash, final_crash = _run_drill(
        crash_dir, chaos="nan@3,kill@5", superstep=2
    )
    assert p_crash.returncode == CHAOS_EXIT_CODE, (
        p_crash.returncode, p_crash.stderr[-3000:],
    )
    assert final_crash is None
    assert latest_valid_step(str(crash_dir)) == 4

    p_res, final_res = _run_drill(
        crash_dir, chaos="nan@3", resume=True, superstep=4
    )
    assert p_res.returncode == 0, p_res.stderr[-3000:]
    assert any(
        "Resumed from" in line and "step 4" in line
        for line in p_res.stdout.splitlines()
    ), p_res.stdout
    assert final_res == final_oracle


@pytest.mark.slow
def test_train_cli_overlap_delayed_runs(tmp_path, capsys):
    """`--overlap delayed` end to end through the CLI: trains, logs the
    compressed Msg(MB), and the dense/psum/single-device misuses die with
    a clear SystemExit before any mesh work."""
    import re

    from atomo_tpu.cli import main

    args = [
        "train", "--network", "LeNet", "--dataset", "MNIST",
        "--synthetic", "--train-dir", str(tmp_path / "d"),
        "--batch-size", "8", "--max-steps", "2", "--eval-freq", "0",
        "--log-interval", "1", "--n-devices", "2", "--code", "qsgd",
        "--aggregate", "gather", "--overlap", "delayed",
    ]
    assert main(args) == 0
    out = capsys.readouterr().out
    msg = re.findall(r"Msg\(MB\):\s+([0-9.]+)", out)
    assert msg and float(msg[-1]) > 0

    with pytest.raises(SystemExit, match="compressing"):
        main(["train", "--synthetic", "--code", "sgd", "--n-devices", "2",
              "--overlap", "delayed", "--max-steps", "1"])
    with pytest.raises(SystemExit, match="gather or ring|delayed"):
        main(["train", "--synthetic", "--code", "qsgd", "--n-devices", "2",
              "--aggregate", "psum", "--overlap", "delayed",
              "--max-steps", "1"])
    with pytest.raises(SystemExit, match="multi-device"):
        main(["train", "--synthetic", "--code", "qsgd", "--n-devices", "1",
              "--overlap", "delayed", "--max-steps", "1"])
