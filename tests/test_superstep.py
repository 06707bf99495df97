"""Superstep (fused K-step) execution — the PR-2 perf tentpole's
correctness contract.

The contract these tests pin down (and the docstrings advertise):

  * Within the fused scan program family, results are BIT-IDENTICAL for
    any block partition of the same step sequence — one step per dispatch
    (a length-1 block) equals one K-step block equals any ragged split.
    That is what makes superstep execution safe to turn on: checkpoints,
    resumes, and K changes across restarts cannot move the trajectory.
  * The legacy per-step program (``superstep=1``, kept byte-for-byte as
    before this PR) is numerically equivalent but NOT bit-identical to
    the scan family: XLA fuses the standalone step body differently than
    the same body inside ``lax.scan`` (last-mantissa-bit drift after a
    few steps). Asserted with tight allclose, documented, and the reason
    ``superstep=1`` remains the default on CPU.
  * The resilience guard's skip(-and-rescale) decisions ride the scan
    carry: a fault injected mid-block produces exactly the sequential
    oracle's trajectory and per-step skip/drop flags.
  * train_loop checkpoint cadence snaps to block boundaries, and resume
    works from a step that is NOT a multiple of K — including a chaos
    kill→restart→resume drill whose crash and resume legs use different
    K values (tests/_ft_worker.py).
"""

import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from atomo_tpu.codecs import QsgdCodec, SvdCodec
from atomo_tpu.data import BatchIterator, SPECS, synthetic_dataset
from atomo_tpu.models import get_model
from atomo_tpu.training import (
    GuardConfig,
    create_state,
    list_steps,
    make_optimizer,
    make_train_step,
    snapshot_state,
    train_loop,
)
from atomo_tpu.utils.chaos import CHAOS_EXIT_CODE, ChaosConfig, ChaosInjector

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO_ROOT = os.path.dirname(_HERE)
_FT_WORKER = os.path.join(_HERE, "_ft_worker.py")


def _model_opt(momentum=0.9):
    # lr 0.01 keeps every codec's short trajectory finite (NaN != NaN
    # would void the bitwise comparisons); momentum exercises the opt
    # state in the scan carry
    return get_model("lenet", 10), make_optimizer("sgd", lr=0.01, momentum=momentum)


def _batches(n, batch=16):
    ds = synthetic_dataset(SPECS["mnist"], True, size=64)
    stream = BatchIterator(ds, batch, seed=0).forever()
    return [next(stream) for _ in range(n)]


def _host_state(model, opt, batches):
    return snapshot_state(
        create_state(model, opt, jax.random.PRNGKey(0), jnp.asarray(batches[0][0]))
    )


def _fresh(host_state):
    # real device copies: the fused step DONATES its carry, and on the CPU
    # backend device_put can alias a host tree's buffers — asarray from the
    # snapshot_state numpy copies is safe to donate repeatedly
    return jax.tree_util.tree_map(jnp.asarray, host_state)


def _params(state):
    return jax.tree_util.tree_leaves(jax.device_get(state.params))


def _trees_equal(a, b):
    return all(np.array_equal(x, y) for x, y in zip(_params(a), _params(b)))


def _run_blocks(step_fn, state, key, batches, sizes):
    """Drive a fused step through the given block partition; returns the
    final state and the flat per-step metrics series."""
    metrics = []
    i = 0
    for k in sizes:
        im = np.stack([b[0] for b in batches[i : i + k]])
        lb = np.stack([b[1] for b in batches[i : i + k]])
        state, m = step_fn(state, key, jnp.asarray(im), jnp.asarray(lb))
        metrics.append(jax.device_get(m))
        i += k
    flat = {
        name: np.concatenate([np.atleast_1d(m[name]) for m in metrics])
        for name in metrics[0]
    }
    return state, flat


# --------------------------------------------------------- single host


@pytest.mark.parametrize(
    "codec",
    [
        None,
        # qsgd/svd re-prove the same fused-vs-sequential invariant over
        # pricier encoders (~26 s qsgd, ~25 s svd on 1 core) — full-suite
        # only; dense keeps the partition witness in the smoke set, and
        # the codec'd superstep math stays tier-1-covered by
        # test_superstep_tracks_legacy_per_step_program and the
        # distributed[gather] variant below
        pytest.param(
            QsgdCodec(bits=4, bucket_size=128), marks=pytest.mark.slow
        ),
        pytest.param(SvdCodec(rank=2), marks=pytest.mark.slow),
    ],
    ids=["dense", "qsgd", "svd"],
)
def test_superstep_bitwise_partition_invariant(codec):
    """(a) K fused steps == K sequential steps, bit for bit: the SAME
    fused program fed one-step blocks (sequential dispatch) and one
    K-block must produce identical per-step losses and final params, for
    every codec. A ragged split covers the resume-shaped partitions."""
    # momentum 0 for SVD (the reference's canonical SVD recipe): heavy
    # momentum amplifies the low-rank estimator's noise into divergence
    # on this short synthetic run, and resulting NaNs would void the
    # bitwise asserts (NaN != NaN)
    model, opt = _model_opt(momentum=0.0 if isinstance(codec, SvdCodec) else 0.9)
    batches = _batches(8)
    key = jax.random.PRNGKey(1)
    host0 = _host_state(model, opt, batches)
    fused = make_train_step(model, opt, codec=codec, superstep=8)

    s_seq, m_seq = _run_blocks(fused, _fresh(host0), key, batches, [1] * 8)
    s_blk, m_blk = _run_blocks(fused, _fresh(host0), key, batches, [8])
    s_rag, m_rag = _run_blocks(fused, _fresh(host0), key, batches, [3, 4, 1])

    np.testing.assert_array_equal(m_seq["loss"], m_blk["loss"])
    np.testing.assert_array_equal(m_rag["loss"], m_blk["loss"])
    assert _trees_equal(s_seq, s_blk)
    assert _trees_equal(s_rag, s_blk)
    assert int(s_blk.step) == 8


def test_superstep_tracks_legacy_per_step_program():
    """The pre-PR standalone step program (superstep=1, unchanged) is the
    same math but a DIFFERENT XLA program: fusion choices differ inside
    vs outside lax.scan, so trajectories agree to float32 rounding, not
    bitwise. This pins the numeric equivalence and documents why mixing
    the legacy program and the scan family mid-timeline is allclose-only."""
    model, opt = _model_opt()
    batches = _batches(6)
    key = jax.random.PRNGKey(1)
    host0 = _host_state(model, opt, batches)

    legacy = make_train_step(model, opt)
    s1 = _fresh(host0)
    legacy_losses = []
    for im, lb in batches:
        s1, m = legacy(s1, key, jnp.asarray(im), jnp.asarray(lb))
        legacy_losses.append(float(m["loss"]))

    fused = make_train_step(model, opt, superstep=6)
    s2, mf = _run_blocks(fused, _fresh(host0), key, batches, [6])

    np.testing.assert_allclose(mf["loss"], legacy_losses, rtol=1e-4)
    for a, b in zip(_params(s1), _params(s2)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_guard_skip_fires_mid_scan_matches_sequential():
    """(b) a chaos NaN at step 3 of a 6-step block: the guard must skip
    exactly that step inside the scan (params/opt state held in the
    carry) and the whole trajectory must equal the sequential oracle's."""
    model, opt = _model_opt()
    batches = _batches(6)
    key = jax.random.PRNGKey(1)
    host0 = _host_state(model, opt, batches)
    chaos = ChaosInjector(ChaosConfig.from_spec("nan@3"))
    fused = make_train_step(
        model, opt, guard=GuardConfig(), chaos=chaos, superstep=6
    )

    s_seq, m_seq = _run_blocks(fused, _fresh(host0), key, batches, [1] * 6)
    s_blk, m_blk = _run_blocks(fused, _fresh(host0), key, batches, [6])

    np.testing.assert_array_equal(m_blk["skipped"], [0, 0, 1, 0, 0, 0])
    np.testing.assert_array_equal(m_seq["skipped"], m_blk["skipped"])
    assert np.all(np.isfinite(m_blk["loss"][[0, 1, 3, 4, 5]]))
    np.testing.assert_array_equal(m_seq["loss"], m_blk["loss"])
    assert _trees_equal(s_seq, s_blk)


def test_snapshot_state_survives_donation():
    """The donation-aliasing footgun helper: snapshot_state must hand back
    independent host copies (numpy, not views of live buffers), so the
    pre-step values survive stepping with the donating fused program."""
    model, opt = _model_opt()
    batches = _batches(2)
    key = jax.random.PRNGKey(1)
    state = create_state(
        model, opt, jax.random.PRNGKey(0), jnp.asarray(batches[0][0])
    )
    snap = snapshot_state(state)
    before = [np.array(l, copy=True) for l in jax.tree_util.tree_leaves(snap.params)]
    for leaf in jax.tree_util.tree_leaves(snap):
        assert isinstance(leaf, np.ndarray)

    fused = make_train_step(model, opt, superstep=2)
    im = np.stack([b[0] for b in batches])
    lb = np.stack([b[1] for b in batches])
    new_state, _ = fused(state, key, jnp.asarray(im), jnp.asarray(lb))

    # the donated input's buffers are gone/reused; the snapshot is not
    after = jax.tree_util.tree_leaves(snap.params)
    assert all(np.array_equal(a, b) for a, b in zip(before, after))
    # and training did move the params (the snapshot is really pre-step)
    assert not _trees_equal(new_state, snap)


# ------------------------------------------------------------ train_loop


def _make_iter():
    return BatchIterator(
        synthetic_dataset(SPECS["mnist"], True, size=64), 16, seed=0
    )


def test_train_loop_superstep_checkpoints_snap_to_boundaries(tmp_path):
    """save_freq=3 with K=4 over 10 steps: cadence points 3/6/9 are crossed
    inside blocks (1-4], (5-8], (9-10] -> checkpoints land on the block
    boundaries 4, 8, 10 (the final one doubling as the autosave)."""
    model, opt = _model_opt()
    state = train_loop(
        model, opt, _make_iter(), max_steps=10, log_every=0, seed=0,
        superstep=4, train_dir=str(tmp_path), save_freq=3,
    )
    assert list_steps(str(tmp_path)) == [4, 8, 10]
    assert int(state.step) == 10


def test_train_loop_resume_at_non_multiple_of_k(tmp_path):
    """(c) resume from a checkpoint step that is NOT a multiple of the
    resuming K: save at 3 (K=2 run), resume with K=4 to 10; final params
    must be bit-identical to an uninterrupted superstep oracle."""
    model, opt = _model_opt()
    oracle = train_loop(
        model, opt, _make_iter(), max_steps=10, log_every=0, seed=0,
        superstep=5,
    )
    train_loop(
        model, opt, _make_iter(), max_steps=3, log_every=0, seed=0,
        superstep=2, train_dir=str(tmp_path), save_freq=3,
    )
    assert list_steps(str(tmp_path)) == [3]
    logs = []
    resumed = train_loop(
        model, opt, _make_iter(), max_steps=10, log_every=0, seed=0,
        superstep=4, train_dir=str(tmp_path), resume=True, log_fn=logs.append,
    )
    assert any("Resumed" in line and "step 3" in line for line in logs), logs
    assert _trees_equal(resumed, oracle)
    assert int(resumed.step) == 10


def _run_ft(train_dir, chaos="", resume=False, superstep=1, timeout=240):
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "ATOMO_FT_DIR": str(train_dir),
        "ATOMO_FT_RESUME": "1" if resume else "0",
        "ATOMO_FT_SUPERSTEP": str(superstep),
        "ATOMO_CHAOS": chaos,
        "PYTHONPATH": _REPO_ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
    }
    proc = subprocess.run(
        [sys.executable, _FT_WORKER],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    final = None
    for line in proc.stdout.splitlines():
        if line.startswith("FTFINAL "):
            final = line.split()[1]
    return proc, final


@pytest.mark.slow  # 3 subprocess trainings (~22 s on 1 core) — full-suite
# only; test_train_loop_resume_at_non_multiple_of_k keeps the non-boundary
# resume contract in the smoke set
def test_superstep_kill_restart_resume_non_boundary(tmp_path):
    """The superstep fault-tolerance drill (PR-1 contract with K>1):

    oracle:  K=4, nan@3 (guard skips it mid-block), 8 steps, uninterrupted
    crash:   K=3 + kill@5 — the kill lands inside block (4..6], which dies
             BEFORE the block runs; newest checkpoint is the block
             boundary 3 (save_freq=2 snaps there)
    resume:  K=4 from step 3 — NOT a multiple of 4 — must reproduce the
             oracle's final params hash exactly (partition invariance)
    """
    from atomo_tpu.training.checkpoint import latest_valid_step

    oracle_dir = tmp_path / "oracle"
    crash_dir = tmp_path / "crash"

    p_oracle, final_oracle = _run_ft(oracle_dir, chaos="nan@3", superstep=4)
    assert p_oracle.returncode == 0, p_oracle.stderr[-3000:]
    assert final_oracle is not None
    # the guard announced the mid-block skip at the block boundary
    assert any(
        line.startswith("Guard: Step: 4") for line in p_oracle.stdout.splitlines()
    ), p_oracle.stdout

    p_crash, final_crash = _run_ft(
        crash_dir, chaos="nan@3,kill@5", superstep=3
    )
    assert p_crash.returncode == CHAOS_EXIT_CODE, (
        p_crash.returncode, p_crash.stderr[-3000:],
    )
    assert final_crash is None  # really died mid-run
    assert latest_valid_step(str(crash_dir)) == 3

    p_res, final_res = _run_ft(crash_dir, chaos="nan@3", resume=True, superstep=4)
    assert p_res.returncode == 0, p_res.stderr[-3000:]
    assert any(
        "Resumed from" in line and "step 3" in line
        for line in p_res.stdout.splitlines()
    ), p_res.stdout
    assert final_res == final_oracle


# ----------------------------------------------------------- distributed


def _dist_setup(mode):
    from atomo_tpu.parallel import make_mesh

    model, opt = _model_opt()
    batches = _batches(4, batch=8)
    host0 = _host_state(model, opt, batches)
    if mode == "hierarchical":
        mesh = make_mesh(4, axes=(("dp", 2), ("ici", 2)))
        kw = dict(
            codec=SvdCodec(rank=2), aggregate="hierarchical", inner_axis="ici"
        )
        axes = ("dp", "ici")
    elif mode == "psum":
        mesh = make_mesh(2)
        kw = dict(codec=None, aggregate="psum")
        axes = "dp"
    elif mode == "ring":
        # PR-3: the ring-streamed exchange must ride the superstep scan
        # with the same partition invariance as every other mode
        mesh = make_mesh(2)
        kw = dict(codec=QsgdCodec(bits=4, bucket_size=128), aggregate="ring")
        axes = "dp"
    else:  # gather / zero1: the compressed-wire flagship
        mesh = make_mesh(2)
        kw = dict(codec=QsgdCodec(bits=4, bucket_size=128), aggregate="gather")
        axes = "dp"
    return model, opt, mesh, kw, axes, batches, host0


def _dist_run_blocks(step_fn, state, key, batches, sizes, mesh, axes):
    from atomo_tpu.parallel.replicated import shard_superbatch

    metrics = []
    i = 0
    for k in sizes:
        im = np.stack([b[0] for b in batches[i : i + k]])
        lb = np.stack([b[1] for b in batches[i : i + k]])
        si, sl = shard_superbatch(mesh, im, lb, axis=axes)
        state, m = step_fn(state, key, si, sl)
        metrics.append(jax.device_get(m))
        i += k
    flat = {
        name: np.concatenate([np.atleast_1d(m[name]) for m in metrics])
        for name in metrics[0]
    }
    return state, flat


@pytest.mark.parametrize(
    "mode",
    [
        "gather",
        # ring/hierarchical/zero1 re-prove the same scan-partition contract
        # over pricier exchanges (~30 s combined on 1 core) — full-suite
        # only; gather+psum keep it in the smoke set
        pytest.param("ring", marks=pytest.mark.slow),
        "psum",
        pytest.param("hierarchical", marks=pytest.mark.slow),
        pytest.param("zero1", marks=pytest.mark.slow),
    ],
)
def test_distributed_superstep_partition_invariant(mode):
    """(a) distributed: K fused SPMD steps == K sequential dispatches of
    the same fused program, bitwise, for every aggregate mode (compressed
    gather, dense psum, hierarchical 2-axis, ZeRO-1 sliced update)."""
    from atomo_tpu.parallel.replicated import (
        make_distributed_train_step,
        replicate_state,
        zero1_state,
    )

    model, opt, mesh, kw, axes, batches, host0 = _dist_setup(mode)
    key = jax.random.PRNGKey(1)

    def make_state():
        if mode == "zero1":
            st, specs = zero1_state(mesh, _fresh(host0), opt)
            return st, specs
        return replicate_state(mesh, _fresh(host0)), None

    st_a, specs = make_state()
    step = make_distributed_train_step(
        model, opt, mesh, superstep=4, zero1_specs=specs, **kw
    )
    s_seq, m_seq = _dist_run_blocks(step, st_a, key, batches, [1] * 4, mesh, axes)
    st_b, _ = make_state()
    s_blk, m_blk = _dist_run_blocks(step, st_b, key, batches, [4], mesh, axes)

    np.testing.assert_array_equal(m_seq["loss"], m_blk["loss"])
    assert m_blk["loss"].shape == (4,)
    assert _trees_equal(s_seq, s_blk)
    assert int(jax.device_get(s_blk.step)) == 4


def test_distributed_guard_rescale_mid_scan_matches_sequential():
    """(b) distributed skip-and-rescale inside the scan: a NaN confined to
    replica 0 at step 3 of a 4-step block must be masked out of the
    aggregation (dropped=1, step NOT skipped — the other replica
    survives) with the identical trajectory either way."""
    from atomo_tpu.parallel.replicated import (
        make_distributed_train_step,
        replicate_state,
    )

    model, opt, mesh, kw, axes, batches, host0 = _dist_setup("gather")
    key = jax.random.PRNGKey(1)
    chaos = ChaosInjector(ChaosConfig.from_spec("nan@3"))  # target_replica=0
    step = make_distributed_train_step(
        model, opt, mesh, superstep=4, guard=GuardConfig(), chaos=chaos, **kw
    )

    s_seq, m_seq = _dist_run_blocks(
        step, replicate_state(mesh, _fresh(host0)), key, batches, [1] * 4,
        mesh, axes,
    )
    s_blk, m_blk = _dist_run_blocks(
        step, replicate_state(mesh, _fresh(host0)), key, batches, [4],
        mesh, axes,
    )

    np.testing.assert_array_equal(m_blk["dropped"], [0, 0, 1, 0])
    np.testing.assert_array_equal(m_blk["skipped"], [0, 0, 0, 0])
    np.testing.assert_array_equal(m_seq["dropped"], m_blk["dropped"])
    np.testing.assert_array_equal(m_seq["loss"], m_blk["loss"])
    assert _trees_equal(s_seq, s_blk)


def test_distributed_train_loop_superstep_runs_and_logs(tmp_path):
    """distributed_train_loop with K=3 over 6 steps: boundary-snapped log
    lines (2 with log_every=2 -> boundaries 3 and 6), checkpoints at
    boundaries."""
    from atomo_tpu.parallel import distributed_train_loop, make_mesh

    model, opt = _model_opt()
    mesh = make_mesh(2)
    logs = []
    state = distributed_train_loop(
        model, opt, mesh, _make_iter(), max_steps=6,
        codec=QsgdCodec(bits=4, bucket_size=128), aggregate="gather",
        log_every=2, log_fn=logs.append, seed=0, superstep=3,
        train_dir=str(tmp_path), save_freq=2,
    )
    worker_lines = [l for l in logs if l.startswith("Worker: 0, Step:")]
    assert [int(l.split("Step: ")[1].split(",")[0]) for l in worker_lines] == [3, 6]
    assert list_steps(str(tmp_path)) == [3, 6]
    assert int(jax.device_get(state.step)) == 6


# ------------------------------------------------------------ perf sweep


@pytest.mark.perf
@pytest.mark.skipif(
    os.environ.get("ATOMO_RUN_PERF") != "1",
    reason="wall-clock perf sweep; set ATOMO_RUN_PERF=1 (meaningless on a "
    "contended CI core)",
)
def test_superstep_amortizes_dispatch_walltime():
    """Opt-in sweep: the fused loop at K=8 must not be slower than K=1
    (on dispatch-dominated backends it is several times faster; on local
    CPU the win is small, so only a no-regression bound is asserted)."""
    model, opt = _model_opt()

    def wall(superstep):
        t0 = time.perf_counter()
        train_loop(
            model, opt, _make_iter(), max_steps=32, log_every=0, seed=0,
            superstep=superstep,
        )
        return time.perf_counter() - t0

    wall(1), wall(8)  # compile both programs
    t1, t8 = wall(1), wall(8)
    assert t8 <= t1 * 1.5, (t1, t8)
