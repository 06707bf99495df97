"""Anomaly-guarded stepping + retry wrapper tests (training/resilience.py;
skip-and-rescale wiring in trainer.py / parallel/replicated.py).

The policy under test: drop an anomalous replica's contribution and
re-scale the surviving average by n/kept — valid because ATOMO's estimator
is unbiased (resilience.py docstring). The psum-mode test checks the
arithmetic EXACTLY against per-shard gradients computed outside the SPMD
step (LeNet is deterministic: no dropout, no BN)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from atomo_tpu.codecs import SvdCodec
from atomo_tpu.models import get_model
from atomo_tpu.parallel.mesh import make_mesh
from atomo_tpu.parallel.replicated import (
    make_distributed_train_step,
    replicate_state,
    shard_batch,
)
from atomo_tpu.training import GuardConfig, create_state, grad_ok, with_retries
from atomo_tpu.training.trainer import make_train_step
from atomo_tpu.utils.chaos import ChaosConfig, ChaosInjector


# ---------------- grad_ok ----------------


def test_grad_ok_screens_nonfinite_and_norm():
    good = {"a": jnp.ones((3,)), "b": jnp.zeros((2, 2))}
    assert bool(grad_ok(good))
    assert not bool(grad_ok({"a": jnp.array([1.0, jnp.nan])}))
    assert not bool(grad_ok({"a": jnp.array([jnp.inf])}))
    # norm screen: ||g|| = 2 over 4 unit entries
    g = {"a": jnp.ones((4,))}
    assert bool(grad_ok(g, max_grad_norm=3.0))
    assert not bool(grad_ok(g, max_grad_norm=1.0))
    # f32 overflow in the sum of squares reads as non-finite -> dropped
    assert not bool(grad_ok({"a": jnp.full((4,), 1e30)}, max_grad_norm=1e6))


# ---------------- with_retries ----------------


def test_with_retries_recovers_and_backs_off():
    calls, slept, notes = [], [], []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("disk on fire")
        return "ok"

    import random

    wrapped = with_retries(
        flaky,
        attempts=4,
        base_delay=0.1,
        max_delay=5.0,
        on_retry=lambda i, exc: notes.append((i, str(exc))),
        sleep=slept.append,
        rng=random.Random(7),
    )
    assert wrapped() == "ok"
    assert len(calls) == 3
    # decorrelated jitter: every delay in [base, max], within the
    # decorrelated envelope (delay_i <= 3 * delay_{i-1})
    assert len(slept) == 2
    assert all(0.1 <= d <= 5.0 for d in slept)
    assert slept[1] <= 3 * max(slept[0], 0.1) + 1e-9
    assert [i for i, _ in notes] == [1, 2]


def test_with_retries_jitter_decorrelates_hosts():
    """Two hosts tripping over the same blip must NOT sleep in lockstep
    (the retry-storm fix); jitter=False restores the deterministic
    schedule for callers that need it."""
    import random

    def make(rng, jitter=True):
        slept = []
        wrapped = with_retries(
            lambda: (_ for _ in ()).throw(OSError("blip")),
            attempts=4,
            base_delay=0.1,
            sleep=slept.append,
            rng=rng,
            jitter=jitter,
        )
        with pytest.raises(OSError):
            wrapped()
        return slept

    a = make(random.Random(1))
    b = make(random.Random(2))
    assert a != b  # decorrelated across hosts
    det = make(random.Random(0), jitter=False)
    assert det == [0.1, 0.2, 0.4]  # the legacy exponential schedule


def test_with_retries_logs_retry_incidents(tmp_path):
    from atomo_tpu.utils.tracing import IncidentLog

    incidents = IncidentLog(str(tmp_path / "incidents.jsonl"))
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 2:
            raise OSError("transient")
        return "ok"

    wrapped = with_retries(
        flaky, attempts=3, sleep=lambda _: None, incidents=incidents,
        incident_cause="checkpoint_save",
    )
    assert wrapped() == "ok"
    recs = IncidentLog.read(str(tmp_path / "incidents.jsonl"))
    assert len(recs) == 1
    assert recs[0]["cause"] == "checkpoint_save"
    assert recs[0]["action"] == "retry"
    assert "transient" in recs[0]["error"]


def test_with_retries_exhausts_and_raises():
    slept = []
    wrapped = with_retries(
        lambda: (_ for _ in ()).throw(OSError("nope")),
        attempts=3,
        sleep=slept.append,
    )
    with pytest.raises(OSError):
        wrapped()
    assert len(slept) == 2


def test_with_retries_unlisted_exception_propagates_immediately():
    calls = []

    def boom():
        calls.append(1)
        raise KeyError("bug, not flake")

    with pytest.raises(KeyError):
        with_retries(boom, attempts=5, sleep=lambda s: None)()
    assert len(calls) == 1


def test_run_supervised_config_error_gives_up_immediately(tmp_path):
    """rc=CONFIG_EXIT_CODE marks a deterministic config reject: the
    supervisor must give up at once, not burn the restart budget on
    children that die identically every attempt."""
    import json
    import sys

    from atomo_tpu.training.resilience import (
        CONFIG_EXIT_CODE,
        run_supervised,
    )

    slept = []
    rc = run_supervised(
        [sys.executable, "-c", f"import sys; sys.exit({CONFIG_EXIT_CODE})"],
        max_restarts=3,
        backoff_base=0.01,
        train_dir=str(tmp_path),
        log_fn=lambda m: None,
        sleep=slept.append,
    )
    assert rc == CONFIG_EXIT_CODE
    assert slept == []  # no restart, no backoff
    recs = [
        json.loads(line)
        for line in (tmp_path / "incidents.jsonl").read_text().splitlines()
    ]
    assert len(recs) == 1
    assert recs[0]["cause"] == "config_error"
    assert recs[0]["action"] == "give_up"


def test_with_retries_rejects_zero_attempts():
    with pytest.raises(ValueError):
        with_retries(lambda: None, attempts=0)


# ---------------- single-host guarded step ----------------


def _lenet_setup(lr=0.1):
    model = get_model("lenet", 10)
    opt = optax.sgd(lr)
    rng = np.random.RandomState(0)
    images = rng.rand(8, 28, 28, 1).astype(np.float32)
    labels = rng.randint(0, 10, (8,)).astype(np.int32)
    state = create_state(model, opt, jax.random.PRNGKey(0), jnp.asarray(images))
    return model, opt, state, jnp.asarray(images), jnp.asarray(labels)


def _leaves(tree):
    return [np.asarray(jax.device_get(l)) for l in jax.tree_util.tree_leaves(tree)]


def test_single_host_guard_skips_injected_nan_step():
    model, opt, state, images, labels = _lenet_setup()
    chaos = ChaosInjector(ChaosConfig.from_spec("nan@2"))
    step = make_train_step(model, opt, guard=GuardConfig(), chaos=chaos)
    key = jax.random.PRNGKey(1)

    state1, m1 = step(state, key, images, labels)
    assert float(m1["skipped"]) == 0.0
    state2, m2 = step(state1, key, images, labels)
    # the poisoned step is skipped: params/opt state held, counter advances
    assert float(m2["skipped"]) == 1.0
    assert int(state2.step) == 2
    for a, b in zip(_leaves(state2.params), _leaves(state1.params)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(_leaves(state2.opt_state), _leaves(state1.opt_state)):
        np.testing.assert_array_equal(a, b)
    # and training continues afterwards with finite params
    state3, m3 = step(state2, key, images, labels)
    assert float(m3["skipped"]) == 0.0
    for leaf in _leaves(state3.params):
        assert np.isfinite(leaf).all()
    assert any(
        not np.array_equal(a, b)
        for a, b in zip(_leaves(state3.params), _leaves(state2.params))
    )


def test_single_host_norm_screen_drops_exploding_step():
    model, opt, state, images, labels = _lenet_setup()
    chaos = ChaosInjector(ChaosConfig.from_spec("explode@1"))
    step = make_train_step(
        model, opt, guard=GuardConfig(max_grad_norm=1e4), chaos=chaos
    )
    state1, m1 = step(state, jax.random.PRNGKey(1), images, labels)
    assert float(m1["skipped"]) == 1.0  # finite but enormous -> screened
    for a, b in zip(_leaves(state1.params), _leaves(state.params)):
        np.testing.assert_array_equal(a, b)


def test_single_host_unguarded_step_reports_not_skipped():
    model, opt, state, images, labels = _lenet_setup()
    step = make_train_step(model, opt)
    _, m = step(state, jax.random.PRNGKey(1), images, labels)
    assert float(m["skipped"]) == 0.0


# ---------------- distributed skip-and-rescale ----------------


def _per_shard_grads(model, params, images, labels, n_shards):
    """Oracle: each replica's raw gradient, computed outside the SPMD step."""
    from atomo_tpu.training.trainer import cross_entropy_loss

    def loss_fn(p, im, lb):
        return cross_entropy_loss(model.apply({"params": p}, im), lb)

    per = len(images) // n_shards
    return [
        jax.grad(loss_fn)(params, images[i * per:(i + 1) * per],
                          labels[i * per:(i + 1) * per])
        for i in range(n_shards)
    ]


def test_distributed_psum_skip_and_rescale_exact():
    """Replica 0's NaN contribution is dropped; the update must equal
    params - lr * mean(g1, g2, g3) exactly (surviving average re-scaled by
    n/kept = 4/3 of the masked sum/4... i.e. sum(g1..g3)/3)."""
    lr = 0.1
    model, opt, state0, images, labels = _lenet_setup(lr)
    # host snapshot first: the step donates its state input, and the
    # replicated copy may alias these buffers
    params_host = jax.device_get(state0.params)
    mesh = make_mesh(4)
    state = replicate_state(mesh, state0)
    chaos = ChaosInjector(ChaosConfig.from_spec("nan@1"))
    step = make_distributed_train_step(
        model, opt, mesh, codec=None, aggregate="psum",
        guard=GuardConfig(), chaos=chaos,
    )
    gi, gl = shard_batch(mesh, images, labels)
    state1, m = step(state, jax.random.PRNGKey(1), gi, gl)
    assert float(m["dropped"]) == 1.0
    assert float(m["skipped"]) == 0.0
    assert np.isfinite(float(m["loss"]))

    g = _per_shard_grads(model, params_host, images, labels, 4)
    mean_surv = jax.tree_util.tree_map(
        lambda a, b, c: (a + b + c) / 3.0, g[1], g[2], g[3]
    )
    expected = jax.tree_util.tree_map(
        lambda p, m_: p - lr * m_, params_host, mean_surv
    )
    for got, want in zip(_leaves(state1.params), _leaves(expected)):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_distributed_gather_guard_rescales_and_stays_finite():
    model, opt, state0, images, labels = _lenet_setup()
    mesh = make_mesh(4)
    state_host = jax.device_get(state0)  # donation-proof template
    chaos = ChaosInjector(ChaosConfig.from_spec("inf@1"))

    def run():
        step = make_distributed_train_step(
            model, opt, mesh, codec=SvdCodec(rank=2), aggregate="gather",
            guard=GuardConfig(), chaos=chaos,
        )
        gi, gl = shard_batch(mesh, images, labels)
        return step(replicate_state(mesh, state_host), jax.random.PRNGKey(1), gi, gl)

    s1, m1 = run()
    assert float(m1["dropped"]) == 1.0 and float(m1["skipped"]) == 0.0
    for leaf in _leaves(s1.params):
        assert np.isfinite(leaf).all()
    # the surviving replicas DID move the params
    assert any(
        not np.array_equal(a, b)
        for a, b in zip(_leaves(s1.params), _leaves(state_host.params))
    )
    # deterministic: the chaos plan and codec keys are reproducible
    s2, m2 = run()
    for a, b in zip(_leaves(s1.params), _leaves(s2.params)):
        np.testing.assert_array_equal(a, b)


def test_distributed_all_replicas_bad_skips_step():
    model, opt, state0, images, labels = _lenet_setup()
    params_host = jax.device_get(state0.params)
    mesh = make_mesh(4)
    state = replicate_state(mesh, state0)
    chaos = ChaosInjector(ChaosConfig.from_spec("nan@1*"))  # every replica
    step = make_distributed_train_step(
        model, opt, mesh, codec=SvdCodec(rank=2), aggregate="gather",
        guard=GuardConfig(), chaos=chaos,
    )
    gi, gl = shard_batch(mesh, images, labels)
    s1, m = step(state, jax.random.PRNGKey(1), gi, gl)
    assert float(m["skipped"]) == 1.0
    assert float(m["dropped"]) == 4.0
    assert int(s1.step) == 1  # counter advances; weights do not
    for got, want in zip(
        _leaves(s1.params), [np.asarray(l) for l in jax.tree_util.tree_leaves(params_host)]
    ):
        np.testing.assert_array_equal(got, want)


def test_distributed_guard_masks_rejected_norms_from_detector_series():
    """A guard-masked replica's huge-but-finite norm must not enter the
    detector's grad_norm series: rung 1 already contained the fault, and
    an unmasked pmean (1e12-amplified outlier / 4) would fire
    grad_norm_trend rollbacks on a run the guard was handling."""
    model, opt, state0, images, labels = _lenet_setup()
    mesh = make_mesh(4)
    state_host = jax.device_get(state0)

    def run(chaos_spec):
        chaos = (
            ChaosInjector(ChaosConfig.from_spec(chaos_spec))
            if chaos_spec
            else None
        )
        step = make_distributed_train_step(
            model, opt, mesh, codec=None, aggregate="psum",
            guard=GuardConfig(max_grad_norm=1e4), chaos=chaos,
            track_grad_norm=True,
        )
        gi, gl = shard_batch(mesh, images, labels)
        _, m = step(
            replicate_state(mesh, state_host), jax.random.PRNGKey(1), gi, gl
        )
        return m

    clean = run(None)
    faulted = run("explode@1")
    assert float(faulted["dropped"]) == 1.0
    assert float(faulted["skipped"]) == 0.0
    # healthy-only mean: same scale as the clean series, nowhere near the
    # amplified outlier a plain pmean would admit
    assert np.isfinite(float(faulted["grad_norm"]))
    assert float(faulted["grad_norm"]) < 10.0 * float(clean["grad_norm"])


def test_hierarchical_guard_drops_poisoned_inner_group():
    model, opt, state0, images, labels = _lenet_setup()
    mesh = make_mesh(4, axes=(("dp", 2), ("ici", 2)))
    state = replicate_state(mesh, state0)
    chaos = ChaosInjector(ChaosConfig.from_spec("nan@1"))  # chip 0 -> group 0
    step = make_distributed_train_step(
        model, opt, mesh, codec=SvdCodec(rank=2), aggregate="hierarchical",
        inner_axis="ici", guard=GuardConfig(), chaos=chaos,
    )
    gi, gl = shard_batch(mesh, images, labels, axis=("dp", "ici"))
    s1, m = step(state, jax.random.PRNGKey(1), gi, gl)
    # the unit of drop is the inner group (its dense pmean is poisoned)
    assert float(m["dropped"]) == 1.0
    assert float(m["skipped"]) == 0.0
    for leaf in _leaves(s1.params):
        assert np.isfinite(leaf).all()


# ---------------- divergence detector ----------------


def _det_cfg(**kw):
    from atomo_tpu.training import DetectorConfig

    base = dict(window=6, zmax=3.0, patience=2, min_history=4)
    base.update(kw)
    return DetectorConfig(**base)


def _scan(cfg, losses, skipped=None, gns=None):
    from atomo_tpu.training import DetectorState, detector_scan

    return detector_scan(cfg, DetectorState(), losses, skipped, gns)


def test_detector_flags_sustained_loss_excursion():
    losses = [2.3, 2.2, 2.1, 2.0, 1.9, 1.9, 1.8, 1.8, 1.7, 50.0, 50.0, 50.0]
    st, step, reason = _scan(_det_cfg(), losses)
    assert reason == "loss_zscore"
    assert step == 11  # patience 2: the second hot step alarms


def test_detector_ignores_single_spike_and_downward_jumps():
    cfg = _det_cfg()
    base = [2.0, 2.1, 1.9, 2.05, 1.95, 2.0, 2.1, 1.9]  # noisy, sane
    # one bad batch is noise, not divergence (patience > 1 resets)
    _, step, reason = _scan(cfg, base + [50.0] + base[:6])
    assert reason is None and step is None
    # a big IMPROVEMENT must never alarm (one-sided z)
    _, step, reason = _scan(cfg, base + [0.01] * 6)
    assert reason is None


def test_detector_nonfinite_loss_alarms_immediately():
    _, step, reason = _scan(_det_cfg(), [2.0] * 5 + [float("nan")])
    assert reason == "nonfinite_loss" and step == 6
    # ...but a guard-SKIPPED step's loss is a rejected update, not an alarm
    _, step, reason = _scan(
        _det_cfg(), [2.0] * 5 + [float("nan")], skipped=[0] * 5 + [1]
    )
    assert reason is None


def test_detector_skip_rate_alarm():
    cfg = _det_cfg(window=4, skip_max=0.5)
    losses = [2.0] * 12
    skipped = [0, 0, 0, 0] + [1] * 8  # the guard starts dropping everything
    _, step, reason = _scan(cfg, losses, skipped)
    assert reason == "skip_rate"


def test_detector_grad_norm_trend_alarm():
    cfg = _det_cfg()
    losses = [2.0] * 12  # loss still looks fine (the spike drill regime)
    gns = [1.0] * 8 + [100.0] * 4
    _, step, reason = _scan(cfg, losses, None, gns)
    assert reason == "grad_norm_trend"
    assert step == 10  # patience 2 over the trend counter


def test_detector_decisions_partition_invariant():
    """The acceptance contract: folding the same per-step series in
    superstep blocks of ANY size gives identical states and identical
    alarm decisions."""
    import numpy as np

    from atomo_tpu.training import DetectorState, detector_scan

    rng = np.random.default_rng(0)
    losses = list(2.5 - 0.05 * np.arange(20) + 0.05 * rng.standard_normal(20))
    losses[14:] = [60.0, 61.0, 62.0, 63.0, 64.0, 65.0]
    skips = [0.0] * 20
    gns = list(1.0 + 0.1 * rng.standard_normal(20))
    cfg = _det_cfg()

    def run(k):
        st = DetectorState()
        step = 1
        for i in range(0, len(losses), k):
            st, alarm_step, reason = detector_scan(
                cfg, st, losses[i:i + k], skips[i:i + k], gns[i:i + k],
                first_step=step,
            )
            if reason is not None:
                return st, alarm_step, reason
            step += len(losses[i:i + k])
        return st, None, None

    ref = run(1)
    for k in (2, 3, 4, 7, 20):
        assert run(k) == ref, f"partition K={k} diverged from K=1"
    assert ref[2] == "loss_zscore"


def test_detector_skipped_step_grad_norm_stays_out_of_baseline():
    """A guard-REJECTED gradient's norm must not enter gn_ref: one
    screened (finite, huge) explosion would otherwise desensitize the
    trend alarm for the rest of the run."""
    from atomo_tpu.training import DetectorState, detector_update

    cfg = _det_cfg(grad_ratio=10.0)
    st = DetectorState()
    for _ in range(5):  # healthy steps establish gn_ref ~ 1
        st, a = detector_update(cfg, st, 2.0, 0.0, grad_norm=1.0)
        assert a is None
    st, a = detector_update(cfg, st, 2.0, 1.0, grad_norm=1e12)  # skipped
    assert a is None
    assert st.gn_ref < 10.0  # baseline unpoisoned
    for _ in range(cfg.patience):  # genuine sustained 100x trend
        st, a = detector_update(cfg, st, 2.0, 0.0, grad_norm=100.0)
    assert a == "grad_norm_trend"


def test_remedy_scale_ramp():
    from atomo_tpu.training import RemedyConfig
    from atomo_tpu.training.resilience import remedy_scale

    r = RemedyConfig(start_step=10, window=5, floor=0.2)
    assert float(remedy_scale(r, 10)) == pytest.approx(0.2)
    assert float(remedy_scale(r, 12)) == pytest.approx(0.2 + 0.8 * 2 / 5)
    assert float(remedy_scale(r, 15)) == pytest.approx(1.0)
    assert float(remedy_scale(r, 100)) == pytest.approx(1.0)  # clamped


# ---------------- divergence doctor ----------------


def _ckpt_state():
    from atomo_tpu.training.trainer import TrainState

    return TrainState(
        step=jnp.int32(0), params={"w": jnp.ones((2,))},
        batch_stats={}, opt_state={},
    )


def test_detector_config_rejects_degenerate_knobs():
    """window=1 makes the EMA variance identically zero (z-alarm can never
    fire) and window<=0 drives the EMAs outside their domains — reject
    instead of silently disarming the feature the user asked for."""
    from atomo_tpu.training.resilience import DetectorConfig

    for bad in (dict(window=1), dict(window=0), dict(window=-3),
                dict(patience=0), dict(zmax=0.0), dict(min_history=-1)):
        with pytest.raises(ValueError):
            DetectorConfig(**bad)
    DetectorConfig(window=2, patience=1, min_history=0)  # minimal sane


def test_diverge_conflict_matrix():
    """One compatibility matrix serves the CLI and both train loops."""
    from atomo_tpu.training.resilience import diverge_conflict

    # saves disabled: no checkpoint can ever earn a healthy tag
    assert "cadence" in diverge_conflict(
        "skip", train_dir="/t", save_freq=0
    )
    ok = dict(train_dir="/tmp/x", codec=object())
    assert diverge_conflict("skip", **ok) is None
    assert diverge_conflict("densify", **ok) is None
    assert "train_dir" in diverge_conflict("skip", train_dir="")
    assert "zero1" in diverge_conflict("skip", train_dir="/t", zero1=True)
    assert "compressing" in diverge_conflict("densify", train_dir="/t")
    for kw, frag in [
        (dict(overlap="delayed"), "delayed"),
        (dict(aggregate="hierarchical"), "hierarchical"),
        (dict(num_aggregate=2), "num-aggregate"),
    ]:
        assert frag in diverge_conflict("densify", **ok, **kw)
        # the densify-only conflicts must not block skip/rewarm
        assert diverge_conflict("rewarm", **ok, **kw) is None
    # keep-last-K shorter than the detector window: no checkpoint would
    # ever survive long enough to earn the healthy tag a rollback needs
    assert "keep-ckpts" in diverge_conflict(
        "skip", **ok, keep_ckpts=1, save_freq=10, window=16
    )
    # keep*freq >= window is fine, as is keep=0 (keep everything)
    assert diverge_conflict(
        "skip", **ok, keep_ckpts=2, save_freq=8, window=16
    ) is None
    assert diverge_conflict(
        "skip", **ok, keep_ckpts=0, save_freq=2, window=16
    ) is None
    assert "cadence" in diverge_conflict(
        "skip", **ok, keep_ckpts=1, save_freq=0, window=16
    )  # saves disabled beats the retention check: nothing to retain


def test_doctor_healthy_tags_and_rollback_planning(tmp_path):
    from atomo_tpu.training import (
        DivergeConfig,
        DivergenceDoctor,
        DivergenceError,
        latest_healthy_step,
        list_steps,
        save_checkpoint,
    )

    state = _ckpt_state()
    cfg = DivergeConfig(
        remedy="skip", detector=_det_cfg(window=4), max_rollbacks=1
    )
    doc = DivergenceDoctor(cfg, str(tmp_path), log_fn=lambda s: None)
    # saves at 2 and 4; sane losses through step 8 clear save@2 and save@4
    for s in (2, 4, 8):
        save_checkpoint(str(tmp_path), state, s)
        doc.note_save(s)
    base = [2.0, 2.1, 1.9, 2.05, 1.95, 2.0, 2.1, 1.9]  # noisy, sane
    a, r = doc.observe_block(1, base)
    assert (a, r) == (None, None)
    assert latest_healthy_step(str(tmp_path)) == 4  # 8+4 hasn't cleared
    # divergence at 9..10: rollback targets the newest HEALTHY step and
    # prunes the diverged timeline above it
    a, r = doc.observe_block(9, [90.0, 95.0])
    assert r == "loss_zscore"
    plan = doc.plan_rollback(a, r)
    assert plan.target == 4
    assert plan.generation == 1
    assert list_steps(str(tmp_path)) == [2, 4]  # step-8 corpse pruned
    # budget (max_rollbacks=1) is now spent: next alarm raises
    a, r = doc.observe_block(5, base[:6] + [90.0, 95.0])
    assert r is not None
    with pytest.raises(DivergenceError):
        doc.plan_rollback(a, r)


def test_alarm_block_still_confirms_pre_alarm_saves(tmp_path):
    """A save whose window cleared BEFORE the alarm step must earn its tag
    even when the alarm lands inside the same superstep block — the
    rollback target must not depend on the block partition K."""
    from atomo_tpu.training import (
        DivergeConfig,
        DivergenceDoctor,
        latest_healthy_step,
        save_checkpoint,
    )

    cfg = DivergeConfig(
        remedy="skip", detector=_det_cfg(window=4), max_rollbacks=1
    )
    base = [2.0, 2.1, 1.9, 2.05, 1.95, 2.0, 2.1, 1.9]

    def run(k):
        d = str(tmp_path / f"k{k}")
        import os

        os.makedirs(d, exist_ok=True)
        doc = DivergenceDoctor(cfg, d, log_fn=lambda s: None)
        save_checkpoint(d, _ckpt_state(), 8)
        doc.note_save(8)
        series = base + base[:4] + [90.0, 95.0]  # sane 1..12, alarm 13..14
        step = 1
        for i in range(0, len(series), k):
            a, r = doc.observe_block(step, series[i:i + k])
            if r is not None:
                return latest_healthy_step(d), doc.plan_rollback(a, r).target
            step += len(series[i:i + k])
        return latest_healthy_step(d), None

    ref = run(1)
    assert ref[0] == 8 and ref[1] == 8  # save@8 cleared at step 12, pre-alarm
    for k in (2, 7, 14):
        assert run(k) == ref, f"partition K={k} changed the rollback target"


def test_doctor_no_healthy_checkpoint_rolls_back_to_init(tmp_path):
    from atomo_tpu.training import DivergeConfig, DivergenceDoctor

    doc = DivergenceDoctor(
        DivergeConfig(remedy="skip", detector=_det_cfg()),
        str(tmp_path), log_fn=lambda s: None,
    )
    a, r = doc.observe_block(
        1, [2.0, 2.1, 1.9, 2.05, 1.95, 2.0, 2.1, 1.9, 90.0, 95.0]
    )
    assert r == "loss_zscore"
    plan = doc.plan_rollback(a, r)
    assert plan.target == 0  # nothing healthy: from scratch


def test_confirm_never_tags_a_pruned_checkpoint(tmp_path):
    """A pending save whose file retention already deleted must be dropped
    UNTAGGED — an orphaned sidecar would let a future checkpoint reusing
    the step number inherit a health verdict it never earned."""
    import os

    from atomo_tpu.training import DivergeConfig, DivergenceDoctor
    from atomo_tpu.training.checkpoint import healthy_marker_path

    doc = DivergenceDoctor(
        DivergeConfig(remedy="skip", detector=_det_cfg(window=2)),
        str(tmp_path), log_fn=lambda s: None,
    )
    doc.note_save(2)  # never actually written (or retention-pruned)
    a, r = doc.observe_block(1, [2.0, 2.1, 1.9, 2.05, 1.95, 2.0])
    assert (a, r) == (None, None)
    assert doc.pending == []  # window cleared: no longer pending...
    assert not os.path.exists(healthy_marker_path(str(tmp_path), 2))


def test_rewarm_remedy_scales_the_update_in_graph():
    """make_train_step(remedy=...): at the ramp floor the applied update
    is exactly floor * the unremedied update (plain SGD: update = -lr*g)."""
    from atomo_tpu.training import RemedyConfig

    model, opt, state, images, labels = _lenet_setup()
    base = make_train_step(model, opt)
    remedied = make_train_step(
        model, opt, remedy=RemedyConfig(start_step=0, window=10, floor=0.25)
    )
    key = jax.random.PRNGKey(1)
    s_base, _ = base(state, key, images, labels)
    s_rem, _ = remedied(state, key, images, labels)
    for p0, pb, pr in zip(
        _leaves(state.params), _leaves(s_base.params), _leaves(s_rem.params)
    ):
        # rtol absorbs the f32 cancellation in (p_after - p_before); the
        # structural claim is the exact 0.25x update ratio
        np.testing.assert_allclose(pr - p0, 0.25 * (pb - p0), rtol=5e-3,
                                   atol=1e-7)
