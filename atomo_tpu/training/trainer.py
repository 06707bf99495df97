"""Single-host trainer: the reference `single_machine.py` / `NN_Trainer`
equivalent, with optional in-loop gradient compression.

Reference behavior (src/nn_ops.py:101-189): per batch zero_grad -> forward ->
cross-entropy -> backward -> optimizer.step -> prec@1/5 log; per epoch
validate. This trainer adds the 'compression on, comm off' mode (SURVEY.md §7
build-order step 4): each step's gradient is encoded and decoded in-graph
before the optimizer update, so codec effects on convergence are measurable
without a mesh — the oracle against which distributed runs are compared
(§4 'single_machine as correctness baseline').

Everything (forward, backward, augment, encode, decode, update) is one
compiled XLA program per step; the host loop only feeds batches and reads
metrics.
"""

from __future__ import annotations

import dataclasses
import os
import time
from functools import partial
from typing import Any, Optional

import flax.struct
import jax
import jax.numpy as jnp
import optax
from flax.core import FrozenDict

from atomo_tpu.codecs import decode_tree, encode_tree
from atomo_tpu.data.pipeline import augment_batch
from atomo_tpu.obs.recorder import emit_worker_line
from atomo_tpu.utils.metrics import StepMetrics, Timer, accuracy
from atomo_tpu.utils.tracing import (
    BLOCK,
    BOUNDARY,
    DISPATCH,
    FEED_START,
    FEED_TAKE,
    FETCH,
    INIT_STATE,
    NEXT_BATCH,
    PROFILE_STEPS,
    STEP,
    ProfileWindow,
    clear_iterations,
    named_phase,
    span,
)


@dataclasses.dataclass
class TrainConfig:
    augment: bool = False
    compress_in_loop: bool = False
    label_smoothing: float = 0.0


@flax.struct.dataclass
class TrainState:
    step: jax.Array
    params: Any
    batch_stats: Any
    opt_state: Any


def cross_entropy_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()


def cast_params(params, compute_dtype):
    """Mixed-precision entry cast of the parameter tree: floating leaves to
    ``compute_dtype`` (bf16 fwd/bwd on the MXU); the f32 master params stay
    outside. The single contract shared by every loss function — CV paths
    also cast their input images (cast_compute_inputs), token-id paths use
    this alone (integer inputs have nothing to cast)."""
    return jax.tree_util.tree_map(
        lambda a: a.astype(compute_dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a,
        params,
    )


def cast_compute_inputs(params, images, compute_dtype):
    """cast_params plus the image batch (see cast_params)."""
    return cast_params(params, compute_dtype), images.astype(compute_dtype)


def cast_compute_outputs(logits, new_stats):
    """Mixed-precision exit cast: loss/softmax and BN running stats in f32."""
    return logits.astype(jnp.float32), jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), new_stats
    )


def create_state(model, optimizer, rng, sample_input) -> TrainState:
    with span(INIT_STATE):  # the initial state of both loops, eager compiles included
        variables = model.init(
            {"params": rng, "dropout": jax.random.PRNGKey(0)}, sample_input, train=False
        )
        params = variables["params"]
        batch_stats = variables.get("batch_stats", FrozenDict())
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            batch_stats=batch_stats,
            opt_state=optimizer.init(params),
        )


def snapshot_state(state) -> "TrainState":
    """Host-side deep copy of a TrainState — the donation-aliasing guard.

    ``make_train_step(..., superstep=K)`` and
    ``make_distributed_train_step`` DONATE their state argument: after the
    call, the caller's reference points at deleted device buffers. On the
    TPU that is literal under jax 0.9.0 — a later read raises "Array has
    been deleted" (the loops only ever read the state a step RETURNED;
    chip_smoke.py's save/eval/resume phases are the check). On the CPU
    backend ``replicate_state``/``jax.device_put`` can ALIAS a host
    source buffer instead of copying, so even a "different" pre-step
    reference may share memory with the donated one. Tests (and any debug
    code) that need pre-step values must snapshot through
    ``jax.device_get`` BEFORE stepping — this helper additionally forces
    a real copy of every leaf, because on the CPU backend device_get
    itself can return views of the live buffers."""
    import numpy as np

    return jax.tree_util.tree_map(
        lambda a: np.array(a, copy=True), jax.device_get(state)
    )


def make_train_step(model, optimizer, codec=None, augment: bool = False,
                    compute_dtype=None, guard=None, chaos=None,
                    superstep: int = 1, remedy=None,
                    track_grad_norm: bool = False,
                    track_quality: bool = False):
    """Build the jitted single-host train step.

    codec != None applies encode->decode to the gradient pytree in-graph
    (per-leaf folded PRNG keys) before the optimizer — the compression
    study path.

    compute_dtype (e.g. jnp.bfloat16) selects mixed-precision compute:
    master params, optimizer state, gradients, loss, and BN running stats
    stay float32; the forward/backward matmuls and convs run in the given
    dtype — the MXU's native bf16 path, a TPU capability the all-f32
    CPU-torch reference has no analogue for. None = full f32.

    guard (resilience.GuardConfig) arms in-graph anomaly screening: a step
    whose raw gradient is non-finite (or beyond guard.max_grad_norm) is
    skipped — params, optimizer state and BN stats hold their pre-step
    values, the step counter still advances (the batch was consumed), and
    metrics["skipped"] is 1. Single host has no surviving contributions to
    rescale; skipping outright is the n=kept=0 case of the distributed
    skip-and-rescale policy (resilience.py rationale).

    chaos (utils.chaos.ChaosInjector) bakes the configured gradient faults
    into the compiled step — test/validation hook, zero-cost when None.

    remedy (resilience.RemedyConfig) applies the divergence doctor's
    ``rewarm`` ramp: the post-codec gradient is pre-scaled by
    ``remedy_scale(remedy, state.step)`` (an in-graph function of the
    carried step counter, so superstep partitions agree bitwise). None
    (default) adds no ops — the program is unchanged.

    track_grad_norm adds ``metrics["grad_norm"]`` (global L2 of the raw
    post-chaos gradient) for the divergence detector's trend counter; off
    (default) leaves the metrics pytree — and therefore the compiled
    program — exactly as before.

    track_quality (``--obs-quality``; needs a codec) adds the in-graph
    per-layer estimator-quality probes (obs.quality.quality_probe):
    ``metrics["q_err2"]``/``metrics["q_rel"]`` are (L,) per-leaf series
    of this step's encode error. Off (default) the program is
    byte-identical (lowered-HLO tested) and on only ADDS metric outputs,
    so trajectories are bit-identical armed vs off.

    superstep > 1 returns the FUSED variant: one jitted program that runs
    ``superstep`` full optimizer steps under a single ``lax.scan``
    (amortizing per-dispatch host cost — see README "Performance"). Call it with ``images``/
    ``labels`` carrying a leading (K,) in-block step axis; it returns
    ``(state, metrics)`` where every metrics leaf is the per-step series
    stacked to shape (K,). Per-step RNG folding is unchanged (keys fold
    from the in-carry ``state.step``), so K fused steps are bit-identical
    to K sequential K=1 steps on the same data; the guard's skip logic
    lives in the scan carry, so an anomalous step inside the block holds
    state exactly as the sequential path would. DONATION: the fused
    variant donates the state argument — the caller's reference is
    invalidated by the call; snapshot via :func:`snapshot_state` first if
    pre-step values are needed (the CPU backend's device_put aliasing
    makes any shallower copy unsafe). Compile cost: the scan length is baked into
    the compiled program, so a run sees at most TWO compiles of this
    variant — the K-block shape plus one shorter tail block when
    (max_steps - start) % K != 0; padding the tail to K was rejected as
    it would complicate the resume-replay data contract for a one-off
    cost.
    """
    from atomo_tpu.training.resilience import grad_ok, select_state, zero_if

    if superstep < 1:
        raise ValueError(f"superstep must be >= 1, got {superstep}")
    if track_quality and codec is None:
        raise ValueError(
            "track_quality probes the codec's estimator error; dense "
            "training has no estimator to probe — drop one"
        )

    def loss_fn(params, batch_stats, images, labels, dropout_key):
        if compute_dtype is not None:
            params, images = cast_compute_inputs(params, images, compute_dtype)
        variables = {"params": params}
        has_bn = bool(jax.tree_util.tree_leaves(batch_stats))
        if has_bn:
            variables["batch_stats"] = batch_stats
        out = model.apply(
            variables,
            images,
            train=True,
            rngs={"dropout": dropout_key},
            mutable=["batch_stats"] if has_bn else [],
        )
        logits, mutated = out
        new_stats = mutated.get("batch_stats", batch_stats)
        if compute_dtype is not None:
            logits, new_stats = cast_compute_outputs(logits, new_stats)
        loss = cross_entropy_loss(logits, labels)
        return loss, (logits, new_stats)

    def step_core(state: TrainState, key: jax.Array, images, labels):
        k_aug, k_drop, k_codec = jax.random.split(jax.random.fold_in(key, state.step), 3)
        if augment:
            images = augment_batch(k_aug, images)
        # device scopes (named_phase): metadata only, read by `report timeline`
        with named_phase("forward_backward"):
            (loss, (logits, new_stats)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(state.params, state.batch_stats, images, labels, k_drop)

        if chaos is not None:
            grads = chaos.inject_grads(grads, state.step + 1)
        gnorm = None
        if track_grad_norm:
            from atomo_tpu.training.resilience import global_sq_norm

            # raw (pre-screen, pre-codec) global L2: the detector's trend
            # signal must see what the screen saw, not what survived it
            gnorm = jnp.sqrt(global_sq_norm(grads))
        ok = None
        if guard is not None:
            ok = grad_ok(grads, guard.max_grad_norm)
            # keep non-finite values out of the codec/optimizer arithmetic;
            # the skipped step's outputs are discarded below regardless
            grads = zero_if(~ok, grads)

        msg_bytes = 0
        qm = None
        if codec is not None:
            with named_phase("encode"):
                payloads, stats = encode_tree(codec, k_codec, grads)
            if track_quality:
                from atomo_tpu.obs.quality import quality_probe

                # per-layer ||decode(encode(g)) - g||^2 of THIS encode —
                # the estimator-variance feed; off adds zero ops
                qm = quality_probe(codec, payloads, grads)
            with named_phase("decode"):
                grads = decode_tree(codec, payloads, grads)
            msg_bytes = stats.payload_bytes

        if remedy is not None:
            from atomo_tpu.training.resilience import apply_remedy

            grads = apply_remedy(remedy, state.step, grads)
        with named_phase("update"):
            updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
        skipped = jnp.float32(0.0)
        if ok is not None:
            new_params = select_state(ok, new_params, state.params)
            new_opt = select_state(ok, new_opt, state.opt_state)
            new_stats = select_state(ok, new_stats, state.batch_stats)
            skipped = 1.0 - ok.astype(jnp.float32)
        prec1, prec5 = accuracy(logits, labels)
        metrics = {
            "loss": loss,
            "prec1": prec1,
            "prec5": prec5,
            "msg_bytes": jnp.asarray(msg_bytes, jnp.int32),
            "skipped": skipped,
        }
        if gnorm is not None:
            metrics["grad_norm"] = gnorm
        if qm is not None:
            metrics.update(qm)
        return (
            TrainState(
                step=state.step + 1,
                params=new_params,
                batch_stats=new_stats,
                opt_state=new_opt,
            ),
            metrics,
        )

    if superstep == 1:
        return jax.jit(step_core)

    @partial(jax.jit, donate_argnums=(0,))
    def train_superstep(state: TrainState, key: jax.Array, images, labels):
        # per-step keys fold from the in-carry state.step, so the scan body
        # IS the sequential step — the fusion only removes dispatches
        def body(st, xs):
            return step_core(st, key, xs[0], xs[1])

        return jax.lax.scan(body, state, (images, labels))

    return train_superstep


def make_eval_step(model):
    @jax.jit
    def eval_step(state: TrainState, images, labels):
        variables = {"params": state.params}
        if jax.tree_util.tree_leaves(state.batch_stats):
            variables["batch_stats"] = state.batch_stats
        logits = model.apply(variables, images, train=False)
        loss = cross_entropy_loss(logits, labels)
        prec1, prec5 = accuracy(logits, labels)
        return {"loss": loss, "prec1": prec1, "prec5": prec5}

    return eval_step


def evaluate(model, state: TrainState, test_iter) -> dict[str, float]:
    """Full-test-set metrics (the reference validate, nn_ops.py:171-189)."""
    eval_step = make_eval_step(model)
    totals: dict[str, float] = {"loss": 0.0, "prec1": 0.0, "prec5": 0.0}
    n = 0
    for images, labels in test_iter.epoch():
        m = eval_step(state, jnp.asarray(images), jnp.asarray(labels))
        bs = images.shape[0]
        for k_ in totals:
            totals[k_] += float(m[k_]) * bs
        n += bs
    return {k_: v / max(n, 1) for k_, v in totals.items()}


def train_loop(
    model,
    optimizer,
    train_iter,
    test_iter=None,
    *,
    codec=None,
    augment: bool = False,
    max_steps: int = 100,
    eval_freq: int = 0,
    seed: int = 0,
    train_dir: Optional[str] = None,
    save_freq: int = 0,
    resume: bool = False,
    compress_ckpt: bool = True,
    log_fn=print,
    log_every: int = 1,
    compute_dtype=None,
    guard=None,
    chaos=None,
    health_timeout: float = 0.0,
    on_health_failure=None,
    keep_ckpts: int = 0,
    superstep: int = 1,
    diverge=None,
    tuner=None,
    track_quality: bool = False,
    recorder=None,
    profile_dir: Optional[str] = None,
) -> TrainState:
    """The reference train_and_validate loop (nn_ops.py:123-169), jitted,
    plus working checkpoint/resume (gap §5.4) and the fault-tolerance
    stack: anomaly-guarded stepping (``guard``), deterministic fault
    injection (``chaos``), a heartbeat watchdog (``health_timeout`` > 0,
    ``on_health_failure`` pluggable), retry-wrapped checkpoint IO, and
    keep-last-K retention (``keep_ckpts``).

    Resume determinism: on resume the data stream is fast-forwarded past
    the ``start_step`` batches the interrupted run consumed, so a
    kill→restart→resume run replays the exact batch sequence of an
    uninterrupted one (host-side numpy indexing — cheap relative to a
    step). ``chaos`` defaults to the ATOMO_CHAOS env config so subprocess
    harnesses inject faults without plumbing.

    ``superstep`` > 1 switches to fused block execution: K optimizer steps
    per dispatch under one ``lax.scan`` (make_train_step's fused variant),
    data fed as device-resident (K, batch, ...) blocks with the next
    block's transfer double-buffered behind the current block's compute,
    and metrics fetched ONCE per block. Host-side cadence — log lines,
    eval, checkpoints, watchdog beats, chaos kill/sleep — is evaluated at
    superstep boundaries: a cadence point crossed inside a block fires at
    the block's final step (checkpoint steps snap to boundaries).
    Trajectories are bit-identical to K=1 (per-step RNG folds from the
    carried step counter; the data stream is index-determined), including
    across kill→restart→resume at a step that is not a multiple of K —
    the resumed run simply starts a fresh block at checkpoint_step+1.
    K=1 preserves the original per-step loop exactly.

    ``diverge`` (resilience.DivergeConfig) arms the divergence doctor:
    the per-step loss/skip/grad-norm series feeds a windowed detector
    (one scalar fetch per step in the per-step loop — the price of
    surveillance; the superstep loop's existing one-fetch-per-block
    amortizes it away), checkpoints earn a ``healthy`` tag only after the
    detector window clears past them, and an alarm rolls the run back to
    the newest healthy checkpoint, replays the data stream, and applies
    the configured remedy — with the chaos generation bumped so
    step-targeted faults do not re-fire on the replay. Budget exhaustion
    raises resilience.DivergenceError (the CLI maps it to
    ROLLBACK_EXIT_CODE for the run-level supervisor).

    ``tuner`` (tuning.autopilot.OnlineRetuner) feeds the per-step
    wall-time series to the step-time drift detector (resilience
    rung 0.5). A single device has no exchange to re-pick, so the
    single-host loop runs the tuner observe-only: sustained drift is
    recorded to ``incidents.jsonl`` at the next checkpoint boundary, the
    config is kept. Costs one scalar fetch per step in the per-step loop
    (the doctor's surveillance price); the superstep loop amortizes it
    into the block's one fetch.

    ``recorder`` (obs.recorder.FlightRecorder) arms the flight recorder:
    one ``metrics.jsonl`` record per step (per-step shares per superstep
    block), pruned in lockstep with the checkpoint timeline on rollback.
    None (default) adds zero device ops — the programs and the stdout
    log are byte-identical. ``track_quality`` arms the in-graph
    per-layer estimator-quality probes (see make_train_step).

    ``profile_dir`` captures a jax.profiler trace of the iterations
    ``distributed_train_loop`` would (steps start+2..start+4, or the
    second superstep block): the trace ``report timeline`` reads. Every
    iteration is also recorded as host spans (utils.tracing.span: parent
    ``step`` or ``block``, children at the boundaries where the host's
    work happens), with or without a trace."""
    from atomo_tpu.training.checkpoint import latest_step, load_checkpoint
    from atomo_tpu.training.resilience import (
        SUPERVISED_ENV,
        DivergenceDoctor,
        RecoveryRig,
        diverge_conflict,
        heartbeat_watchdog,
        resolve_chaos,
        retrying_saver,
    )
    from atomo_tpu.utils.tracing import IncidentLog

    chaos = resolve_chaos(chaos)
    if chaos is not None:
        chaos.maybe_die_crashloop()  # crashloop@M: attempt-keyed death
    sample_images, _ = next(iter(train_iter.epoch()))
    state = create_state(
        model, optimizer, jax.random.PRNGKey(seed), jnp.asarray(sample_images)
    )
    start_step = 0
    if resume and train_dir and latest_step(train_dir) is not None:
        try:
            state = load_checkpoint(train_dir, state)
            start_step = int(state.step)
            log_fn(f"Resumed from {train_dir} at step {start_step}")
        except FileNotFoundError as exc:
            # files exist but none passed integrity checks — a fresh start
            # beats dying when the operator asked for elastic restarts
            log_fn(f"Resume requested but {exc}; starting fresh")

    rig = None
    incidents = None
    if train_dir and (
        diverge is not None or tuner is not None
        or os.environ.get(SUPERVISED_ENV) == "1"
    ):
        incidents = IncidentLog.for_train_dir(train_dir)
    if tuner is not None:
        tuner.bind(incidents=incidents, log_fn=log_fn)
    if diverge is not None:
        reason = diverge_conflict(
            diverge.remedy,
            train_dir=train_dir,
            codec=codec,
            keep_ckpts=keep_ckpts,
            save_freq=save_freq,
            window=diverge.detector.window,
        )
        if reason:
            raise ValueError(reason)

    def build_step(generation=0, remedy_cfg=None, densify=False):
        chaos_now = (
            chaos.with_generation(generation)
            if chaos is not None and generation
            else chaos
        )
        return make_train_step(
            model, optimizer,
            codec=None if densify else codec,
            augment=augment, compute_dtype=compute_dtype, guard=guard,
            chaos=chaos_now, superstep=superstep, remedy=remedy_cfg,
            track_grad_norm=diverge is not None,
            # the densify window swaps to dense aggregation — no
            # estimator left to probe for its duration
            track_quality=False if densify else track_quality,
        )

    if track_quality and codec is None:
        raise ValueError(
            "track_quality (--obs-quality) probes the codec's estimator "
            "error; dense training has no estimator — drop one"
        )
    if recorder is not None:
        recorder.context.setdefault("aggregate", "local")
        # a resumed run replays from the checkpoint: cut the stale metric
        # tail the killed attempt wrote past its last save, or the replay
        # would duplicate those steps in the timeline
        recorder.prune_past(start_step)
        if track_quality:
            from atomo_tpu.obs.quality import quality_meta

            # the static per-layer kept-byte split, once (trace-time
            # shapes only — nothing materializes)
            recorder.write_meta(
                quality_meta(codec, jax.device_get(state.params))
            )
    step_fn = build_step()
    save_fn = retrying_saver(log_fn, incidents)
    key = jax.random.PRNGKey(seed + 1)
    timer = Timer()
    # replay: skip the batches the interrupted run consumed so the resumed
    # data order matches the uninterrupted run's (docstring); index-only.
    # The RNG snapshot is the rollback engine's replay anchor
    # (pipeline.BatchIterator.restream) and MUST be taken before forever()
    # advances the shuffle RNG; it is a doctor-only iterator requirement,
    # so a disarmed loop keeps the old iterator contract.
    rng_snapshot = train_iter.snapshot_rng() if diverge is not None else None
    stream = train_iter.forever(skip=start_step)
    if diverge is not None:

        def _reload(target):
            tpl = create_state(
                model, optimizer, jax.random.PRNGKey(seed),
                jnp.asarray(sample_images),
            )
            if target <= 0:
                return tpl  # no healthy checkpoint survived: from scratch
            return load_checkpoint(train_dir, tpl, step=target)

        rig = RecoveryRig(
            DivergenceDoctor(diverge, train_dir, incidents, log_fn),
            diverge,
            _reload,
            lambda target: train_iter.restream(rng_snapshot, skip=target),
            build_step,
        )
    n_train = len(train_iter.dataset)
    last_saved = start_step
    clear_iterations()  # the ring holds set-up and this loop's iterations
    if superstep > 1:
        # the watchdog beats once per BLOCK: scale its budget by K so a
        # --health-timeout tuned for per-step beats does not falsely fire
        # on a healthy fused run (K steps + one metric fetch per beat)
        with heartbeat_watchdog(
            health_timeout * superstep, on_health_failure
        ) as monitor:
            return _superstep_steps(
                state, step_fn, model, stream, train_iter, test_iter, key,
                timer, n_train, start_step, max_steps, superstep, log_every,
                log_fn, eval_freq, save_freq, train_dir, compress_ckpt,
                save_fn, monitor, guard=guard, chaos=chaos,
                keep_ckpts=keep_ckpts, rig=rig, tuner=tuner,
                recorder=recorder, profile_dir=profile_dir,
            )
    prof = ProfileWindow(profile_dir, log_fn, recorder)
    with heartbeat_watchdog(health_timeout, on_health_failure) as monitor:
        step = start_step
        t_obs = time.perf_counter()  # the tuner's step-time series anchor
        t_rec = time.perf_counter()  # the flight recorder's wall anchor
        while step < max_steps:
            step += 1
            with span(STEP, step):
                if chaos is not None:
                    chaos.maybe_die(step)
                    chaos.maybe_sleep(step)
                if step == start_step + 2:  # step 1 is dominated by compilation
                    prof.open(step, step + PROFILE_STEPS - 1)
                with span(NEXT_BATCH):
                    images, labels = next(stream)
                    images, labels = jnp.asarray(images), jnp.asarray(labels)
                with span(DISPATCH):
                    state, metrics = step_fn(state, key, images, labels)
                log_due = bool(log_every) and step % log_every == 0
                if (
                    log_due or monitor is not None or recorder is not None
                    or rig is not None or tuner is not None
                ):
                    # the iteration's one wait on the device: whatever reads
                    # the metrics below finds them ready, and the span's end
                    # is a fenced stamp with a step number. Nothing armed and
                    # no line due: no fetch, dispatch runs ahead as before
                    with span(FETCH):
                        jax.block_until_ready(metrics["loss"])
                if prof.ends_at(step):
                    jax.block_until_ready(metrics["loss"])
                    prof.close()
                with span(BOUNDARY):
                    if monitor is not None:
                        monitor.beat(step)
                    if recorder is not None:
                        # one fetch per step — the doctor's surveillance-price
                        # precedent; record BEFORE the doctor observes, so a
                        # diverged step lands in the timeline and the rollback's
                        # prune (checkpoint.prune_after -> prune_metrics_after)
                        # cuts it in lockstep with the checkpoint files
                        m_host = jax.device_get(metrics)
                        now_r = time.perf_counter()
                        recorder.record_block(
                            step, m_host, wall_s=now_r - t_rec,
                            drift=tuner.state if tuner is not None else None,
                            generation=(
                                rig.doctor.generation if rig is not None else None
                            ),
                        )
                        t_rec = now_r
                    if rig is not None:
                        # one scalar fetch per step: per-step surveillance is the
                        # price of per-step rollback granularity (the superstep
                        # loop amortizes it into the block's single fetch)
                        alarm_step, reason = rig.observe(step, metrics)
                        if reason is not None:
                            # raises DivergenceError when the budget is spent
                            state, stream, step_fn, chaos, step = rig.recover(
                                alarm_step, reason, chaos
                            )
                            last_saved = min(last_saved, step)
                            # recovery wall is not step time: restamp the tuner's
                            # anchor or it pollutes the next drift observation
                            t_obs = time.perf_counter()
                            t_rec = time.perf_counter()
                            continue
                        new_fn = rig.maybe_end_densify(step)
                        if new_fn is not None:
                            step_fn = new_fn
                    if tuner is not None:
                        # stamped behind the fetch span's fence (async
                        # dispatch would time the enqueue)
                        now = time.perf_counter()
                        tuner.observe(now - t_obs)
                        t_obs = now
                    # guard diagnostics share the log cadence: fetching the skip
                    # flag every step would block host dispatch on every step's
                    # result even when nothing is ever dropped
                    if (
                        guard is not None and log_due
                        and float(metrics["skipped"]) > 0
                    ):
                        log_fn(
                            f"Guard: Step: {step}, Dropped: 1/1, Action: skip "
                            "(anomalous gradient; params/opt state held)"
                        )
                    if log_due:
                        rec = StepMetrics(
                            rank=0,
                            step=step,
                            epoch=step * train_iter.batch_size // max(n_train, 1),
                            samples_seen=(step * train_iter.batch_size) % max(n_train, 1),
                            dataset_size=n_train,
                            loss=float(metrics["loss"]),
                            time_cost=timer.lap(),
                            msg_bytes=int(metrics["msg_bytes"]),
                            prec1=float(metrics["prec1"]),
                            prec5=float(metrics["prec5"]),
                        )
                        emit_worker_line(recorder, rec, log_fn)
                    if eval_freq and test_iter is not None and step % eval_freq == 0:
                        ev = evaluate(model, state, test_iter)
                        log_fn(
                            "Validation: Step: {}, Loss: {:.4f}, Prec@1: {:.4f}, Prec@5: {:.4f}".format(
                                step, ev["loss"], ev["prec1"], ev["prec5"]
                            )
                        )
                    if save_freq and train_dir and step % save_freq == 0:
                        path = save_fn(
                            train_dir, state, step, compress=compress_ckpt,
                            keep=keep_ckpts,
                        )
                        last_saved = step
                        if rig is not None:
                            rig.note_save(step)
                        if chaos is not None:
                            chaos.maybe_corrupt_checkpoint(path, step)
                        if tuner is not None:
                            # observe-only on one device: records the drift
                            # incident at the boundary, keeps the config
                            tuner.maybe_retune(step, "local")
                    if tuner is not None:
                        # restamp after boundary work (eval/save): cadence costs
                        # must not enter the drift baseline
                        t_obs = time.perf_counter()
                    if recorder is not None:
                        t_rec = time.perf_counter()  # same boundary-work rule
        prof.close()  # a run shorter than the profiled window
        # autosave the final state so a restart never replays the tail
        # (strictly `<`: a resume past max_steps runs no steps and must not
        # write a file whose name disagrees with the state's step field)
        if save_freq and train_dir and last_saved < max_steps:
            path = save_fn(
                train_dir, state, max_steps, compress=compress_ckpt,
                keep=keep_ckpts,
            )
            if rig is not None:
                rig.note_save(max_steps)
            if chaos is not None:  # ckpt faults target autosaves too
                chaos.maybe_corrupt_checkpoint(path, max_steps)
    return state


def _crossed(cadence: int, lo: int, hi: int) -> bool:
    """True iff a multiple of ``cadence`` lies in (lo, hi] — the boundary
    test that snaps every per-step cadence (log/eval/save) to superstep
    boundaries: the event fires at ``hi``, the block's final step."""
    return bool(cadence) and hi // cadence > lo // cadence


def _chaos_corrupt_range(chaos, path, lo: int, hi: int) -> None:
    """Apply chaos checkpoint faults aimed at ANY step in (lo, hi] to the
    boundary checkpoint written at ``hi`` — the same block-boundary snap
    kill/sleep get (a ``truncate@3`` drill must still corrupt the file the
    save cadence snapped to step 4)."""
    if chaos is None:
        return
    for t in range(lo + 1, hi + 1):
        chaos.maybe_corrupt_checkpoint(path, t)


def _block_log_record(s, m, train_iter, n_train, lap, last_logged):
    """Worker-line record for a superstep block boundary: loss/precision
    are PER-STEP AVERAGES over the block (msg_bytes is a per-step
    constant), time_cost the per-step average of the span since the last
    log. Shared by the single-host and distributed block loops so the log
    format cannot drift between them."""
    import numpy as np

    return StepMetrics(
        rank=0,
        step=s,
        epoch=s * train_iter.batch_size // max(n_train, 1),
        samples_seen=(s * train_iter.batch_size) % max(n_train, 1),
        dataset_size=n_train,
        loss=float(np.mean(m["loss"])),
        time_cost=lap / max(s - last_logged, 1),
        msg_bytes=int(np.asarray(m["msg_bytes"]).reshape(-1)[-1]),
        prec1=float(np.mean(m["prec1"])),
        prec5=float(np.mean(m["prec5"])),
    )


def _superstep_steps(
    state, step_fn, model, stream, train_iter, test_iter, key, timer,
    n_train, start_step, max_steps, superstep, log_every, log_fn,
    eval_freq, save_freq, train_dir, compress_ckpt, save_fn, monitor,
    guard=None, chaos=None, keep_ckpts=0, rig=None, tuner=None,
    recorder=None, profile_dir=None,
):
    """train_loop's fused block path: one dispatch per K steps, one metric
    fetch per block (the fetch is also the fence the watchdog beats on),
    next block double-buffered onto the device behind the current one.
    ``rig`` (resilience.RecoveryRig) adds divergence rollback: the block's
    per-step (K,) metric series feeds the detector at the block's one
    fetch, and a rollback rebuilds the feed from the replayed stream —
    the resumed run starts a fresh block at target+1, which the scan
    family's partition invariance makes bit-identical to never having
    diverged."""
    import numpy as np

    from atomo_tpu.data.pipeline import BlockStream, SuperstepFeed

    put_fn = lambda im, lb: (jax.device_put(jnp.asarray(im)),  # noqa: E731
                             jax.device_put(jnp.asarray(lb)))
    feed = SuperstepFeed(BlockStream(stream), put_fn)
    s = start_step
    last_saved = start_step
    last_logged = start_step
    t_obs = time.perf_counter()  # the tuner's step-time series anchor
    t_rec = time.perf_counter()  # the flight recorder's wall anchor
    feed.start(min(superstep, max_steps - s))
    prof = ProfileWindow(profile_dir, log_fn, recorder)
    block_idx = 0
    while s < max_steps:
        with span(BLOCK, s + min(superstep, max_steps - s)):
            with span(FEED_TAKE):
                kb, dev_im, dev_lb = feed.take()
            b0, s = s, s + kb
            if chaos is not None:
                # host faults resolve at the block boundary: the block is ONE
                # dispatch, so a kill/sleep aimed at any step it covers fires
                # before the block runs (none of its steps have executed yet
                # — the checkpoint/resume contract is preserved)
                for t in range(b0 + 1, s + 1):
                    chaos.maybe_die(t)
                    chaos.maybe_sleep(t)
            block_idx += 1
            if block_idx == 2:  # block 1 is dominated by compilation
                prof.open(b0 + 1, s, "superstep block")
            with span(DISPATCH):
                state, mblk = step_fn(state, key, dev_im, dev_lb)
            # enqueue the NEXT block's host->device transfer while the current
            # superstep executes (async dispatch above returns immediately)
            with span(FEED_START):
                feed.start(min(superstep, max_steps - s))
            with span(FETCH):
                m = jax.device_get(mblk)  # the block's ONE host sync
            prof.close()
            with span(BOUNDARY):
                if monitor is not None:
                    monitor.beat(s)
                if recorder is not None:
                    # rides the block's one fetch (zero extra device ops); the
                    # block wall becomes kb equal per-step shares — the drift
                    # detector's partition-consistency convention. Recorded
                    # BEFORE the doctor observes: a diverged block lands in the
                    # timeline and the rollback prune cuts it in lockstep.
                    now_r = time.perf_counter()
                    recorder.record_block(
                        b0 + 1, m, wall_s=now_r - t_rec,
                        drift=tuner.state if tuner is not None else None,
                        generation=(
                            rig.doctor.generation if rig is not None else None
                        ),
                    )
                    t_rec = now_r
                if rig is not None:
                    alarm_step, reason = rig.observe(b0 + 1, m)
                    if reason is not None:
                        state, stream, step_fn, chaos, s = rig.recover(
                            alarm_step, reason, chaos
                        )
                        last_saved = min(last_saved, s)
                        last_logged = min(last_logged, s)
                        # drop the feed's staged lookahead block: it belongs to
                        # the discarded timeline
                        feed = SuperstepFeed(BlockStream(stream), put_fn)
                        feed.start(min(superstep, max_steps - s))
                        # recovery wall is not step time: restamp the tuner anchor
                        t_obs = time.perf_counter()
                        t_rec = time.perf_counter()
                        continue
                    new_fn = rig.maybe_end_densify(s)
                    if new_fn is not None:
                        step_fn = new_fn
                if tuner is not None:
                    # the block's wall as kb equal per-step shares (the
                    # device_get above already fenced the dispatch): one mean
                    # per block would make the detector K-times less sensitive
                    # than the per-step loop — partition consistency
                    kb_n = max(kb, 1)
                    tuner.observe([(time.perf_counter() - t_obs) / kb_n] * kb_n)
                n_skipped = float(np.sum(m["skipped"])) if guard is not None else 0.0
                if guard is not None and _crossed(log_every, b0, s) and n_skipped > 0:
                    log_fn(
                        f"Guard: Step: {s}, Dropped: {int(n_skipped)}/{kb}, "
                        "Action: skip (anomalous gradient inside the superstep; "
                        "params/opt state held for those steps)"
                    )
                if _crossed(log_every, b0, s):
                    rec = _block_log_record(
                        s, m, train_iter, n_train, timer.lap(), last_logged
                    )
                    last_logged = s
                    emit_worker_line(recorder, rec, log_fn)
                if eval_freq and test_iter is not None and _crossed(eval_freq, b0, s):
                    ev = evaluate(model, state, test_iter)
                    log_fn(
                        "Validation: Step: {}, Loss: {:.4f}, Prec@1: {:.4f}, Prec@5: {:.4f}".format(
                            s, ev["loss"], ev["prec1"], ev["prec5"]
                        )
                    )
                if save_freq and train_dir and _crossed(save_freq, b0, s):
                    path = save_fn(
                        train_dir, state, s, compress=compress_ckpt, keep=keep_ckpts
                    )
                    last_saved = s
                    if rig is not None:
                        rig.note_save(s)
                    # ckpt faults snap like kill/sleep: a fault aimed anywhere in
                    # this block corrupts the boundary file
                    _chaos_corrupt_range(chaos, path, b0, s)
                    if tuner is not None:
                        tuner.maybe_retune(s, "local")  # observe-only on 1 device
                if tuner is not None:
                    t_obs = time.perf_counter()  # boundary work is not step time
                if recorder is not None:
                    t_rec = time.perf_counter()  # same boundary-work rule
    # autosave the final state so a restart never replays the tail (same
    # strictly-< contract as the per-step loop)
    if save_freq and train_dir and last_saved < max_steps:
        path = save_fn(
            train_dir, state, max_steps, compress=compress_ckpt,
            keep=keep_ckpts,
        )
        if rig is not None:
            rig.note_save(max_steps)
        _chaos_corrupt_range(chaos, path, last_saved, max_steps)
    return state
