"""Anomaly-guarded stepping + bounded retries — the train loop's immune
system.

Why skip-and-rescale is *valid here*: ATOMO's whole construction is an
unbiased gradient estimator (PAPER.md — E[decode(encode(g))] = g). The mean
over any subset of replicas is therefore still an unbiased estimate of the
true gradient, just with more variance; dropping an anomalous contribution
and re-scaling the surviving average by n/kept is statistically equivalent
to one step at a smaller world size. The reference has no analogue: one
worker shipping a NaN gradient NaNs the PS momentum buffer permanently
(sync_replicas_master_nn.py:281-296 averages whatever arrives).

The escalation ladder (one level of autonomy per rung; each rung only sees
what the rung below it let through):

  1. In-graph screening (:func:`grad_ok`, used by trainer.make_train_step
     and parallel.replicated.make_distributed_train_step): finiteness plus
     an optional global-L2-norm ceiling, computed on the raw per-replica
     gradient BEFORE it is encoded/aggregated. Single host: an anomalous
     step is skipped outright (params, opt state, BN stats all held).
     Distributed: the anomalous replica's payload is masked out of the
     gather/psum and the surviving mean is re-scaled; only a step with zero
     survivors is skipped.

  2. Windowed divergence detection (:func:`detector_update` /
     :class:`DivergenceDoctor`): the per-step screen sees one gradient at a
     time — a run diverging with perfectly FINITE gradients (an
     over-aggressive svd rank or qsgd level, the variance blow-up the
     paper's Fig. 5 warns about) sails straight through ``grad_ok``. The
     detector watches the per-step loss series (the same ``(K,)`` block
     superstep execution already returns), a guard skip-rate EMA, and a
     gradient-norm trend counter; a robust z-score sustained past
     ``patience`` steps raises the alarm. The math is a pure sequential
     fold over the per-step series, so its decisions are IDENTICAL for any
     superstep block partition of the same run.

  3. Rollback-and-replay (:meth:`DivergenceDoctor.plan_rollback` + the
     train loops): checkpoints earn a ``healthy`` tag only after the
     detector window clears past them (training.checkpoint.mark_healthy);
     on alarm the loop reloads the newest healthy checkpoint (params, opt
     state, BN stats, AND the in-flight ``--overlap delayed`` payload),
     replays the data stream to the rollback step (the PR-1 resume-replay
     machinery), and applies the configured remedy (``--on-diverge``):
     ``skip`` re-runs the window unchanged (transient-fault model),
     ``rewarm`` ramps the effective LR from ``rewarm_floor`` back to 1
     over the detector window (:class:`RemedyConfig`), ``densify``
     temporarily de-escalates to dense (uncompressed) aggregation — valid
     because every codec is an unbiased estimator of the same mean.

  4. Supervised restarts (:func:`run_supervised`): a crash-looping host
     burns a bounded budget with decorrelated-jitter backoff instead of
     the job; exit codes distinguish clean-exit / rollback-requested
     (:data:`ROLLBACK_EXIT_CODE`, raised when the in-process rollback
     budget is exhausted) / crash, and every decision lands in the
     machine-readable incident log (utils.tracing.IncidentLog). Both
     prune surfaces — the doctor's in-process rollback and the
     supervisor's rc=23 cut — go through checkpoint.prune_after, which
     also cuts the flight recorder's metrics.jsonl timeline in lockstep
     (obs.recorder.prune_metrics_after), so no artifact ever describes
     a trajectory the checkpoints discarded.

  5. Host-side bounded retries (:func:`with_retries`): checkpoint IO, the
     data pipeline, and ``jax.distributed.initialize`` are fallible host
     ops whose transient failures (NFS blips, coordinator races) should
     cost a backoff, not the job. Backoff delays carry decorrelated
     jitter so a fleet-wide blip does not synchronize a retry storm.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import os
import random
import time
from typing import Callable, Optional, Sequence

# re-export: the supervisor protocol constant lives in utils.tracing so
# utils.chaos (crashloop's reader side) can share it without an import cycle
from atomo_tpu.utils.tracing import ATTEMPT_ENV  # noqa: F401

SUPERVISED_ENV = "ATOMO_SUPERVISED"  # set by run_supervised on children
# the trainer's "roll me back from a clean checkpoint" exit: distinct from
# crashes (1), the watchdog's 13, and chaos's 43 — the supervisor prunes
# the diverged timeline back to the last healthy checkpoint before the
# restart, so --resume cannot land on diverged weights
ROLLBACK_EXIT_CODE = 23
# deterministic config errors discovered only in-run (they need the
# resolved device count / built codec): rc=2 — argparse's own usage-error
# code — tells the supervisor the child will fail identically every time,
# so it gives up at once instead of burning the restart budget on
# jax-booting re-execs of the same reject
CONFIG_EXIT_CODE = 2
# the elastic membership boundary: the child recorded the NEXT epoch in
# train_dir/membership.json (a shrink to the surviving roster, or a
# re-grow back to the full one) and exits so the supervisor can re-exec
# it at the new world size. A PLANNED reshape, not a crash — it is never
# charged against the restart budget
MEMBERSHIP_EXIT_CODE = 29


@dataclasses.dataclass(frozen=True)
class GuardConfig:
    """Anomaly screen settings.

    max_grad_norm: reject a contribution whose global L2 norm exceeds this
        (0 = finiteness check only). This is a *screen*, not clipping — the
        gradient is dropped, not shrunk, so the estimator stays unbiased.
    """

    max_grad_norm: float = 0.0


# ---------------------------------------------------------------------------
# Windowed divergence detection (escalation rung 2)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DetectorConfig:
    """Divergence-detector knobs.

    window: EMA window (steps) for the loss baseline and skip-rate, the
        number of alarm-free steps a checkpoint must outlive to earn its
        healthy tag, AND the rewarm/densify remedy span — one time
        constant for the whole ladder keeps the knobs coherent.
    zmax: robust z-score threshold on the loss vs its EMA baseline.
    patience: consecutive above-threshold steps before the alarm fires (a
        single bad batch is noise; a sustained excursion is divergence).
    min_history: steps of warmup before z/skip/trend alarms arm.
    skip_max: alarm when the guard's skip-rate EMA exceeds this (a run
        whose screen constantly fires is wedged, not unlucky).
    grad_ratio: alarm when the gradient norm exceeds this multiple of its
        own EMA for ``patience`` consecutive steps (the finite-explosion
        trend ``grad_ok`` cannot see).
    """

    window: int = 16
    zmax: float = 6.0
    patience: int = 3
    min_history: int = 8
    skip_max: float = 0.5
    grad_ratio: float = 10.0

    def __post_init__(self):
        # window == 1 makes alpha = 1, the EMA variance identically zero,
        # and the z-score alarm silently unfireable; window <= 0 drives
        # the EMAs outside their domains
        if self.window < 2:
            raise ValueError(
                f"detector window must be >= 2, got {self.window} (a "
                "1-step window has zero variance — the z-score alarm "
                "could never fire)"
            )
        if self.patience < 1:
            raise ValueError(
                f"detector patience must be >= 1, got {self.patience}"
            )
        if self.min_history < 0:
            raise ValueError(
                f"detector min_history must be >= 0, got {self.min_history}"
            )
        if self.zmax <= 0:
            raise ValueError(f"detector zmax must be > 0, got {self.zmax}")


@dataclasses.dataclass(frozen=True)
class DetectorState:
    """The detector's carry — a handful of scalars folded once per step."""

    n: int = 0
    mean: float = 0.0  # loss EMA baseline
    var: float = 0.0  # loss EMA variance (frozen while hot — see update)
    hot: int = 0  # consecutive steps with z > zmax
    skip_ema: float = 0.0  # guard skip-rate EMA
    gn_ref: float = 0.0  # gradient-norm EMA baseline
    gn_hot: int = 0  # consecutive steps with norm > grad_ratio * gn_ref


def detector_update(
    cfg: DetectorConfig,
    st: DetectorState,
    loss: float,
    skipped: float = 0.0,
    grad_norm: Optional[float] = None,
) -> tuple[DetectorState, Optional[str]]:
    """One detector step: fold ``(loss, skipped[, grad_norm])`` into the
    carry, return ``(new_state, alarm_reason | None)``.

    A pure sequential fold — feeding a loss series step by step, or in
    ``(K,)`` superstep blocks of ANY partition, produces identical states
    and identical alarm decisions (tested). While the z-score is hot the
    loss baseline is FROZEN: absorbing diverging losses into the EMA would
    raise the mean until z drops back under ``zmax`` and the alarm never
    fires. Guard-skipped steps update only the skip-rate (their loss
    describes an update that was rejected, and their gradient norm is the
    rejected outlier's — folding either into a baseline would desensitize
    its alarm); a non-finite loss on an UN-skipped step alarms immediately
    — the guard should have caught it, so the trajectory itself is
    already poisoned.
    """
    loss = float(loss)
    alpha = 2.0 / (cfg.window + 1.0)
    armed = st.n >= cfg.min_history
    skip = 1.0 if skipped and float(skipped) > 0 else 0.0
    skip_ema = st.skip_ema + alpha * (skip - st.skip_ema)
    mean, var, hot = st.mean, st.var, st.hot
    gn_ref, gn_hot = st.gn_ref, st.gn_hot
    alarm = None

    if not math.isfinite(loss):
        if skip < 0.5:
            alarm = "nonfinite_loss"
    elif skip < 0.5:
        if st.n == 0 or (mean == 0.0 and var == 0.0 and st.hot == 0):
            mean, var, hot = loss, 0.0, 0
        else:
            diff = loss - mean
            sd = math.sqrt(var) if var > 0 else 0.0
            z = diff / sd if sd > 0 else 0.0
            if armed and sd > 0 and z > cfg.zmax:
                hot += 1  # baseline frozen while hot
            else:
                hot = 0
                mean += alpha * diff
                var = (1.0 - alpha) * (var + alpha * diff * diff)

    if alarm is None and hot >= cfg.patience:
        alarm = "loss_zscore"
    if alarm is None and armed and skip_ema > cfg.skip_max:
        alarm = "skip_rate"

    if grad_norm is not None:
        g = float(grad_norm)
        # skip-gated like the loss path: a guard-REJECTED gradient's norm
        # (e.g. a screened explosion) must not enter the gn_ref baseline,
        # or one rejected outlier desensitizes the trend alarm for good
        if math.isfinite(g) and g > 0 and skip < 0.5:
            if armed and gn_ref > 0 and g > cfg.grad_ratio * gn_ref:
                gn_hot += 1  # baseline frozen while trending
            else:
                gn_hot = 0
                gn_ref = g if gn_ref <= 0 else gn_ref + alpha * (g - gn_ref)
    if alarm is None and gn_hot >= cfg.patience:
        alarm = "grad_norm_trend"

    return (
        DetectorState(
            n=st.n + 1,
            mean=mean,
            var=var,
            hot=hot,
            skip_ema=skip_ema,
            gn_ref=gn_ref,
            gn_hot=gn_hot,
        ),
        alarm,
    )


def detector_scan(
    cfg: DetectorConfig,
    st: DetectorState,
    losses,
    skipped=None,
    grad_norms=None,
    first_step: int = 1,
) -> tuple[DetectorState, Optional[int], Optional[str]]:
    """Fold a per-step series (a superstep block's ``(K,)`` metrics, or a
    single step's scalars as length-1 sequences) through the detector.
    Stops at the FIRST alarm — the caller rolls back from there, so later
    entries of the block describe a timeline about to be discarded.
    Returns ``(state, alarm_step | None, reason | None)``."""
    losses = [float(x) for x in _as_seq(losses)]
    skips = (
        [0.0] * len(losses) if skipped is None
        else [float(x) for x in _as_seq(skipped)]
    )
    gns = (
        [None] * len(losses) if grad_norms is None
        else [float(x) for x in _as_seq(grad_norms)]
    )
    for i, (loss, sk, gn) in enumerate(zip(losses, skips, gns)):
        st, alarm = detector_update(cfg, st, loss, sk, gn)
        if alarm is not None:
            return st, first_step + i, alarm
    return st, None, None


def _as_seq(x):
    import numpy as np

    return np.asarray(x).reshape(-1)


# ---------------------------------------------------------------------------
# Step-time drift detection (escalation rung 0.5: performance, not health)
# ---------------------------------------------------------------------------
#
# The loss detector above watches the TRAJECTORY; this one watches the
# THROUGHPUT series beside it — per-step wall seconds. Sustained step-time
# drift (a contended host, a degraded link, a changed load profile) does
# not poison the math, so the response is the gentlest rung on the ladder:
# re-probe the performance config at the next checkpoint boundary
# (tuning.autopilot.OnlineRetuner) instead of rolling anything back. Same
# design rules as DetectorConfig: a pure sequential fold, an EMA baseline
# FROZEN while the signal is hot (absorbing a drifting series into its own
# baseline would chase the drift and never alarm), and a patience count so
# one slow step (a GC pause, an eval) is noise, not an incident.


@dataclasses.dataclass(frozen=True)
class DriftConfig:
    """Step-time drift knobs.

    window: EMA span (observations) for the step-time baseline.
    ratio: alarm threshold — an observation counts as drifting when it
        exceeds ``ratio`` x the frozen baseline.
    patience: consecutive drifting observations before the alarm fires.
    min_history: warmup observations before the alarm arms (the first
        steps after a (re)compile are not a baseline).
    """

    window: int = 32
    ratio: float = 1.5
    patience: int = 8
    min_history: int = 8

    def __post_init__(self):
        if self.window < 2:
            raise ValueError(
                f"drift window must be >= 2, got {self.window}"
            )
        if not self.ratio > 1.0:
            raise ValueError(
                f"drift ratio must be > 1, got {self.ratio} (a ratio <= 1 "
                "would alarm on the baseline itself)"
            )
        if self.patience < 1:
            raise ValueError(
                f"drift patience must be >= 1, got {self.patience}"
            )
        if self.min_history < 0:
            raise ValueError(
                f"drift min_history must be >= 0, got {self.min_history}"
            )


@dataclasses.dataclass(frozen=True)
class DriftState:
    """The drift detector's carry — folded once per observation."""

    n: int = 0
    mean: float = 0.0  # step-time EMA baseline (frozen while hot)
    hot: int = 0  # consecutive observations above ratio * mean


# downward EMA coefficient: the baseline tracks the step-time FLOOR, so
# speedups are adopted fast (a compile-inflated first observation decays
# within ~10 normal steps instead of ~window*ln(inflation) of them —
# during that decay a genuine slowdown could not clear ratio*mean and
# real drift would be silently absorbed) while slowdowns stay on the
# slow window EMA + hot-counting path that defines drift
_DRIFT_DOWN_ALPHA = 0.5


def drift_update(
    cfg: DriftConfig, st: DriftState, dt: float
) -> tuple[DriftState, Optional[str]]:
    """Fold one per-step wall time into the carry; returns
    ``(new_state, "step_time_drift" | None)``. Non-finite or non-positive
    observations are ignored (the count still advances — a gap is not a
    baseline sample). The baseline is asymmetric by design: observations
    BELOW it adapt at :data:`_DRIFT_DOWN_ALPHA` (the floor follows
    speedups and sheds compile-inflated seeds quickly), observations
    above it move the slow window EMA or, past ``ratio`` x, freeze it
    and count toward the alarm. A pure fold: feeding the same series one
    value at a time or in blocks of any partition produces identical
    states and identical alarm decisions (the superstep block loops rely
    on this)."""
    dt = float(dt)
    alpha = 2.0 / (cfg.window + 1.0)
    armed = st.n >= cfg.min_history
    mean, hot = st.mean, st.hot
    alarm = None
    if math.isfinite(dt) and dt > 0:
        if mean <= 0.0:
            mean, hot = dt, 0
        elif armed and dt > cfg.ratio * mean:
            hot += 1  # baseline frozen while hot (see module note)
        else:
            hot = 0
            mean += (
                alpha if dt >= mean else _DRIFT_DOWN_ALPHA
            ) * (dt - mean)
        if hot >= cfg.patience:
            alarm = "step_time_drift"
            hot = 0  # one alarm per sustained excursion; the retuner
            # resets the whole state after acting on it
    return DriftState(n=st.n + 1, mean=mean, hot=hot), alarm


def drift_scan(
    cfg: DriftConfig, st: DriftState, dts
) -> tuple[DriftState, Optional[str]]:
    """Fold a block of per-step wall times (the superstep loops observe
    once per block: the block wall divided into K equal per-step shares).
    Unlike detector_scan there is nothing to roll back, so the fold always
    consumes the whole block; the FIRST alarm in it is returned."""
    alarm = None
    for dt in _as_seq(dts):
        st, a = drift_update(cfg, st, dt)
        if a is not None and alarm is None:
            alarm = a
    return st, alarm


class DivergenceError(RuntimeError):
    """The in-process rollback budget is exhausted: the run keeps
    diverging after ``max_rollbacks`` rollback+remedy attempts. Callers
    (the CLI) translate this into :data:`ROLLBACK_EXIT_CODE` so a
    supervisor can prune to the last healthy checkpoint and restart —
    or give up against ITS budget."""

    def __init__(self, step: int, reason: str, rollbacks: int):
        super().__init__(
            f"divergence at step {step} ({reason}) after {rollbacks} "
            "rollback(s); in-process budget exhausted"
        )
        self.step = step
        self.reason = reason
        self.rollbacks = rollbacks


@dataclasses.dataclass(frozen=True)
class RemedyConfig:
    """The ``rewarm`` remedy, baked into the rebuilt step program: the
    effective LR ramps from ``floor`` back to 1.0 over ``window`` steps
    after ``start_step`` (implemented as an in-graph gradient pre-scale —
    scaling an unbiased gradient estimate keeps it unbiased, and the ramp
    is a function of the carried step counter, so superstep block
    partitions see identical arithmetic)."""

    start_step: int
    window: int
    floor: float = 0.1


def remedy_scale(remedy: RemedyConfig, step):
    """Traced ramp factor in [floor, 1] for the step counter ``step``."""
    import jax.numpy as jnp

    t = jnp.clip(
        (jnp.asarray(step, jnp.float32) - jnp.float32(remedy.start_step))
        / jnp.float32(max(remedy.window, 1)),
        0.0,
        1.0,
    )
    floor = jnp.float32(remedy.floor)
    return floor + (jnp.float32(1.0) - floor) * t


def apply_remedy(remedy: RemedyConfig, step, grads):
    """Pre-scale the aggregated gradient tree by the rewarm ramp — ONE
    definition shared by the single-host, blocking-distributed, and
    delayed-overlap update paths, so which step counter drives the ramp is
    decided exactly once per call site and the arithmetic cannot drift."""
    import jax

    scale = remedy_scale(remedy, step)
    return jax.tree_util.tree_map(
        lambda g: g * scale.astype(g.dtype), grads
    )


def global_sq_norm(grads):
    """Traced f32 sum of squares over every leaf — the raw global-L2
    signal (pre-screen, pre-codec) the divergence detector's grad-norm
    trend counter folds. ONE definition for the single-host and
    distributed ``track_grad_norm`` metrics so the two series cannot
    disagree about the same gradient. (:func:`grad_ok` keeps its own
    interleaved finiteness+norm leaf pass — it predates this helper and
    its traced op ORDER is pinned by the frozen guarded-program
    contracts; the arithmetic is the same.)"""
    import jax
    import jax.numpy as jnp

    sq = jnp.float32(0.0)
    for leaf in jax.tree_util.tree_leaves(grads):
        lf = leaf.astype(jnp.float32)
        sq += jnp.sum(lf * lf)
    return sq


@dataclasses.dataclass(frozen=True)
class DivergeConfig:
    """``--on-diverge`` settings: which remedy, the detector, and the
    in-process rollback budget."""

    remedy: str = "skip"  # skip | rewarm | densify
    detector: DetectorConfig = dataclasses.field(
        default_factory=DetectorConfig
    )
    max_rollbacks: int = 2
    rewarm_floor: float = 0.1

    def __post_init__(self):
        if self.remedy not in ("skip", "rewarm", "densify"):
            raise ValueError(
                f"unknown --on-diverge remedy {self.remedy!r}; expected "
                "skip | rewarm | densify"
            )


def diverge_conflict(
    remedy,
    *,
    train_dir,
    codec=None,
    aggregate=None,
    overlap=None,
    zero1=False,
    num_aggregate=None,
    keep_ckpts=None,
    save_freq=None,
    window=None,
):
    """The ``--on-diverge`` compatibility matrix, stated once.

    Returns the human-readable reason the combination cannot work, or
    None when it can. Every surface that arms the doctor (the CLI and
    both train loops) asks here and raises its own error type with the
    returned message; a surface passes only the features it actually
    has — omitted ones are treated as off.
    """
    if not train_dir:
        return (
            "diverge (--on-diverge) needs a train_dir: rollback "
            "restores from checkpoints"
        )
    if save_freq is not None and not save_freq:
        # save_freq None = the caller has no cadence concept (unit tests);
        # 0 = checkpointing explicitly disabled — no save can ever earn a
        # healthy tag, so every rollback would replay from step 0
        return (
            "--on-diverge needs a checkpoint cadence (--save-freq or "
            "--eval-freq > 0): with saves disabled no checkpoint can earn "
            "a healthy tag and every rollback would restart from scratch"
        )
    if keep_ckpts and save_freq and window and keep_ckpts * save_freq < window:
        # a checkpoint earns the healthy tag only once the detector window
        # clears past it (~window steps after the save), but keep-last-K
        # retention deletes it keep_ckpts*save_freq steps after the save:
        # with keep*freq < window NO checkpoint ever survives to be tagged,
        # so the first alarm would roll back to step 0 and prune everything
        return (
            f"--on-diverge with --keep-ckpts {keep_ckpts} and --save-freq "
            f"{save_freq} retains checkpoints for only "
            f"{keep_ckpts * save_freq} steps — shorter than the "
            f"--diverge-window of {window}, so none would live long enough "
            "to earn the healthy tag a rollback needs; raise --keep-ckpts "
            "(or drop it to keep all checkpoints)"
        )
    if zero1:
        return (
            "--on-diverge is not supported with --zero1 (the sharded "
            "optimizer template cannot be rebuilt mid-run); drop one"
        )
    if remedy == "densify":
        if codec is None:
            return (
                "--on-diverge densify needs a compressing --code — "
                "dense training has nothing denser to de-escalate to"
            )
        if overlap == "delayed":
            return (
                "--on-diverge densify cannot compose with --overlap "
                "delayed (the dense fallback has no delayed form); "
                "use skip or rewarm"
            )
        if aggregate == "hierarchical":
            return (
                "--on-diverge densify cannot compose with --aggregate "
                "hierarchical (the dense fallback aggregates with a flat "
                "psum; every two-level topology plan — the legacy "
                "psum+gather schedule and the re-encoded plans alike — "
                "needs a codec to compress at least one tier); use skip "
                "or rewarm"
            )
        if num_aggregate:
            return (
                "--on-diverge densify cannot compose with "
                "--num-aggregate (a dense psum cannot subset "
                "replicas); use skip or rewarm"
            )
    return None


@dataclasses.dataclass(frozen=True)
class RollbackPlan:
    """What the loop must do about an alarm: reload ``target``, replay the
    data stream to it, and rebuild the step program at ``generation``
    (chaos disarmed) with the remedy applied."""

    target: int
    remedy: str
    window: int
    generation: int
    reason: str
    alarm_step: int


class DivergenceDoctor:
    """Host-side controller tying detection to recovery: folds the
    per-step metric series through the detector, grants healthy tags to
    checkpoints the window has cleared, and turns alarms into
    :class:`RollbackPlan`s against the in-process budget.

    The doctor is loop-agnostic — the four train loops (single-host and
    distributed, per-step and superstep) share one instance's policy and
    incident log; only the state reload/stream rebuild is loop-specific.
    """

    def __init__(
        self,
        cfg: DivergeConfig,
        train_dir: Optional[str],
        incidents=None,
        log_fn=print,
    ):
        self.cfg = cfg
        self.train_dir = train_dir
        self.incidents = incidents
        self.log_fn = log_fn
        self.state = DetectorState()
        self.pending: list[int] = []  # saved steps awaiting the healthy tag
        self.rollbacks = 0
        self.generation = 0

    # -- observation ----------------------------------------------------

    def note_save(self, step: int) -> None:
        """A checkpoint landed at ``step``; it earns the healthy tag only
        after the detector window clears past it without an alarm."""
        if step not in self.pending:
            self.pending.append(step)

    def observe_block(
        self, first_step: int, losses, skipped=None, grad_norms=None
    ) -> tuple[Optional[int], Optional[str]]:
        """Fold the per-step series for steps ``first_step..`` (a superstep
        block or a single step) into the detector; confirm pending healthy
        tags for checkpoints the window has cleared. Returns
        ``(alarm_step, reason)`` or ``(None, None)``."""
        losses = _as_seq(losses)
        self._confirm_through(first_step - 1)
        self.state, alarm_step, reason = detector_scan(
            self.cfg.detector, self.state, losses, skipped, grad_norms,
            first_step=first_step,
        )
        if reason is None:
            self._confirm_through(first_step + len(losses) - 1)
        else:
            # the steps BEFORE the alarm were observed alarm-free, and the
            # K=1 trajectory confirms them before its alarm call's scan —
            # confirm through alarm_step-1 so a save whose window cleared
            # pre-alarm stays a rollback target under ANY block partition
            self._confirm_through(alarm_step - 1)
        return alarm_step, reason

    def _confirm_through(self, step: int) -> None:
        """Grant healthy tags to pending saves whose window [save,
        save+window] finished strictly before or at ``step`` alarm-free.
        A pending save whose file retention already pruned is dropped
        untagged — marking it would leave an orphaned sidecar that a
        FUTURE checkpoint reusing the step number (a post-rollback
        timeline) would inherit without earning."""
        if not self.pending:
            return
        from atomo_tpu.training.checkpoint import (
            checkpoint_path,
            mark_healthy,
        )

        w = self.cfg.detector.window
        still = []
        for s in sorted(self.pending):
            if s + w <= step:
                if self.train_dir and os.path.exists(
                    checkpoint_path(self.train_dir, s)
                ):
                    mark_healthy(self.train_dir, s)
            else:
                still.append(s)
        self.pending = still

    # -- recovery -------------------------------------------------------

    def plan_rollback(self, alarm_step: int, reason: str) -> RollbackPlan:
        """Turn an alarm into a rollback plan (or raise
        :class:`DivergenceError` once the budget is spent). Prunes the
        diverged timeline above the target so no resume path can land on
        it, resets the detector, and bumps the chaos generation."""
        from atomo_tpu.training.checkpoint import (
            latest_healthy_step,
            prune_after,
        )

        if self.rollbacks >= self.cfg.max_rollbacks:
            pruned: list[int] = []
            if self.train_dir:
                # make the same cut a supervisor would on rc=23: without
                # it an unsupervised run's later --resume lands on the
                # diverged tail written during this final excursion
                pruned = prune_after(
                    self.train_dir, latest_healthy_step(self.train_dir) or 0
                )
            if self.incidents is not None:
                self.incidents.append(
                    "divergence",
                    action="give_up",
                    step=alarm_step,
                    reason=reason,
                    rollbacks=self.rollbacks,
                    pruned=pruned,
                )
            raise DivergenceError(alarm_step, reason, self.rollbacks)
        self.rollbacks += 1
        target = None
        removed: list[int] = []
        if self.train_dir:
            target = latest_healthy_step(self.train_dir)
            removed = prune_after(self.train_dir, target or 0)
        target = int(target) if target is not None else 0
        self.generation += 1
        self.state = DetectorState()
        self.pending = [s for s in self.pending if s <= target]
        plan = RollbackPlan(
            target=target,
            remedy=self.cfg.remedy,
            window=self.cfg.detector.window,
            generation=self.generation,
            reason=reason,
            alarm_step=alarm_step,
        )
        self.log_fn(
            f"Doctor: divergence at step {alarm_step} ({reason}); rolling "
            f"back to step {target} with remedy {plan.remedy!r} "
            f"(rollback {self.rollbacks}/{self.cfg.max_rollbacks}"
            + (f", pruned steps {removed}" if removed else "")
            + ")"
        )
        if self.incidents is not None:
            self.incidents.append(
                "divergence",
                action=f"rollback+{plan.remedy}",
                step=alarm_step,
                target=target,
                reason=reason,
                pruned=removed,
                rollbacks=self.rollbacks,
            )
        return plan


class RecoveryRig:
    """The loop-facing half of the rollback engine: binds a
    :class:`DivergenceDoctor` to one train loop's reload / replay /
    step-rebuild closures, so the four loops (single-host and distributed,
    per-step and superstep) share the recovery sequence verbatim.

    ``reload_state(target)`` must return the loop's state restored from
    the step-``target`` checkpoint (target 0 = fresh init — no healthy
    checkpoint survived); ``restream(target)`` must return a data stream
    replayed past ``target`` batches from the run-start RNG snapshot;
    ``build_step(generation, remedy_cfg, densify)`` must return the loop's
    step callable with chaos at ``generation``, the optional rewarm ramp,
    and (densify) the codec swapped out for dense aggregation.
    """

    def __init__(self, doctor, diverge, reload_state, restream, build_step):
        self.doctor = doctor
        self.diverge = diverge
        self._reload = reload_state
        self._restream = restream
        self._build = build_step
        self.densify_until: Optional[int] = None
        self.remedy_until: Optional[int] = None  # rewarm ramp end step

    def observe(self, first_step, metrics):
        """Feed a fetched metrics dict (per-step scalars or (K,) block
        series) to the detector; returns (alarm_step, reason).

        ``sample_skipped`` (delayed-overlap programs) wins over
        ``skipped``: in that mode "skipped" describes the CONSUMED
        step-(t-1) payload while the loss describes this step's forward,
        so gating on it would be off by one — folding a forward whose
        every chip the guard rejected (loss collapsed to 0.0) as a clean
        sample."""
        return self.doctor.observe_block(
            first_step,
            metrics["loss"],
            metrics.get("sample_skipped", metrics.get("skipped")),
            metrics.get("grad_norm"),
        )

    def note_save(self, step):
        self.doctor.note_save(step)

    def rollback(self, alarm_step, reason):
        """Execute the doctor's plan; returns (plan, state, stream,
        step_fn) for the loop to adopt. Raises DivergenceError when the
        in-process budget is spent."""
        plan = self.doctor.plan_rollback(alarm_step, reason)
        remedy_cfg = (
            RemedyConfig(
                start_step=plan.target,
                window=plan.window,
                floor=self.diverge.rewarm_floor,
            )
            if plan.remedy == "rewarm"
            else None
        )
        densify = plan.remedy == "densify"
        self.densify_until = (
            plan.target + plan.window if densify else None
        )
        self.remedy_until = (
            plan.target + plan.window if plan.remedy == "rewarm" else None
        )
        state = self._reload(plan.target)
        stream = self._restream(plan.target)
        step_fn = self._build(plan.generation, remedy_cfg, densify)
        return plan, state, stream, step_fn

    def recover(self, alarm_step, reason, chaos):
        """The whole recovery sequence the four loops share: execute the
        rollback, advance the loop's OWN chaos injector to the plan's
        generation (host-side faults — kill/slow/ckpt corruption — must
        disarm with the step program, or they re-fire on the replayed
        range), and fetch the restored step counter the loop's cadence
        counters clamp to. Feed/profiler teardown stays at the call site —
        it is the only part that differs per loop. Returns
        ``(state, stream, step_fn, chaos, step)``; raises DivergenceError
        when the in-process budget is spent."""
        import jax

        plan, state, stream, step_fn = self.rollback(alarm_step, reason)
        if chaos is not None:
            chaos = chaos.with_generation(plan.generation)
        step = int(jax.device_get(state.step))
        return state, stream, step_fn, chaos, step

    def maybe_end_densify(self, step):
        """After the densify window closes, rebuild the real-codec step
        (snapped to the first step/block boundary past the window);
        returns the new step_fn or None."""
        if self.densify_until is not None and step >= self.densify_until:
            self.densify_until = None
            return self._build(self.doctor.generation, None, False)
        return None

    def remedy_active(self, step) -> bool:
        """True while a rollback remedy still shapes the step program:
        the densify window is open, or the rewarm ramp has not yet
        saturated (past ``target + window`` the ramp computes exactly
        1.0, so a program rebuilt WITHOUT it is arithmetically
        identical). The online re-tuner defers its aggregate-switch
        rebuild past this window — a default ``build_step()`` rebuild
        mid-treatment would silently drop the doctor's remedy."""
        if self.densify_until is not None and step < self.densify_until:
            return True
        return self.remedy_until is not None and step < self.remedy_until


def grad_ok(grads, max_grad_norm: float = 0.0):
    """Traced bool scalar: True iff every leaf is finite (and the global L2
    norm is within ``max_grad_norm`` when > 0). An overflowing
    sum-of-squares is itself non-finite, so the norm screen also catches
    exploding gradients whose square overflows f32."""
    import jax
    import jax.numpy as jnp

    leaves = jax.tree_util.tree_leaves(grads)
    ok = jnp.bool_(True)
    sq = jnp.float32(0.0)
    for leaf in leaves:
        lf = leaf.astype(jnp.float32)
        ok &= jnp.all(jnp.isfinite(lf))
        sq += jnp.sum(lf * lf)
    if max_grad_norm and max_grad_norm > 0:
        ok &= sq <= jnp.float32(max_grad_norm) ** 2
    return ok


def select_state(ok, new_tree, old_tree):
    """Per-leaf ``where(ok, new, old)`` — the skip: holding params, opt
    state and BN stats at their pre-step values when ``ok`` is False."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(ok, n, o), new_tree, old_tree
    )


def zero_if(bad, tree):
    """Zero every leaf when ``bad`` — keeps non-finite values out of the
    optimizer update (whose arithmetic would propagate NaN into the
    momentum buffers even if the result is later discarded)."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda g: jnp.where(bad, jnp.zeros((), g.dtype), g), tree
    )


def resolve_chaos(chaos):
    """Default the fault injector from the ATOMO_CHAOS env when the caller
    passed none — the flagless path subprocess drills use. One definition
    for both train loops."""
    from atomo_tpu.utils.chaos import ChaosInjector

    return ChaosInjector.from_env() if chaos is None else chaos


@contextlib.contextmanager
def heartbeat_watchdog(health_timeout: float, on_failure=None):
    """Arm the step-heartbeat watchdog around a train loop body (no-op at
    timeout 0). Yields the HealthMonitor to ``beat()`` — or None — and
    guarantees the watchdog thread stops on the way out. One definition
    for both train loops, so arming/stop semantics cannot drift."""
    from atomo_tpu.parallel.launch import HealthMonitor, HealthWatchdog

    monitor = watchdog = None
    if health_timeout > 0:
        monitor = HealthMonitor(timeout=health_timeout)
        watchdog = HealthWatchdog(
            monitor,
            interval=min(health_timeout / 4, 10.0),
            on_failure=on_failure,
        ).start()
    try:
        yield monitor
    finally:
        if watchdog is not None:
            watchdog.stop()


def retrying_saver(log_fn=print, incidents=None):
    """save_checkpoint wrapped in the standard bounded backoff — the one
    saver both train loops (single-host and distributed) use, so retry
    policy and logging cannot drift between them. With ``incidents`` (an
    IncidentLog), each retried save lands in the post-mortem record."""
    from atomo_tpu.training.checkpoint import save_checkpoint

    return with_retries(
        save_checkpoint,
        on_retry=lambda i, exc: log_fn(
            f"Checkpoint save failed (attempt {i}): {exc}; retrying"
        ),
        incidents=incidents,
        incident_cause="checkpoint_save",
    )


def masked_mean(tree, ok, kept, axis):
    """Skip-and-rescale, psum form: zero this replica's contribution when
    ``ok`` is False, sum over ``axis``, divide by the surviving count
    (floored at 1 so the zero-survivor step stays finite; the caller's
    select_state discards it anyway)."""
    import jax
    import jax.numpy as jnp

    summed = jax.lax.psum(zero_if(~ok, tree), axis)
    return jax.tree_util.tree_map(
        lambda s: s / jnp.maximum(kept, 1.0).astype(s.dtype), summed
    )


def rescale_by_survivors(tree, n_contrib, kept):
    """Skip-and-rescale, gather form: a mean taken over all ``n_contrib``
    slots (anomalous ones masked to zero) re-scaled by n/kept so it equals
    the mean over survivors alone."""
    import jax
    import jax.numpy as jnp

    scale = n_contrib / jnp.maximum(kept, 1.0)
    return jax.tree_util.tree_map(
        lambda g: g * scale.astype(g.dtype), tree
    )


def decorrelated_delay(
    prev: float, base: float, cap: float, rng: random.Random
) -> tuple[float, float]:
    """One decorrelated-jitter backoff step: ``delay = min(cap,
    uniform(base, 3*prev))``. Returns ``(delay, next_prev)`` — the floor
    at ``base`` keeps the envelope from collapsing. The ONE backoff
    formula for both the retry path (:func:`with_retries`) and the
    supervisor (:func:`run_supervised`); hosts tripping over the same
    fleet-wide blip must not re-synchronize into a retry storm."""
    delay = min(cap, rng.uniform(base, prev * 3))
    return delay, max(delay, base)


def with_retries(
    fn: Callable,
    *,
    attempts: int = 3,
    base_delay: float = 0.1,
    max_delay: float = 5.0,
    exceptions: Sequence[type] = (OSError,),
    on_retry: Optional[Callable[[int, BaseException], None]] = None,
    sleep: Callable[[float], None] = time.sleep,
    jitter: bool = True,
    rng: Optional[random.Random] = None,
    incidents=None,
    incident_cause: str = "retry",
) -> Callable:
    """Wrap a fallible host-side op with bounded, jittered backoff.

    Returns a callable with ``fn``'s signature that retries on the listed
    exception types and re-raises the last failure once ``attempts`` are
    exhausted. Anything not in ``exceptions`` propagates immediately —
    retrying a programming error just hides it.

    Backoff is DECORRELATED JITTER (delay_i = uniform(base, 3 * delay_{i-1})
    capped at ``max_delay``): the old deterministic base * 2**i schedule
    made every host that tripped over the same NFS blip retry at the same
    instant, turning one transient into a synchronized retry storm.
    ``jitter=False`` restores the deterministic schedule (tests); ``rng``
    injects a seeded random.Random. With ``incidents`` (an IncidentLog),
    each retry's cause is recorded under ``incident_cause``.
    """
    if attempts < 1:
        raise ValueError(f"attempts must be >= 1, got {attempts}")
    exc_types = tuple(exceptions)
    rng = rng if rng is not None else random.Random()

    def wrapped(*args, **kwargs):
        prev = base_delay
        for i in range(attempts):
            try:
                return fn(*args, **kwargs)
            except exc_types as exc:
                if i + 1 >= attempts:
                    raise
                if on_retry is not None:
                    on_retry(i + 1, exc)
                if incidents is not None:
                    incidents.append(
                        incident_cause,
                        action="retry",
                        attempt=i + 1,
                        op=getattr(fn, "__name__", str(fn)),
                        error=f"{type(exc).__name__}: {exc}",
                    )
                if jitter:
                    delay, prev = decorrelated_delay(
                        prev, base_delay, max_delay, rng
                    )
                else:
                    delay = min(base_delay * (2 ** i), max_delay)
                sleep(delay)

    return wrapped


# ---------------------------------------------------------------------------
# Run-level supervision (escalation rung 4)
# ---------------------------------------------------------------------------


def run_supervised(
    cmd: Sequence[str],
    *,
    max_restarts: int = 2,
    backoff_base: float = 1.0,
    backoff_max: float = 30.0,
    train_dir: Optional[str] = None,
    resume_flag: Optional[str] = "--resume",
    log_fn=print,
    rng: Optional[random.Random] = None,
    sleep: Callable[[float], None] = time.sleep,
    env: Optional[dict] = None,
) -> int:
    """Supervise a train command with a crash-loop budget.

    Runs ``cmd`` as a child process (with :data:`SUPERVISED_ENV` set so the
    child never re-supervises itself, and :data:`ATTEMPT_ENV` carrying the
    0-based run attempt for attempt-keyed chaos). Exit codes are triaged:

      0                    clean exit — done.
      ROLLBACK_EXIT_CODE   rollback requested (the child's in-process
                           rollback budget is spent): the supervisor cuts
                           the checkpoint timeline back to the newest
                           HEALTHY step (prune_after) so the restart's
                           ``--resume`` cannot land on diverged weights,
                           then restarts against the budget.
      CONFIG_EXIT_CODE     deterministic config error (argparse usage
                           errors and the CLI's in-run rejects that need
                           the resolved mesh/codec): give up immediately —
                           every restart would die identically.
      MEMBERSHIP_EXIT_CODE elastic membership boundary: the child recorded
                           the next epoch in train_dir/membership.json; the
                           supervisor rewrites ``--n-devices`` to the new
                           world size (elastic.apply_world_to_argv), hands
                           the epoch id to children via
                           ATOMO_MEMBERSHIP_EPOCH, and re-execs WITHOUT
                           charging the restart budget — a planned reshape
                           is not a crash. A membership exit whose plan is
                           missing or not newer than the last adopted one
                           is triaged as a crash (the runaway-reshape
                           guard).
      anything else        crash — restart against the budget.

    Crash/rollback restarts append ``resume_flag`` to the command (once),
    wait a decorrelated-jittered backoff (base ``backoff_base`` s, capped
    at ``backoff_max`` s), and burn one unit of the ``max_restarts``
    budget; exhaustion returns the child's last exit code. Membership
    re-execs resume immediately, budget untouched. Every decision is one
    record in ``train_dir/incidents.jsonl``.
    """
    import subprocess

    from atomo_tpu.utils.tracing import MEMBERSHIP_EPOCH_ENV, IncidentLog

    incidents = (
        IncidentLog.for_train_dir(train_dir) if train_dir else None
    )
    rng = rng if rng is not None else random.Random()
    base_env = dict(os.environ if env is None else env)
    cmd = list(cmd)
    extra_env: dict = {}
    attempt = 0  # every child run, incl. membership re-execs (ATTEMPT_ENV)
    budget_used = 0  # crash/rollback restarts only — the actual budget
    last_epoch: Optional[int] = None
    prev = max(backoff_base, 1e-3)
    while True:
        run_cmd = list(cmd)
        if attempt > 0 and resume_flag and resume_flag not in run_cmd:
            run_cmd.append(resume_flag)
        child_env = {
            **base_env, **extra_env,
            SUPERVISED_ENV: "1", ATTEMPT_ENV: str(attempt),
        }
        t0 = time.time()
        rc = subprocess.call(run_cmd, env=child_env)
        wall = round(time.time() - t0, 3)
        if rc == 0:
            if incidents is not None:
                incidents.append(
                    "clean_exit", action="done", attempt=attempt, run_s=wall
                )
            log_fn(f"Supervisor: clean exit (attempt {attempt})")
            return 0
        if rc == MEMBERSHIP_EXIT_CODE and train_dir:
            plan = None
            try:
                from atomo_tpu.elastic.membership import MembershipLog

                plan = MembershipLog.load(train_dir).latest()
            except Exception:  # noqa: BLE001 — unreadable plan = crash triage
                plan = None
            if plan is not None and (
                last_epoch is None or plan.epoch > last_epoch
            ):
                from atomo_tpu.elastic.membership import apply_world_to_argv

                last_epoch = plan.epoch
                cmd = apply_world_to_argv(cmd, plan.world_size)
                extra_env[MEMBERSHIP_EPOCH_ENV] = str(plan.epoch)
                if incidents is not None:
                    incidents.append(
                        "membership_change",
                        action=f"reshape->{plan.world_size}",
                        attempt=attempt,
                        rc=rc,
                        epoch=plan.epoch,
                        world=plan.world_size,
                        reason=plan.reason,
                        run_s=wall,
                    )
                log_fn(
                    f"Supervisor: membership epoch {plan.epoch} "
                    f"({plan.reason}); re-exec with --n-devices "
                    f"{plan.world_size} (planned reshape — restart "
                    "budget untouched)"
                )
                attempt += 1
                continue
            log_fn(
                f"Supervisor: attempt {attempt} exited rc={rc} "
                "(membership-change) but membership.json holds no newer "
                "epoch; triaging as a crash"
            )
        if rc == CONFIG_EXIT_CODE:
            # deterministic: every restart would die on the same reject
            if incidents is not None:
                incidents.append(
                    "config_error",
                    action="give_up",
                    attempt=attempt,
                    rc=rc,
                    run_s=wall,
                )
            log_fn(
                f"Supervisor: attempt {attempt} exited rc={rc} (config "
                "error — deterministic); not restarting"
            )
            return rc
        cause = "rollback_requested" if rc == ROLLBACK_EXIT_CODE else "crash"
        target = None
        if rc == ROLLBACK_EXIT_CODE and train_dir:
            from atomo_tpu.training.checkpoint import (
                latest_healthy_step,
                prune_after,
            )

            target = latest_healthy_step(train_dir) or 0
            prune_after(train_dir, target)
        if budget_used >= max_restarts:
            if incidents is not None:
                incidents.append(
                    "budget_exhausted",
                    action="give_up",
                    attempt=attempt,
                    rc=rc,
                    run_s=wall,
                    max_restarts=max_restarts,
                )
            log_fn(
                f"Supervisor: budget exhausted after attempt {attempt} "
                f"(rc={rc}, {cause}); giving up"
            )
            return rc
        if train_dir:
            # a LIVE reshape (--elastic-reshard live) advances
            # membership.json WITHOUT an rc=29 exit, so a later crash
            # must not relaunch at the stale world: membership.json is
            # the source of truth for the next attempt's --n-devices
            # regardless of how the epoch advanced. Charged as a normal
            # crash — the reshape already happened in-process.
            try:
                from atomo_tpu.elastic.membership import MembershipLog

                plan = MembershipLog.load(train_dir).latest()
            except Exception:  # noqa: BLE001 — unreadable plan: keep argv
                plan = None
            if plan is not None and (
                last_epoch is None or plan.epoch > last_epoch
            ):
                from atomo_tpu.elastic.membership import (
                    apply_world_to_argv,
                )

                last_epoch = plan.epoch
                new_cmd = apply_world_to_argv(cmd, plan.world_size)
                extra_env[MEMBERSHIP_EPOCH_ENV] = str(plan.epoch)
                if new_cmd != cmd:
                    cmd = new_cmd
                    log_fn(
                        f"Supervisor: membership.json holds epoch "
                        f"{plan.epoch} (world {plan.world_size}, "
                        f"{plan.reason}) — reshaped before the crash; "
                        f"restarting with --n-devices {plan.world_size}"
                    )
        delay, prev = decorrelated_delay(prev, backoff_base, backoff_max, rng)
        delay = round(delay, 3)
        if incidents is not None:
            incidents.append(
                cause,
                action="restart",
                attempt=attempt,
                rc=rc,
                target=target,
                backoff_s=delay,
                run_s=wall,
            )
        log_fn(
            f"Supervisor: attempt {attempt} exited rc={rc} ({cause}); "
            f"restarting in {delay:.2f}s "
            f"({max_restarts - budget_used} restart(s) left)"
        )
        sleep(delay)
        attempt += 1
        budget_used += 1
