"""FlightRecorder — structured per-step telemetry (``metrics.jsonl``).

Every subsystem built in PRs 5-10 left its evidence in its own artifact
(incidents.jsonl, membership.json, tune_decision.json) while
the per-step signal that EXPLAINS them — loss, step wall, guard verdicts,
wire bytes, the aggregate mode actually in effect after a re-tune — lived
only as ephemeral stdout text. The recorder makes the run itself a
first-class artifact: one JSON line per training step appended to
``train_dir/metrics.jsonl`` with the IncidentLog discipline (append-only,
one ``write()`` per append, torn trailing lines skipped on read), pruned
in lockstep with the checkpoint timeline on rollback
(training.checkpoint.prune_after calls :func:`prune_metrics_after`).

Record kinds (every record carries ``kind``):

  ``step``  one training step: ``step``, ``loss``, ``step_ms`` (host wall
            per-step share — a superstep block's wall divided into K
            equal shares, the PR-9 detector precedent), guard
            ``skipped``/``dropped`` (+ ``ok_bits`` when elastic
            membership tracking is on), ``msg_bytes``/``dense_bytes``
            (the comm_model wire accounting), ``grad_norm`` (when the
            doctor tracks it), per-layer estimator-quality columns
            ``q_err2``/``q_rel`` (when ``--obs-quality`` is armed), the
            ``aggregate`` mode in effect (re-tunes become visible),
            ``epoch`` (membership) and ``generation`` (chaos/rollback),
            drift-detector state (``drift_ms``/``drift_hot``), and the
            rolling predicted-vs-measured calibration column
            (``predicted_ms``/``calib`` — comm_model.rolling_calibration,
            the autopilot's one-shot >2x warning as a tracked series),
            generalized PER FABRIC TIER when the tier decomposition is
            known (``calib_tiers`` — {tier label: blame-bound EMA}; see
            the ``predicted_tier_ms`` note on ``__init__``).
  ``log``   the reference worker line, structured: the SAME StepMetrics
            record the stdout line is formatted from
            (:func:`emit_worker_line` — one sink, so the two surfaces
            cannot disagree).
  ``meta``  one-off run context (the per-layer kept-byte split of
            ``--obs-quality``, obs/quality.quality_meta).

Cost contract: disarmed (recorder is None) the loops add ZERO new device
ops and the compiled programs are byte-identical; armed, the superstep
loops ride the one ``device_get`` per block they already perform, and the
per-step loops pay one fetch per step — the same surveillance price the
divergence doctor already set the precedent for.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Optional

from atomo_tpu.utils.tracing import MEMBERSHIP_EPOCH_ENV, read_jsonl

METRICS_FILE_NAME = "metrics.jsonl"

# metric keys copied verbatim (per-step scalar) into each ``step`` record
# when the fetched metrics dict carries them — absent keys are absent in
# the record too (the programs are not reshaped for the recorder's sake)
_SCALAR_KEYS = (
    "loss",
    "prec1",
    "prec5",
    "msg_bytes",
    "dense_bytes",
    "skipped",
    "dropped",
    "grad_norm",
    "ok_bits",
    "ef_res_norm",
    "quorum_kept",
    "stale_dropped",
)
# per-layer vector columns (the --obs-quality probes): recorded as lists
_VECTOR_KEYS = ("q_err2", "q_rel")


def metrics_path(train_dir: str) -> str:
    return os.path.join(train_dir, METRICS_FILE_NAME)


def resolve_predicted_ms(train_dir: Optional[str]) -> Optional[float]:
    """The calibration column's reference: the decision winner's
    predicted ms/step — from ``train_dir/controller_decision.json`` when
    the global controller solved (the superseding artifact), else
    ``tune_decision.json``, else None (no prediction -> no calibration
    column; the recorder never invents a model the run did not use)."""
    if not train_dir:
        return None
    from atomo_tpu.controller.artifact import controller_path
    from atomo_tpu.tuning.autopilot import decision_path

    doc = None
    for path in (controller_path(train_dir), decision_path(train_dir)):
        try:
            with open(path) as f:
                doc = json.load(f)
            break
        except (OSError, ValueError):
            continue
    win = (doc or {}).get("winner") or {}
    pred = win.get("predicted_ms_per_step")
    return float(pred) if isinstance(pred, (int, float)) and pred > 0 else None


def _env_membership_epoch() -> int:
    try:
        return int(os.environ.get(MEMBERSHIP_EPOCH_ENV, "0") or 0)
    except ValueError:
        return 0


def _sanitize(obj):
    """Non-finite floats -> None, recursively. Python's json.dumps would
    emit the non-standard ``NaN`` token, and the recorder's whole point
    is documenting exactly the runs where losses GO non-finite — a
    diverged step must not make the machine-readable artifact unparseable
    to strict consumers (jq, JSON.parse, non-Python pipelines)."""
    import math

    if isinstance(obj, float):
        return obj if math.isfinite(obj) else None
    if isinstance(obj, dict):
        return {k: _sanitize(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_sanitize(v) for v in obj]
    return obj


class FlightRecorder:
    """Append-only per-step telemetry stream (see module docstring).

    One recorder per run process. Context fields (``aggregate``, the
    membership ``epoch``, free-form extras) are set once via
    :meth:`set_context` and re-stamped onto every record; the loops
    update them at the same boundaries the state actually changes (a
    re-tune switches the aggregate column from its step onward).
    """

    def __init__(
        self,
        path: str,
        predicted_ms: Optional[float] = None,
        predicted_tier_ms: Optional[dict] = None,
    ):
        self.path = path
        self.predicted_ms = (
            float(predicted_ms)
            if predicted_ms is not None and predicted_ms > 0
            else None
        )
        # the per-TIER calibration column (the fabric-observatory lift of
        # the scalar `calib` series): {tier label: predicted comm ms} —
        # obs.fabric.predicted_tier_ms decomposes the winner's predicted
        # step over the fabric tiers it crosses. Per record the column
        # tracks the BLAME BOUND per tier: the ratio the tier's predicted
        # time would have to move by to explain the whole step-time
        # residual alone ((measured - (predicted - tier)) / tier, EMA'd).
        # A run on target keeps every tier's column at ~1; a drifting one
        # shows which tier CAN'T explain the excursion (ratio exploding
        # past plausibility) — the retuner's fabric re-probe then decides
        # for real. A bound, not a joint estimate — stated here and in
        # the README.
        self.predicted_tier_ms = {
            str(k): float(v)
            for k, v in (predicted_tier_ms or {}).items()
            if isinstance(v, (int, float)) and v > 0
        } if self.predicted_ms is not None else {}
        self._calib: Optional[float] = None
        self._calib_tiers: dict = {}
        self.context: dict = {"epoch": _env_membership_epoch()}
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)

    @classmethod
    def for_train_dir(
        cls,
        train_dir: str,
        predicted_ms: Optional[float] = None,
        predicted_tier_ms: Optional[dict] = None,
    ) -> "FlightRecorder":
        return cls(
            metrics_path(train_dir),
            predicted_ms=predicted_ms,
            predicted_tier_ms=predicted_tier_ms,
        )

    def set_context(self, **kw) -> "FlightRecorder":
        """Merge context fields stamped onto every subsequent record
        (None values delete the field)."""
        for k, v in kw.items():
            if v is None:
                self.context.pop(k, None)
            else:
                self.context[k] = v
        return self

    # -- writes ---------------------------------------------------------

    def _append_lines(self, records: list[dict]) -> None:
        if not records:
            return
        payload = "".join(
            json.dumps(_sanitize(r), allow_nan=False) + "\n"
            for r in records
        )
        try:
            with open(self.path, "a") as f:
                f.write(payload)
        except OSError as exc:
            # best-effort, the IncidentLog.append rationale: telemetry is
            # recorded exactly when the filesystem may be misbehaving and
            # must never crash the run it documents
            import warnings

            warnings.warn(f"flight recorder append failed: {exc}")

    def write_meta(self, meta: dict) -> None:
        """One-off run-context record (kind="meta") — e.g. the per-layer
        kept-byte split of --obs-quality (obs/quality.quality_meta).
        Idempotent per ``what``: a resumed or supervisor-restarted
        attempt re-arms the recorder against the SAME file (prune_past
        keeps step-less meta lines), and re-appending an identical meta
        every attempt would leave one duplicate per restart."""
        what = meta.get("what")
        if what is not None and any(
            r.get("kind") == "meta" and r.get("what") == what
            for r in read_jsonl(self.path)
        ):
            return
        self._append_lines(
            [{"kind": "meta", "ts": round(time.time(), 3), **meta}]
        )

    def record_block(
        self,
        first_step: int,
        metrics: Any,
        *,
        wall_s: Optional[float] = None,
        drift=None,
        generation: Optional[int] = None,
    ) -> list[dict]:
        """Append one ``step`` record per step of a fetched metrics dict.

        ``metrics`` is the host-side dict the loops already fetch: per-step
        scalars (the K=1 loops) or ``(K,)`` series / ``(K, L)`` per-layer
        series (the superstep block loops). ``wall_s`` is the host wall
        spanning the block; it is recorded as K EQUAL per-step shares
        (``step_ms``) — the same share convention the drift detector
        folds, so the recorded series is partition-consistent: the same
        run under any superstep block size produces the same number of
        records with the same total wall. ``drift`` is the online
        re-tuner's DriftState (or None); ``generation`` the doctor's
        chaos/rollback generation. Returns the records written.
        """
        import numpy as np

        losses = np.asarray(metrics["loss"]).reshape(-1)
        k = int(losses.size)
        if k == 0:
            return []
        share_ms = (float(wall_s) / k * 1e3) if wall_s is not None else None

        def col(name, i):
            v = metrics.get(name)
            if v is None:
                return None
            a = np.asarray(v)
            if a.ndim == 0:
                return a.item()
            if k == 1:
                # per-step-loop fetch: the whole leaf belongs to this step
                return a.item() if a.size == 1 else a
            return a[i]

        now = round(time.time(), 3)
        records = []
        for i in range(k):
            rec = {
                "kind": "step",
                "ts": now,
                "step": int(first_step) + i,
            }
            for name in _SCALAR_KEYS:
                v = col(name, i)
                if v is not None:
                    rec[name] = float(v)
            for name in _VECTOR_KEYS:
                v = col(name, i)
                if v is not None:
                    rec[name] = [
                        float(x) for x in np.asarray(v).reshape(-1)
                    ]
            if share_ms is not None:
                rec["step_ms"] = round(share_ms, 4)
                if self.predicted_ms is not None:
                    from atomo_tpu.utils.comm_model import (
                        rolling_calibration,
                    )

                    self._calib = rolling_calibration(
                        self._calib, share_ms / 1e3, self.predicted_ms / 1e3
                    )
                    rec["predicted_ms"] = self.predicted_ms
                    if self._calib is not None:
                        rec["calib"] = round(self._calib, 4)
                    if self.predicted_tier_ms:
                        for lbl, tms in self.predicted_tier_ms.items():
                            # the per-tier blame bound (__init__ note):
                            # attribute the whole residual to this tier
                            implied = share_ms - (
                                self.predicted_ms - tms
                            )
                            self._calib_tiers[lbl] = rolling_calibration(
                                self._calib_tiers.get(lbl),
                                implied / 1e3,
                                tms / 1e3,
                            )
                        tiers = {
                            lbl: round(v, 4)
                            for lbl, v in self._calib_tiers.items()
                            if v is not None
                        }
                        if tiers:
                            rec["calib_tiers"] = tiers
            if generation is not None:
                rec["generation"] = int(generation)
            if drift is not None:
                rec["drift_ms"] = round(float(drift.mean) * 1e3, 4)
                rec["drift_hot"] = int(drift.hot)
            rec.update(self.context)
            records.append(rec)
        self._append_lines(records)
        return records

    def record_log(self, step_metrics) -> dict:
        """Append the worker-line record (kind="log") — called ONLY by
        :func:`emit_worker_line`, the single sink that also formats the
        stdout line from the same record."""
        rec = {
            "kind": "log",
            "ts": round(time.time(), 3),
            **dataclasses.asdict(step_metrics),
        }
        # context minus the membership epoch: StepMetrics already has an
        # ``epoch`` field (the DATASET epoch) and the membership counter
        # must not silently overwrite it in the log record
        rec.update({k: v for k, v in self.context.items() if k != "epoch"})
        self._append_lines([rec])
        return rec

    # -- reads ----------------------------------------------------------

    @staticmethod
    def read(path: str) -> list[dict]:
        """Parse a metrics.jsonl; missing file = empty, torn trailing
        lines skipped (utils.tracing.read_jsonl — the incident-log
        discipline)."""
        return read_jsonl(path)

    @staticmethod
    def read_steps(path: str) -> list[dict]:
        """The kind="step" records only, in file order."""
        return [r for r in read_jsonl(path) if r.get("kind") == "step"]

    def prune_past(self, step: int) -> int:
        """Drop records past ``step`` from this recorder's own file —
        the RESUME hook: a crash-restart resumes from the last
        checkpoint and replays the steps above it, so the stale tail
        (written by the killed attempt past its last save) must be cut
        before the replay re-records those steps, or the timeline would
        hold duplicates. The rollback path gets the same cut via
        checkpoint.prune_after -> :func:`prune_metrics_after`."""
        return _prune_file_after(self.path, step)


def emit_worker_line(recorder: Optional[FlightRecorder], rec, log_fn=print):
    """The ONE worker-line sink: stdout and metrics.jsonl are fed from
    the SAME StepMetrics record, so the two surfaces cannot disagree —
    the reference's regex-parsed print format
    (StepMetrics.worker_line) and the structured json_line used to be
    formatted at independent call sites. With ``recorder`` None (the
    default, disarmed path) this is byte-identical to the historical
    ``log_fn(rec.worker_line())`` (golden-line regression tested)."""
    log_fn(rec.worker_line())
    if recorder is not None:
        recorder.record_log(rec)


def prune_metrics_after(train_dir: Optional[str], step: int) -> int:
    """Cut the metrics timeline in lockstep with the checkpoint timeline:
    drop every record whose ``step`` exceeds ``step`` (records without a
    step field — meta lines — are kept). Called by
    training.checkpoint.prune_after, so BOTH prune surfaces — the
    divergence doctor's rollback and the supervisor's rc=23 cut — prune
    metrics exactly when they prune checkpoints; a resume can never land
    on a metrics tail describing a discarded trajectory. Atomic rewrite
    (tmp + os.replace); torn trailing lines are dropped with the tail
    they belong to. Returns the number of records removed (0 when the
    file does not exist)."""
    if not train_dir:
        return 0
    return _prune_file_after(metrics_path(train_dir), step)


def _prune_file_after(path: str, step: int) -> int:
    if not os.path.exists(path):
        return 0
    recs = read_jsonl(path)
    keep = [
        r for r in recs
        if "step" not in r or int(r["step"]) <= int(step)
    ]
    removed = len(recs) - len(keep)
    if removed == 0:
        return 0
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            f.write("".join(json.dumps(r) + "\n" for r in keep))
        os.replace(tmp, path)
    except OSError as exc:
        import warnings

        warnings.warn(f"flight recorder prune failed: {exc}")
        return 0
    return removed
