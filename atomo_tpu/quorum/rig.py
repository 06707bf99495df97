"""The host-side quorum rig: schedule, wait, record, replay.

The compiled quorum step is schedule-agnostic — it consumes a per-step
(n_dev,) staleness-assignment vector as a traced input. This rig is the
single producer of that vector:

  * LIVE: derive it from the chaos ``slow@S:R:SEC`` table (a pure
    function of step — quorum.schedule), sleep the exposed wait the
    quorum floor implies (the rig OWNS the wait; the chaos blocking
    sleep ``maybe_sleep_replica`` stands down when a rig is armed),
    append the record to ``arrival_schedule.jsonl``;
  * REPLAY (``--replay-arrivals``): read the vectors back from a
    recorded schedule — wait-free, because the trajectory depends only
    on the vectors — and re-record them verbatim into this run's own
    artifact, so a replayed run's train_dir is as complete as the
    original's.

Every DROPPED entry lands one ``staleness_exceeded`` incident (action
'drop', the offending replica as target) — the 'never a silent stale
apply' half of the staleness contract, auditable by ``report``'s
``quorum_schedule_consistent`` check.
"""

from __future__ import annotations

import os
import time
from typing import Optional

import numpy as np

from atomo_tpu.quorum.artifact import (
    append_record,
    prune_schedule_after,
    read_schedule,
    schedule_path,
)
from atomo_tpu.quorum.schedule import DROPPED, staleness_vector


class QuorumRig:
    def __init__(
        self,
        config,
        *,
        n_dev: int,
        train_dir: Optional[str] = None,
        chaos=None,
        incidents=None,
        replay_path: Optional[str] = None,
        log_fn=print,
    ):
        if config.quorum > n_dev:
            raise ValueError(
                f"--quorum {config.quorum} exceeds the {n_dev}-replica "
                "mesh: a step can never collect more arrivals than there "
                "are replicas"
            )
        self.config = config
        self.n_dev = n_dev
        self.train_dir = train_dir
        self.incidents = incidents
        self.log_fn = log_fn
        self.faults = ()
        if chaos is not None and not chaos.membership_epoch:
            # die@'s epoch keying: a shrunken/re-grown world starts clean
            self.faults = chaos.config.slow_replica_faults
        self._replay: Optional[dict[int, dict]] = None
        if replay_path:
            meta, arrivals = read_schedule(replay_path)
            if not arrivals:
                raise ValueError(
                    f"--replay-arrivals {replay_path!r}: no arrival "
                    "records found (not a recorded quorum schedule?)"
                )
            self._check_meta(meta, replay_path)
            self._replay = arrivals
        self._own_path = None
        if train_dir:
            self._own_path = schedule_path(train_dir)
            rp = os.path.abspath(replay_path) if replay_path else None
            if rp == os.path.abspath(self._own_path):
                # replaying a dir's own schedule in place: reading and
                # re-appending the same file would duplicate every line
                self._own_path = None
            else:
                meta, _ = read_schedule(self._own_path)
                self._check_meta(meta, self._own_path)
                if meta is None:
                    append_record(self._own_path, self._meta_record())

    def _meta_record(self) -> dict:
        return {
            "kind": "meta",
            "what": "quorum_config",
            "quorum": self.config.quorum,
            "staleness": self.config.staleness,
            "n_replicas": self.n_dev,
            "period_s": self.config.period_s,
        }

    def _check_meta(self, meta: Optional[dict], path: str) -> None:
        """Refuse knobs that disagree with a recorded schedule: vectors
        derived under one (Q, K, N, period) silently mean something else
        under another — the decision_reusable discipline, applied to the
        arrival artifact itself."""
        if meta is None:
            return
        want = self._meta_record()
        for k in ("quorum", "staleness", "n_replicas", "period_s"):
            if meta.get(k) != want[k]:
                raise ValueError(
                    f"quorum schedule {path!r} was recorded with "
                    f"{k}={meta.get(k)!r} but this run sets {want[k]!r}; "
                    "match the recorded knobs or remove the artifact — "
                    "refusing to mix schedules"
                )

    def prune_past(self, step: int) -> None:
        """Resume discipline (the flight recorder's): cut the killed
        attempt's recorded tail past the restart checkpoint so the
        replayed steps re-record their lines instead of duplicating."""
        if self.train_dir and self._own_path is not None:
            prune_schedule_after(self.train_dir, step)

    def begin_step(self, step: int) -> np.ndarray:
        """Produce step ``step``'s staleness-assignment vector: sleep the
        exposed wait (live mode), record, incident every drop. Returns
        the (n_dev,) int32 vector the compiled step consumes."""
        if self._replay is not None:
            rec = self._replay.get(step)
            if rec is None:
                raise ValueError(
                    f"--replay-arrivals: recorded schedule has no step "
                    f"{step} — the replay ran past (or resumed before) "
                    "the recorded run's range"
                )
            sigma = [int(x) for x in rec["staleness"]]
            if len(sigma) != self.n_dev:
                raise ValueError(
                    f"--replay-arrivals: step {step} records "
                    f"{len(sigma)} replicas, this run has {self.n_dev}"
                )
            drops = [(r, None) for r, s in enumerate(sigma) if s == DROPPED]
        else:
            sigma, exposed, drops = staleness_vector(
                step,
                n_dev=self.n_dev,
                quorum=self.config.quorum,
                staleness=self.config.staleness,
                faults=self.faults,
                period_s=self.config.period_s,
            )
            if exposed > 0:
                # the rig owns the straggler wait: Q-th-arrival exposure,
                # not the blocking max — this sleep IS the cost a blocking
                # step pays as max(delays) (comm_model.quorum_exposed_wait_s)
                time.sleep(exposed)
            rec = {
                "kind": "arrival",
                "step": step,
                "staleness": list(sigma),
                "kept": sum(1 for s in sigma if s >= 0),
                "dropped": sum(1 for s in sigma if s == DROPPED),
                "exposed_wait_ms": round(exposed * 1e3, 3),
            }
        if self._own_path is not None:
            append_record(self._own_path, rec)
        if self.incidents is not None:
            for rep, avail in drops:
                detail = {"bound": self.config.staleness}
                if avail is not None:
                    detail["available_staleness"] = avail
                self.incidents.append(
                    "staleness_exceeded",
                    action="drop",
                    step=step,
                    target=rep,
                    **detail,
                )
        return np.asarray(sigma, np.int32)
