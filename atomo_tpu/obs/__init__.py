"""Observability subsystem — flight recorder (PR 11) + fabric observatory.

Five layers over the evidence artifacts PRs 5-12 established:

  * :mod:`~atomo_tpu.obs.recorder` — ``FlightRecorder``: one JSON line
    per training step into ``train_dir/metrics.jsonl`` (the IncidentLog
    append/torn-line discipline), carrying the per-step signal that used
    to exist only as ephemeral stdout text — loss, step wall, guard
    verdicts, wire bytes, the aggregate mode actually in effect — plus a
    rolling predicted-vs-measured calibration column, tracked per fabric
    tier when the tier decomposition is known.
  * :mod:`~atomo_tpu.obs.quality` — opt-in in-graph estimator-quality
    probes (``--obs-quality``): per-layer compression error of the
    codec's unbiased estimator inside the fused step, the data feed the
    adaptive variance-budget work (ROADMAP open item 5) consumes.
  * :mod:`~atomo_tpu.obs.fabric` — the measured fabric: a startup probe
    that times fenced ``ppermute``/``all_gather`` ladders per tier on
    the real mesh, records ``train_dir/fabric_probe.json``, and resolves
    ``--fabric measured`` so every prediction prices from measurement
    instead of a named preset (ROADMAP: "measure the fabric instead of
    naming it"). Also the drift-blame re-probe the online retuner uses.
  * :mod:`~atomo_tpu.obs.timeline` — ``report timeline``: per-step
    encode/exchange/decode/compute phase spans parsed from a
    ``--profile-dir`` trace (the ``named_phase`` scopes inside the fused
    step), joined against metrics.jsonl — the live exposed-vs-hidden
    attribution of the shipped programs.
  * :mod:`~atomo_tpu.obs.report` — join metrics.jsonl + incidents.jsonl
    + membership.json + tune_decision.json + fabric_probe.json into one
    time-ordered ``run_report.json`` with cross-artifact consistency
    checks (the ``report`` CLI verb).
"""

from atomo_tpu.obs.recorder import (  # noqa: F401
    METRICS_FILE_NAME,
    FlightRecorder,
    emit_worker_line,
    metrics_path,
    prune_metrics_after,
)
from atomo_tpu.obs.fabric import (  # noqa: F401
    FABRIC_PROBE_NAME,
    probe_fabric,
    probe_path,
    read_fabric_probe,
)
