"""Tuning — the knob-selection subsystem.

Grew out of the single-file LR grid search (src/tune.sh parity) into a
package when PR 7 added the performance autopilot:

  * :mod:`gridsearch` — the reference's LR grid search (regex log contract
    kept), now recording its results as a JSON artifact through the shared
    probe ladder.
  * :mod:`probe` — the measured-probe runner the autopilot and the grid
    search share: fenced short-run timing of a candidate step program,
    with every completed row written atomically
    (``write_json_atomic``).
  * :mod:`autopilot` — ``--auto tune``: predict a ranked candidate list
    from the comm model, probe the top of it, pick the knob vector, write
    the ``tune_decision.json`` decision artifact, and re-tune online when
    the step-time drift detector fires.

The historical ``atomo_tpu.tuning`` import surface is preserved here.
"""

from atomo_tpu.tuning.gridsearch import (  # noqa: F401
    DEFAULT_GRID,
    WORKER_LINE_RE,
    TuneResult,
    grid_search,
    parse_worker_lines,
)
