"""Read on the chip what each limit of a cell's `correct` is set from
("How correct is decided", steps 3 to 5): the lower readings, which are the
program against the reference over a dozen seeds, and the upper ones, which
are the control (the reference in the nearest lower precision, put in the
program's place) and the faults a training cell can have, over a few seeds.
All in one process, because set-up is most of a run; each reading is one JSON
line, and the last line sums them up. The benchmark's own runs never call this.

    python benchmarks/limits.py --workload <cell> --seeds 101,102,103 --upper-seeds 3
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def half_batch(batch):
    """Half of the batch left out, the mean taken over the rest: the same as
    feeding the first half twice."""
    import numpy as np

    if isinstance(batch, (tuple, list)):
        axis = np.asarray(batch[1]).ndim - 1  # the row axis: last of the labels'
        return tuple(_repeat_first_half(np.asarray(x), axis) for x in batch)
    return _repeat_first_half(np.asarray(batch), 0)


def _repeat_first_half(x, axis):
    import numpy as np

    half = x.shape[axis] // 2
    first = np.take(x, range(half), axis=axis)
    return np.concatenate([first, first], axis=axis)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--upper-seeds", type=int, default=3)
    parser.add_argument("--seconds", type=float, default=2.0)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)

    from benchmarks import check, run

    values = lambda numbers: {k: v["value"] for k, v in numbers.items()}  # noqa: E731
    lower: dict[str, list] = {}
    upper: dict[str, dict[str, list]] = {}
    for index, seed in enumerate(int(s) for s in args.seeds.split(",")):
        keep: dict = {}
        run_args = run.parse(["--workload", args.workload, "--seed", str(seed),
                              "--seconds", str(args.seconds)] + (["--rehearse"] * args.rehearse))
        result = run.run_cell(run_args, keep=keep)
        sound = values(result["compared"])
        print(json.dumps({"seed": seed, "kind": "program", "numbers": sound}), flush=True)
        for name, value in sound.items():
            lower.setdefault(name, []).append(value)
        reference, config, probe = keep["reference"], keep["config"], keep["probe"]
        follow = lambda batches, **how: reference.train_steps(  # noqa: E731
            reference.init_params(config, seed), batches, config, flags=keep["flags"], **how)
        # read, not compared: the worst leaf, which a randomised codec makes swing
        worst, where = check.worst_leaf_gap(probe.change_norms, keep["ref"]["change_norms"])
        print(json.dumps({"seed": seed, "kind": "program_worst_leaf_change", "gap": worst, "at": where}), flush=True)
        if index >= args.upper_seeds:
            continue
        trials = {
            **{f"control_{mode}": (lambda mode=mode: follow(probe.batches, mode=mode, draws=1))
               for mode in reference.CONTROLS},
            "half_batch": lambda: follow([half_batch(b) for b in probe.batches], draws=1),
        }
        for kind, trial in trials.items():
            stood_in = {**trial(), "msg_bytes": probe.counters.get("msg_bytes")}
            if not probe.one_step_per_call:
                stood_in["grad1_norms"] = None  # as hidden as the program's own inside a block
            got = values(check.training_numbers(stood_in, keep["ref"]))
            print(json.dumps({"seed": seed, "kind": kind, "numbers": got}), flush=True)
            for name, value in got.items():
                upper.setdefault(kind, {}).setdefault(name, []).append(value)
    summary = {
        "workload": args.workload,
        "lower": {k: max(v) for k, v in lower.items()},
        "lower_all": lower,
        "upper": {kind: {k: min(v) for k, v in per.items()} for kind, per in upper.items()},
    }
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
