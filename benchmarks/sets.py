"""Make the two sets of runs a bound is set from, as the contract's `bound`
paragraph says, and print each metric's spread: for a cell, two sets of
`--runs` runs with the same seeds in both, every run a new process of the
benchmark's own command, and for each metric the wider of the two sets'
spreads, a spread being the distance between the first and third quartile
(`statistics.quantiles(values, n=4)`) as a share of the median.

    chiprun -- python3 benchmarks/sets.py --workload <cell> [--cold-first]

This process never touches JAX: the chip belongs to each child in turn. With
`--cold-first` the compile cache is the checkout's own `.jax_cache/`, emptied
before each set, so that each set's first run compiles as the driver's does;
its set-up is printed apart and left out of `setup_s`'s spread.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
SEEDS = (7, 2147483900, 1234567, 40, 3000000017, 99991, 2025, 86243)


def spread(values):
    if len(values) < 2:
        return float("nan")
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--runs", type=int, default=6)
    parser.add_argument("--sets", type=int, default=2)
    parser.add_argument("--trace", type=int, default=0)
    parser.add_argument("--seconds", type=int, default=None)
    parser.add_argument("--cold-first", action="store_true")
    args = parser.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    seconds = args.seconds or bench["run_seconds"]
    env = dict(os.environ)
    if args.cold_first:
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
    sets: list[dict[str, list[float]]] = []
    for s in range(args.sets):
        if args.cold_first:
            shutil.rmtree(ROOT / ".jax_cache", ignore_errors=True)
        per_metric: dict[str, list[float]] = {}
        for r, seed in enumerate(SEEDS[: args.runs]):
            done = subprocess.run(
                [*bench["command"], "--workload", args.workload, "--seed", str(seed),
                 "--seconds", str(seconds), "--trace", str(args.trace)],
                cwd=ROOT, env=env, capture_output=True, text=True,
            )
            lines = done.stdout.strip().splitlines()
            if done.returncode or not lines:
                print(f"set {s} run {r} seed {seed}: exit {done.returncode}\n{done.stderr[-3000:]}", flush=True)
                return 1
            line = json.loads(lines[-1])
            got = {k: v["value"] for k, v in line["metrics"].items()}
            print(json.dumps({"set": s, "run": r, "seed": seed, "correct": line["correct"],
                              "attempted": line["attempted"], "metrics": got,
                              "device": line["device"], "compared": {k: v["value"] for k, v in line["compared"].items()},
                              **({"breakdown": line["breakdown"]} if "breakdown" in line else {})}), flush=True)
            if not line["correct"]:
                print(done.stderr[-2000:], flush=True)
            for name, value in got.items():
                if name == "setup_s" and r == 0 and args.cold_first:
                    print(json.dumps({"set": s, "first_setup_s": value}), flush=True)
                    continue
                per_metric.setdefault(name, []).append(value)
        sets.append(per_metric)
    summary = {}
    for name in sets[0]:
        spreads = [spread(per[name]) for per in sets]
        summary[name] = {
            "medians": [statistics.median(per[name]) for per in sets],
            "spreads": spreads, "widest": max(spreads), "five_times": 5 * max(spreads),
        }
    print(json.dumps({"workload": args.workload, "seconds": seconds, "summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
