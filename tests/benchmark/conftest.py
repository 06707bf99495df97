"""Tests of the benchmark (BENCHMARK.json and benchmarks/). They live in a
directory of their own under tests/ so that the tier-1 command collects them
and BENCHMARK.json can list the directory under `paths`."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


@pytest.fixture
def rehearsal_args():
    """run.py's arguments for a whole run at the files' tiny sizes, with the
    harness's look for a chip skipped."""
    from benchmarks import run

    def args(cell, seed=7, seconds=1.0, trace=0):
        return run.parse(["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
                          "--trace", str(trace), "--rehearse"])

    return args
