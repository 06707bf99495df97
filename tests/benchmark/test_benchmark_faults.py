"""The faults a training cell can have, planted under the timed path at the
files' tiny sizes on the CPU: each has to come out of a whole run of run.py as
`correct: false`. The harness's look for a chip is skipped (`--rehearse`); the
rest of the run is the one the chip sees."""

import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


def _copy(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(jnp.copy, tree)


def _plant_in_lm(monkeypatch, fault):
    """A fault in the lm program, beneath the adapter's probe."""
    import jax.numpy as jnp

    import atomo_tpu.parallel.model_axes as model_axes

    real = model_axes.build_model_axis_program

    def build(*args, **kwargs):
        prog = real(*args, **kwargs)

        def step(state, key, tokens):
            if fault == "half_batch":  # half of the batch left out, the mean over the rest
                half = tokens.shape[0] // 2
                tokens = jnp.concatenate([tokens[:half], tokens[:half]])
            if fault == "state_unchanged":
                _, metrics = prog.step(_copy(state), key, tokens)
                return state, metrics
            return prog.step(state, key, tokens)

        return prog._replace(step=step)

    monkeypatch.setattr(model_axes, "build_model_axis_program", build)
    return lambda: model_axes.build_model_axis_program is build


def _plant_in_train(monkeypatch, fault):
    """The same faults in the single-device train step (rows are axis 1 of a
    superstep block)."""
    import jax.numpy as jnp

    import atomo_tpu.training.trainer as trainer

    real = trainer.make_train_step

    def make(*args, **kwargs):
        inner = real(*args, **kwargs)

        def step(state, key, images, labels):
            if fault == "half_batch":
                half = labels.shape[-1] // 2
                images = jnp.concatenate([images[:, :half], images[:, :half]], axis=1)
                labels = jnp.concatenate([labels[:, :half], labels[:, :half]], axis=1)
            if fault == "state_unchanged":
                _, metrics = inner(_copy(state), key, images, labels)
                return state, metrics
            return inner(state, key, images, labels)

        return step

    monkeypatch.setattr(trainer, "make_train_step", make)
    return lambda: trainer.make_train_step is make


PLANTERS = {"lm": _plant_in_lm, "train": _plant_in_train}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
@pytest.mark.parametrize("cell", CELLS)
def test_a_fault_under_the_timed_path_comes_out_as_not_correct(cell, fault, monkeypatch,
                                                               rehearsal_args):
    from benchmarks import run

    data = run.Data(ROOT / "BENCHMARK.json")
    adapter = data.config(data.cell(cell)["config"])["adapter"]
    still_planted = PLANTERS[adapter](monkeypatch, fault)
    result = run.run_cell(rehearsal_args(cell, seed=11))
    assert still_planted()  # the adapter put back what it found, not the program's original
    assert result["correct"] is False, result["compared"]
    if fault == "state_unchanged":
        assert result["compared"]["change_gap"]["value"] == pytest.approx(1.0)
