"""The documents and recipes name only commands the program accepts.

  * every ``python -m atomo_tpu ...`` command in a fenced block of
    README.md parses with ``cli.build_parser()`` and, for ``train``,
    passes ``cli._argv_preflight`` (the argv-knowable conflict matrix);
  * the flags of the four recipe scripts, with their ``${VAR:-default}``
    defaults filled in, parse the same way, and ``run_tpu.sh`` states the
    recipe ``benchmarks/configs/resnet18-cifar10.json`` names it as the
    source of;
  * every script under ``scripts/`` that has a parser answers ``--help``
    with exit 0, so none imports something that is gone.

No backend is touched: parsing and preflight run before jax initialises.
"""

import json
import os
import re
import shlex
import subprocess
import sys

import pytest

from atomo_tpu.cli import _argv_preflight, build_parser

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VERBS = {"train", "evaluate", "tune", "lm", "report"}
RECIPES = ("run_tpu.sh", "run_lm_tpu.sh", "tune_tpu.sh", "evaluate_tpu.sh")
SCRIPTS_WITH_PARSER = (
    "bf16_probe", "comm_crossover", "convergence_artifact",
    "encode_profile", "lm_convergence_artifact", "scenario_table",
    "supervise", "svdecay_artifact",
)


def _program_argv(command: str):
    """The argv after ``python -m atomo_tpu[.cli]`` in one shell command
    (env assignments before it and a trailing ``"$@"`` dropped), or None
    when the command runs another module."""
    words = shlex.split(command, comments=True)
    for i, w in enumerate(words[:-2]):
        if w.startswith("python") and words[i + 1] == "-m":
            if words[i + 2] not in ("atomo_tpu", "atomo_tpu.cli"):
                return None
            return [a for a in words[i + 3:] if a != "$@"]
    return None


def _commands(text: str):
    """Shell commands of ``text`` with backslash continuations joined."""
    joined = re.sub(r"\\\n\s*", " ", text)
    return [ln.strip() for ln in joined.splitlines() if "-m atomo_tpu" in ln]


def _readme_commands():
    with open(os.path.join(REPO, "README.md")) as f:
        blocks = re.findall(r"^```[a-z]*\n(.*?)^```", f.read(), re.S | re.M)
    out = []
    for block in blocks:
        for cmd in _commands(block):
            argv = _program_argv(cmd)
            if argv is not None:
                out.append(argv)
    return out


def _fill_defaults(text: str) -> str:
    return re.sub(r"\$\{\w+:-([^}]*)\}", r"\1", text)


def _accepts(argv):
    """What ``cli.main`` does with ``argv`` before any work starts."""
    if argv and argv[0] not in VERBS:
        argv = ["train"] + argv  # bare flags behave like the reference CLI
    args = build_parser().parse_args(argv)
    if argv[0] == "train":
        _argv_preflight(args)
    return args


README_COMMANDS = _readme_commands()


def test_readme_holds_commands_to_check():
    assert len(README_COMMANDS) >= 12


@pytest.mark.parametrize(
    "argv", README_COMMANDS, ids=[" ".join(a)[:70] for a in README_COMMANDS]
)
def test_readme_command_parses_and_passes_preflight(argv):
    _accepts(argv)


def _recipe_argv(name):
    with open(os.path.join(REPO, "scripts", name)) as f:
        (cmd,) = _commands(_fill_defaults(f.read()))
    return _program_argv(cmd)


@pytest.mark.parametrize("name", RECIPES)
def test_recipe_script_flags_parse(name):
    _accepts(_recipe_argv(name))


def test_run_tpu_recipe_is_the_benchmark_configurations_source():
    """``benchmarks/configs/resnet18-cifar10.json`` names
    ``scripts/run_tpu.sh`` as its source: the two state one recipe."""
    args = _accepts(_recipe_argv("run_tpu.sh"))
    with open(os.path.join(
        REPO, "benchmarks", "configs", "resnet18-cifar10.json"
    )) as f:
        cfg = json.load(f)
    with open(os.path.join(
        REPO, "benchmarks", "traffic", "1chip-svd3.json"
    )) as f:
        mix = json.load(f)
    assert "scripts/run_tpu.sh" in cfg["source"]
    assert (args.network, args.dataset) == (cfg["network"], cfg["dataset"])
    assert (args.lr, args.momentum, args.lr_shrinkage) == (
        cfg["lr"], cfg["momentum"], cfg["lr_shrinkage"]
    )
    assert args.code == mix["flags"]["--code"]
    assert args.svd_rank == cfg["svd_rank"]


@pytest.mark.parametrize("name", SCRIPTS_WITH_PARSER)
def test_script_help_runs(name):
    res = subprocess.run(
        [sys.executable, os.path.join("scripts", name + ".py"), "--help"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert res.returncode == 0, res.stderr[-2000:]
    assert "usage" in res.stdout.lower()
