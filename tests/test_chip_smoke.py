"""chip_smoke.py's own contract, checked without a chip: the parent stays
off jax, a child on another platform is stopped and fails the run, no
failure ever prints a result line, and the dense-size table matches the
models it names. The passing path needs the TPU (run it through the chip
tool; `--dry-run` walks the same phases on the CPU)."""

import ast
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

FAKE_CHILD = """
import os, sys, time
open(sys.argv[1], "w").write(str(os.getpid()))
print('Device: {"platform": "%s", "kind": "fake", "count": 1, "mesh": {}}', flush=True)
time.sleep(%d)
"""


def _alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    # a killed child of this process may linger as a zombie until reaped
    with open(f"/proc/{pid}/stat") as f:
        return f.read().split(")")[-1].split()[0] != "Z"


def test_parent_imports_neither_jax_nor_the_package():
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        tree = ast.parse(f.read())
    roots = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            roots |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            roots.add((node.module or "").split(".")[0])
    assert roots <= set(sys.stdlib_module_names) | {"__future__"}, roots


@pytest.mark.parametrize("platform,sleep_s,why", [
    ("cpu", 60, "not 'tpu'"),        # wrong platform: stopped at once
    ("tpu", 0, "logged steps []"),   # right platform, but it took no steps
])
def test_failure_exits_nonzero_and_prints_no_result(
    tmp_path, monkeypatch, capsys, platform, sleep_s, why
):
    fake = tmp_path / "fake_child.py"
    fake.write_text(FAKE_CHILD % (platform, sleep_s))
    pidfile = tmp_path / "pid"
    # ENTRY + argv: the fake takes the pidfile where "train" would be
    monkeypatch.setattr(
        chip_smoke, "ENTRY", [sys.executable, str(fake), str(pidfile)]
    )
    t0 = time.monotonic()
    rc = chip_smoke.main(["--out", str(tmp_path / "out")])
    assert rc != 0
    assert time.monotonic() - t0 < 20
    cap = capsys.readouterr()
    assert why in cap.err and "FAILED" in cap.err
    assert '"ok"' not in cap.out
    assert not _alive(int(pidfile.read_text()))
    assert not os.path.exists(tmp_path / "out" / "run")  # checkpoints cleaned


def test_dense_mb_table_matches_the_models():
    from atomo_tpu.models import get_model

    for name, shape in (("ResNet18", (1, 32, 32, 3)), ("LeNet", (1, 28, 28, 1))):
        model = get_model(name.lower(), 10)
        variables = jax.eval_shape(
            lambda m=model, s=shape: m.init(
                jax.random.PRNGKey(0), jnp.zeros(s), train=False
            )
        )
        n = sum(
            math.prod(leaf.shape)
            for leaf in jax.tree_util.tree_leaves(variables["params"])
        )
        assert chip_smoke.DENSE_MB[name] == n * 4 / 2**20
