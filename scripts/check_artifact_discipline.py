#!/usr/bin/env python
"""Artifact-writer lint — the one-discipline rule, enforced.

Every evidence artifact a run writes under its ``train_dir`` (membership,
tune decision, run report, lr grid, ...) must go through
``utils.tracing.write_json_atomic`` (tmp + os.replace — readers never see
a torn file, even under SIGKILL) or the append-only line discipline of
``IncidentLog``/``FlightRecorder`` (one ``write()`` of newline-terminated
lines). That rule used to be remembered; this lint makes it enforced:

  * inside ``atomo_tpu/`` any bare ``json.dump(...)`` call is rejected
    unless it is the ``write_json_atomic`` implementation itself
    (utils/tracing.py) — the package owns every train_dir artifact, so a
    direct dump there is a discipline escape by construction;
  * in ``scripts/`` a ``json.dump`` whose argument
    expressions mention a train_dir path is rejected (those entrypoints
    legitimately write repo-level artifacts/ files with their own
    atomicity story, which stays out of scope — the rule is about the
    artifacts the robustness stack drills kills against).

Wired into scripts/tier1.sh AND run as a tier-1 test
(tests/test_artifact_discipline.py), so both verification surfaces gate
on it. Exit 0 = clean, 1 = violations (printed one per line).
"""

from __future__ import annotations

import ast
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the write_json_atomic implementation and the IncidentLog append are the
# discipline, not an escape from it
ALLOWED_IN_PACKAGE = {os.path.join("atomo_tpu", "utils", "tracing.py")}


def _is_json_dump(node: ast.Call) -> bool:
    f = node.func
    return (
        isinstance(f, ast.Attribute)
        and f.attr == "dump"
        and isinstance(f.value, ast.Name)
        and f.value.id == "json"
    )


def _mentions_train_dir(node: ast.Call) -> bool:
    try:
        text = ast.unparse(node)
    except Exception:  # pragma: no cover - unparse of exotic nodes
        return True  # can't prove it's safe -> flag it
    return "train_dir" in text


def scan_file(path: str, rel: str) -> list[str]:
    with open(path) as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=rel)
    except SyntaxError as exc:
        return [f"{rel}: unparseable ({exc})"]
    in_package = rel.startswith("atomo_tpu" + os.sep)
    out = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and _is_json_dump(node)):
            continue
        if in_package:
            if rel in ALLOWED_IN_PACKAGE:
                continue
            out.append(
                f"{rel}:{node.lineno}: json.dump inside the package — "
                "train_dir artifacts must go through write_json_atomic "
                "or IncidentLog/FlightRecorder appends"
            )
        elif _mentions_train_dir(node):
            out.append(
                f"{rel}:{node.lineno}: json.dump to a train_dir path — "
                "use atomo_tpu.utils.tracing.write_json_atomic"
            )
    return out


def collect_violations(repo: str = REPO) -> list[str]:
    targets = []
    for base, _dirs, files in os.walk(os.path.join(repo, "atomo_tpu")):
        if "__pycache__" in base:
            continue
        targets += [os.path.join(base, f) for f in files if f.endswith(".py")]
    sdir = os.path.join(repo, "scripts")
    if os.path.isdir(sdir):
        targets += [
            os.path.join(sdir, f)
            for f in os.listdir(sdir)
            if f.endswith(".py")
        ]
    violations = []
    for path in sorted(targets):
        violations += scan_file(path, os.path.relpath(path, repo))
    return violations


def main() -> int:
    violations = collect_violations()
    if violations:
        print("artifact-discipline lint FAILED:")
        for v in violations:
            print("  " + v)
        return 1
    print("artifact-discipline lint OK (json.dump bypasses: none)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
