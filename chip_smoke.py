#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the trainer still starts on the chip.

Drives the main path once, at the full width of the paper's recipe
(ResNet-18, CIFAR-10 shapes, batch 128, svd rank 3, lr 0.01, momentum 0,
synthetic data from a seed), through ``python -m atomo_tpu`` and nothing
else — the entry points a user calls:

  train       --n-devices 1: steps through the default superstep, in-loop
              eval, compressed checkpoints (the native codec is built from
              lossless.cc on the way)
  resume      a second process continues from the saved step
  evaluate    a third process re-scores the checkpoints; its losses must
              agree with the trainer's own in-loop validation lines
  supervised  --max-restarts 1 --chaos kill@12: the restarted child must
              get the chip back from a parent that never touched it
  dp4-*       only when four chips are visible: svd/gather (with eval, a
              checkpoint and a resuming second process), qsgd/ring and
              the dense psum twin on a dp4 mesh, each proving four
              distinct devices hold state and batch shards

This process imports neither jax nor atomo_tpu: a chip belongs to one
process at a time, so the parent stays off the backend and runs its
children one after another. Every fact it reports is read from a child's
own output (the ``Device:`` / ``Placement:`` lines, the log lines, the
compile-cache report). Any phase that fails makes the exit code non-zero
and no result line is printed; a child that reports a platform other than
``tpu`` is stopped at once.

Last line of stdout on success:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

``--dry-run`` walks the same phases at LeNet size on a forced 4-device CPU
mesh to debug the control flow before a chip call. It never prints an
``ok`` line: a CPU run is not a chip pass.

Writes only under ``--out`` (default ./chip_smoke_out: logs/, summary.json
and a run/ directory of checkpoints that is removed at the end); the
children keep their compile cache where utils/compile_cache.py says.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ENTRY = [sys.executable, "-u", "-m", "atomo_tpu"]
DEADLINE_S = 1150  # the contract allows 1200 s, compilation included
PHASE_TIMEOUT_S = 480

# f32 parameter bytes of each model at 10 classes — what a dense exchange
# would move per step (pinned to the models by tests/test_chip_smoke.py)
DENSE_MB = {
    "ResNet18": 11_173_962 * 4 / 2**20,
    "LeNet": 431_080 * 4 / 2**20,
}

WORKER_RE = re.compile(
    r"^Worker: 0, Step: (\d+), .*?Loss: ([^,]+), .*?Msg\(MB\):\s*([^,]+),"
)
VALID_RE = re.compile(r"^(Validation|Evaluator): Step: (\d+), Loss: ([^,]+),")
RESUMED_RE = re.compile(r"^Resumed from (.+) at step (\d+)")
CACHE_RE = re.compile(
    r"^XLA compilation cache: (\d+) hits, (\d+) misses, ([0-9.]+) s compiling"
)
SHOWN = ("Device: ", "Placement: ", "Worker: ", "Validation: ", "Evaluator: ",
         "Resumed from ", "Supervisor: ", "CHAOS: ", "XLA compilation cache: ")


class PhaseFailed(Exception):
    pass


class Phase:
    """One child process that exited 0: its parsed output, and checks
    that raise."""

    def __init__(self, name: str, out_lines: list[str], seconds: float):
        self.name, self.seconds = name, seconds
        self.devices, self.placements = [], []
        self.losses, self.msg_mb, self.validation = {}, {}, {}
        self.resumed_at = None
        self.cache = {"hits": 0, "misses": 0, "compile_s": 0.0}
        self.text = "".join(out_lines)
        for line in out_lines:
            if line.startswith("Device: "):
                self.devices.append(json.loads(line[len("Device: "):]))
            elif line.startswith("Placement: "):
                self.placements.append(json.loads(line[len("Placement: "):]))
            elif m := WORKER_RE.match(line):
                self.losses[int(m[1])] = float(m[2])
                self.msg_mb[int(m[1])] = float(m[3])
            elif m := VALID_RE.match(line):
                self.validation[int(m[2])] = float(m[3])
            elif m := RESUMED_RE.match(line):
                self.resumed_at = int(m[2])
            elif m := CACHE_RE.match(line):
                self.cache["hits"] += int(m[1])
                self.cache["misses"] += int(m[2])
                self.cache["compile_s"] += float(m[3])

    def need(self, cond: bool, why: str) -> None:
        if not cond:
            raise PhaseFailed(f"{self.name}: {why}")

    def check_common(self) -> None:
        """run_child already failed a non-zero exit and any ``Device:``
        line on another platform; what is left is that the child said what
        it ran on at all, and that the native codec built."""
        self.need(bool(self.devices), "no 'Device:' line in the child's output")
        self.need(
            "checkpoint compression unavailable" not in self.text,
            "the native checkpoint codec did not build "
            "('checkpoint compression unavailable')",
        )

    def check_losses(self, steps: list[int], dense_mb: float, code: str) -> None:
        self.need(
            sorted(self.losses) == steps,
            f"logged steps {sorted(self.losses)}, expected {steps}",
        )
        for s in steps:
            self.need(
                math.isfinite(self.losses[s]) and 0.0 < self.losses[s] < 50.0,
                f"step {s} loss {self.losses[s]}",
            )
            if code == "sgd":
                self.need(
                    abs(self.msg_mb[s] - dense_mb) < 0.01,
                    f"step {s} dense Msg(MB) {self.msg_mb[s]} != {dense_mb:.4f}",
                )
            else:
                self.need(
                    0.0 < self.msg_mb[s] < dense_mb,
                    f"step {s} Msg(MB) {self.msg_mb[s]} not below dense "
                    f"{dense_mb:.4f}",
                )

    def check_spread(self, n: int) -> None:
        self.need(bool(self.placements), "no 'Placement:' line")
        self.need(
            self.devices[0]["mesh"] == {"dp": n},
            f"mesh {self.devices[0]['mesh']}, expected dp{n}",
        )
        p = self.placements[0]
        for key in ("state_devices", "batch_devices"):
            self.need(
                len(set(p[key])) == n,
                f"{key} {p[key]}: not {n} distinct devices",
            )
        if self.devices[0]["platform"] == "tpu":
            in_use = p["bytes_in_use"]
            self.need(
                len(in_use) == n and all(v and v > 0 for v in in_use.values()),
                f"bytes_in_use {in_use}: not non-zero on {n} devices",
            )

    def record(self) -> dict:
        return {
            "name": self.name, "seconds": round(self.seconds, 1),
            "devices": self.devices, "placements": self.placements,
            "losses": {str(k): v for k, v in sorted(self.losses.items())},
            "validation": {str(k): v for k, v in sorted(self.validation.items())},
            "cache": self.cache,
        }


def _kill_group(proc: subprocess.Popen) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass


def run_child(name: str, argv: list[str], *, env: dict, log_dir: str,
              platform: str, timeout_s: float) -> Phase:
    """Run one child to its end in its own process group, teeing its
    output to a log. Stops it early on a wrong platform or the timeout;
    nothing it started outlives this call."""
    print(f"[{name}] $ python -m atomo_tpu {' '.join(argv)}", flush=True)
    t0 = time.monotonic()
    proc = subprocess.Popen(
        ENTRY + argv, cwd=HERE, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, start_new_session=True,
    )
    timed_out = threading.Event()

    def _on_timeout():
        timed_out.set()
        _kill_group(proc)

    timer = threading.Timer(max(timeout_s, 1.0), _on_timeout)
    timer.start()
    lines, wrong = [], None
    try:
        with open(os.path.join(log_dir, name + ".log"), "w") as log:
            for line in proc.stdout:
                log.write(line)
                lines.append(line)
                if line.startswith(SHOWN):
                    print(f"[{name}] {line.rstrip()[:300]}", flush=True)
                if line.startswith("Device: ") and wrong is None:
                    # every Device line: a supervised run prints one per child
                    got = json.loads(line[len("Device: "):])["platform"]
                    if got != platform:
                        wrong = got
                        _kill_group(proc)
        rc = proc.wait()
    finally:
        timer.cancel()
        _kill_group(proc)  # a supervised child's own children included
    if wrong is not None:
        raise PhaseFailed(
            f"{name}: child ran on platform {wrong!r}, not {platform!r} — "
            "stopped it"
        )
    if timed_out.is_set():
        raise PhaseFailed(f"{name}: no end after {timeout_s:.0f} s — stopped it")
    if rc != 0:
        tail = "".join(lines[-15:])
        raise PhaseFailed(f"{name}: exit code {rc}\n{tail}")
    phase = Phase(name, lines, time.monotonic() - t0)
    print(f"[{name}] done in {phase.seconds:.1f} s", flush=True)
    return phase


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(HERE, "chip_smoke_out"))
    ap.add_argument("--dry-run", action="store_true",
                    help="LeNet on a forced 4-device CPU mesh; never a pass")
    args = ap.parse_args(argv)
    t_start = time.monotonic()
    out = os.path.abspath(args.out)
    run_dir, log_dir = os.path.join(out, "run"), os.path.join(out, "logs")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir, exist_ok=True)
    os.makedirs(log_dir, exist_ok=True)

    env = dict(os.environ)
    if args.dry_run:
        platform, network, dataset, batch = "cpu", "LeNet", "MNIST", "32"
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4"
        ).strip()
    else:
        platform, network, dataset, batch = "tpu", "ResNet18", "Cifar10", "128"
    dense_mb = DENSE_MB[network]
    model = ["--network", network, "--dataset", dataset, "--synthetic",
             "--batch-size", batch, "--lr", "0.01", "--momentum", "0.0"]
    svd3 = ["--code", "svd", "--svd-rank", "3"]
    phases: list[Phase] = []

    def child(name, argv):
        left = DEADLINE_S - (time.monotonic() - t_start)
        p = run_child(name, argv, env=env, log_dir=log_dir, platform=platform,
                      timeout_s=min(PHASE_TIMEOUT_S, left))
        p.check_common()
        phases.append(p)
        return p

    def body() -> dict:
        one = os.path.join(run_dir, "one")
        fit = ["train"] + model + svd3 + [
            "--n-devices", "1", "--eval-freq", "16", "--save-freq", "16",
            "--log-interval", "8", "--compress", "--train-dir", one,
        ]
        p = child("train", fit + ["--max-steps", "40"])
        p.check_losses([8, 16, 24, 32, 40], dense_mb, "svd")
        p.need(sorted(p.validation) == [16, 32],
               f"in-loop eval at steps {sorted(p.validation)}, expected [16, 32]")
        p.need(all(math.isfinite(v) for v in p.validation.values()),
               f"validation losses {p.validation}")
        saved = sorted(
            int(f.rsplit("_", 1)[1]) for f in os.listdir(one)
            if re.fullmatch(r"model_step_\d+", f)
        )
        p.need(saved == [16, 32, 40], f"checkpoints at {saved}, expected [16, 32, 40]")
        device = p.devices[0]
        trained = dict(p.validation)

        p = child("resume", fit + ["--max-steps", "56", "--resume"])
        p.need(p.resumed_at == 40, f"resumed at step {p.resumed_at}, expected 40")
        p.check_losses([48, 56], dense_mb, "svd")
        trained.update(p.validation)

        p = child("evaluate", ["evaluate"] + model + [
            "--model-dir", one, "--max-polls", "1", "--stop-when-idle"])
        p.need(bool(trained) and set(trained) <= set(p.validation),
               f"evaluator scored steps {sorted(p.validation)}, trainer "
               f"validated at {sorted(trained)}")
        for s, want in sorted(trained.items()):
            got = p.validation[s]
            p.need(abs(got - want) <= 1e-3 * max(1.0, abs(want)),
                   f"step {s}: evaluator loss {got} vs trainer's {want}")

        sup = os.path.join(run_dir, "sup")
        p = child("supervised", ["train"] + model + svd3 + [
            "--n-devices", "1", "--eval-freq", "0", "--save-freq", "8",
            "--log-interval", "8", "--train-dir", sup, "--max-steps", "24",
            "--max-restarts", "1", "--restart-backoff", "0.1",
            "--chaos", "kill@12"])
        p.need("CHAOS: killing process before step 12" in p.text,
               "the injected kill did not fire")
        p.need(p.resumed_at == 8, f"restart resumed at {p.resumed_at}, expected 8")
        p.need("Supervisor: clean exit (attempt 1)" in p.text,
               "no clean exit on the restarted attempt")
        p.need(len(p.devices) == 2, f"{len(p.devices)} 'Device:' lines, expected 2")
        p.check_losses([8, 16, 24], dense_mb, "svd")

        if device["count"] >= 4:
            four = os.path.join(run_dir, "four")
            dp4 = ["train"] + model + ["--n-devices", "4", "--log-interval", "8"]
            gather = dp4 + svd3 + [
                "--aggregate", "gather", "--eval-freq", "16", "--save-freq",
                "16", "--compress", "--train-dir", four]
            p = child("dp4-svd-gather", gather + ["--max-steps", "24"])
            p.check_losses([8, 16, 24], dense_mb, "svd")
            p.check_spread(4)
            p.need(sorted(p.validation) == [16],
                   f"in-loop eval at steps {sorted(p.validation)}, expected [16]")
            p = child("dp4-resume", gather + ["--max-steps", "32", "--resume"])
            p.need(p.resumed_at == 24, f"resumed at step {p.resumed_at}, expected 24")
            p.check_losses([32], dense_mb, "svd")
            p.check_spread(4)
            for name, code in (
                ("dp4-qsgd-ring", ["--code", "qsgd", "--quantization-level", "8",
                                   "--aggregate", "ring"]),
                ("dp4-dense-psum", ["--code", "sgd"]),
            ):
                p = child(name, dp4 + code + [
                    "--eval-freq", "0", "--train-dir", "", "--max-steps", "24"])
                p.check_losses([8, 16, 24], dense_mb, code[1])
                p.check_spread(4)
        else:
            print(f"[dp4-*] skipped: {device['count']} device(s) visible, "
                  "the distributed phases need 4", flush=True)
        return device

    try:
        device = body()
        failure = None
    except PhaseFailed as exc:
        device, failure = None, str(exc)
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)
    summary = {
        "dry_run": args.dry_run, "failure": failure,
        "seconds": round(time.monotonic() - t_start, 1),
        "phases": [p.record() for p in phases],
    }
    tmp = os.path.join(out, "summary.json.tmp")
    with open(tmp, "w") as f:
        f.write(json.dumps(summary, indent=1) + "\n")
    os.replace(tmp, os.path.join(out, "summary.json"))
    if failure is not None:
        print(f"chip_smoke: FAILED — {failure}", file=sys.stderr, flush=True)
        return 1
    cache = {k: sum(p.cache[k] for p in phases) for k in ("hits", "misses", "compile_s")}
    print(f"chip_smoke: {len(phases)} phases in {summary['seconds']} s; compile "
          f"cache {cache['hits']} hits, {cache['misses']} misses, "
          f"{cache['compile_s']:.1f} s compiling (set-up time, not a speed "
          "number)", flush=True)
    result = {"platform": device["platform"], "kind": device["kind"],
              "count": device["count"]}
    if args.dry_run:
        print(json.dumps({"dry_run": True, "phases_passed": len(phases),
                          "device": result}), flush=True)
    else:
        print(json.dumps({"ok": True, "device": result}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
