"""Run one cell of BENCHMARK.json once: set up, warm, measure one window, check
the timed path against the plain reference, print one JSON line, exit.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data that this file finds by name: the
cell's entry in BENCHMARK.json names a configuration and a traffic mix
(configs/<name>.json, traffic/<name>.json, together the argv of
`atomo_tpu.cli.main`), the configuration names its adapter (which loop of the
program, adapters/<name>.py), its plain reference (reference/<name>.py) and
its FLOP count (flops/<name>.py), and every metric is a file
metrics/<name>.json that names its reducer (reducers/<name>.py). This file
holds no cell, no size and no metric by name.

The window is driven through `atomo_tpu.cli.main` in this process: one call,
whose first steps are the ones the reference follows, and whose later steps
are the window. The loops run to --max-steps and not to a clock, so the
benchmark gives them more steps than fit and closes the window from the log:
when a step's line appears it is stamped, and once `--seconds` have passed
since the window's first stamp the stamper raises `WindowClosed` through the
program's own loop.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # as near to the process's start as Python lets us

import argparse  # noqa: E402
import atexit  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT))  # `benchmarks.*` for the files found by name, `atomo_tpu` for the program
SETTLE_LINES = 3  # step lines between the checked calls and the window
TRACE_AT = 0.4  # of the window: where the profiled slice starts
TRACE_SECONDS = 3.0
LAST_WORDS: list[str] = []  # what standard error ends with, whatever exits


@atexit.register  # first registered, so last to run: after the program's own exit report
def _say_last_words():
    for line in LAST_WORDS:
        print(line, file=sys.stderr, flush=True)


class WindowClosed(BaseException):
    """Raised through the program's loop when the window has lasted its
    seconds. A BaseException, so that no `except Exception` of the program
    takes it for a fault."""


class Data:
    """Finds the benchmark's files by kind and name: beside BENCHMARK.json
    first (a test or a later PR brings its own), then in this directory."""

    def __init__(self, benchmark: Path):
        self.bench = json.loads(benchmark.read_text())
        self.roots = [benchmark.resolve().parent / "benchmarks", HERE]

    def path(self, kind: str, name: str, ext: str) -> Path:
        for root in self.roots:
            candidate = root / kind / f"{name}{ext}"
            if candidate.is_file():
                return candidate
        raise SystemExit(f"no {kind}/{name}{ext} under {[str(r) for r in self.roots]}")

    def json(self, kind: str, name: str) -> dict:
        return json.loads(self.path(kind, name, ".json").read_text())

    def module(self, kind: str, name: str):
        path = self.path(kind, name, ".py")
        spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def cell(self, workload: str) -> dict:
        for cell in self.bench["workloads"]:
            if cell["name"] == workload:
                return cell
        raise SystemExit(f"BENCHMARK.json has no workload {workload!r}")

    def config(self, name: str) -> dict:
        for entry in self.bench["configs"]:
            if entry["name"] == name:
                for root in (self.roots[0].parent, ROOT):
                    if (root / entry["file"]).is_file():
                        return json.loads((root / entry["file"]).read_text())
        raise SystemExit(f"BENCHMARK.json has no configuration {name!r} with its file")

    def metrics(self, kind: str, cell: dict) -> list[dict]:
        """The cell's metrics of one kind, each entry of BENCHMARK.json
        joined with its file under metrics/."""
        out = []
        for entry in self.bench[kind]:
            if "workloads" in entry and cell["name"] not in entry["workloads"]:
                continue
            out.append({**self.json("metrics", entry["name"]), **entry})
        return out


def program_argv(config: dict, traffic: dict, seed: int) -> tuple[list[str], dict]:
    """The argv of atomo_tpu.cli.main: the configuration's flags, then the
    mix's, each value a format string over the configuration's sizes."""
    sizes = {k: v for k, v in config.items() if isinstance(v, (int, float, str))}
    flags = {**config["flags"], **traffic["flags"], "--seed": seed % (2**31 - 1)}
    argv, resolved = [config["subcommand"]], {}
    for flag, value in flags.items():
        if value is True:
            argv.append(flag)
            resolved[flag] = True
        elif value is not False and value is not None:
            text = value.format(**sizes) if isinstance(value, str) else str(value)
            argv += [flag, text]
            resolved[flag] = text
    return argv, resolved


def tiny(config: dict, traffic: dict) -> tuple[dict, dict]:
    """The rehearsal's sizes: each file's own `tiny` block laid over it."""
    config = {**config, **config.get("tiny", {})}
    traffic = {**traffic, "flags": {**traffic["flags"], **traffic.get("tiny", {})}}
    return config, traffic


def leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "name", k))) for k in path)


class Probe:
    """What the benchmark reads from the timed path's own first calls: the
    batches it was fed, its losses, and how far each leaf moved from the
    seeded weights. After the last checked call it only counts."""

    def __init__(self, reference, adapter, config: dict, seed: int):
        self.reference, self.config, self.seed = reference, config, seed
        self.check_calls = tuple(adapter.CHECK_CALLS)
        self.one_step_per_call = adapter.ONE_STEP_PER_CALL
        self.calls = 0
        self.batches, self.losses = [], []
        self.grad1_norms = self.change_norms = None
        self.counters: dict[str, float] = {}
        self.call_clock: list[tuple[float, float]] = []  # entering the step, back from its dispatch
        self._shardings = None

    def weights(self, params):
        import jax

        paths, treedef = jax.tree_util.tree_flatten_with_path(params)
        leaves = [(leaf_name(p), x) for p, x in paths]
        held = {name: tuple(x.shape) for name, x in leaves}
        said = {k: tuple(v) for k, v in self.reference.param_shapes(self.config).items()}
        if held != said:
            odd = sorted(k for k in set(held) | set(said) if held.get(k) != said.get(k))
            raise SystemExit(
                f"the reference describes other leaves than the program holds: {odd[:8]}"
            )
        self._shardings = {name: x.sharding for name, x in leaves}
        new = self.reference.init_params(self.config, self.seed, self._shardings)
        return jax.tree_util.tree_unflatten(treedef, [new[name] for name, _ in leaves])

    def before_call(self, batch):
        self.calls += 1
        self._entered = time.perf_counter()
        if self.calls <= self.check_calls[-1]:
            import jax

            self.batches.append(jax.device_get(batch))

    def after_call(self, params, metrics):
        self.call_clock.append((self._entered, time.perf_counter()))
        if self.calls > self.check_calls[-1]:
            return
        import jax
        import numpy as np

        self.losses += [float(x) for x in np.asarray(metrics["loss"]).reshape(-1)]
        for name, value in metrics.items():
            if name.endswith("_bytes"):
                self.counters[name] = float(np.asarray(value).reshape(-1)[-1])
        if self.calls not in self.check_calls:
            return
        leaves, _ = jax.tree_util.tree_flatten_with_path(params)
        now = {leaf_name(p): x for p, x in leaves}
        start = self.reference.init_params(self.config, self.seed, self._shardings)
        norms = jax.jit(
            lambda a, b: {k: jax.numpy.sqrt(jax.numpy.sum(jax.numpy.square(a[k] - b[k]))) for k in a}
        )(now, start)
        moved = {k: float(v) for k, v in norms.items()}
        if self.calls == self.check_calls[0] and self.one_step_per_call:
            # the first gradient as the optimizer got it: the first step of
            # SGD, with or without momentum, moves a leaf by lr times it
            self.grad1_norms = {k: v / self.config["lr"] for k, v in moved.items()}
        if self.calls == self.check_calls[-1]:
            self.change_norms = moved

    def readings(self) -> dict:
        return {
            "losses": self.losses,
            "grad1_norms": self.grad1_norms,
            "change_norms": self.change_norms,
            "msg_bytes": self.counters.get("msg_bytes"),
        }


class Stamper(io.TextIOBase):
    """Stands in for sys.stdout while the program runs. Each line of a step
    is stamped as it appears; the stamps decide where the window opens, where
    the profiled slice lies and when the window closes."""

    def __init__(self, pattern, sink, seconds: float, skip_lines: int, trace_dir=None,
                 on_open=None):
        self.pattern, self.sink, self.on_open = pattern, sink, on_open
        self.seconds, self.skip_lines, self.trace_dir = seconds, skip_lines, trace_dir
        self.buffer = ""
        self.stamps: list[tuple[float, int, float]] = []  # clock, step, loss
        self.first = self.last = None  # indices into stamps: the window
        self.slice = None  # indices into stamps: the profiled slice
        self._tracing = False

    def writable(self):
        return True

    def write(self, text):
        self.buffer += text
        while "\n" in self.buffer:
            line, self.buffer = self.buffer.split("\n", 1)
            now = time.perf_counter()
            found = self.pattern.match(line)
            if found:
                try:
                    loss = float(found.group(2))
                except ValueError:
                    loss = math.nan
                self.stamps.append((now, int(found.group(1)), loss))
                self._on_step(now)
            else:
                print(f"[{now - T_PROCESS:7.2f}s] {line}", file=self.sink, flush=True)
        return len(text)

    def flush(self):
        pass

    def _on_step(self, now: float):
        index = len(self.stamps) - 1
        if self.first is None:
            if index >= self.skip_lines:
                self.first = index
                if self.on_open:
                    self.on_open()
            return
        elapsed = now - self.stamps[self.first][0]
        if self.trace_dir and self.slice is None and elapsed >= TRACE_AT * self.seconds:
            import jax

            # the Python tracer slows the host's own work in the slice; the
            # runtime's own host spans are enough to name the idle gaps
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            self._tracing = True
            self.slice = [index, None]
            self._slice_opened = now
        elif self._tracing and now - self._slice_opened >= min(TRACE_SECONDS, self.seconds / 4):
            self.close_trace()
        if elapsed >= self.seconds:
            self.last = index
            raise WindowClosed

    def close_trace(self):
        if self._tracing:
            import jax

            self._tracing = False
            jax.profiler.stop_trace()
            self.slice[1] = len(self.stamps) - 1


def run_cell(args, benchmark: Path | None = None, keep: dict | None = None) -> dict:
    """One run of one cell; returns the result line's object. With
    `args.rehearse` the sizes are the files' tiny ones, any platform will do,
    and no metric that only a chip can give is reported. `keep`, where given,
    is filled with what limits.py reads the controls and faults from."""
    data = Data(benchmark or ROOT / "BENCHMARK.json")
    cell = data.cell(args.workload)
    config, traffic = data.config(cell["config"]), data.json("traffic", cell["traffic"])
    if args.rehearse:
        config, traffic = tiny(config, traffic)
    adapter = data.module("adapters", config["adapter"])
    reference = data.module("reference", config["reference"])
    argv, flags = program_argv(config, traffic, args.seed)

    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if not args.rehearse and (device["platform"] != "tpu" or len(devices) < cell["chips"]):
        raise SystemExit(
            f"{cell['name']} needs {cell['chips']} TPU chip(s); JAX found "
            f"{len(devices)} x {device['platform']} ({device['kind']})"
        )
    peaks = data.json(".", "peaks")
    if not args.rehearse and device["kind"] not in peaks:
        raise SystemExit(f"benchmarks/peaks.json has no device_kind {device['kind']!r}")

    compiles: list[tuple[float, float]] = []

    def on_duration(event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append((time.perf_counter(), duration_secs))

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    out_dir = ROOT / "bench_out" / cell["name"]
    out_dir.mkdir(parents=True, exist_ok=True)
    trace_dir = out_dir / "trace"
    shutil.rmtree(trace_dir, ignore_errors=True)

    probe = Probe(reference, adapter, config, args.seed)
    used = devices[: cell["chips"]]
    held_in_window: list[int] = []  # bytes_in_use of each device as the window opens
    stamper = Stamper(
        adapter.STEP_LINE, sys.stderr, args.seconds,
        skip_lines=adapter.CHECK_CALLS[-1] + SETTLE_LINES,
        trace_dir=str(trace_dir) if args.trace else None,
        on_open=lambda: held_in_window.extend(
            (d.memory_stats() or {}).get("bytes_in_use", 0) for d in used
        ),
    )
    uninstall = adapter.install(probe)
    real_stdout, sys.stdout = sys.stdout, stamper
    closed = False
    try:
        from atomo_tpu.cli import main as program_main

        program_main(argv)
    except WindowClosed:
        closed = True
    finally:
        sys.stdout = real_stdout
        stamper.close_trace()
        uninstall()
    if not closed:
        raise SystemExit(
            f"the program returned after {len(stamper.stamps)} step lines, before "
            f"the window of {args.seconds} s closed: give it more --max-steps"
        )
    gc.collect()  # the program's state went with the exception's frames
    peak_bytes = [device_peak(d.memory_stats(), held) for d, held in zip(used, held_in_window)]
    peak_bytes = [b for b in peak_bytes if b is not None]

    stamps = stamper.stamps
    window = stamps[stamper.first : stamper.last + 1]
    # one file per run, never overwritten: a stall is rare, and its stamps are the evidence
    (out_dir / f"stamps-seed{args.seed}-trace{args.trace}-{int(time.time())}.json").write_text(json.dumps({
        "workload": cell["name"], "seed": args.seed, "device": device,
        "process_start": T_PROCESS, "window": [stamper.first, stamper.last],
        "profiled_slice": stamper.slice,
        "stamps": [{"clock_s": t, "step": n, "loss": loss} for t, n, loss in stamps],
        "calls": [{"entered_s": a, "dispatched_s": b} for a, b in probe.call_clock],
        "memory_stats": [d.memory_stats() for d in used], "held_in_window": held_in_window,
    }))

    ctx = {
        "cell": cell, "config": config, "flags": flags, "device": device,
        "peaks": peaks.get(device["kind"]),
        "process_start": T_PROCESS, "stamps": stamps,
        "window": (stamper.first, stamper.last), "slice": stamper.slice,
        "peak_bytes": peak_bytes, "counters": probe.counters, "compiles": compiles,
        "flops_per_step": data.module("flops", config["flops"]).train_flops_per_step(config, flags),
        "trace": None,
    }
    breakdown = None
    if args.trace:
        from benchmarks import breakdown as breakdown_module
        from benchmarks import trace as trace_module

        xplane = trace_module.newest_xplane(str(trace_dir))
        if xplane is None:
            raise SystemExit(f"the profiler left no trace under {trace_dir}")
        ctx["trace"] = trace_module.load(xplane)
        if ctx["trace"]["devices"]:
            busy_s, window_s = breakdown_module.busy_and_window(ctx["trace"])
            device["busy_s"], device["window_s"] = busy_s, window_s
            breakdown = breakdown_module.breakdown(ctx["trace"])
        shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = {}
    for metric in data.metrics("per_layer" if args.trace else "end_to_end", cell):
        if args.rehearse and metric["source"] != "program_counter":
            continue  # a time or a memory reading off the chip is no device metric
        reducer = data.module("reducers", metric["reducer"])
        value = reducer.reduce(ctx, **metric.get("args", {}))
        if value is not None:
            metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}

    steps = window[-1][1] - window[0][1]
    failed = sum(  # a line whose loss is not finite fails the steps it stands for
        b[1] - a[1] for a, b in zip(window, window[1:]) if not math.isfinite(b[2])
    )
    if peak_bytes:
        device["memory_peak_bytes"] = max(peak_bytes)

    # the reference runs last: after the peak was read and the program's state freed
    from benchmarks import check

    rows = [row.tobytes() for batch in probe.batches for row in _rows(batch)]
    if len(set(rows)) != len(rows):
        raise SystemExit("rows of the checked batches repeat: the feed is not what the check assumes")
    reference_started = time.perf_counter()
    ref = reference.train_steps(
        reference.init_params(config, args.seed), probe.batches, config, flags=flags
    )
    reference_s = time.perf_counter() - reference_started
    numbers = check.training_numbers(probe.readings(), ref)
    if keep is not None:
        keep.update(probe=probe, reference=reference, config=config, flags=flags, ref=ref)
    correct, compared = check.judge(numbers, data.json("limits", cell["name"])["limits"])
    correct = correct and failed == 0
    for name, got in compared.items():
        LAST_WORDS.append(
            f"compared {name}: {got['value']:.6g} (limit {got['limit']:.6g}) at {got['at']}"
        )
    LAST_WORDS.append(f"correct: {correct}")

    result = {
        "correct": correct, "attempted": steps, "failed": failed,
        "metrics": metrics, "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["reference_s"] = reference_s  # what every run pays after its window, outside setup_s
    result["compared"] = compared
    return result


def device_peak(stats: dict | None, held_in_window: int) -> int | None:
    """The most a device held at once, from its allocator's counters. The
    TPU's allocator counts what a running program reserves for its
    temporaries apart from the arrays in use (`peak_bytes_reserved`, beside
    `peak_bytes_in_use`), and keeps no peak of their sum. While the window's
    step runs the device holds the arrays in use as the window opens plus that
    reservation, so that sum is the window's peak; `peak_bytes_in_use` alone
    still counts where set-up held more arrays than that."""
    if not stats or "peak_bytes_in_use" not in stats:
        return None
    return max(stats["peak_bytes_in_use"], held_in_window + stats.get("peak_bytes_reserved", 0))


def _rows(batch):
    """The rows of a fed batch, whatever the loop feeds: the first array of a
    tuple, flattened to (rows, everything else)."""
    import numpy as np

    array = np.asarray(batch[0] if isinstance(batch, (tuple, list)) else batch)
    lead = array.shape[:-1] if array.ndim <= 2 else array.shape[: array.ndim - 3]
    return array.reshape(int(np.prod(lead)) if lead else 1, -1)


def parse(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny sizes on whatever JAX finds; reports no device metric")
    return parser.parse_args(argv)


if __name__ == "__main__":
    print(json.dumps(run_cell(parse())), flush=True)
