"""The linear-attention layers' mixer core (the program's `linear_attention`
scope: convolution, normalisation, gates, chunk products and the scan over
chunks) in the device trace of the profiled slice. `what` is one of:

  ms            busy time of the core's operations per optimizer step
  roofline_pct  the least time the chip could take for the core's work
                (flops/<family>.py `linear_attention_work`: the larger of
                FLOPs over the bf16 peak and bytes over the HBM bandwidth of
                peaks.json) over that busy time

The trace names an operation by its HLO line and carries no scope
(benchmarks/trace.py), so the core's operations are found by what their lines
carry. (1) Loops: a `while` operation covers its body's operations in time,
and the only loops of a step with linear layers are the scans over chunks,
forward and backward. (2) Outside the loops, an operation belongs to the core
if it writes an array of a shape that only this mixer makes (`is_core`): a
chunk's or a state's matrix (the last two dimensions both among the chunk
length and the key and value head sizes), a gate per head and position in a
chunk, an array per token and head, or a float32 array per token with the
heads side by side. The configuration and the cell's flags give the sizes. On
a capture whose operations carry `report timeline`'s scopes
(tests/benchmark/fixtures/tpu_v5e_hybrid_trace.json) the rule reads 4% above
the scopes: the difference is operations the compiler made with no scope on
the core's own arrays (the scan's zeroed stacks, layout changes). A step
without such operations (a program without linear layers, or from before them)
gives None.
"""

import importlib.util
import re
from pathlib import Path

from benchmarks import trace as T
from benchmarks.reducers.device_trace import _steps_per_run

CHUNK = 64
SHAPE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]+)\]")


def sizes_of(ctx) -> dict:
    config, flags = ctx["config"], ctx["flags"]
    heads, dk, dv = (config[k] for k in ("num_attention_heads", "linear_key_head_dim", "linear_value_head_dim"))
    tokens = int(flags["--seq-len"])
    return {"inner": {CHUNK, dk, dv}, "heads": heads, "per_head": {dk, dv},
            "flat": {heads * dk, heads * dv}, "tokens": {tokens, tokens * int(flags["--batch-size"])}}


def _written(line: str):
    """(dtype, dimensions) of each array an instruction writes: the shapes
    between ` = ` and the operation's own name."""
    head, _, rest = line.partition(" = ")
    base = head.lstrip("%").split(".")[0]
    written = re.split(rf" (?:{re.escape(base)}|fusion)\(", rest, maxsplit=1)[0]
    return [(dtype, tuple(int(x) for x in dims.split(","))) for dtype, dims in SHAPE.findall(written)]


def is_core(line: str, sizes: dict) -> bool:
    """Whether an operation outside the loops writes an array of a shape that
    only the mixer core makes. Left out: the asynchronous copies the compiler
    puts in (`copy-start`, `copy-done`: no scope has them), and the
    projections' matmuls, which lie before and after the core and write the
    same token-major shapes in bfloat16."""
    if line.lstrip("%").startswith(("copy-start", "copy-done")):
        return False
    written = _written(line)
    in_bf16 = {dims for dtype, dims in written if dtype == "bf16"}
    for dtype, dims in written:
        if len(dims) < 2:
            continue
        last, before = dims[-1], dims[-2]
        if last in sizes["inner"] and before in sizes["inner"]:
            return True  # a chunk's or a state's matrix
        if last == CHUNK and before == sizes["heads"]:
            return True  # a gate per chunk, head and position in the chunk
        chunked = len(dims) > 2 and dims[-3] == CHUNK
        if last in sizes["per_head"] and before == sizes["heads"] and (dtype == "f32" or chunked):
            return True  # per token and head: q, k, v, o and the output gate
        if (dtype == "f32" and dims not in in_bf16 and before in sizes["tokens"]
                and (last in sizes["flat"] or last == sizes["heads"])):
            return True  # per token, heads side by side, float32: convolution, SiLU, the gates' logits
    return False


def core_intervals(device: dict, lo: float, hi: float, ctx):
    """[start, end] inside [lo, hi] of the operations taken for the core."""
    sizes, out = sizes_of(ctx), []
    for name, start, dur in device["ops"]:
        if start + dur <= lo or start >= hi:
            continue
        if name.lstrip("%").startswith("while") or is_core(name, sizes):
            out.append((max(start, lo), min(start + dur, hi)))
    return out


def busy_ms(ctx):
    trace, config = ctx["trace"], ctx["config"]
    if not trace or not trace["devices"] or "linear_key_head_dim" not in config:
        return None
    device = T.fullest_device(trace)
    span = T.whole_runs(device)
    if span is None:
        return None
    lo, hi, runs, _ = span
    mine = core_intervals(device, lo, hi, ctx)
    if not mine:
        return None
    return T.union_len(mine) / ((len(runs) - 1) * _steps_per_run(ctx)) / 1e6


def reduce(ctx, what):
    ms = busy_ms(ctx)
    if ms is None or what == "ms":
        return ms
    if what != "roofline_pct":
        raise ValueError(f"unknown linear_attention reduction {what!r}")
    if not ctx["peaks"]:
        return None
    path = Path(__file__).resolve().parent.parent / "flops" / f"{ctx['config']['flops']}.py"
    spec = importlib.util.spec_from_file_location("bench_flops_for_linear_attention", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    flops, moved = module.linear_attention_work(ctx["config"], ctx["flags"])
    least_s = max(flops / ctx["peaks"]["bf16_flops_per_s"], moved / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
