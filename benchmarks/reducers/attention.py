"""The attention core (the program's `attention` scope: the softmax
attention of the `full` and `window` layers from q, k, v to the heads'
outputs, forward and backward) in the device trace of the profiled slice.
`what` is one of:

  ms            busy time of the core's operations per optimizer step
  roofline_pct  the least time the chip could take for the core's work
                (flops/<family>.py `attention_work`: the larger of FLOPs over
                the bf16 peak and bytes over the HBM bandwidth of peaks.json)
                over that busy time: the same work whatever implements it

The trace names an operation by its HLO line and carries no scope
(benchmarks/trace.py), so the core's operations are found by what their lines
carry (`is_core`): the fused kernels by their stable names
(`fused_attention_fwd`, `fused_attention_dkv`, `fused_attention_dq`:
ops/attention_kernels.py names its three `pallas_call`s), and the one
operation of the scope outside them, `delta` = rowsum(dO x O), by the array
only it writes: float32, one number a (sequence, query head, position), shaped
(B, H, S), or (B, H, 1, S) as the kernels read it. The configuration and the cell's flags
give the sizes. On a capture whose operations carry `report timeline`'s scopes
(tests/benchmark/fixtures/tpu_v5e_window_moe_trace.json) the rule reads the
scope's own 93.29 ms a step (the kernels 91.85, `delta` 1.43). A step whose core runs as the jnp blocks (a head size
`ring.FUSED_BLOCKS` leaves out, or a program from before the kernels) has no
such operation and gives None, as does a configuration whose flops file
states no `attention_work`.
"""

from benchmarks import trace as T
from benchmarks.reducers.device_trace import _steps_per_run
from benchmarks.reducers.linear_attention import _written
from benchmarks.reducers.moe_counters import flops_module

KERNELS = "fused_attention_"


def sizes_of(ctx) -> dict:
    config, flags = ctx["config"], ctx["flags"]
    b, h, s = int(flags["--batch-size"]), config["num_attention_heads"], int(flags["--seq-len"])
    return {"delta": {(b, h, s), (b, h, 1, s)}}


def is_core(line: str, sizes: dict) -> bool:
    name = line.lstrip("%")
    if name.startswith(KERNELS):
        return True
    if name.startswith(("copy-start", "copy-done")):
        return False
    return any(dtype == "f32" and dims in sizes["delta"] for dtype, dims in _written(line))


def core_intervals(device: dict, lo: float, hi: float, ctx):
    sizes, out, kernels = sizes_of(ctx), [], 0
    for name, start, dur in device["ops"]:
        if start + dur <= lo or start >= hi:
            continue
        if is_core(name, sizes):
            kernels += name.lstrip("%").startswith(KERNELS)
            out.append((max(start, lo), min(start + dur, hi)))
    return out if kernels else []  # `delta` alone is no core: its shape is not the kernels' name


def busy_ms(ctx):
    trace, config = ctx["trace"], ctx["config"]
    if not trace or not trace["devices"] or "num_attention_heads" not in config:
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
        raise ValueError(f"unknown attention reduction {what!r}")
    work = getattr(flops_module(ctx), "attention_work", None)
    if not ctx["peaks"] or work is None:
        return None
    flops, moved = work(ctx["config"], ctx["flags"])
    least_s = max(flops / ctx["peaks"]["bf16_flops_per_s"], moved / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
