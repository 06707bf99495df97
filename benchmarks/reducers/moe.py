"""The routed experts of the expert layers (the program's `moe` scope: router,
top-k and weights; sort, gather and the rows' way back; the grouped products)
in the device trace of the profiled slice. `what` is one of:

  ms            busy time of the scope's operations per optimizer step
  roofline_pct  the least time the chip could take for the grouped products'
                work at the rows the step itself counted
                (flops/<family>.py `expert_work`: the larger of FLOPs over the
                bf16 peak and bytes over the HBM bandwidth of peaks.json) over
                that busy time: the same work whatever implements it

The trace names an operation by its HLO line and carries no scope
(benchmarks/trace.py), so the scope's operations are found by what their
lines carry (`is_moe`): the grouped products by name (`ragged-dot`: the TPU's
compiler makes them custom calls, which carry no scope in any trace, and
`report timeline` knows them by the same name), and any operation that writes
an array only this layer makes: one with a dimension of tokens x experts per
token (the sorted assignments, their rows and the rows' results), one per
token and chosen expert, a float32 array per token over the router's outputs,
a bfloat16 matrix of all the step's tokens as rows (the other layers keep
batch and position apart), or a copy of the held experts' matrices (the
layout the grouped product wants; the entry cast is a `convert`). The
configuration and the cell's flags give the sizes. On a capture whose
operations carry `report timeline`'s scopes
(tests/benchmark/fixtures/tpu_v5e_moe_trace.json) the rule reads 1.2% over
the scopes (76.25 ms a step against 75.32): it takes a layout copy of the cast
matrices that carries no scope of the layer's (1.1 ms) and misses the
router's weight gradient (0.3 ms). A step without such operations (another
model, or a program from before the layer) gives None.
"""

from benchmarks import trace as T
from benchmarks.reducers.device_trace import _steps_per_run
from benchmarks.reducers.linear_attention import _written
from benchmarks.reducers.moe_counters import flops_module, held_rows

def sizes_of(ctx) -> dict:
    config, flags = ctx["config"], ctx["flags"]
    tokens = int(flags["--seq-len"]) * int(flags["--batch-size"])
    held, d, f = (config[k] for k in ("n_routed_experts", "hidden_size", "moe_intermediate_size"))
    return {"tokens": tokens, "per_token": config["num_experts_per_tok"],
            "rows": tokens * config["num_experts_per_tok"], "outputs": config["routed_experts_total"],
            "width": d, "experts": {(held, d, f), (held, f, d)}}


def is_moe(line: str, sizes: dict) -> bool:
    name = line.lstrip("%")
    if name.startswith(("copy-start", "copy-done")):
        return False  # the compiler's asynchronous copies: no scope has them
    if name.startswith("ragged-dot"):
        return True
    for dtype, dims in _written(line):
        if sizes["rows"] in dims:
            return True  # the sorted assignments, their rows and the rows' results
        if len(dims) >= 2 and dims[0] == sizes["tokens"] and dims[1] == sizes["per_token"]:
            return True  # per token and chosen expert: choices, weights, the rows brought back
        if dtype == "f32" and dims == (sizes["tokens"], sizes["outputs"]):
            return True  # the router's scores
        if dtype == "bf16" and dims == (sizes["tokens"], sizes["width"]):
            return True  # the layer's input and result, tokens as rows
        if name.startswith("copy") and dims in sizes["experts"]:
            return True  # the held experts' matrices in the grouped product's layout
    return False


def moe_intervals(device: dict, lo: float, hi: float, ctx):
    sizes, out = sizes_of(ctx), []
    for name, start, dur in device["ops"]:
        if start + dur <= lo or start >= hi:
            continue
        if is_moe(name, sizes):
            out.append((max(start, lo), min(start + dur, hi)))
    return out


def busy_ms(ctx):
    trace, config = ctx["trace"], ctx["config"]
    if not trace or not trace["devices"] or "routed_experts_total" not in config:
        return None
    device = T.fullest_device(trace)
    span = T.whole_runs(device)
    if span is None:
        return None
    lo, hi, runs, _ = span
    mine = moe_intervals(device, lo, hi, ctx)
    if not mine:
        return None
    return T.union_len(mine) / ((len(runs) - 1) * _steps_per_run(ctx)) / 1e6


def reduce(ctx, what):
    ms = busy_ms(ctx)
    if ms is None or what == "ms":
        return ms
    if what != "roofline_pct":
        raise ValueError(f"unknown moe reduction {what!r}")
    rows = held_rows(ctx)
    if not ctx["peaks"] or rows is None:
        return None
    flops, moved = flops_module(ctx).expert_work(ctx["config"], ctx["flags"], rows)
    least_s = max(flops / ctx["peaks"]["bf16_flops_per_s"], moved / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
