"""What the expert layers count as the step runs (models/moe.py), from the
step's own metrics: `moe_held_row_bytes`, the token rows computed for held
experts over all expert layers, and `moe_max_expert_row_bytes`, the most one
held expert got in one layer, both in bytes of rows as the experts read them
(the probe keeps a step's byte counts). `what` is one of:

  held_rows      the assignments to held experts computed in a step, in rows
  max_over_mean  the fullest expert's rows over the mean rows an expert and
                 layer got: the imbalance the grouped products see

A program that counts neither (another model, or one from before the expert
layer) gives None.
"""

import importlib.util
from pathlib import Path


def flops_module(ctx):
    path = Path(__file__).resolve().parent.parent / "flops" / f"{ctx['config']['flops']}.py"
    spec = importlib.util.spec_from_file_location("bench_flops_for_moe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def held_rows(ctx):
    held = ctx["counters"].get("moe_held_row_bytes")
    if not held:
        return None
    return round(held / flops_module(ctx).row_bytes(ctx["config"], ctx["flags"]))


def reduce(ctx, what):
    rows = held_rows(ctx)
    if rows is None or what == "held_rows":
        return rows
    if what != "max_over_mean":
        raise ValueError(f"unknown moe_counters reduction {what!r}")
    most = ctx["counters"].get("moe_max_expert_row_bytes")
    if not most:
        return None
    flops, config = flops_module(ctx), ctx["config"]
    mean = rows / (flops.expert_layers(config) * config["n_routed_experts"])
    return most / flops.row_bytes(config, ctx["flags"]) / mean
