"""Model FLOP/s utilisation of the whole step: the forward and backward
FLOPs the model needs per optimizer step, counted from shapes by
flops/<family>.py (recomputation and the codec's arithmetic do not count),
over the median step time, the chips and the chip's bf16 peak from
peaks.json. float32 work is held to the same bf16 peak: it is the only
matrix peak the v5e publishes, and the MXU runs float32 as bf16 passes."""

import statistics

from benchmarks.reducers import stamp_stat


def reduce(ctx):
    times = stamp_stat.step_times_ms(ctx)
    if not times or not ctx["peaks"]:
        return None
    seconds = statistics.median(times) / 1e3
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["cell"]["chips"]
    return 100.0 * ctx["flops_per_step"] / seconds / peak
