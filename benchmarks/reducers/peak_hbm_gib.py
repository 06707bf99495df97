"""The most the cell's fullest device held at once (run.device_peak: arrays in
use as the window opens plus the step program's reserved temporaries, or the
allocator's own peak of arrays in use where that is more), read after the
window and before the reference runs."""


def reduce(ctx):
    return max(ctx["peak_bytes"]) / 2**30 if ctx["peak_bytes"] else None
