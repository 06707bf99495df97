"""Wall clock of the whole window over all optimizer steps in it: first stamp
to last, both fenced by the loss the line carries. A stall anywhere in the
window moves it."""


def reduce(ctx):
    first, last = ctx["window"]
    (t0, n0, _), (t1, n1, _) = ctx["stamps"][first], ctx["stamps"][last]
    return 1e3 * (t1 - t0) / (n1 - n0) if n1 > n0 else None
