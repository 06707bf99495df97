"""Backend compilations (jax.monitoring's backend_compile_duration events)
between the window's first and last stamp. Expected 0."""


def reduce(ctx):
    first, last = ctx["window"]
    t0, t1 = ctx["stamps"][first][0], ctx["stamps"][last][0]
    return float(sum(1 for t, _ in ctx["compiles"] if t0 <= t <= t1))
