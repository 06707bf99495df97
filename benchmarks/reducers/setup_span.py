"""Where `setup_s` goes, from the program's span ring (`atomo_tpu.utils.
tracing.spans()`, read as span_stat reads it): the records between the
process's start and the window's first stamp, cut to that interval. Times are
unions of intervals per kind, never sums: a jit traced inside another's trace
nests in it. `what` is one of:

  init      `init_state` spans: building the loop's initial state
  trace     `jax_trace` records: JAX tracing functions to jaxprs
  lower     `jax_lower`: jaxprs lowered to MLIR modules
  compile   `jax_compile`: backend compiles, and loads from the persistent cache
  untraced  `setup_s` less the union of every record before the window (the
            kinds above and the loop's own spans): imports, reaching the chip
            and the Python between spans, which no record explains
  misses    the count of `jax_cache_miss` records: programs the persistent
            cache did not hold (0 where every program loaded)

A ring without the kind asked for gives None: a commit from before these
records has none of them. `misses` and `untraced` need only JAX's records or
an `init_state` span before the window.
"""

from benchmarks.reducers.span_stat import program_spans
from benchmarks.trace import union_len

KINDS = {"init": "init_state", "trace": "jax_trace", "lower": "jax_lower", "compile": "jax_compile"}
SETUP = (*KINDS.values(), "jax_cache_miss")
WHATS = (*KINDS, "untraced", "misses")


def reduce(ctx, what):
    if what not in WHATS:
        raise ValueError(f"unknown setup_span reduction {what!r}")
    start, first = ctx["process_start"], ctx["stamps"][ctx["window"][0]][0]
    records = ctx["spans"] if ctx.get("spans") is not None else program_spans()
    before = [
        (name, max(t0, start), min(t1, first))
        for name, _, _, t0, t1 in records
        if t1 >= start and t0 <= first
    ]
    if what in KINDS:
        mine = [(t0, t1) for name, t0, t1 in before if name == KINDS[what]]
        return union_len(mine) if mine else None
    if not any(name in SETUP for name, _, _ in before):
        return None
    if what == "misses":
        return float(sum(1 for name, _, _ in before if name == "jax_cache_miss"))
    return (first - start) - union_len([(t0, t1) for _, t0, t1 in before])
