"""Where the persistent XLA compilation cache lives, and what it did.

One rule, shared by every entry point (cli, and through it chip_smoke.py
and the benchmark's adapters):

  * ``JAX_COMPILATION_CACHE_DIR`` set -> JAX reads it itself; nothing here
    names a directory.
  * unset -> :data:`DEFAULT_CACHE_DIR`, one fixed git-ignored directory in
    the checkout. The path is part of every cache key's neighbourhood: a
    directory that moves (a ``mktemp``, a pid, a timestamp) never hits, so
    there is exactly one.

``JAX_ENABLE_COMPILATION_CACHE=false`` (JAX's own switch) turns the cache
off: the tier-1 suite and the bit-parity drills run cache-cold through it,
and their child processes inherit it from the environment.
"""

from __future__ import annotations

import atexit
import os

import jax

from atomo_tpu.utils import tracing

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

_ENABLED_AT = None


def enable_compile_cache(log_fn=print):
    """Start the span ring's compile records (``tracing.listen``, cache or
    no cache), point the persistent cache at its directory (the module
    rule), drop the size/time floors so every program caches, and report at
    exit what the cache did: hits and misses as JAX's own monitoring events
    count them, and the seconds spent in backend compilation (cache loads
    included), ``tracing.compile_totals``. Compile time is set-up time,
    never a speed number.

    Touches ``jax.config`` only — no backend is initialised here, so a
    supervising parent may call it and still leave the chip to its child.
    Returns the cache directory, or None when the cache is switched off.
    Idempotent per process (in-process callers of ``cli.main`` would
    otherwise stack one exit report per call).
    """
    global _ENABLED_AT
    tracing.listen()
    if not jax.config.jax_enable_compilation_cache:
        return None
    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    path = jax.config.jax_compilation_cache_dir
    if _ENABLED_AT == path:
        return path
    _ENABLED_AT = path
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    log_fn(f"XLA compilation cache: {path}")

    def _report():
        seen = tracing.compile_totals()
        log_fn(
            f"XLA compilation cache: {seen['hits']} hits, "
            f"{seen['misses']} misses, {seen['compile_s']:.1f} s compiling "
            f"this run ({path})"
        )

    atexit.register(_report)
    return path
