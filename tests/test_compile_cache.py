"""The compile-cache rule (utils/compile_cache.py): JAX_COMPILATION_CACHE_DIR
set -> JAX reads it itself and the program names no directory; unset -> one
fixed git-ignored directory inside the checkout."""

import os
import subprocess
import sys
import types

from atomo_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fake_jax(cache_dir, enabled=True):
    """Stand-in for the three jax surfaces the rule touches, so the rule is
    exercised without mutating this process's real jax config (the suite
    itself runs cache-cold)."""
    updates = []
    config = types.SimpleNamespace(
        jax_enable_compilation_cache=enabled,
        jax_compilation_cache_dir=cache_dir,
    )

    def update(name, value):
        updates.append((name, value))
        setattr(config, name, value)

    config.update = update
    monitoring = types.SimpleNamespace(
        register_event_listener=lambda fn: None,
        register_event_duration_secs_listener=lambda fn: None,
    )
    return types.SimpleNamespace(config=config, monitoring=monitoring), updates


def _patched(monkeypatch, fake):
    monkeypatch.setattr(compile_cache, "jax", fake)
    monkeypatch.setattr(compile_cache, "_ENABLED_AT", None)
    monkeypatch.setattr(compile_cache.atexit, "register", lambda fn: None)


def test_env_var_set_means_no_directory_set_in_code(monkeypatch):
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, "/some/dir")
    fake, updates = _fake_jax("/some/dir")  # jax read the variable itself
    _patched(monkeypatch, fake)
    assert compile_cache.enable_compile_cache(log_fn=lambda m: None) == "/some/dir"
    assert "jax_compilation_cache_dir" not in [name for name, _ in updates]


def test_env_var_unset_means_the_fixed_in_checkout_path(monkeypatch):
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    fake, updates = _fake_jax(None)
    _patched(monkeypatch, fake)
    got = compile_cache.enable_compile_cache(log_fn=lambda m: None)
    assert got == compile_cache.DEFAULT_CACHE_DIR
    assert ("jax_compilation_cache_dir", got) in updates
    # one fixed path inside the checkout: no temp name, pid or time in it
    assert got == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_jax_own_switch_turns_the_cache_off(monkeypatch):
    """JAX_ENABLE_COMPILATION_CACHE=false (how this suite and the
    bit-parity drills run cache-cold) leaves the config untouched."""
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    fake, updates = _fake_jax(None, enabled=False)
    _patched(monkeypatch, fake)
    assert compile_cache.enable_compile_cache(log_fn=lambda m: None) is None
    assert updates == []
    # and the suite's own children inherit the switch from the environment
    assert os.environ["JAX_ENABLE_COMPILATION_CACHE"] == "false"


def test_real_cache_lands_where_the_env_var_says_and_reports_hits(tmp_path):
    """The real thing, in a subprocess (the cache dir is process-global jax
    config): entries land under JAX_COMPILATION_CACHE_DIR, the exit report
    counts a miss for the fresh compile and a hit for the reload."""
    code = """
import atexit, os, jax, jax.numpy as jnp
from atomo_tpu.utils.compile_cache import enable_compile_cache
assert enable_compile_cache(log_fn=print) == os.environ["JAX_COMPILATION_CACHE_DIR"]
f = lambda a: jnp.sin(a) * 2
jax.jit(f)(jnp.arange(64.0)).block_until_ready()
jax.clear_caches()
jax.jit(f)(jnp.arange(64.0)).block_until_ready()
"""
    env = {
        **os.environ,
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
        "JAX_ENABLE_COMPILATION_CACHE": "true",
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO,
    }
    p = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=120,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    assert any((tmp_path / "cache").iterdir())
    report = [ln for ln in p.stdout.splitlines() if " hits, " in ln]
    assert report, p.stdout
    hits, misses = (int(report[-1].split()[i]) for i in (3, 5))
    assert hits >= 1 and misses >= 1, report[-1]
