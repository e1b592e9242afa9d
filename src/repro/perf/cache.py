"""Persistent XLA compilation cache placement.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps its cache in that
directory by itself and nothing here sets a directory. Otherwise the
launchers (``repro.launch.{train,serve}``, ``chip_smoke.py``) put it at
:data:`DEFAULT_CACHE_DIR`, one fixed, gitignored directory inside the
checkout: the path is part of what the cache is keyed on, so a directory
that moves never hits. Libraries (the sessions) never touch the setting.

Entries are content-addressed by XLA on the (HLO, compile options,
backend) fingerprint, so a restart recompiles nothing that already
compiled anywhere sharing the directory. Both the entry-size and the
compile-time minimums are set to 0: the codec kernels are small and fast
to compile, exactly the entries the stock 1-second threshold would skip.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (this file is <checkout>/src/repro/perf/cache.py)
DEFAULT_CACHE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))),
    ".jax_cache")


def enable_persistent_cache(*, min_entry_size_bytes: int = 0,
                            min_compile_time_secs: float = 0.0) -> str:
    """Turn jax's persistent compilation cache on and return the
    directory it writes to: ``$JAX_COMPILATION_CACHE_DIR`` when set (left
    to jax), else :data:`DEFAULT_CACHE_DIR`."""
    cache_dir = os.environ.get(ENV_VAR, "").strip()
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                      min_entry_size_bytes)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_time_secs)
    _reset_cache_state()
    return cache_dir


def disable_persistent_cache() -> None:
    jax.config.update("jax_enable_compilation_cache", False)
    _reset_cache_state()


def _reset_cache_state() -> None:
    """jax initializes its cache object once, at the first compile; a
    setting changed after that point is silently ignored. Resetting the
    cached state makes enable/disable effective mid-process (e.g. a
    launcher that already compiled something)."""
    try:
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()
    except Exception:
        pass  # private-ish API: a jax without it just loses mid-process


def cache_entries(cache_dir: str) -> int:
    """Number of cache entries on disk (one content-addressed file per
    compiled executable; ``-atime`` sidecars excluded)."""
    if not os.path.isdir(cache_dir):
        return 0
    return sum(1 for f in os.listdir(cache_dir)
               if not f.endswith("-atime") and not f.startswith("."))
