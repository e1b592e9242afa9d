"""Performance infrastructure: profiling, compile caching, AOT steps.

The subsystem that catches fused-kernel regressions at authoring time
(the PR-5 log-decode 0.23x went unnoticed because the CI gate's blanket
1.5x grace tolerated it) and eliminates jit cold-start on fleet
restarts:

  * :mod:`repro.perf.profiling` - the program's spans and host-time
    counters, and ``trace`` (the launchers' ``--profile DIR``);
  * :mod:`repro.perf.cache`     - persistent XLA compilation cache
    placement shared by the launchers;
  * :mod:`repro.perf.aot`       - ahead-of-time export/load of compiled
    train/decode steps keyed on (config digest, mesh, mode, codec);
  * :mod:`repro.perf.autotune`  - per-backend tile-width tuning for the
    fused kernels (installs ``comm.kernels.set_enc_rows`` and
    ``comm.matmul.set_mm_cols``).
"""
from repro.perf import aot, autotune, cache, profiling
from repro.perf.aot import load_or_compile, step_key
from repro.perf.cache import (cache_entries, disable_persistent_cache,
                              enable_persistent_cache)
from repro.perf.profiling import span, trace

__all__ = [
    "aot", "autotune", "cache", "profiling",
    "span", "trace",
    "cache_entries", "disable_persistent_cache", "enable_persistent_cache",
    "load_or_compile", "step_key",
]
