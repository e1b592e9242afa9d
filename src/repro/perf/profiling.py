"""The program's tracing: named spans on the profiler's timeline, host-time
counters, and the switch that records a profile.

``span(name, stats, key, **args)`` marks a phase of the program. It opens
a ``jax.profiler.TraceAnnotation``, so a recorded profile holds the span
on the host's timeline, on the same clock as the device's operations; a
profile's idle device gaps can then be attributed to the phase the host
was in. Given a ``stats`` dict, the span also adds its elapsed
``time.perf_counter_ns()`` to ``stats[key]``: an integer counter that
works with the profiler off. With nothing profiling a span costs about a
microsecond. Spans of one request carry its handle (``handle=h``), so
they share an identifier.

``trace(dir)`` records everything inside it into ``dir``; the launchers'
``--profile DIR`` wraps their whole run in it. View a profile with
``tensorboard --logdir <dir>`` (Profile tab), or give the ``*.xplane.pb``
under ``<dir>/plugins/profile/<run>/`` to ``jax.profiler.ProfileData``.
"""
from __future__ import annotations

import contextlib
import glob
import os
import time
from typing import Dict, Iterator, Optional

import jax

DEFAULT_TRACE_DIR = os.path.join("results", "traces")


def span(name: str, stats: Optional[Dict[str, int]] = None,
         key: Optional[str] = None, **args):
    """A named phase: a profiler span, and with ``stats`` its elapsed
    nanoseconds added to ``stats[key]``. Use as a context manager."""
    if stats is None:
        return jax.profiler.TraceAnnotation(name, **args)
    return _CountedSpan(name, stats, key, args)


class _CountedSpan:
    __slots__ = ("_ann", "_stats", "_key", "_t0")

    def __init__(self, name, stats, key, args):
        self._ann = jax.profiler.TraceAnnotation(name, **args)
        self._stats, self._key = stats, key

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self._stats[self._key] += time.perf_counter_ns() - self._t0
        return self._ann.__exit__(*exc)


@contextlib.contextmanager
def trace(log_dir: Optional[str] = None, *,
          enabled: bool = True) -> Iterator[Optional[str]]:
    """Profile everything inside the context into ``log_dir``.

    Yields the log dir (or None when ``enabled=False``, so callers can
    wrap unconditionally: ``with trace(d, enabled=args.trace):``).
    """
    if not enabled:
        yield None
        return
    log_dir = log_dir or DEFAULT_TRACE_DIR
    os.makedirs(log_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    # no event per Python call: the host's lines hold the program's spans
    # and JAX's own, and the host runs at its usual pace
    opts.python_tracer_level = 0
    with jax.profiler.trace(log_dir, profiler_options=opts):
        yield log_dir


def trace_runs(log_dir: str) -> list:
    """Profile run directories written under ``log_dir``, newest last."""
    runs = glob.glob(os.path.join(log_dir, "plugins", "profile", "*"))
    return sorted(runs, key=os.path.getmtime)
