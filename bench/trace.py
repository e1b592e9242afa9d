"""Reduction of a ``jax.profiler`` trace to the numbers the benchmark
reports: device busy time over the window, device time per kernel (by
the kernels' stable names), collective time that no other operation on
the device overlaps, and the breakdown (largest device operations,
longest idle gaps by what the host was doing).

The window is the host span the run opens around its measured loop
(``span(<name>_window)``); device events are on the same clock.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
from typing import Dict, Iterable, List, Optional, Tuple

COLLECTIVE = re.compile(r"^(all-to-all|all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|send|recv)", re.I)
CONTROL_FLOW = ("while", "conditional", "call")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SUFFIX = "_window"


class _Window:
    path: Optional[str] = None


@contextlib.contextmanager
def window(path: Optional[str]):
    """Profile everything inside the context into ``path`` (nothing when
    ``path`` is None)."""
    import jax
    tw = _Window()
    if path is None:
        yield tw
        return
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path, exist_ok=True)
    tw.path = path
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0    # no event per Python call
    jax.profiler.start_trace(path, profiler_options=opts)
    try:
        yield tw
    finally:
        jax.profiler.stop_trace()


def span(name: str):
    """A host span on the profiler's timeline."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def cleanup(path: Optional[str]) -> None:
    if path:
        shutil.rmtree(path, ignore_errors=True)


def xplane_file(path: str) -> str:
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


# ---------------------------------------------------------------------------
# interval arithmetic (nanoseconds)
# ---------------------------------------------------------------------------

def union(iv: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(iv):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(iv) -> int:
    return sum(e - s for s, e in iv)


def subtract(a, b) -> List[Tuple[int, int]]:
    """Intervals of union ``a`` not covered by union ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# ---------------------------------------------------------------------------
# reading the trace
# ---------------------------------------------------------------------------

def op_name(name: str) -> str:
    """The stable name of a device op: the HLO instruction's name without
    its numeric suffixes (``%codec_ef_encode.25 = (...) custom-call(...)``
    -> ``codec_ef_encode``; a Pallas kernel's instruction carries the
    kernel's ``name``)."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.(\d+|clone))+$", "", head)


def leaves(ops):
    """The ops that are not control flow: a ``while`` (or ``conditional``,
    ``call``) event spans the ops of its body, which the line holds too."""
    return [o for o in ops if o[2] not in CONTROL_FLOW]


def load(path: str):
    """(host events, {device id: [(start, end, op name)]}) of the trace
    under ``path``: host events are (start, end, name, thread)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_file(path))
    host, devices = [], {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    ops.append((s, s + int(ev.duration_ns),
                                op_name(ev.name)))
            devices[int(m.group(1))] = ops
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    host.append((s, s + int(ev.duration_ns), ev.name,
                                 line.name))
    return host, devices


def window_bounds(host) -> Tuple[int, int]:
    spans = [(s, e) for s, e, name, _ in host if name.endswith(WINDOW_SUFFIX)]
    if not spans:
        raise ValueError("the trace holds no window span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def summarize(path: str, *, devices: int, kernels: Iterable[str] = ()) -> dict:
    """Busy and idle time over the window averaged over the first
    ``devices`` chips, device seconds per kernel (summed over those chips,
    for every op named as one of ``kernels``), collective seconds that
    overlap no other op (averaged over chips), and the breakdown (ops
    that contain other ops, such as a ``while``, count only through
    those)."""
    host, planes = load(path)
    return reduce(host, planes, devices=devices, kernels=kernels)


def reduce(host, planes, *, devices: int, kernels: Iterable[str] = ()):
    """``summarize`` of loaded events (see ``load``)."""
    lo, hi = window_bounds(host)
    ids = sorted(planes)[:devices]
    if not ids:
        raise ValueError("the trace holds no TPU device plane")
    busy, exposed, kernel_ns = 0, 0, {k: 0 for k in kernels}
    by_op: Dict[str, int] = {}
    gaps0 = []
    for i in ids:
        ops = [(max(s, lo), min(e, hi), n)
               for s, e, n in planes[i] if e > lo and s < hi]
        all_iv = union((s, e) for s, e, _ in ops)
        busy += length(all_iv)
        ops = leaves(ops)
        coll = union((s, e) for s, e, n in ops if COLLECTIVE.match(n))
        other = union((s, e) for s, e, n in ops if not COLLECTIVE.match(n))
        exposed += length(subtract(coll, other))
        for s, e, n in ops:
            by_op[n] = by_op.get(n, 0) + (e - s)
            if n in kernel_ns:
                kernel_ns[n] += e - s
        if i == ids[0]:
            gaps0 = subtract([(lo, hi)], all_iv)
    n = len(ids)
    window_ns = hi - lo
    return {"window_s": window_ns / 1e9, "busy_s": busy / n / 1e9,
            "devices": n,
            "kernel_s": {k: v / 1e9 for k, v in kernel_ns.items()},
            "collective_exposed_s": exposed / n / 1e9,
            "breakdown": {
                "device_ops": [[k, v / n / 1e9] for k, v in sorted(
                    by_op.items(), key=lambda kv: -kv[1])[:10]],
                "idle_gaps": _attribute(gaps0, host, lo, hi)}}


def _attribute(gaps, host, lo, hi, top=10):
    """Idle seconds of the first chip, grouped by the innermost host span
    open at each gap's start (or "host: none")."""
    spans = sorted((s, e, name) for s, e, name, _ in host
                   if e > lo and s < hi and not name.endswith(WINDOW_SUFFIX))
    starts = [s for s, _, _ in spans]
    import bisect
    total: Dict[str, int] = {}
    for gs, ge in gaps:
        k = bisect.bisect_right(starts, gs)
        best = None
        for s, e, name in reversed(spans[max(0, k - 200):k]):
            if e > gs and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        label = "host: " + (best[2] if best else "none")
        total[label] = total.get(label, 0) + (ge - gs)
    return [[k, v / 1e9] for k, v in sorted(total.items(),
                                            key=lambda kv: -kv[1])[:top]]

