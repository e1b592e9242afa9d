"""What every cell shares: finding the files ``BENCHMARK.json`` names,
the device check, peaks, the compile counter, per-layer metric readers,
and the result line.

Everything is found by name: a workload names its configuration (the
``file`` in ``configs``) and its traffic (``bench/traffic/<traffic>.json``,
whose ``kind`` picks the module ``bench/kinds/<kind>.py``); a per-layer
metric ``<name>`` is read by ``bench/metrics/<name>.py``. Adding a cell
adds files and entries, and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class SetupError(SystemExit):
    """The run cannot start: no result is printed."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def spec(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise SetupError(f"no BENCHMARK.json in {root}")
    return load_json(path)


@dataclasses.dataclass
class Cell:
    """One workload with its configuration and traffic, as the files
    hold them."""
    name: str
    chips: int
    config: dict         # the configuration file
    traffic: dict        # the traffic file
    end_to_end: List[dict]
    per_layer: List[dict]
    limits: dict         # bench/limits/<name>.json: the compared numbers'


def traffic_path(name: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", "traffic", f"{name}.json")


def reports(metric: dict, cell_name: str) -> bool:
    """Whether a cell reports a metric: listed in its ``workloads`` key;
    one without the key (``setup_s``), every cell."""
    return cell_name in metric.get("workloads", [cell_name])


def find_cell(name: str, root: str = ROOT) -> Cell:
    sp = spec(root)
    entry = next((w for w in sp["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SetupError(f"no workload {name!r} in BENCHMARK.json")
    conf_entry = next(c for c in sp["configs"] if c["name"] == entry["config"])
    return Cell(name=name, chips=int(entry["chips"]),
                config=load_json(os.path.join(root, conf_entry["file"])),
                traffic=load_json(traffic_path(entry["traffic"], root)),
                end_to_end=[m for m in sp["end_to_end"] if reports(m, name)],
                per_layer=[m for m in sp["per_layer"] if reports(m, name)],
                limits=load_json(os.path.join(
                    root, "bench", "limits", f"{name}.json"))["limits"])


def traffic_kind(kind: str):
    return importlib.import_module(f"bench.kinds.{kind}")


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": min(chips, len(devs))}


def require_chips(chips: int) -> None:
    """Refuse to run anywhere but on ``chips`` TPU chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SetupError(f"bench: no TPU found (JAX sees "
                         f"{devs[0].platform}); nothing was measured")
    if len(devs) < chips:
        raise SetupError(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")


def peak_memory(devices) -> Optional[int]:
    """``peak_bytes_in_use`` of the fullest device, or None where the
    backend keeps no statistics."""
    peaks = []
    for d in devices:
        st = d.memory_stats()
        if st and "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def memory_in_use(devices) -> Optional[int]:
    """``bytes_in_use`` of the fullest device now, or None."""
    used = [int(st["bytes_in_use"]) for st in
            (d.memory_stats() for d in devices) if st and "bytes_in_use" in st]
    return max(used) if used else None


def peaks(kind: str) -> dict:
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if kind not in table["devices"]:
        raise SetupError(f"bench: no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table["devices"][kind]


class CompileCounter:
    """Counts XLA backend compilations (``jax.monitoring`` events) so a
    run can show that nothing compiled inside its window."""

    def __init__(self):
        import jax
        self.n = 0

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


def compile_cache() -> str:
    """The persistent compile cache at its fixed place: the program's
    own choice (``$JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache/`` in
    the checkout), with no size cap. A cap that evicts least recently
    used entries (one near 190 MiB was seen on the chip's machines) drops
    a cell's step program while the same run compiles its reference, so
    every run compiled its step again; a run writes each of its programs
    once."""
    import jax
    from repro import perf
    jax.config.update("jax_compilation_cache_max_size", -1)
    return perf.enable_persistent_cache()


# ---------------------------------------------------------------------------
# per-layer metrics
# ---------------------------------------------------------------------------

def reader(name: str, root: str = ROOT):
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    sp = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def kernels_of(cell: Cell) -> List[str]:
    """Kernel names the cell's per-layer readers time (their ``KERNELS``)."""
    names: List[str] = []
    for m in cell.per_layer:
        for k in getattr(reader(m["name"]), "KERNELS", ()):
            if k not in names:
                names.append(k)
    return names


def note(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr)


def read_per_layer(cell: Cell, run) -> Dict[str, dict]:
    """Every per-layer metric of the cell that finds something to read."""
    out = {}
    for m in cell.per_layer:
        value = reader(m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# result
# ---------------------------------------------------------------------------

def check(name: str, value: float, limit: float) -> dict:
    """One compared number beside its limit (value must not exceed it)."""
    return {"name": name, "value": float(value), "limit": float(limit),
            "ok": bool(value <= limit)}


def result_line(*, checks: List[dict], attempted: int, failed: int,
                metrics: Dict[str, dict], device: dict,
                breakdown: Optional[dict] = None) -> str:
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r}) "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    line = {"correct": bool(checks) and all(c["ok"] for c in checks),
            "attempted": int(attempted), "failed": int(failed),
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in checks}
    return json.dumps(line)


def now() -> float:
    return time.perf_counter()
