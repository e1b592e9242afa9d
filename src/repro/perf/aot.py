"""Ahead-of-time export/load of compiled step executables.

The persistent XLA cache (:mod:`repro.perf.cache`) skips *compilation*
on restart but still pays tracing + lowering per process. This module
removes that too: a compiled step is serialized once
(``jax.experimental.serialize_executable``) under a key digesting
everything its machine code depends on - train/serve config, mesh
geometry, mode, codec specs, abstract argument shapes/dtypes/shardings,
device topology, jax version - and later restarts
``deserialize_and_load`` the executable directly.

Artifact layout: ``<aot_dir>/<sha256[:24]>.aotstep``, a pickle of
``{format, jax, key_facts, payload, in_tree, out_tree}``. Donation is
baked into the serialized executable, so a loaded step donates exactly
the argnums the original ``jax.jit`` did. Any load failure (missing,
corrupt, version-skewed) falls back to compiling - an AOT dir is a
cache, never a correctness dependency.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pickle
from typing import Any, Optional

import jax
from jax.experimental import serialize_executable as _se

# 2: keys name the packed wire layout (repro.comm.bits.LAYOUT), which
# a compiled step bakes in
FORMAT = 2
SUFFIX = ".aotstep"


def _canon(obj):
    """Canonicalize config-ish objects into JSON-able structure."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {"__dc__": type(obj).__name__,
                **{k: _canon(v) for k, v in
                   dataclasses.asdict(obj).items()}}
    if isinstance(obj, dict):
        return {str(k): _canon(v) for k, v in sorted(obj.items())}
    if isinstance(obj, (list, tuple)):
        return [_canon(v) for v in obj]
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    return repr(obj)


def digest(facts: Any) -> str:
    blob = json.dumps(_canon(facts), sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:24]


def _abstract(tree) -> Any:
    """Shape/dtype/sharding signature of an argument pytree. Python
    scalars abstract to their TYPE only: jit traces them as weak-typed
    scalars, so the executable is value-independent (the train step's
    ring ``slot`` varies per dispatch and must not fork the key)."""
    def leaf(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            sh = getattr(x, "sharding", None)
            return (tuple(x.shape), str(x.dtype),
                    repr(sh) if sh is not None else None)
        if isinstance(x, (bool, int, float)):
            return ("py", type(x).__name__)
        return x if isinstance(x, (str, type(None))) else repr(x)
    return jax.tree_util.tree_map(leaf, tree)


def _device_facts() -> dict:
    from repro.comm.bits import LAYOUT
    devs = jax.devices()
    return {
        "backend": jax.default_backend(),
        "device_kind": devs[0].device_kind,
        "n_devices": len(devs),
        "jax": jax.__version__,
        "format": FORMAT,
        "wire_layout": LAYOUT,
    }


def step_key(facts: Any, args: tuple = ()) -> str:
    """Digest of (caller facts, abstract args, device topology) - the
    name the step executable is stored under."""
    return digest({"facts": facts, "args": _abstract(args),
                   "device": _device_facts()})


def artifact_path(aot_dir: str, key: str) -> str:
    return os.path.join(aot_dir, key + SUFFIX)


def save(aot_dir: str, key: str, compiled) -> str:
    """Serialize a ``jax.stages.Compiled`` under ``key``. Atomic
    (tmp + rename) so a crashed writer never leaves a torn artifact."""
    os.makedirs(aot_dir, exist_ok=True)
    payload, in_tree, out_tree = _se.serialize(compiled)
    blob = pickle.dumps({
        "format": FORMAT,
        "jax": jax.__version__,
        "payload": payload,
        "in_tree": in_tree,
        "out_tree": out_tree,
    })
    path = artifact_path(aot_dir, key)
    tmp = path + f".tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(blob)
    os.replace(tmp, path)
    return path


def load(aot_dir: Optional[str], key: str):
    """Load the executable stored under ``key``, or None when absent /
    corrupt / built by a different jax (AOT dirs are caches: every
    failure mode is a miss, never an error)."""
    if not aot_dir:
        return None
    path = artifact_path(aot_dir, key)
    if not os.path.exists(path):
        return None
    try:
        with open(path, "rb") as f:
            art = pickle.load(f)
        if art.get("format") != FORMAT or art.get("jax") != jax.__version__:
            return None
        return _se.deserialize_and_load(art["payload"], art["in_tree"],
                                        art["out_tree"])
    except Exception:
        return None


def load_or_compile(jitted, args: tuple, *, aot_dir: Optional[str],
                    facts: Any, stats: Optional[dict] = None):
    """The session-side entry point: return a ready executable for
    ``jitted(*args)``, loading from ``aot_dir`` when a matching artifact
    exists and compiling + exporting otherwise.

    Without an ``aot_dir`` the jitted callable is returned as-is (its
    first call compiles, possibly hitting the persistent XLA cache).
    ``stats`` counters incremented: ``aot_loads`` on a hit,
    ``compilations`` otherwise (and ``aot_saves`` after an export).
    """
    def bump(name):
        if stats is not None:
            stats[name] = stats.get(name, 0) + 1

    if not aot_dir:
        bump("compilations")
        return jitted
    key = step_key(facts, args)
    compiled = load(aot_dir, key)
    if compiled is not None:
        bump("aot_loads")
        return compiled
    compiled = jitted.lower(*args).compile()
    bump("compilations")
    save(aot_dir, key, compiled)
    bump("aot_saves")
    return compiled
