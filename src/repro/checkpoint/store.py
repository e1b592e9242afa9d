"""Sharding-aware checkpointing: npz payloads + json manifest.

No orbax offline; this stores any pytree of arrays (train state, serve
params) with dtype/shape manifest and restores onto a mesh by device_put
with the original NamedShardings (or host arrays when mesh is None).

Layout: each ``save(path, tree, step=N)`` writes a *step-versioned*
subdirectory ``path/step_00000N/`` via a temp dir + atomic ``os.replace``
- a crash mid-save leaves at most a stale ``.tmp-*`` dir and never
corrupts an existing checkpoint. ``keep`` prunes to the last N steps.
``latest_step``/``restore`` scan the subdirs (and still understand the
pre-PR4 flat single-manifest layout). ``extra`` rides in the manifest for
host-side resume metadata (step counters, data-stream position).

Optional codec compression (``save(..., codec="uniform_amax:7")``):
leaves under the ``codec_keys`` top-level keys (default: the optimizer
moments m/v/e) are stored as ``repro.comm`` wire buffers - packed codes
+ scales - instead of raw f32, cutting moment snapshots ~4x at k_x=7.
The manifest records the codec spec and the packed byte layout
(``repro.comm.bits.LAYOUT``) per leaf; ``restore`` decodes
transparently, and refuses a leaf packed in another layout rather than
decode it wrongly. (Lossy by construction - exactly the quantizer's grid
error; master weights and counters always stay exact.)
"""
from __future__ import annotations

import json
import os
import shutil
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

_STEP_PREFIX = "step_"
_TMP_PREFIX = ".tmp-"

MOMENT_KEYS = ("m", "v", "e", "es")


def _flatten(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    keys = ["/".join(str(getattr(k, "key", getattr(k, "name", k)))
                     for k in path) for path, _ in flat]
    vals = [v for _, v in flat]
    return keys, vals, treedef


def _step_dirname(step: int) -> str:
    return f"{_STEP_PREFIX}{step:08d}"


def _list_steps(path: str) -> List[int]:
    """Step numbers of the complete (manifest-bearing) versioned subdirs."""
    try:
        names = os.listdir(path)
    except FileNotFoundError:
        return []
    steps = []
    for n in names:
        if not n.startswith(_STEP_PREFIX):
            continue
        if not os.path.exists(os.path.join(path, n, "manifest.json")):
            continue  # partial dir (crash before the atomic rename)
        try:
            steps.append(int(n[len(_STEP_PREFIX):]))
        except ValueError:
            continue
    return sorted(steps)


def _resolve_dir(path: str, step: Optional[int] = None) -> str:
    """Directory holding the requested (default: latest) checkpoint.
    Falls back to ``path`` itself for the legacy flat layout."""
    if step is not None:
        return os.path.join(path, _step_dirname(step))
    steps = _list_steps(path)
    if steps:
        return os.path.join(path, _step_dirname(steps[-1]))
    return path


def _codec_eligible(key: str, arr: np.ndarray,
                    codec_keys: Sequence[str]) -> bool:
    return (key.split("/", 1)[0] in codec_keys
            and arr.dtype.kind == "f" and arr.size > 1)


def _write_payload(d: str, tree: Any, step: Optional[int],
                   extra: Optional[Dict], codec: Optional[str] = None,
                   codec_keys: Sequence[str] = MOMENT_KEYS) -> None:
    os.makedirs(d, exist_ok=True)
    if codec is not None:
        from repro import comm
        from repro.comm.bits import LAYOUT
        cd = comm.get_codec(codec)
    keys, vals, _ = _flatten(tree)
    arrays = {}
    manifest = {"step": step, "leaves": []}
    if extra:
        manifest["extra"] = extra
    for i, (k, v) in enumerate(zip(keys, vals)):
        arr = np.asarray(jax.device_get(v))
        shape = list(arr.shape)  # before ascontiguousarray 0d->1d promotion
        arr = np.ascontiguousarray(arr)
        name = f"leaf_{i}"
        if codec is not None and _codec_eligible(k, arr, codec_keys):
            wb = cd.encode(jnp.asarray(arr))
            arrays[name] = np.asarray(jax.device_get(wb.payload))
            arrays[f"{name}_scale"] = np.asarray(jax.device_get(wb.scale))
            manifest["leaves"].append(
                {"key": k, "name": name, "dtype": str(arr.dtype),
                 "shape": shape, "codec": cd.spec, "layout": LAYOUT})
            continue
        # store raw bytes: npz mangles non-native dtypes (bfloat16 -> |V2)
        arrays[name] = arr.view(np.uint8).reshape(-1)
        manifest["leaves"].append(
            {"key": k, "name": name, "dtype": str(arr.dtype),
             "shape": shape})
    np.savez(os.path.join(d, "arrays.npz"), **arrays)
    with open(os.path.join(d, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=1)


def save(path: str, tree: Any, step: Optional[int] = None,
         keep: Optional[int] = None, extra: Optional[Dict] = None,
         codec: Optional[str] = None,
         codec_keys: Sequence[str] = MOMENT_KEYS) -> str:
    """Write one checkpoint; returns the directory written.

    With ``step``, writes ``path/step_XXXXXXXX/`` atomically (temp dir +
    ``os.replace``) and, with ``keep``, prunes to the newest ``keep``
    versioned checkpoints. Without ``step``, writes the flat legacy
    layout directly into ``path`` (serve params snapshots). ``codec``
    turns on codec-compressed snapshots for the ``codec_keys`` subtrees
    (see the module docstring).
    """
    if step is None:
        _write_payload(path, tree, None, extra, codec, codec_keys)
        return path
    os.makedirs(path, exist_ok=True)
    final = os.path.join(path, _step_dirname(step))
    tmp = os.path.join(path, f"{_TMP_PREFIX}{_step_dirname(step)}.{os.getpid()}")
    shutil.rmtree(tmp, ignore_errors=True)
    try:
        _write_payload(tmp, tree, step, extra, codec, codec_keys)
        if os.path.isdir(final):
            shutil.rmtree(final)
        os.replace(tmp, final)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    if keep is not None and keep > 0:
        for s in _list_steps(path)[:-keep]:
            shutil.rmtree(os.path.join(path, _step_dirname(s)),
                          ignore_errors=True)
    return final


def restore(path: str, like: Any, shardings: Any = None,
            step: Optional[int] = None) -> Any:
    """`like`: pytree with the target structure. `shardings`: optional
    matching pytree of jax.sharding.Sharding to place leaves. `step`:
    which versioned checkpoint to read (default: the latest; legacy flat
    layouts restore transparently)."""
    d = _resolve_dir(path, step)
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    data = np.load(os.path.join(d, "arrays.npz"))
    keys, vals, treedef = _flatten(like)
    by_key = {l["key"]: l for l in manifest["leaves"]}
    out = []
    import ml_dtypes  # registers bfloat16 etc. with numpy  # noqa: F401
    for k, v in zip(keys, vals):
        ent = by_key[k]
        raw = data[ent["name"]]
        dt = np.dtype(ent["dtype"])
        if ent.get("codec"):
            from repro import comm
            from repro.comm.bits import LAYOUT
            if ent.get("layout") != LAYOUT:
                raise ValueError(
                    f"{d}: leaf {k!r} was packed in wire layout "
                    f"{ent.get('layout', 1)}; this code reads layout "
                    f"{LAYOUT} - restore it with the code that wrote it "
                    f"and save it again")
            wb = comm.WireBuffer(
                payload=jnp.asarray(raw),
                scale=jnp.asarray(data[f"{ent['name']}_scale"]),
                spec=ent["codec"], shape=tuple(ent["shape"]))
            arr = np.asarray(jax.device_get(wb.decode())).astype(dt)
        else:
            arr = raw.view(dt).reshape(ent["shape"])
        assert list(arr.shape) == list(v.shape), (k, arr.shape, v.shape)
        out.append(jnp.asarray(arr))
    tree = jax.tree.unflatten(treedef, out)
    if shardings is not None:
        tree = jax.tree.map(jax.device_put, tree, shardings)
    return tree


def latest_step(path: str) -> Optional[int]:
    steps = _list_steps(path)
    if steps:
        return steps[-1]
    try:  # legacy flat layout
        with open(os.path.join(path, "manifest.json")) as f:
            return json.load(f).get("step")
    except FileNotFoundError:
        return None


def read_extra(path: str, step: Optional[int] = None) -> Dict:
    """Host-side resume metadata stored alongside a checkpoint."""
    d = _resolve_dir(path, step)
    try:
        with open(os.path.join(d, "manifest.json")) as f:
            return json.load(f).get("extra") or {}
    except FileNotFoundError:
        return {}
