"""Reference logits of served requests, layer by layer.

The deployment serves every large leaf as Q_x codes (``k_x`` bits, one
scale per layer for the stacked per-layer leaves, one per tensor
otherwise; leaves under ``min_numel`` elements stay as they are). The
reference applies the same grid to the benchmark's weights itself
(``qadam.uniform_grid``) and runs the full forward pass over each prompt
with its served tokens, one layer at a time so that only one layer's
float32 weights exist at once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import model as M
from bench.reference import qadam as Q

def dequant(x, k_x, min_numel, per_layer=False):
    """The served values of one leaf (per_layer: x is one layer's slice
    of a stacked leaf, which has a scale of its own)."""
    x = x.astype(jnp.float32)
    if x.size < min_numel and not per_layer:
        return x
    return Q.uniform_grid(x, Q.amax_or_one(x), k_x)


@functools.lru_cache(maxsize=None)
def _layer_fn(dm, precision, k_x, coded):
    """Jitted layer; ``coded`` names the stacked leaves served as codes
    (a stacked leaf is coded when the whole stack is large enough)."""
    dmd = dict(dm)

    def one(path, a):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if name in coded:
            return dequant(a, k_x, 0, per_layer=True)
        return a.astype(jnp.float32)

    @jax.jit
    def fn(x, p):
        p = jax.tree_util.tree_map_with_path(one, p)
        return M.layer(x, p, dmd, precision)
    return fn


@functools.partial(jax.jit, static_argnames=("dm", "precision", "k_x",
                                             "min_numel"))
def _head(x, final_w, unembed, *, dm, precision, k_x, min_numel):
    params = {"final_norm": {"w": final_w.astype(jnp.float32)},
              "unembed": dequant(unembed, k_x, min_numel)}
    return M.head(x, params, dict(dm), precision)


@functools.partial(jax.jit, static_argnames=("k_x", "min_numel"))
def _embed(embed, tokens, *, k_x, min_numel):
    return dequant(embed, k_x, min_numel)[tokens]


def logits(params, tokens, dm, *, k_x, min_numel, length,
           precision="float32"):
    """Float32 logits (T, V) of every position of the int sequence
    ``tokens`` (T,), run padded to ``length`` positions (one compiled
    shape for every request; causal, so the padding changes nothing)."""
    T = len(tokens)
    ids = np.zeros((1, length), np.int32)
    ids[0, :T] = tokens
    dmf = tuple(sorted(dm.items()))
    x = _embed(params["embed"], jnp.asarray(ids), k_x=k_x,
               min_numel=min_numel)
    coded = frozenset(
        "/".join(str(getattr(k, "key", k)) for k in path)
        for path, a in jax.tree_util.tree_flatten_with_path(
            params["blocks"])[0] if a.size >= min_numel)
    layer_fn = _layer_fn(dmf, precision, k_x, coded)
    for l in range(dm["L"]):
        x = layer_fn(x, M.layer_params(params["blocks"], l))
    out = _head(x, params["final_norm"]["w"], params["unembed"], dm=dmf,
                precision=precision, k_x=k_x, min_numel=min_numel)
    return out[0, :T]
