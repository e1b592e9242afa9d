"""Weights and keys made by the benchmark from ``--seed``.

Both the program under test and the plain reference receive these same
weights, made on the device in one jitted call: every norm gain 1, every
other leaf a normal truncated at two standard deviations, times 0.02
(the usual initialisation of this model family).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

STD = 0.02


def seed_key(seed: int):
    """A PRNG key for any whole ``seed`` (also above 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def _is_norm(path) -> bool:
    return getattr(path[-1], "key", None) == "w"


def _make(shapes, key, dtype):
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        if _is_norm(path):
            leaves.append(jnp.ones(shape, dtype))
        else:
            x = jax.random.truncated_normal(jax.random.fold_in(key, i), -2.0,
                                            2.0, shape, jnp.float32) * STD
            leaves.append(x.astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


@functools.lru_cache(maxsize=8)
def _maker(shapes_key, dtype_name):
    shapes = _unfreeze(shapes_key)
    return jax.jit(functools.partial(_make, shapes,
                                     dtype=jnp.dtype(dtype_name)))


def _freeze(tree):
    if isinstance(tree, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in tree.items()))
    return ("shape", tuple(tree))


def _unfreeze(frozen):
    if frozen and frozen[0] == "shape":
        return tuple(frozen[1])
    return {k: _unfreeze(v) for k, v in frozen}


def make_params(shapes: dict, key, dtype=jnp.float32):
    """The weights of ``shapes`` (a nested dict of shape tuples) from
    ``key``, as arrays of ``dtype`` on the default device."""
    return _maker(_freeze(shapes), jnp.dtype(dtype).name)(key)
