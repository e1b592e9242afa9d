"""Plain float32 QAdam-EF (the paper's Algorithms 1-3) over W workers.

Per step t (1-based), for every parameter leaf:

* weight broadcast: the flattened leaf is split into W equal chunks
  (zero padded at the end); each chunk goes through Q_x, the uniform grid
  of ``2^k_x`` steps over [-s, s] with s the chunk's largest |value|.
  Leaves smaller than ``weight_min_numel`` are sent as they are.
* every worker w takes the gradient g_w of the mean loss over its own
  batch at those weights, then
  ``v = theta_t v + (1 - theta_t) g^2``, ``m = beta m + (1 - beta) g``,
  ``d = alpha m / sqrt(v + eps) + e``, with ``theta_t = 1 - theta / t``;
  it sends Q_g(d), the log grid {0, +-s 2^-j : j = 0..k_g} with s the
  largest |d| of the leaf (nearest level in linear distance; 0 below half
  the smallest level), and keeps ``e = d - Q_g(d)``.
* the masters move by the mean over workers of what was sent.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Hyper:
    alpha: float = 1e-3
    beta: float = 0.99
    theta: float = 0.999
    eps: float = 1e-5
    grad_bits: int = 4
    weight_bits: int = 6
    weight_min_numel: int = 2 ** 14
    error_feedback: bool = True

    @classmethod
    def from_config(cls, train: dict) -> "Hyper":
        keys = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in train.items() if k in keys})


def amax_or_one(x):
    a = jnp.max(jnp.abs(x))
    return jnp.where(a > 0, a, 1.0).astype(jnp.float32)


def log_grid(x, scale, k):
    """Q_g(x) as values: the nearest of s * 2^-j (j = 0..k), keeping the
    sign; 0 where |x| < s * 2^-(k+1). Ties go to the larger level."""
    y = jnp.abs(x.astype(jnp.float32)) / scale
    best = jnp.ones_like(y)
    for j in range(1, k + 1):
        lvl = np.float32(2.0 ** -j)
        best = jnp.where(jnp.abs(y - lvl) < jnp.abs(y - best), lvl, best)
    best = jnp.where(y < np.float32(2.0 ** -(k + 1)), 0.0, best)
    return jnp.sign(x) * best * scale


def uniform_grid(x, scale, k):
    """Q_x(x) as values: round(x / s * 2^k) / 2^k * s, |x / s| clipped to 1."""
    n = np.float32(2.0 ** k)
    y = jnp.clip(x.astype(jnp.float32) / scale, -1.0, 1.0)
    return jnp.round(y * n) / n * scale


def chunked(flat, W):
    c = -(-flat.shape[0] // W)
    return jnp.pad(flat, (0, W * c - flat.shape[0])).reshape(W, c)


def broadcast_weights(x, W, hp: Hyper):
    """What every worker computes with: the leaf through Q_x chunk by
    chunk (small leaves exact)."""
    if x.size < hp.weight_min_numel or not hp.weight_bits:
        return x
    rows = chunked(x.reshape(-1), W)
    scales = jax.vmap(amax_or_one)(rows)
    q = uniform_grid(rows, scales[:, None], hp.weight_bits)
    return q.reshape(-1)[:x.size].reshape(x.shape)


def moments(g, m, v, e, t, hp: Hyper):
    """One worker's Adam + error feedback on one leaf. Returns
    (m', v', sent, e')."""
    g = g.astype(jnp.float32)
    th = 1.0 - hp.theta / t
    v = th * v + (1.0 - th) * g * g
    m = hp.beta * m + (1.0 - hp.beta) * g
    d = hp.alpha * m / jnp.sqrt(v + hp.eps) + e
    if hp.grad_bits:
        sent = log_grid(d, amax_or_one(d), hp.grad_bits)
    else:
        sent = d
    e = d - sent if hp.error_feedback else jnp.zeros_like(d)
    return m, v, sent, e
