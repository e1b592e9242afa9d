"""The first steps of QAdam-EF training, worked out by the plain reference.

One worker per device (``jax.pmap`` over the workers): worker w keeps its
own m, v and e, every worker keeps a copy of the masters, and all apply
the workers' mean update. ``fault`` plants a known error in the
reference's own steps, to read what such a fault does to the compared
numbers: ``"half_batch"`` lets only the first half of each sequence's
tokens into the loss.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import model as M
from bench.reference import qadam as Q


def _norms(tree):
    return [jnp.linalg.norm(x.reshape(-1)) for x in jax.tree.leaves(tree)]


def run(make_params, batches, dm, hp: Q.Hyper, devices, *,
        precision="float32", fault=None):
    """``len(batches)`` steps from the weights ``make_params()`` on the
    global batches ``batches`` (dicts of (W * b, S) host arrays, worker w
    taking rows [w * b, (w + 1) * b)).

    Returns ``{"losses", "grad_norms", "update_norms"}``: the mean loss of
    each step, every leaf's norm of the workers' mean first gradient, and
    every leaf's norm of the masters' change after the last step (leaves
    in ``jax.tree.leaves`` order)."""
    W = len(devices)

    def loss_fn(xq, batch):
        if fault == "half_batch":
            S = batch["mask"].shape[1]
            batch = dict(batch, mask=batch["mask"].at[:, S // 2:].set(0))
        return M.mean_loss(xq, batch, dm, precision, remat=True)

    @functools.partial(jax.pmap, axis_name="w", devices=devices,
                       donate_argnums=(0, 1, 2, 3))
    def step(master, m, v, e, batch, t):
        xq = jax.tree.map(lambda x: Q.broadcast_weights(x, W, hp), master)
        loss, g = jax.value_and_grad(loss_fn)(xq, batch)
        out = jax.tree.map(
            lambda g_, m_, v_, e_: Q.moments(g_, m_, v_, e_, t, hp),
            g, m, v, e)
        part = lambda i: jax.tree.map(lambda o: o[i], out,
                                      is_leaf=lambda o: isinstance(o, tuple))
        sent = jax.lax.pmean(part(2), "w")
        master = jax.tree.map(lambda x, s: x - s, master, sent)
        return master, part(0), part(1), part(3), jax.lax.pmean(loss, "w")

    @functools.partial(jax.pmap, axis_name="w", devices=devices)
    def mean_grad_norms(m):
        return _norms(jax.lax.pmean(m, "w"))

    @functools.partial(jax.pmap, axis_name="w", devices=devices)
    def change_norms(master, x0):
        return _norms(jax.tree.map(jnp.subtract, master, x0))

    rep = lambda tree: jax.device_put_replicated(tree, devices)
    x0 = make_params()
    master = rep(x0)
    del x0
    zeros = jax.tree.map(lambda x: jnp.zeros(x.shape[1:], jnp.float32),
                         master)
    m, v, e = rep(zeros), rep(zeros), rep(zeros)
    del zeros
    losses, grad_norms = [], []
    for t, batch in enumerate(batches, start=1):
        per = {k: np.asarray(a).reshape((W, -1) + a.shape[1:])
               for k, a in batch.items()}
        ts = np.full((W,), t, np.float32)
        master, m, v, e, loss = step(master, m, v, e, per, ts)
        losses.append(float(loss[0]))
        if t == 1:
            grad_norms = [float(n[0]) / (1.0 - hp.beta)
                          for n in mean_grad_norms(m)]
    del m, v, e
    x0 = rep(make_params())
    update_norms = [float(n[0]) for n in change_norms(master, x0)]
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": update_norms}
