"""The paper's mode (Algorithms 2+3): Adam+EF per worker, log-grid Q_g
codes on the update-exchange wire (fused encode straight to payload
rows - the codes never hit HBM unpacked)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import comm
from repro.dist import collectives as C
from repro.dist import sharding as SH
from repro.dist.modes.base import (ModeSpec, WorkerCtx, ctx_tiers,
                                   tier_grad_mean, worker_mean)
from repro.opt import engine, grids


def wire_codec(grad_k=None) -> comm.Codec:
    """Log-grid codec packed to its lane width; identity (f32 rows) when
    the wire is unquantized."""
    if grad_k is None:
        return comm.IdentityCodec()
    return comm.LogCodec(k_g=grad_k)


def make_updater(tc, ctx: WorkerCtx):
    codec = wire_codec(tc.grad_k)
    tiers = ctx_tiers(ctx)

    def upd(g, m, v, e, chunk, meta, a_t, th_t, key, idx):
        # hierarchical: fp node-mean gradient first; the quantized
        # exchange below then ships one row per node over the slow tier.
        g = tier_grad_mean(g, tiers)
        with jax.named_scope("qadam.update"):
            m2, v2, de = engine.adam_ef_moments(
                g, m, v, e, a_t, tc.beta, th_t, tc.eps, backend=ctx.backend)
        with jax.named_scope("qadam.exchange"):
            if tc.grad_k is None:
                rows = SH.flatten_pad(de, ctx.n_workers)
                recv = C.exchange_rows_tiered(rows, tiers)
                e2 = jnp.zeros_like(e)
            else:
                scale = grids.amax_scale(de)
                payload, e2 = comm.encode_rows_ef(de, scale, codec,
                                                  ctx.n_workers,
                                                  backend=ctx.backend)
                if not tc.error_feedback:
                    e2 = jnp.zeros_like(e)
                recv = C.exchange_decode_tiered(payload, scale, codec,
                                                meta.c, tiers,
                                                backend=ctx.backend)
            return chunk - worker_mean(recv), m2, v2, e2
    return upd


SPEC = ModeSpec(name="qadam", chunk_sharded_moments=False,
                make_updater=make_updater, wire_codec=wire_codec)
