"""Fused Adam+EF worker-step kernel (Algorithm 3 lines 4-7, minus comm).

Two Pallas passes over the parameter shard:

  pass A (`adam_moments`): one streamed read of (g, m, v, e), one write of
      (m', v', Delta+e) plus per-block amax partials -> the scale for Q_g.
      Naively this is 6 separate elementwise XLA ops with ~10 HBM
      round-trips; the fusion does 4 reads + 3 writes.
  pass B (`ef_quantize`): reads Delta+e, writes int8 codes and the new
      error-feedback residual e' = (Delta+e) - deq(codes).

Scalars (alpha_t, beta, theta_t, eps) arrive as a (4,) f32 operand placed
whole in SMEM; the amax is folded block by block into an SMEM scalar.

Both kernel bodies call the canonical math in ``repro.opt.grids`` on their
VMEM tiles, so the fused path is bit-identical to the jnp backend by
construction (asserted by ``tests/test_opt_engine.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.comm.kernels import BLOCK_ROWS, LANES, smem
from repro.opt import grids


def _moments_kernel(g_ref, m_ref, v_ref, e_ref, hp_ref,
                    m_out, v_out, de_out, amax_out):
    m_new, v_new, de = grids.adam_ef_moments(
        g_ref[...], m_ref[...], v_ref[...], e_ref[...],
        alpha_t=hp_ref[0], beta=hp_ref[1], theta_t=hp_ref[2], eps=hp_ref[3])
    m_out[...] = m_new
    v_out[...] = v_new
    de_out[...] = de
    part = grids.block_amax(de)
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        amax_out[0] = part

    @pl.when(i > 0)
    def _():
        amax_out[0] = jnp.maximum(amax_out[0], part)


def adam_moments_pallas(g2d, m2d, v2d, e2d, hp, *, interpret: bool):
    """hp: (4,) f32 = [alpha_t, beta, theta_t, eps]."""
    rows = g2d.shape[0]
    grid = rows // BLOCK_ROWS
    blk = lambda: pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    m_new, v_new, de, amax = pl.pallas_call(
        _moments_kernel,
        grid=(grid,),
        in_specs=[blk(), blk(), blk(), blk(), smem()],
        out_specs=[blk(), blk(), blk(), smem()],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.float32),
        ],
        interpret=interpret,
        name="adam_ef_moments",
    )(g2d, m2d, v2d, e2d, hp)
    return m_new, v_new, de, amax[0]


def _ef_quantize_kernel(de_ref, scale_ref, codes_ref, e_out, *, k_g: int):
    codes, e_new = grids.adam_ef_quantize(de_ref[...], scale_ref[0], k_g)
    codes_ref[...] = codes
    e_out[...] = e_new


def ef_quantize_pallas(de2d, scale, k_g: int, *, interpret: bool):
    rows = de2d.shape[0]
    grid = rows // BLOCK_ROWS
    blk = lambda: pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_ef_quantize_kernel, k_g=k_g),
        grid=(grid,),
        in_specs=[blk(), smem()],
        out_specs=[blk(), blk()],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.int8),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        ],
        interpret=interpret,
        name="adam_ef_quantize",
    )(de2d, scale.reshape(1))
