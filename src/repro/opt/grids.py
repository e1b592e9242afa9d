"""Canonical definition of the paper's quantizer grids + Adam+EF leaf math.

This module is *the* single source of truth for the update arithmetic
(Algorithm 1 lines 3-6) and the four grids (log Q_g, uniform Q_x,
TernGrad ternary, Zheng-et-al blockwise sign). Every other layer is a
view of these functions:

  * ``repro.opt.engine``   - backend dispatch (jnp vs Pallas) around them;
  * ``repro.kernels.*``    - Pallas kernel bodies *call* these functions on
    their VMEM-resident tiles, so kernels cannot drift from the oracle;
  * ``repro.core.quantizers`` - the QTensor wire objects encode/decode
    through them;
  * ``repro.dist.modes``   - the distributed per-mode updaters.

All functions are pure jnp, shape-polymorphic, and operate on explicit
scales (the two-pass scheme: pass 1 amax, pass 2 quantize). Stochastic
grids take pre-drawn uniforms so both backends consume identical bits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def block_amax(x: jax.Array) -> jax.Array:
    """Per-call global amax (the scale pass)."""
    return jnp.max(jnp.abs(x.astype(jnp.float32)))


def amax_scale(x: jax.Array) -> jax.Array:
    """Amax scale with the zero-guard every channel must share: the
    bit-equivalence tests depend on the scales matching across layers."""
    amax = block_amax(x)
    return jnp.where(amax > 0, amax, 1.0).astype(jnp.float32)


# ---------------------------------------------------------------------------
# log grid (the paper's Q_g)
# ---------------------------------------------------------------------------

def log_quantize(x: jax.Array, scale: jax.Array, k_g: int) -> jax.Array:
    """Nearest-in-linear-space log-grid codes given a scale.

    Code layout: 0 encodes 0; signed code c with |c| in [1, k_g+1]
    encodes +/- 2^{-(k_g+1-|c|)}.
    """
    x = x.astype(jnp.float32)
    s = jnp.maximum(scale, 1e-30)
    y = jnp.abs(x) / s
    safe_y = jnp.where(y > 0, y, 1.0)
    e_float = -jnp.log2(safe_y)
    e_lo = jnp.floor(e_float)
    # midpoint in linear space between 2^-e_lo and 2^-(e_lo+1)
    mid = 1.5 * jnp.exp2(-(e_lo + 1.0))
    e_near = jnp.where(y >= mid, e_lo, e_lo + 1.0)
    e_near = jnp.clip(e_near, 0.0, float(k_g))
    # zero threshold: halfway to the smallest level
    # the threshold is a host constant (2^-k_g is exact in f32): a
    # constant exp2 would be traced into the kernels, which TPU kernels
    # cannot lower
    is_zero = (y < 2.0 ** -k_g * 0.5) | (x == 0.0)
    mag = jnp.where(is_zero, 0.0, float(k_g) + 1.0 - e_near)
    return jnp.where(x < 0, -mag, mag).astype(jnp.int8)


def log_dequantize(codes: jax.Array, scale: jax.Array, k_g: int) -> jax.Array:
    c = codes.astype(jnp.float32)
    mag = jnp.abs(c)
    val = jnp.exp2(mag - (float(k_g) + 1.0))
    val = jnp.where(mag == 0, 0.0, val)
    return jnp.sign(c) * val * scale


@functools.lru_cache(maxsize=None)
def log_dequant_table(k_g: int, bits: int) -> np.ndarray:
    """Scale-1 dequant values for every ``bits``-wide lane code, ordered by
    raw lane value (index = code + 2^{bits-1}).

    A k_g log grid has only 2k_g+3 representable values, so decode can be a
    table gather instead of a per-element exp2. The table is built by
    evaluating :func:`log_dequantize` itself rather than recomputing powers
    of two host-side: XLA lowers exp2 as exp(x*ln2), which is off by an ulp
    for large integral arguments, and bit-identity must hold for *every*
    representable lane code, in-range or not.
    """
    n = 1 << bits
    # first call may happen under an outer jit trace (the codec entry
    # points build it lazily); force compile-time eval so the oracle runs
    # concretely and the table is a plain host constant.
    with jax.ensure_compile_time_eval():
        codes = jnp.arange(-(n // 2), n // 2, dtype=jnp.int32)
        table = log_dequantize(codes, jnp.float32(1.0), k_g)
    return np.asarray(table)


def log_dequantize_lut(codes: jax.Array, scale: jax.Array, lut: jax.Array) -> jax.Array:
    """Table form of :func:`log_dequantize`: ``lut[code + n/2] * scale``.

    Bit-identical to the oracle because the table holds ``sign(c) * val``
    at scale 1 and the original associates as ``(sign(c) * val) * scale``.
    ``lut`` comes from :func:`log_dequant_table`; codes must be lane-range
    (|c| < 2^{bits-1}), which every packed payload guarantees.
    """
    lut = jnp.asarray(lut, dtype=jnp.float32)
    idx = codes.astype(jnp.int32) + lut.shape[0] // 2
    return jnp.take(lut, idx, axis=0, mode="clip") * scale


# ---------------------------------------------------------------------------
# uniform grid (the paper's Q_x)
# ---------------------------------------------------------------------------

def uniform_code_dtype(k_x: int):
    """Codes live in [-2^k, 2^k]: int8 holds k_x <= 6, int16 k_x <= 14."""
    if k_x <= 6:
        return jnp.int8
    return jnp.int16 if k_x <= 14 else jnp.int32


def uniform_quantize(x: jax.Array, scale: jax.Array, k_x: int) -> jax.Array:
    n = float(2 ** k_x)
    y = jnp.clip(x.astype(jnp.float32) / jnp.maximum(scale, 1e-30), -1.0, 1.0)
    return jnp.round(y * n).astype(uniform_code_dtype(k_x))


def uniform_dequantize(codes: jax.Array, scale: jax.Array, k_x: int) -> jax.Array:
    n = float(2 ** k_x)
    return codes.astype(jnp.float32) / n * scale


# ---------------------------------------------------------------------------
# ternary grid (TernGrad baseline)
# ---------------------------------------------------------------------------

def ternary_quantize(x: jax.Array, u: jax.Array, scale: jax.Array) -> jax.Array:
    """Unbiased stochastic ternary codes {-1, 0, +1}. ``u`` are uniforms in
    [0, 1) drawn outside (``jax.random.uniform(key, x.shape)``) so the jnp
    and Pallas backends consume identical randomness; ``u < |x|/scale`` is
    exactly ``jax.random.bernoulli(key, |x|/scale)``."""
    x = x.astype(jnp.float32)
    p = jnp.abs(x) / jnp.maximum(scale, 1e-30)
    b = (u < p).astype(jnp.int8)
    return jnp.sign(x).astype(jnp.int8) * b


def ternary_dequantize(codes: jax.Array, scale: jax.Array) -> jax.Array:
    return codes.astype(jnp.float32) * scale


# ---------------------------------------------------------------------------
# blockwise sign grid (Zheng et al. '19 baseline)
# ---------------------------------------------------------------------------

def blockwise_quantize(x2d: jax.Array):
    """(nb, block) f32 -> (sign codes int8, per-block mean-|.| scales)."""
    x2d = x2d.astype(jnp.float32)
    scale = jnp.mean(jnp.abs(x2d), axis=-1)
    return jnp.sign(x2d).astype(jnp.int8), scale


def blockwise_dequantize(codes2d: jax.Array, scales: jax.Array) -> jax.Array:
    return codes2d.astype(jnp.float32) * scales[..., None]


# ---------------------------------------------------------------------------
# Adam+EF leaf math (Algorithm 1 lines 3-6)
# ---------------------------------------------------------------------------

def adam_ef_moments(g, m, v, e, *, alpha_t, beta, theta_t, eps):
    """Moment updates + the full-precision Delta_t + e_t (pre-quantize).

    Returns (m_new, v_new, delta_plus_e). The ``m / sqrt(v + eps)``
    formulation is load-bearing: the Pallas kernel body calls this same
    function, so both backends round identically and the bit-equivalence
    guarantees hold.
    """
    g = g.astype(jnp.float32)
    v_new = theta_t * v + (1.0 - theta_t) * g * g
    m_new = beta * m + (1.0 - beta) * g
    delta_plus_e = alpha_t * m_new / jnp.sqrt(v_new + eps) + e
    return m_new, v_new, delta_plus_e


def adam_ef_quantize(delta_plus_e, scale, k_g: int):
    """Codes + EF residual (Algorithm 1 lines 5-6)."""
    codes = log_quantize(delta_plus_e, scale, k_g)
    deq = log_dequantize(codes, scale, k_g)
    e_new = delta_plus_e - deq
    return codes, e_new
