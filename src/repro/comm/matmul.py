"""Fused dequant-matmul for code-resident Q_x weights (the serving hot
path).

``QuantizedLeaf.dequantize()`` runs unpack + dequant as a separate
memory-bound pass that materializes the full fp32 weight tensor before
every projection. Here the contraction consumes the codes directly: the
Pallas kernel walks a (row tile, column tile, K tile) grid, loads one
code tile + the scale (SMEM) per step, unpacks and dequantizes it in
VMEM, and feeds it straight into ``jnp.dot`` - the fp32 weight tensor
never exists in HBM. A packed column tile is one whole packing block
(``repro.comm.bits.block_codes``), whose byte planes are whole 128-lane
vregs; ``quantize_params`` pads packed rows to whole blocks for it.

Exactness contract (asserted by ``tests/test_comm_matmul.py``): where K
fits one K tile (``_MAX_K_TILE``), every backend returns *exactly*
``x @ leaf.dequantize().astype(dt)``:

  * tiling rows and output columns keeps each output element's
    k-reduction identical to the full dot;
  * uniform dequant is ``(codes / 2^k) * scale`` in both paths.

A longer K (d_ff 11008 at yi-6b widths) is summed tile by tile in an
f32 VMEM accumulator and rounded to the output dtype once, so it may
differ from the one-dot reference by the f32 summation order
(``chip_smoke.py`` checks prefill logits against the unfused path).

Backend dispatch mirrors ``repro.comm.codec``: Pallas on TPU for
covered shapes, the jnp reference (one fused XLA program) everywhere
else, and an explicit ``backend=`` always wins ("pallas" off TPU runs
in interpret mode). Shapes the kernel doesn't cover - output width not
a multiple of the tile, packed rows shorter than one block, oversized
activations - fall back to dequantize-then-matmul inside the same jit.

``mm_cols()`` is the output-tile width of unpacked (int8/int16) codes;
``repro.perf.autotune.tune_mm_cols`` measures candidates and installs
the winner via ``set_mm_cols``, exactly like ``tune_enc_rows`` does for
the codec kernels.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.comm import bits as B
from repro.comm import codec as C
from repro.comm import kernels as CK
from repro.opt import grids

# output columns per grid step (one N-tile of the result). 128 keeps the
# packed tile a whole number of VREG lanes at every supported lane width
# (group sizes divide it) and matches the MXU column width.
MM_COLS = 128

# per-backend tile-width override (autotuning hook), same shape as
# kernels._ENC_ROWS_OVERRIDE: ``repro.perf.autotune.tune_mm_cols``
# installs the measured winner for ``jax.default_backend()``.
_MM_COLS_OVERRIDE: dict = {}

# activations taller than this skip the Pallas path (prefill-sized
# calls; where the threshold belongs is a chip measurement)
_MAX_FUSED_ROWS = 1024

# tile bounds that keep one grid step's blocks and unpack temporaries
# inside the scoped VMEM limit below at yi-6b widths (K up to 11008)
_ROW_TILE = 256
_MAX_K_TILE = 1024
_VMEM_LIMIT = 64 << 20


def mm_cols() -> int:
    """Output columns per fused dequant-matmul grid step."""
    return _MM_COLS_OVERRIDE.get(jax.default_backend(), MM_COLS)


def set_mm_cols(cols, backend: Optional[str] = None) -> None:
    """Install (or, with ``cols=None``, clear) the output-tile width for
    ``backend`` (default: the active one). Must be a positive multiple
    of 128 so packed tiles stay whole byte groups and whole VREGs."""
    key = backend or jax.default_backend()
    if cols is None:
        _MM_COLS_OVERRIDE.pop(key, None)
        return
    if cols % 128 != 0 or cols <= 0:
        raise ValueError(f"mm_cols must be a positive multiple of 128: {cols}")
    _MM_COLS_OVERRIDE[key] = int(cols)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# dequant helpers (both backends call the same repro.opt.grids math)
# ---------------------------------------------------------------------------

def _dequant_codes(codes, scale, *, k_x, w_dtype, cast_dtype):
    """Signed codes -> weights, replicating the unfused cast chain
    ``dequantize() -> .astype(leaf.dtype) -> .astype(x.dtype)`` exactly
    (collapsing it would change values when the leaf dtype is narrower
    than the activation dtype)."""
    w = grids.uniform_dequantize(codes, scale, k_x)
    w = w.astype(jnp.dtype(w_dtype))
    if cast_dtype is not None:
        w = w.astype(jnp.dtype(cast_dtype))
    return w


def _unpack_tile(codes, pack_bits, n):
    if pack_bits:
        return B.unpack_lanes(codes, pack_bits, n)
    return codes


# ---------------------------------------------------------------------------
# jnp reference backend (and universal fallback): dequantize-then-matmul
# in ONE jit program - the oracle the Pallas kernel must match bitwise
# ---------------------------------------------------------------------------

def _matmul_jnp(x2, codes, scale, *, k_x, pack_bits, n, w_dtype,
                cast_dtype, transpose):
    full = B.unpack_rows(codes, pack_bits, n) if pack_bits else codes
    w = _dequant_codes(full, scale, k_x=k_x, w_dtype=w_dtype,
                       cast_dtype=cast_dtype)
    return x2 @ (w.T if transpose else w)


# ---------------------------------------------------------------------------
# Pallas kernels: grid over output-column tiles, full (M, K) activation
# and one code tile per step; codes never leave VMEM unpacked
# ---------------------------------------------------------------------------

def _mm_body(x_ref, codes_ref, scale_ref, o_ref, *acc, k_x, pack_bits,
             w_dtype, cast_dtype):
    """One (row, column) output tile, one K step: unpack + dequant the
    code tile, one MXU dot. A single K step writes the dot straight out
    (the reduction is then exactly the reference's); several accumulate
    in an f32 VMEM scratch."""
    codes = _unpack_tile(codes_ref[...], pack_bits, o_ref.shape[-1])
    w = _dequant_codes(codes, scale_ref[0], k_x=k_x, w_dtype=w_dtype,
                       cast_dtype=cast_dtype)
    if not acc:
        o_ref[...] = jnp.dot(x_ref[...], w)
        return
    acc_ref, = acc
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w,
                            preferred_element_type=jnp.float32)

    @pl.when(kk == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _mm_t_body(x_ref, codes_ref, scale_ref, o_ref, *, k_x, pack_bits, n,
               w_dtype, cast_dtype):
    """Transposed orientation (``x @ W.T``, tied embedding heads): the
    grid tiles code ROWS; each step contracts x against a row tile of
    the dequantized weight (= a column tile of W.T)."""
    codes = _unpack_tile(codes_ref[...], pack_bits, n)
    w = _dequant_codes(codes, scale_ref[0], k_x=k_x, w_dtype=w_dtype,
                       cast_dtype=cast_dtype)
    o_ref[...] = jax.lax.dot_general(x_ref[...], w,
                                     (((1,), (1,)), ((), ())))


def _col_tile(pack_bits: int) -> int:
    """Output columns per grid step: one whole packing block for packed
    codes (its byte planes are whole 128-lane vregs), else mm_cols()."""
    return B.block_codes(pack_bits) if pack_bits else mm_cols()


def _k_tile(K: int) -> int:
    """Contraction rows per grid step: all of K up to _MAX_K_TILE, else
    the largest divisor of K that is a multiple of 128 and fits."""
    if K <= _MAX_K_TILE:
        return K
    for tk in range(_MAX_K_TILE - _MAX_K_TILE % 128, 0, -128):
        if K % tk == 0:
            return tk
    return 0


def _matmul_pallas(x2, codes, scale, *, k_x, pack_bits, n, w_dtype,
                   cast_dtype, transpose, interpret):
    M, K = x2.shape
    scale = jnp.asarray(scale, jnp.float32).reshape(1)
    out_dtype = jnp.result_type(x2.dtype,
                                jnp.dtype(cast_dtype or w_dtype))
    if transpose:
        tile = mm_cols()
        rows = codes.shape[0]
        body = functools.partial(_mm_t_body, k_x=k_x, pack_bits=pack_bits,
                                 n=n, w_dtype=w_dtype, cast_dtype=cast_dtype)
        return pl.pallas_call(
            body,
            grid=(rows // tile,),
            in_specs=[pl.BlockSpec((M, K), lambda i: (0, 0)),
                      pl.BlockSpec((tile, codes.shape[1]), lambda i: (i, 0)),
                      CK.smem()],
            out_specs=pl.BlockSpec((M, tile), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((M, rows), out_dtype),
            interpret=interpret,
        )(x2, codes, scale)
    # normal orientation: grid (row tiles, column tiles, K steps); a
    # packed column tile is one whole block of codes (block_nbytes
    # payload bytes); packed rows were padded to whole blocks when the
    # leaf was quantized, so the output may run past n and is cut back
    tn = _col_tile(pack_bits)
    cw = B.block_nbytes(pack_bits) if pack_bits else tn
    n_cols = codes.shape[1] // cw
    tk = _k_tile(K)
    tm = M if M <= _ROW_TILE else _ROW_TILE
    mp = -(-M // tm) * tm
    if mp != M:
        x2 = jnp.pad(x2, ((0, mp - M), (0, 0)))
    nk = K // tk
    out = pl.pallas_call(
        functools.partial(_mm_body, k_x=k_x, pack_bits=pack_bits,
                          w_dtype=w_dtype, cast_dtype=cast_dtype),
        grid=(mp // tm, n_cols, nk),
        in_specs=[pl.BlockSpec((tm, tk), lambda i, j, kk: (i, kk)),
                  pl.BlockSpec((tk, cw), lambda i, j, kk: (kk, j)),
                  CK.smem()],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, n_cols * tn), out_dtype),
        scratch_shapes=([pltpu.VMEM((tm, tn), jnp.float32)]
                        if nk > 1 else []),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="dequant_matmul",
    )(x2, codes, scale)
    return out[:M, :n]


def _pallas_covers(x2, codes, *, pack_bits, n, transpose) -> bool:
    if x2.shape[0] > _MAX_FUSED_ROWS:
        return False
    if transpose:
        return codes.shape[0] % mm_cols() == 0
    if _k_tile(x2.shape[1]) == 0:
        return False
    if pack_bits:
        # whole packing blocks only (rows shorter than one block keep
        # their exact tail and stay on the reference path)
        return codes.shape[1] % B.block_nbytes(pack_bits) == 0
    return n % mm_cols() == 0


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def dequant_matmul(x, codes, scale, *, k_x: int, n: int, pack_bits: int = 0,
                   w_dtype: str = "float32", cast_dtype: Optional[str] = None,
                   transpose: bool = False,
                   backend: Optional[str] = None) -> jax.Array:
    """``x @ W`` (or ``x @ W.T``) where W exists only as integer codes.

    x: (..., K) activations ((..., d) against code rows for
        ``transpose=True``).
    codes: (K, payload|n) - packed uint8 rows (``pack_bits`` set) or raw
        int8/int16 codes; for ``transpose`` the roles flip ((rows, ...)
        codes contract along their unpacked width).
    scale: per-tensor () amax scale (per-layer stacks are vmapped by the
        caller, one scalar per layer).
    n: the LOGICAL last-dim length of the weight (the codes' aux shape -
        packed payloads and scan-sliced stacked leaves can't tell).
    w_dtype / cast_dtype: the leaf's dtype and the pending ``astype``
        target - the unfused cast chain, replicated exactly.

    Equal to ``x @ dequantize-then-cast`` on every backend, exactly
    where K fits one K tile, else up to the f32 summation order.
    """
    lead = x.shape[:-1]
    x2 = x.reshape((-1, x.shape[-1]))
    bk = C.resolve_backend(backend, codes.size, tile=x2.shape[1] * mm_cols())
    kw = dict(k_x=k_x, pack_bits=pack_bits, n=n, w_dtype=w_dtype,
              cast_dtype=cast_dtype, transpose=transpose)
    if bk == "pallas" and _pallas_covers(x2, codes, pack_bits=pack_bits,
                                         n=n, transpose=transpose):
        out2 = _matmul_pallas(x2, codes, scale, interpret=_interpret(), **kw)
    else:
        out2 = _matmul_jnp(x2, codes, scale, **kw)
    return out2.reshape(lead + (out2.shape[-1],))
