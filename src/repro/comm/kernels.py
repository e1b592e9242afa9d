"""Fused Pallas TPU kernels for the compression codec stack.

One kernel launch per direction (the acceptance contract of the codec
subsystem):

  * **encode** = amax + quantize + bit-pack. Data-dependent scales use a
    two-phase grid over the SAME ``pallas_call`` - phase 0 streams the
    tensor and folds per-block amax partials into an SMEM scratch
    accumulator, phase 1 re-streams it, quantizes against the final
    scale, and packs the codes to their wire lanes in VMEM. (TPU grids
    iterate sequentially, which is what makes the scratch carry work.)
    Codecs with static scales (the paper's absolute Q_x) skip phase 0.
  * **decode** = unpack + dequantize, one pass.
  * **ef-encode** = quantize + pack + error-feedback residual
    ``e' = x - deq(codes)`` in one pass (the scale arrives from the Adam
    moment pass, see ``repro.kernels.adam_ef``).

The packed payload never exists as an unpacked int8 code tensor in HBM:
codes live only in VMEM registers between the quantize and pack steps.

Every kernel body calls the canonical math in ``repro.opt.grids`` and
``repro.comm.bits`` on its VMEM tile, so the fused path is bit-identical
to the jnp reference backend by construction (asserted across all lane
widths by ``tests/test_comm_codecs.py``).

Lane geometry: every payload row is one whole packing block
(``repro.comm.bits.block_codes``): ``group_codes`` planes of 128 codes,
packed to ``group_nbytes`` byte planes of 128 lanes (e.g. 3-bit lanes
read 8 planes of floats and write (rows, 384) bytes). The float side is
an array of 128-lane rows, ``group_codes`` rows per block, which a
kernel reads and writes plane by plane with a sublane stride: packing
then only touches whole vregs, which is what the TPU compiler lowers,
and the array is the flat tensor's own row-major layout, so reshaping
between it and the tensor's shape moves no data. (A 256-lane or wider
float array is tiled differently from a flat one: XLA must relayout
it, and at yi-6b widths it emitted tens of MB of unrolled code per
leaf for that copy.)

The historical per-op kernels (separate amax / quantize / dequantize
passes) also live here now; ``repro.kernels.quantize`` and
``repro.kernels.pack`` re-export them for backward compatibility.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.comm import bits as B
from repro.opt import grids

# legacy two-pass tiling (kept: repro.opt.engine's update core uses it)
BLOCK_ROWS = 256
LANES = 128

# fused-codec tiling: rows per grid step (f32 sublane multiple; small so
# sub-tile tensors don't over-pad) and input lanes per lane width (the
# packed output tile is then a whole number of 128-lane VREGs).
ENC_ROWS = 32
LANES_IN = {b: B.block_codes(b) for b in B.SUPPORTED_BITS}

# blockwise sign codes: block rows per grid step
BLOCKWISE_ROWS = 8

# per-backend tile-width override (autotuning hook): maps a
# ``jax.default_backend()`` name to the rows-per-grid-step the fused
# codec kernels should use there. ``repro.perf.autotune`` measures the
# candidates and installs the winner; unset backends fall back to
# ENC_ROWS. Callers must size/pad payloads with ``enc_rows()`` — never
# the bare constant — so a retune changes every tiling consistently.
_ENC_ROWS_OVERRIDE: dict = {}


def enc_rows() -> int:
    """Rows per fused-codec grid step for the active backend."""
    return _ENC_ROWS_OVERRIDE.get(jax.default_backend(), ENC_ROWS)


def set_enc_rows(rows, backend: str | None = None) -> None:
    """Install (or, with ``rows=None``, clear) a tile-rows override for
    ``backend`` (default: the active one). Rows must keep f32 sublane
    alignment (multiple of 8)."""
    key = backend or jax.default_backend()
    if rows is None:
        _ENC_ROWS_OVERRIDE.pop(key, None)
        return
    if rows % 8 != 0 or rows <= 0:
        raise ValueError(f"enc_rows must be a positive multiple of 8: {rows}")
    _ENC_ROWS_OVERRIDE[key] = int(rows)


def lanes_in(bits: int) -> int:
    return LANES_IN[bits]


def planes(bits: int) -> int:
    """128-lane float rows per packing block (``group_codes``)."""
    return B.group_codes(bits)


def lanes_out(bits: int) -> int:
    return LANES_IN[bits] * bits // 8


def smem():
    """Whole-array SMEM placement: scalar operands and results (scales,
    hyper-parameters, amax) live in scalar memory on TPU - a vector
    memory block cannot hold or store a scalar."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _read_planes(ref, g: int):
    """(rows * g, 128) float block -> its g planes, each (rows, 128):
    plane j of block row r is row ``r * g + j``."""
    if g == 1:
        return [ref[...]]
    rows = ref.shape[0] // g
    return [ref[pl.ds(j, rows, stride=g), :] for j in range(g)]


def _write_planes(ref, planes_):
    """Inverse of :func:`_read_planes`."""
    g = len(planes_)
    if g == 1:
        ref[...] = planes_[0]
        return
    rows = ref.shape[0] // g
    for j, p in enumerate(planes_):
        ref[pl.ds(j, rows, stride=g), :] = p


def _pack_codes(code_planes, bits):
    """Code planes -> (rows, lanes_out) uint8 payload tile."""
    out = B.pack_planes([B.lane_values(c, bits) for c in code_planes], bits)
    return (out[0] if len(out) == 1
            else jnp.concatenate(out, axis=-1)).astype(jnp.uint8)


def _unpack_codes(payload, bits):
    """(rows, lanes_out) uint8 payload tile -> code planes."""
    p = payload.astype(jnp.int32)
    nb = B.group_nbytes(bits)
    byte_planes = [p[:, b * LANES:(b + 1) * LANES] for b in range(nb)]
    return [B.signed_codes(u, bits) for u in B.unpack_planes(byte_planes,
                                                              bits)]


def lut_dequant(codes, scale, lut_ref):
    """Table dequant inside a kernel: ``lut[code + n/2] * scale`` with
    the table in SMEM. A select chain over the 2^bits scalars replaces
    the vector gather that TPU kernels cannot do; reading the entries
    in ascending order reproduces ``jnp.take(..., mode="clip")``, so the
    result equals :func:`repro.opt.grids.log_dequantize_lut` bit for
    bit."""
    n = lut_ref.shape[0]
    idx = codes.astype(jnp.int32) + n // 2
    val = jnp.full(codes.shape, lut_ref[0], jnp.float32)
    for j in range(1, n):
        val = jnp.where(idx >= j, lut_ref[j], val)
    return val * scale


# ---------------------------------------------------------------------------
# in-kernel quantize/dequantize dispatch (static kind)
# ---------------------------------------------------------------------------

def _quant(x, scale, u, *, kind: str, k: int, clip_abs):
    if kind == "log":
        codes = grids.log_quantize(x, scale, k)
    elif kind == "uniform":
        codes = grids.uniform_quantize(x, scale, k)
    elif kind == "ternary":
        codes = grids.ternary_quantize(x, u, scale)
    else:
        raise ValueError(kind)
    if clip_abs is not None:
        codes = jnp.clip(codes, -clip_abs, clip_abs)
    return codes


def _dequant(codes, scale, *, kind: str, k: int, lut=None):
    """Dequantize dispatch. For the log grid a precomputed table (see
    ``grids.log_dequant_table``) turns the per-element exp2 into a gather
    — the transcendental re-evaluated on every lane-strided unpacked code
    is what made fused log decode 0.23x of legacy. The other grids are
    already a single multiply (uniform/ternary/blockwise dequant is
    ``codes * scale``), so a table buys them nothing and ``lut`` only
    applies to ``kind == "log"``."""
    if kind == "log":
        if lut is not None:
            return grids.log_dequantize_lut(codes, scale, lut)
        return grids.log_dequantize(codes, scale, k)
    if kind == "uniform":
        return grids.uniform_dequantize(codes, scale, k)
    if kind == "ternary":
        return grids.ternary_dequantize(codes, scale)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# fused encode (single launch)
# ---------------------------------------------------------------------------

def _kdequant(codes, scale, *, kind, k, lut_ref):
    if lut_ref is not None:
        return lut_dequant(codes, scale, lut_ref)
    return _dequant(codes, scale, kind=kind, k=k)


def _encode2_body(*refs, kind, bits, k, clip_abs):
    """Two-phase: (0, i) amax partials -> SMEM; (1, i) quantize + pack."""
    if kind == "ternary":
        x_ref, u_ref, payload_ref, scale_ref, acc_ref = refs
    else:
        (x_ref, payload_ref, scale_ref, acc_ref), u_ref = refs, None
    ph = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(ph == 0)
    def _():
        part = grids.block_amax(x_ref[...])

        @pl.when(i == 0)
        def _():
            acc_ref[0] = part

        @pl.when(i > 0)
        def _():
            acc_ref[0] = jnp.maximum(acc_ref[0], part)

    @pl.when(ph == 1)
    def _():
        amax = acc_ref[0]
        scale = jnp.where(amax > 0, amax, 1.0).astype(jnp.float32)

        @pl.when(i == 0)
        def _():
            scale_ref[0] = scale

        g = planes(bits)
        us = (_read_planes(u_ref, g) if u_ref is not None
              else [None] * g)
        payload_ref[...] = _pack_codes(
            [_quant(x, scale, u, kind=kind, k=k, clip_abs=clip_abs)
             for x, u in zip(_read_planes(x_ref, g), us)], bits)


def _encode1_body(x_ref, scale_ref, payload_ref, *, kind, bits, k,
                  clip_abs):
    """Single-phase encode with a known scale (absolute grids)."""
    s = scale_ref[0]
    payload_ref[...] = _pack_codes(
        [_quant(x, s, None, kind=kind, k=k, clip_abs=clip_abs)
         for x in _read_planes(x_ref, planes(bits))], bits)


def encode_pallas(x2d: jax.Array, kind: str, bits: int, k: int, *,
                  scale=None, u2d=None, clip_abs=None,
                  interpret: bool):
    """Fused amax+quantize+pack, ONE ``pallas_call``.

    x2d: (R * planes(bits), 128) f32, R a multiple of ENC_ROWS, every
    ``planes(bits)`` rows one whole packing block
    (``repro.comm.bits.to_blocks``). Returns ``(payload2d uint8
    (R, lanes_out), scale ())``; with ``scale=`` given the amax phase is
    skipped and the same scale is returned.
    """
    g = planes(bits)
    rows = x2d.shape[0] // g
    er = enc_rows()
    lo = lanes_out(bits)
    assert x2d.shape[1] == LANES and x2d.shape[0] == rows * g \
        and rows % er == 0, (x2d.shape, bits)
    nb = rows // er
    xblk = pl.BlockSpec((er * g, LANES), lambda p, i: (i, 0))
    payload_shape = jax.ShapeDtypeStruct((rows, lo), jnp.uint8)

    if scale is not None:
        scale = jnp.asarray(scale, jnp.float32)
        payload = pl.pallas_call(
            functools.partial(_encode1_body, kind=kind, bits=bits, k=k,
                              clip_abs=clip_abs),
            grid=(1, nb),
            in_specs=[xblk, smem()],
            out_specs=pl.BlockSpec((er, lo), lambda p, i: (i, 0)),
            out_shape=payload_shape,
            interpret=interpret,
            name="codec_encode",
        )(x2d, scale.reshape(1))
        return payload, scale

    operands, in_specs = (x2d,), [xblk]
    if kind == "ternary":
        operands, in_specs = (x2d, u2d), [xblk, xblk]
    # phase 0 parks the payload window on block 0 (i * p), so no block
    # is written back before phase 1 has filled it
    payload, scale_out = pl.pallas_call(
        functools.partial(_encode2_body, kind=kind, bits=bits, k=k,
                          clip_abs=clip_abs),
        grid=(2, nb),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((er, lo), lambda p, i: (i * p, 0)), smem()],
        out_shape=[payload_shape,
                   jax.ShapeDtypeStruct((1,), jnp.float32)],
        scratch_shapes=[pltpu.SMEM((1,), jnp.float32)],
        interpret=interpret,
        name="codec_encode",
    )(*operands)
    return payload, scale_out[0]


# ---------------------------------------------------------------------------
# fused decode (single launch)
# ---------------------------------------------------------------------------

def _decode_body(*refs, kind, bits, k, out_dtype, tiles_per_scale,
                 has_lut):
    """Unpack, then dequantize; with a table (log grid) the dequant is a
    select over SMEM scalars instead of an exp2 per lane-strided code
    (the 0.23x fused-log-decode regression)."""
    if has_lut:
        payload_ref, scale_ref, lut_ref, o_ref = refs
    else:
        (payload_ref, scale_ref, o_ref), lut_ref = refs, None
    t = tiles_per_scale
    s = scale_ref[pl.program_id(0) // t] if t else scale_ref[0]
    _write_planes(o_ref, [
        _kdequant(c, s, kind=kind, k=k, lut_ref=lut_ref).astype(out_dtype)
        for c in _unpack_codes(payload_ref[...], bits)])


def decode_pallas(payload2d: jax.Array, scales: jax.Array, kind: str,
                  bits: int, k: int, *, tiles_per_scale: int = 0,
                  out_dtype=jnp.float32, lut=None,
                  interpret: bool) -> jax.Array:
    """Fused unpack+dequantize, ONE ``pallas_call``.

    payload2d: (R, lanes_out(bits)) uint8; returns (R * planes(bits),
    128) values in ``encode_pallas``'s input layout. ``scales`` is either
    a scalar (per-tensor) or a (n_rows,) vector with ``tiles_per_scale``
    grid steps per wire row (the per-source-worker scales of the dist
    channels). ``lut`` (log grid only) is the (2^bits,) scale-1 dequant
    table from ``grids.log_dequant_table``; it rides in SMEM.
    """
    rows = payload2d.shape[0]
    er = enc_rows()
    g, lo = planes(bits), lanes_out(bits)
    assert payload2d.shape[1] == lo and rows % er == 0
    scales = jnp.asarray(scales, jnp.float32).reshape(-1)
    operands = [payload2d, scales]
    in_specs = [pl.BlockSpec((er, lo), lambda i: (i, 0)), smem()]
    if lut is not None:
        operands.append(jnp.asarray(lut, jnp.float32))
        in_specs.append(smem())
    return pl.pallas_call(
        functools.partial(_decode_body, kind=kind, bits=bits, k=k,
                          out_dtype=out_dtype,
                          tiles_per_scale=tiles_per_scale,
                          has_lut=lut is not None),
        grid=(rows // er,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((er * g, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows * g, LANES), out_dtype),
        interpret=interpret,
        name="codec_decode",
    )(*operands)


# ---------------------------------------------------------------------------
# fused EF encode (quantize + pack + residual, single launch)
# ---------------------------------------------------------------------------

def _ef_encode_body(*refs, kind, bits, k, clip_abs, has_lut):
    """The residual's dequant reads the SMEM table when there is one (it
    pays the same per-element exp2 as decode otherwise)."""
    if has_lut:
        x_ref, scale_ref, lut_ref, payload_ref, e_ref = refs
    else:
        (x_ref, scale_ref, payload_ref, e_ref), lut_ref = refs, None
    s = scale_ref[0]
    xs = _read_planes(x_ref, planes(bits))
    codes = [_quant(x, s, None, kind=kind, k=k, clip_abs=clip_abs)
             for x in xs]
    payload_ref[...] = _pack_codes(codes, bits)
    _write_planes(e_ref, [
        x - _kdequant(c, s, kind=kind, k=k, lut_ref=lut_ref)
        for x, c in zip(xs, codes)])


def ef_encode_pallas(x2d: jax.Array, scale: jax.Array, kind: str,
                     bits: int, k: int, *, clip_abs=None, lut=None,
                     interpret: bool):
    """(x, scale) -> (packed payload, EF residual e' = x - deq(codes)),
    one launch, in ``encode_pallas``'s layouts (the residual in x2d's).
    The codes never leave VMEM."""
    g = planes(bits)
    rows = x2d.shape[0] // g
    er = enc_rows()
    lo = lanes_out(bits)
    assert x2d.shape[1] == LANES and x2d.shape[0] == rows * g \
        and rows % er == 0
    operands = [x2d, jnp.asarray(scale, jnp.float32).reshape(1)]
    xblk = pl.BlockSpec((er * g, LANES), lambda i: (i, 0))
    in_specs = [xblk, smem()]
    if lut is not None:
        operands.append(jnp.asarray(lut, jnp.float32))
        in_specs.append(smem())
    return pl.pallas_call(
        functools.partial(_ef_encode_body, kind=kind, bits=bits, k=k,
                          clip_abs=clip_abs, has_lut=lut is not None),
        grid=(rows // er,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((er, lo), lambda i: (i, 0)), xblk],
        out_shape=[jax.ShapeDtypeStruct((rows, lo), jnp.uint8),
                   jax.ShapeDtypeStruct(x2d.shape, jnp.float32)],
        interpret=interpret,
        name="codec_ef_encode",
    )(*operands)


# ---------------------------------------------------------------------------
# standalone pack/unpack kernels (generic lane widths)
# ---------------------------------------------------------------------------

def _pack_body(codes_ref, payload_ref, *, bits):
    payload_ref[...] = B.pack_lanes(codes_ref[...], bits)


def pack_pallas(codes2d: jax.Array, bits: int, *, interpret: bool):
    """(R, lanes_in) codes -> (R, lanes_out) uint8, one launch."""
    rows = codes2d.shape[0]
    er = enc_rows()
    li, lo = lanes_in(bits), lanes_out(bits)
    assert codes2d.shape[1] == li and rows % er == 0
    return pl.pallas_call(
        functools.partial(_pack_body, bits=bits),
        grid=(rows // er,),
        in_specs=[pl.BlockSpec((er, li), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((er, lo), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lo), jnp.uint8),
        interpret=interpret,
    )(codes2d)


def _unpack_body(payload_ref, codes_ref, *, bits):
    codes_ref[...] = B.unpack_lanes(payload_ref[...], bits,
                                    codes_ref.shape[-1])


def unpack_pallas(payload2d: jax.Array, bits: int, *, interpret: bool):
    rows = payload2d.shape[0]
    er = enc_rows()
    li, lo = lanes_in(bits), lanes_out(bits)
    assert payload2d.shape[1] == lo and rows % er == 0
    dtype = jnp.int16 if bits == 16 else jnp.int8
    return pl.pallas_call(
        functools.partial(_unpack_body, bits=bits),
        grid=(rows // er,),
        in_specs=[pl.BlockSpec((er, lo), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((er, li), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, li), dtype),
        interpret=interpret,
    )(payload2d)


# ---------------------------------------------------------------------------
# historical per-op kernels (separate passes), moved here from
# repro.kernels.quantize; that module re-exports them unchanged.
# ---------------------------------------------------------------------------

def _amax_kernel(x_ref, o_ref):
    part = grids.block_amax(x_ref[...])
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        o_ref[0] = part

    @pl.when(i > 0)
    def _():
        o_ref[0] = jnp.maximum(o_ref[0], part)


def amax_pallas(x2d: jax.Array, *, interpret: bool) -> jax.Array:
    """Global amax, folded block by block into an SMEM scalar. x2d:
    (R, 128), R % BLOCK_ROWS == 0."""
    rows = x2d.shape[0]
    out = pl.pallas_call(
        _amax_kernel,
        grid=(rows // BLOCK_ROWS,),
        in_specs=[pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))],
        out_specs=smem(),
        out_shape=jax.ShapeDtypeStruct((1,), jnp.float32),
        interpret=interpret,
    )(x2d)
    return out[0]


def _log_quantize_kernel(x_ref, scale_ref, codes_ref, *, k_g: int):
    codes_ref[...] = grids.log_quantize(x_ref[...], scale_ref[0], k_g)


def log_quantize_pallas(x2d: jax.Array, scale: jax.Array, k_g: int,
                        *, interpret: bool) -> jax.Array:
    rows = x2d.shape[0]
    grid = rows // BLOCK_ROWS
    return pl.pallas_call(
        functools.partial(_log_quantize_kernel, k_g=k_g),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            smem(),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int8),
        interpret=interpret,
    )(x2d, scale.reshape(1))


def _log_dequantize_kernel(codes_ref, scale_ref, o_ref, *, k_g: int,
                           out_dtype):
    o_ref[...] = grids.log_dequantize(
        codes_ref[...], scale_ref[0], k_g).astype(out_dtype)


def log_dequantize_pallas(codes2d: jax.Array, scale: jax.Array, k_g: int,
                          *, out_dtype=jnp.float32, interpret: bool) -> jax.Array:
    rows = codes2d.shape[0]
    grid = rows // BLOCK_ROWS
    return pl.pallas_call(
        functools.partial(_log_dequantize_kernel, k_g=k_g, out_dtype=out_dtype),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            smem(),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), out_dtype),
        interpret=interpret,
    )(codes2d, scale.reshape(1))


def _uniform_quantize_kernel(x_ref, scale_ref, codes_ref, *, k_x: int):
    codes_ref[...] = grids.uniform_quantize(x_ref[...], scale_ref[0], k_x)


def uniform_quantize_pallas(x2d: jax.Array, scale: jax.Array, k_x: int,
                            *, interpret: bool) -> jax.Array:
    """Codes dtype follows the grid width: int8 for k_x <= 6, int16 above
    (codes reach +/- 2^k_x, which overflows int8 at k_x = 7)."""
    rows = x2d.shape[0]
    grid = rows // BLOCK_ROWS
    return pl.pallas_call(
        functools.partial(_uniform_quantize_kernel, k_x=k_x),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            smem(),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES),
                                       grids.uniform_code_dtype(k_x)),
        interpret=interpret,
    )(x2d, scale.reshape(1))


def _uniform_dequantize_kernel(codes_ref, scale_ref, o_ref, *, k_x: int,
                               out_dtype):
    o_ref[...] = grids.uniform_dequantize(
        codes_ref[...], scale_ref[0], k_x).astype(out_dtype)


def uniform_dequantize_pallas(codes2d: jax.Array, scale: jax.Array, k_x: int,
                              *, out_dtype=jnp.float32,
                              interpret: bool) -> jax.Array:
    rows = codes2d.shape[0]
    grid = rows // BLOCK_ROWS
    return pl.pallas_call(
        functools.partial(_uniform_dequantize_kernel, k_x=k_x,
                          out_dtype=out_dtype),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            smem(),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), out_dtype),
        interpret=interpret,
    )(codes2d, scale.reshape(1))


def _ternary_quantize_kernel(x_ref, u_ref, scale_ref, codes_ref):
    codes_ref[...] = grids.ternary_quantize(x_ref[...], u_ref[...],
                                            scale_ref[0])


def ternary_quantize_pallas(x2d: jax.Array, u2d: jax.Array,
                            scale: jax.Array, *, interpret: bool) -> jax.Array:
    """TernGrad codes from pre-drawn uniforms (stochastic rounding bits are
    generated outside so the jnp backend sees identical draws)."""
    rows = x2d.shape[0]
    grid = rows // BLOCK_ROWS
    blk = lambda: pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        _ternary_quantize_kernel,
        grid=(grid,),
        in_specs=[blk(), blk(), smem()],
        out_specs=blk(),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int8),
        interpret=interpret,
    )(x2d, u2d, scale.reshape(1))


def _blockwise_quantize_kernel(x_ref, codes_ref, scale_ref):
    codes, scale = grids.blockwise_quantize(x_ref[...])
    codes_ref[...] = codes
    scale_ref[...] = scale


def blockwise_quantize_pallas(x2d: jax.Array, *, interpret: bool):
    """(nb, block) -> (sign codes, per-block scales). The block dim rides
    the lane axis whole (one EF block per sublane row); nb must be a
    multiple of BLOCKWISE_ROWS (the engine pads with zero rows)."""
    nb, block = x2d.shape
    grid = nb // BLOCKWISE_ROWS
    codes, scales = pl.pallas_call(
        _blockwise_quantize_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((BLOCKWISE_ROWS, block), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((BLOCKWISE_ROWS, block), lambda i: (i, 0)),
                   pl.BlockSpec((BLOCKWISE_ROWS,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((nb, block), jnp.int8),
                   jax.ShapeDtypeStruct((nb,), jnp.float32)],
        interpret=interpret,
    )(x2d)
    return codes, scales
