"""The codec registry: one compression stack behind every wire,
residency, and checkpoint path.

A :class:`Codec` is a named, registrable compression operator carrying

  * ``encode(x) -> WireBuffer``   (fused amax + quantize + bit-pack)
  * ``decode(wb) -> x_hat``       (fused unpack + dequantize)
  * exact byte accounting: ``payload_nbytes`` (packed codes only - what
    the collectives move and what ``comm_bytes_per_step`` counts) and
    ``wire_nbytes`` (payload + f32 scale side-channel - what a resident
    or checkpointed buffer actually occupies)
  * ``bits``: the packed lane width per element (see ``repro.comm.bits``)

plus the code-level primitives (``compute_scale`` / ``quantize`` /
``dequantize``) the thin shims in ``repro.core.quantizers`` and the
in-kernel bodies share. Backends: ``backend="jnp"`` is the reference
path (canonical ``repro.opt.grids`` math + ``repro.comm.bits`` packing
under one XLA fusion); ``backend="pallas"`` runs the fused single-launch
kernels in ``repro.comm.kernels`` (interpret mode off TPU) whose bodies
call the *same* functions, so payloads and scales are bit-identical;
``backend=None`` picks Pallas on TPU for tile-sized tensors.

Row-chunked entry points (``encode_rows`` / ``encode_rows_ef`` /
``decode_rows``) emit the worker-ownership layout of Algorithm 2: each
of ``n_rows`` chunks packs to a byte-aligned payload row, which is
exactly the array ``repro.dist.collectives`` moves - no unpacked code
tensor is materialized between quantize and the wire.

Registry specs: ``none|identity|fp32``, ``log:k``, ``uniform:k``,
``uniform_amax:k``, ``terngrad|ternary``, ``blockwise:b``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import bits as B
from repro.comm import kernels as K
from repro.opt import grids

BACKENDS = ("jnp", "pallas")


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def resolve_backend(backend: Optional[str], numel: Optional[int] = None,
                    tile: Optional[int] = None) -> str:
    """Auto: Pallas on TPU when the tensor fills at least one kernel tile
    (padding overhead dominates below that), jnp otherwise. An explicit
    ``backend=`` always wins - "pallas" off TPU runs in interpret mode."""
    if backend is not None:
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; "
                             f"expected one of {BACKENDS}")
        return backend
    if tile is None:
        tile = K.enc_rows() * K.LANES
    if jax.default_backend() == "tpu" and (numel is None or numel >= tile):
        return "pallas"
    return "jnp"


# ---------------------------------------------------------------------------
# wire buffer (the pytree the channels/residency/checkpoints hold)
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class WireBuffer:
    """One tensor in wire form: packed uint8 payload + f32 scale(s).

    payload: uint8, ``codec.payload_nbytes(numel)`` bytes (flat) or
        ``(n_rows, payload_nbytes(c))`` for row-chunked buffers.
    scale: () per-tensor, or (nb,) per-block (blockwise codec).
    spec/shape: static - the codec spec string and the logical element
        shape, enough to decode without outside context.
    """

    payload: jax.Array
    scale: jax.Array
    spec: str = dataclasses.field(metadata=dict(static=True))
    shape: Tuple[int, ...] = dataclasses.field(metadata=dict(static=True))

    def tree_flatten(self):
        return (self.payload, self.scale), (self.spec, self.shape)

    @classmethod
    def tree_unflatten(cls, aux, children):
        payload, scale = children
        spec, shape = aux
        return cls(payload=payload, scale=scale, spec=spec, shape=shape)

    @property
    def numel(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1

    @property
    def bits(self) -> int:
        return get_codec(self.spec).bits

    @property
    def nbytes(self) -> int:
        """Actual buffer bytes (payload + scales)."""
        return int(self.payload.nbytes) + int(self.scale.nbytes)

    def decode(self, *, backend: Optional[str] = None,
               out_dtype=jnp.float32) -> jax.Array:
        return get_codec(self.spec).decode(self, backend=backend,
                                           out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# tiling helpers: every wire row becomes whole packing blocks, one per
# kernel row (``repro.comm.bits.to_blocks``), in a multiple of the
# kernels' rows per grid step
# ---------------------------------------------------------------------------

def _row_blocks(c: int, bits: int) -> int:
    """Kernel rows (packing blocks) per wire row of c elements."""
    er = K.enc_rows()
    return -(-c // (er * K.lanes_in(bits))) * er


def _kernel_rows(rows: jax.Array, bits: int) -> jax.Array:
    """(R, c) values -> (R * _row_blocks(c) * planes, 128) kernel tiles:
    the whole-block rows' own row-major order, 128 lanes wide, so the
    reshape moves no data."""
    nblk = _row_blocks(rows.shape[1], bits)
    return B.to_blocks(rows, bits, nblk).reshape(-1, K.LANES)


# ---------------------------------------------------------------------------
# codecs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Codec:
    """Base: a scalar-scale grid codec (log / uniform / ternary)."""

    name = "base"
    kind = "base"          # fused-kernel dispatch key
    stochastic = False

    # -- static facts ------------------------------------------------------
    @property
    def spec(self) -> str:
        raise NotImplementedError

    @property
    def bits(self) -> int:
        """Packed payload bits per element (the wire lane width)."""
        raise NotImplementedError

    @property
    def k(self) -> int:
        """Grid parameter forwarded to the kernels (k_g / k_x)."""
        return 0

    @property
    def clip_abs(self) -> Optional[int]:
        """Clip codes to +/- this before packing (None = exact lanes)."""
        return None

    @property
    def static_scale(self) -> Optional[float]:
        """Data-independent scale (the paper's absolute Q_x grid), or
        None when the scale is an amax pass over the tensor."""
        return None

    # -- byte accounting ---------------------------------------------------
    def scale_numel(self, numel: int) -> int:
        return 1

    def payload_nbytes(self, numel: int) -> int:
        """Exact packed-code bytes (what the collectives move; scale
        side-channels excluded - see ``wire_nbytes``)."""
        return B.payload_nbytes(numel, self.bits)

    def wire_nbytes(self, numel: int) -> int:
        """Exact total buffer bytes: payload + f32 scales."""
        return self.payload_nbytes(numel) + 4 * self.scale_numel(numel)

    # -- code-level primitives (shared with QTensor shims and kernels) ----
    def compute_scale(self, x: jax.Array) -> jax.Array:
        if self.static_scale is not None:
            return jnp.float32(self.static_scale)
        return grids.amax_scale(x)

    def quantize(self, x: jax.Array, scale, *, u=None) -> jax.Array:
        codes = K._quant(x.astype(jnp.float32), scale, u, kind=self.kind,
                         k=self.k, clip_abs=self.clip_abs)
        return codes

    def dequant_lut(self):
        """(2^bits,) scale-1 dequant table for table-driven decode, or
        None for grids whose dequant is already a single multiply
        (uniform/ternary/blockwise: ``codes * scale``, no transcendental
        to amortize — evaluated and deliberately left table-free)."""
        return None

    def dequantize(self, codes: jax.Array, scale) -> jax.Array:
        return K._dequant(codes, scale, kind=self.kind, k=self.k,
                          lut=self.dequant_lut())

    # -- fused encode/decode ----------------------------------------------
    def _draw(self, key, shape):
        if not self.stochastic:
            return None
        assert key is not None, f"{self.name} codec is stochastic; pass key="
        return jax.random.uniform(key, shape)

    def encode(self, x: jax.Array, *, key=None,
               backend: Optional[str] = None) -> WireBuffer:
        """Fused amax+quantize+pack -> :class:`WireBuffer` (one kernel
        launch on the Pallas backend). Jitted whole, like the engine
        entry points: eager-vs-compiled float rounding (FMA contraction)
        would otherwise break the backend bit-parity contract."""
        if self.stochastic and key is None:
            raise ValueError(f"{self.name} codec is stochastic; pass key=")
        key = key if key is not None else jax.random.PRNGKey(0)
        return _encode_jit(x, key, codec=self, backend=backend)

    def decode(self, wb: WireBuffer, *, backend: Optional[str] = None,
               out_dtype=jnp.float32) -> jax.Array:
        return _decode_jit(wb, codec=self, backend=backend,
                           out_dtype=jnp.dtype(out_dtype).name)

    def _encode_impl(self, x: jax.Array, *, key,
                     backend: Optional[str]) -> WireBuffer:
        shape = tuple(x.shape)
        flat = x.reshape(-1).astype(jnp.float32)
        n = flat.shape[0]
        u = self._draw(key, flat.shape)
        if resolve_backend(backend, n) == "jnp":
            scale = self.compute_scale(flat)
            codes = self.quantize(flat, scale, u=u)
            # fence codes off from the packer: the lane packer reads G
            # strided slices of them, and XLA loop fusion would
            # otherwise duplicate the (transcendental) quantize work
            # into every slice read - measured 2x slower on CPU
            codes = jax.lax.optimization_barrier(codes)
            payload = B.pack_flat(codes, self.bits)
            return WireBuffer(payload=payload, scale=scale,
                              spec=self.spec, shape=shape)
        x2d = _kernel_rows(flat[None], self.bits)
        u2d = _kernel_rows(u[None], self.bits) if u is not None else None
        payload2d, scale = K.encode_pallas(
            x2d, self.kind, self.bits, self.k,
            scale=(None if self.static_scale is None
                   else jnp.float32(self.static_scale)),
            u2d=u2d, clip_abs=self.clip_abs, interpret=_interpret())
        payload = B.payload_from_blocks(payload2d.reshape(1, -1), n,
                                        self.bits)[0]
        return WireBuffer(payload=payload, scale=scale, spec=self.spec,
                          shape=shape)

    def _decode_impl(self, wb: WireBuffer, *, backend: Optional[str] = None,
                     out_dtype=jnp.float32) -> jax.Array:
        n = wb.numel
        if resolve_backend(backend, n) == "jnp":
            codes = B.unpack_flat(wb.payload, self.bits, n)
            return self.dequantize(codes, wb.scale).astype(
                out_dtype).reshape(wb.shape)
        p2d = B.payload_to_blocks(wb.payload[None], n, self.bits,
                                  _row_blocks(n, self.bits))
        out = K.decode_pallas(p2d.reshape(-1, K.lanes_out(self.bits)),
                              wb.scale, self.kind, self.bits, self.k,
                              out_dtype=out_dtype, lut=self.dequant_lut(),
                              interpret=_interpret())
        return B.from_blocks(out.reshape(1, -1), n,
                             self.bits)[0].reshape(wb.shape)


@dataclasses.dataclass(frozen=True)
class LogCodec(Codec):
    """The paper's Q_g: log grid, per-tensor amax scale. Codes live in
    [-(k_g+1), k_g+1] and pack to the smallest lane holding them."""

    k_g: int = 6
    name = "log"
    kind = "log"

    @property
    def spec(self):
        return f"log:{self.k_g}"

    @property
    def bits(self):
        return B.lane_bits_for(self.k_g + 1)

    @property
    def k(self):
        return self.k_g

    def dequant_lut(self):
        # 2k_g+3 representable values: decode is a gather, not an exp2
        # per element (the PR-5 0.23x fused-log-decode regression).
        return grids.log_dequant_table(self.k_g, self.bits)


@dataclasses.dataclass(frozen=True)
class UniformCodec(Codec):
    """The paper's Q_x: uniform grid over [-scale, scale].

    ``absolute=True`` pins scale = 0.5 (Assumption 3's additive grid);
    ``absolute=False`` uses a per-tensor amax scale. Codes reach
    +/- 2^k_x; by default they pack exactly into the next lane up
    (residency / QTensor semantics). ``wire_bits`` pins a narrower lane
    and clips the out-of-range extreme codes into it - the historical
    int8-wire semantics (``k_x=7`` rides 8-bit lanes at +/-127); see
    :func:`uniform_wire_codec` for the broadcast channel's choice."""

    k_x: int = 7
    absolute: bool = True
    wire_bits: Optional[int] = None
    name = "uniform"
    kind = "uniform"

    def __post_init__(self):
        if self.wire_bits is not None:
            assert self.wire_bits in B.SUPPORTED_BITS, self.wire_bits

    @property
    def spec(self):
        base = "uniform" if self.absolute else "uniform_amax"
        suffix = f":w{self.wire_bits}" if self.wire_bits else ""
        return f"{base}:{self.k_x}{suffix}"

    @property
    def bits(self):
        if self.wire_bits is not None:
            return self.wire_bits
        return B.lane_bits_for(2 ** self.k_x)

    @property
    def k(self):
        return self.k_x

    @property
    def clip_abs(self):
        top = 2 ** (self.bits - 1) - 1
        return top if 2 ** self.k_x > top else None

    @property
    def static_scale(self):
        return 0.5 if self.absolute else None


def uniform_wire_codec(k_x: int, absolute: bool = True) -> UniformCodec:
    """The weight-broadcast wire's Q_x lanes: the smallest lane whose
    clipped range loses only the two extreme codes (+/- 2^k_x -> the lane
    edge) - k_x=7 rides 8-bit lanes at +/-127 (the historical int8
    wire), k_x=3 rides 4-bit lanes."""
    return UniformCodec(k_x=k_x, absolute=absolute,
                        wire_bits=B.lane_bits_for(2 ** k_x - 1))


@dataclasses.dataclass(frozen=True)
class TernaryCodec(Codec):
    """TernGrad: unbiased stochastic ternary {-1, 0, +1}, 2-bit lanes."""

    name = "terngrad"
    kind = "ternary"
    stochastic = True

    @property
    def spec(self):
        return "terngrad"

    @property
    def bits(self):
        return 2


@dataclasses.dataclass(frozen=True)
class BlockwiseCodec(Codec):
    """Zheng et al. '19: sign codes + per-block mean-|.| scales.

    Deliberately outside the ``encode_rows``/``decode_rows`` contract:
    those assume one scale per source row, while blockwise scales ride a
    per-block side-channel whose decode slicing depends on the receiving
    worker's chunk OFFSET - mesh state, not codec state. The ef_sgd mode
    packs its rows through ``comm.pack_rows`` at this codec's lane width
    and handles the scale columns itself."""

    block: int = 256
    name = "blockwise"
    kind = "blockwise"

    @property
    def spec(self):
        return f"blockwise:{self.block}"

    @property
    def bits(self):
        return 2

    def scale_numel(self, numel: int) -> int:
        return -(-int(numel) // self.block)

    def compute_scale(self, x):
        raise NotImplementedError("blockwise scales ride encode()")

    def quantize(self, x, scale, *, u=None):
        return jnp.sign(x.astype(jnp.float32)).astype(jnp.int8)

    def dequantize(self, codes, scale):
        # scale: per-block, broadcast over the block dim by the caller
        return codes.astype(jnp.float32) * scale

    def _blocks(self, flat):
        n = flat.shape[0]
        nb = -(-n // self.block)
        return jnp.pad(flat, (0, nb * self.block - n)).reshape(
            nb, self.block), nb

    def _encode_impl(self, x, *, key, backend) -> WireBuffer:
        shape = tuple(x.shape)
        flat = x.reshape(-1).astype(jnp.float32)
        n = flat.shape[0]
        x2d, nb = self._blocks(flat)
        if resolve_backend(backend, n) == "jnp":
            codes, scales = grids.blockwise_quantize(x2d)
            codes = jax.lax.optimization_barrier(codes)  # see Codec
        else:
            rpad = (-nb) % K.BLOCKWISE_ROWS
            codes, scales = K.blockwise_quantize_pallas(
                jnp.pad(x2d, ((0, rpad), (0, 0))), interpret=_interpret())
            codes, scales = codes[:nb], scales[:nb]
        payload = B.pack_flat(codes.reshape(-1)[:n], self.bits)
        return WireBuffer(payload=payload, scale=scales, spec=self.spec,
                          shape=shape)

    def _decode_impl(self, wb: WireBuffer, *, backend=None,
                     out_dtype=jnp.float32) -> jax.Array:
        n = wb.numel
        nb = self.scale_numel(n)
        padded = nb * self.block
        codes = B.unpack_flat(wb.payload, self.bits, n)
        codes2d = jnp.pad(codes, (0, padded - n)).reshape(nb, self.block)
        vals = grids.blockwise_dequantize(codes2d, wb.scale)
        return vals.reshape(-1)[:n].astype(out_dtype).reshape(wb.shape)


@dataclasses.dataclass(frozen=True)
class IdentityCodec(Codec):
    """No compression: the payload is the f32 bytes (4 bytes/element)."""

    name = "identity"
    kind = "identity"

    @property
    def spec(self):
        return "identity"

    @property
    def bits(self):
        return 32

    def scale_numel(self, numel: int) -> int:
        return 0

    def payload_nbytes(self, numel: int) -> int:
        return 4 * int(numel)

    def compute_scale(self, x):
        return jnp.float32(1.0)

    def quantize(self, x, scale, *, u=None):
        return x.astype(jnp.float32)

    def dequantize(self, codes, scale):
        return codes.astype(jnp.float32)

    def _encode_impl(self, x, *, key, backend) -> WireBuffer:
        flat = x.reshape(-1).astype(jnp.float32)
        payload = jax.lax.bitcast_convert_type(flat, jnp.uint8).reshape(-1)
        return WireBuffer(payload=payload, scale=jnp.zeros((0,), jnp.float32),
                          spec=self.spec, shape=tuple(x.shape))

    def _decode_impl(self, wb: WireBuffer, *, backend=None,
                     out_dtype=jnp.float32) -> jax.Array:
        vals = jax.lax.bitcast_convert_type(
            wb.payload.reshape(-1, 4), jnp.float32)
        return vals.astype(out_dtype).reshape(wb.shape)


# jitted entry points: the codec (a hashable frozen dataclass) rides as a
# static argument, so each (codec, backend) pair compiles once. Both
# backends then see the SAME compilation mode - comparing an eager jnp
# run against a compiled Pallas kernel would pick up FMA-contraction
# rounding differences that are compilation artifacts, not codec bugs.

@functools.partial(jax.jit, static_argnames=("codec", "backend"))
def _encode_jit(x, key, *, codec, backend):
    return codec._encode_impl(x, key=key, backend=backend)


@functools.partial(jax.jit, static_argnames=("codec", "backend", "out_dtype"))
def _decode_jit(wb, *, codec, backend, out_dtype):
    return codec._decode_impl(wb, backend=backend,
                              out_dtype=jnp.dtype(out_dtype))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def get_codec(spec: Optional[str]) -> Codec:
    """Parse a codec spec string (same grammar as the historical
    quantizer specs): 'none', 'log:k', 'uniform:k', 'uniform_amax:k',
    'terngrad', 'blockwise:b'; a trailing ':wire' on the uniform specs
    selects the clipped wire lanes."""
    if spec is None or spec in ("none", "identity", "fp32"):
        return IdentityCodec()
    parts = spec.split(":")
    head, args = parts[0], parts[1:]
    wire_bits = None
    if "wire" in args:
        args.remove("wire")
        wire_bits = "wire"
    for a in list(args):
        if a.startswith("w") and a[1:].isdigit():
            wire_bits = int(a[1:])
            args.remove(a)
    arg = args[0] if args else ""
    if head == "log":
        return LogCodec(k_g=int(arg or 6))
    if head in ("uniform", "uniform_amax"):
        k_x = int(arg or 7)
        absolute = head == "uniform"
        if wire_bits == "wire":
            return uniform_wire_codec(k_x, absolute)
        return UniformCodec(k_x=k_x, absolute=absolute, wire_bits=wire_bits)
    if head in ("terngrad", "ternary"):
        return TernaryCodec()
    if head == "blockwise":
        return BlockwiseCodec(block=int(arg or 256))
    raise ValueError(f"unknown codec spec: {spec}")


CODEC_NAMES = ("identity", "log", "uniform", "uniform_amax", "terngrad",
               "blockwise")


# ---------------------------------------------------------------------------
# row-chunked wire entry points (the layout the dist collectives move)
# ---------------------------------------------------------------------------

def encode_rows(x: jax.Array, codec: Codec, n_rows: int, *, key=None,
                backend: Optional[str] = None):
    """Fused encode into worker-ownership rows: flat x -> ``(n_rows,
    payload_nbytes(c))`` uint8 payload (byte-aligned per row - exactly
    the array the all_to_all moves) plus the per-tensor scale. One
    kernel launch on the Pallas backend."""
    if codec.stochastic and key is None:
        raise ValueError(f"{codec.name} codec is stochastic; pass key=")
    key = key if key is not None else jax.random.PRNGKey(0)
    return _encode_rows_jit(x, key, codec=codec, n_rows=n_rows,
                            backend=backend)


@functools.partial(jax.jit, static_argnames=("codec", "n_rows", "backend"))
def _encode_rows_jit(x, key, *, codec, n_rows, backend):
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    c = -(-n // n_rows)
    u = codec._draw(key, flat.shape)
    if resolve_backend(backend, n) == "jnp":
        scale = codec.compute_scale(flat)
        codes = codec.quantize(flat, scale, u=u)
        codes = jax.lax.optimization_barrier(codes)  # see _encode_impl
        return B.pack_rows(B.pad_rows(codes, n_rows), codec.bits), scale
    x2d = _kernel_rows(B.pad_rows(flat, n_rows), codec.bits)
    u2d = (_kernel_rows(B.pad_rows(u, n_rows), codec.bits)
           if u is not None else None)
    payload2d, scale = K.encode_pallas(
        x2d, codec.kind, codec.bits, codec.k,
        scale=(None if codec.static_scale is None
               else jnp.float32(codec.static_scale)),
        u2d=u2d, clip_abs=codec.clip_abs, interpret=_interpret())
    return B.payload_from_blocks(payload2d.reshape(n_rows, -1), c,
                                 codec.bits), scale


def encode_rows_ef(x: jax.Array, scale, codec: Codec, n_rows: int, *,
                   backend: Optional[str] = None):
    """Fused encode + error feedback: flat x -> (payload rows, residual
    ``e' = x - deq(codes)`` in x's shape). The scale arrives from the
    caller (the Adam moment pass); codes never hit HBM unpacked."""
    return _encode_rows_ef_jit(x, scale, codec=codec, n_rows=n_rows,
                               backend=backend)


@functools.partial(jax.jit, static_argnames=("codec", "n_rows", "backend"))
def _encode_rows_ef_jit(x, scale, *, codec, n_rows, backend):
    shape = x.shape
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    c = -(-n // n_rows)
    if resolve_backend(backend, n) == "jnp":
        codes = codec.quantize(flat, scale)
        # the codes feed BOTH the packer (G strided reads) and the
        # residual - fence them so neither consumer re-runs quantize
        codes = jax.lax.optimization_barrier(codes)
        e_new = flat - codec.dequantize(codes, scale)
        return (B.pack_rows(B.pad_rows(codes, n_rows), codec.bits),
                e_new.reshape(shape))
    x2d = _kernel_rows(B.pad_rows(flat, n_rows), codec.bits)
    payload2d, e2d = K.ef_encode_pallas(x2d, scale, codec.kind, codec.bits,
                                        codec.k, clip_abs=codec.clip_abs,
                                        lut=codec.dequant_lut(),
                                        interpret=_interpret())
    payload = B.payload_from_blocks(payload2d.reshape(n_rows, -1), c,
                                    codec.bits)
    e_new = B.from_blocks(e2d.reshape(n_rows, -1), c,
                          codec.bits).reshape(-1)[:n]
    return payload, e_new.reshape(shape)


def decode_rows(payload_rows: jax.Array, scales, codec: Codec, c: int, *,
                backend: Optional[str] = None,
                out_dtype=jnp.float32) -> jax.Array:
    """Fused decode of received payload rows: ``(n_rows, nbytes)`` uint8
    + per-source-row scales -> ``(n_rows, c)`` dequantized values."""
    return _decode_rows_jit(payload_rows, scales, codec=codec, c=c,
                            backend=backend,
                            out_dtype=jnp.dtype(out_dtype).name)


@functools.partial(jax.jit,
                   static_argnames=("codec", "c", "backend", "out_dtype"))
def _decode_rows_jit(payload_rows, scales, *, codec, c, backend, out_dtype):
    out_dtype = jnp.dtype(out_dtype)
    n_rows = payload_rows.shape[0]
    scales = jnp.asarray(scales, jnp.float32).reshape(n_rows)
    if resolve_backend(backend, n_rows * c) == "jnp":
        codes = B.unpack_rows(payload_rows, codec.bits, c)
        return codec.dequantize(codes, scales[:, None]).astype(out_dtype)
    nblk = _row_blocks(c, codec.bits)
    p2d = B.payload_to_blocks(payload_rows, c, codec.bits, nblk)
    out = K.decode_pallas(p2d.reshape(-1, K.lanes_out(codec.bits)), scales,
                          codec.kind, codec.bits, codec.k,
                          tiles_per_scale=nblk // K.enc_rows(),
                          out_dtype=out_dtype, lut=codec.dequant_lut(),
                          interpret=_interpret())
    return B.from_blocks(out.reshape(n_rows, -1), c, codec.bits)
