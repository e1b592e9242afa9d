"""Code-resident quantized weights for serving.

The paper motivates Q_x by "limited storage in edge devices" (Tables 2-3,
'Size'). The old ``quantize_resident_weights`` stored ``Q_x(x)`` *values*
back in fp32 - zero actual memory saved. This module keeps the integer
codes themselves resident:

  * ``quantize_params(params, k_x)`` replaces every large float leaf with a
    :class:`QuantizedLeaf` - integer codes (int16 above k_x=6; packed to
    the registry codec's 3/4/6-bit lanes with ``pack=True``) plus f32
    scales. Scan-stacked
    ``blocks`` leaves get one amax scale *per layer* (shape ``(L,)``), so
    ``lax.scan`` slices codes and scale together and each layer dequantizes
    independently.
  * ``make_dequant_gather()`` is a ``ShardCtx.param_gather`` hook: matmul-
    shaped leaves (projections, embeddings) stay as CODES end to end -
    their contractions run the fused dequant-matmul in
    :mod:`repro.comm.matmul` via ``QuantizedLeaf.__rmatmul__``/``take``,
    never materializing the fp tensor - and the remaining leaves
    dequantize *inside* the layer scan, at use. The resident footprint is
    the codes (``params_nbytes`` measures it: ~fp32/4 at k_x<=6).

Quantization itself goes through ``repro.opt.engine`` (Pallas kernels on
TPU, the same ``repro.opt.grids`` math everywhere else), and the packed
layout + lane width come from the ``repro.comm`` codec registry - so
resident payloads match the training/wire codecs bit-for-bit, and every
lane the registry packs (3/4/6-bit) is a residency option for free.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import comm
from repro.comm import bits as B
from repro.opt import engine, grids

_STACKED_KEYS = ("blocks", "enc_blocks")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedLeaf:
    """One parameter tensor held as integer codes + scales.

    codes: integer codes with the leaf's logical shape; when ``pack_bits``
        is set, uint8 with the last dim holding ``pack_bits``-bit lanes
        (``repro.comm.bits`` layout, per leading row - the same bytes
        the dist wire ships).
    scale: f32 scalar (per-tensor) or (L,) per-layer for stacked leaves.
        ``lax.scan`` slices it alongside the codes.
    """

    codes: jax.Array
    scale: jax.Array
    k_x: int = dataclasses.field(metadata=dict(static=True))
    shape: Tuple[int, ...] = dataclasses.field(metadata=dict(static=True))
    dtype: str = dataclasses.field(metadata=dict(static=True))
    pack_bits: int = dataclasses.field(default=0, metadata=dict(static=True))
    # pending ``astype`` target: leaves routed through the fused matmul
    # record the activation-dtype cast here instead of materializing it,
    # and the kernel replicates the dequant->dtype->cast chain exactly
    cast: Optional[str] = dataclasses.field(
        default=None, metadata=dict(static=True))

    def tree_flatten(self):
        return ((self.codes, self.scale),
                (self.k_x, self.shape, self.dtype, self.pack_bits,
                 self.cast))

    @classmethod
    def tree_unflatten(cls, aux, children):
        codes, scale = children
        k_x, shape, dtype, pack_bits, cast = aux
        return cls(codes=codes, scale=scale, k_x=k_x, shape=shape,
                   dtype=dtype, pack_bits=pack_bits, cast=cast)

    @property
    def nbytes(self) -> int:
        """Actual resident bytes (codes + scales)."""
        return int(self.codes.nbytes) + int(self.scale.nbytes)

    def astype(self, dt) -> "QuantizedLeaf":
        """Defer a dtype cast (models call ``w.astype(x.dtype)`` on every
        projection); applied after dequant by every consuming path."""
        return dataclasses.replace(self, cast=jnp.dtype(dt).name)

    def dequantize(self) -> jax.Array:
        """Codes -> float tensor (called per-layer inside the model scan,
        where a stacked leaf's codes/scale arrive sliced to one layer)."""
        codes = self.codes
        if self.pack_bits:
            lead = codes.shape[:-1]
            flat = codes.reshape((-1, codes.shape[-1]))
            numel = self.shape[-1]  # logical last-dim length
            rows = comm.unpack_rows(flat, self.pack_bits, numel)
            codes = rows.reshape(lead + (numel,))
        scale = self.scale
        if scale.ndim:
            scale = scale.reshape(scale.shape + (1,) * (codes.ndim - scale.ndim))
        out = grids.uniform_dequantize(codes, scale, self.k_x).astype(
            jnp.dtype(self.dtype))
        return out.astype(jnp.dtype(self.cast)) if self.cast else out

    # -- fused contraction surface (repro.comm.matmul) ------------------
    # ``x @ leaf`` reflects to __rmatmul__ (jax arrays return
    # NotImplemented for unknown rhs types), so models' existing
    # ``x @ w.astype(x.dtype)`` projections dispatch here unchanged.

    def _mm(self, x, *, transpose: bool = False,
            backend: Optional[str] = None) -> jax.Array:
        kw = dict(k_x=self.k_x, n=self.shape[-1], pack_bits=self.pack_bits,
                  w_dtype=self.dtype, cast_dtype=self.cast,
                  transpose=transpose, backend=backend)
        if self.codes.ndim == 3:
            # stacked (L, ...) leaf used outside the scan: one fused call
            # per layer (each layer has its own scalar scale)
            return jnp.stack([
                comm.dequant_matmul(x[l], self.codes[l], self.scale[l], **kw)
                for l in range(self.codes.shape[0])])
        return comm.dequant_matmul(x, self.codes, self.scale, **kw)

    def matmul(self, x, backend: Optional[str] = None) -> jax.Array:
        """``x @ W`` without materializing W (fused dequant-matmul)."""
        return self._mm(x, backend=backend)

    def matmul_t(self, x, backend: Optional[str] = None) -> jax.Array:
        """``x @ W.T`` (tied-embedding logit heads) from codes."""
        return self._mm(x, transpose=True, backend=backend)

    def __rmatmul__(self, x) -> jax.Array:
        return self._mm(x)

    def take(self, idx) -> jax.Array:
        """Row lookup (embedding tables): gather only the requested code
        rows and dequantize those - bitwise identical to indexing the
        full ``dequantize()`` (elementwise dequant commutes with gather),
        without ever decoding the whole table."""
        codes = self.codes[idx]
        if self.pack_bits:
            lead = codes.shape[:-1]
            flat = codes.reshape((-1, codes.shape[-1]))
            numel = self.shape[-1]
            codes = comm.unpack_rows(flat, self.pack_bits, numel).reshape(
                lead + (numel,))
        out = grids.uniform_dequantize(codes, self.scale, self.k_x).astype(
            jnp.dtype(self.dtype))
        return out.astype(jnp.dtype(self.cast)) if self.cast else out


def _is_qleaf(x) -> bool:
    return isinstance(x, QuantizedLeaf)


def _path_head(path) -> Optional[str]:
    if not path:
        return None
    k = path[0]
    return getattr(k, "key", getattr(k, "name", None))


def _quantize_leaf(p: jax.Array, k_x: int, absolute: bool, per_layer: bool,
                   pack: bool) -> QuantizedLeaf:
    x = p.astype(jnp.float32)
    # engine dispatch: fused Pallas amax+quantize tiles on TPU; mapped
    # over the layer dim for stacked leaves (one scale per layer). A
    # loop, not vmap: the kernels' SMEM scalars cannot take a batch dim
    if per_layer:
        codes, scale = jax.lax.map(
            lambda xl: engine.quantize_uniform(xl, k_x, absolute=absolute), x)
    else:
        codes, scale = engine.quantize_uniform(x, k_x, absolute=absolute)
    # the registry's exact (unclipped) lane for this grid: 3/4/6-bit
    # lanes below int8 are worth packing, 8/16-bit codes stay as-is
    codec = comm.UniformCodec(k_x=k_x, absolute=absolute)
    pack_bits = 0
    if pack and codec.bits < 8:
        pack_bits = codec.bits
        lead = codes.shape[:-1]
        flat = codes.reshape((-1, codes.shape[-1]))
        # rows of at least one packing block are padded to whole blocks,
        # which the fused matmul's column tiles need (the unpack paths
        # cut the codes back to the logical width)
        blk = B.block_codes(pack_bits)
        if flat.shape[1] >= blk and flat.shape[1] % blk:
            flat = jnp.pad(flat, ((0, 0), (0, -flat.shape[1] % blk)))
        rows = comm.pack_rows(flat, pack_bits)
        codes = rows.reshape(lead + (rows.shape[-1],))
    return QuantizedLeaf(codes=codes, scale=scale, k_x=k_x,
                         shape=tuple(p.shape), dtype=jnp.dtype(p.dtype).name,
                         pack_bits=pack_bits)


def quantize_params(params, k_x: int = 6, *, absolute: bool = False,
                    min_numel: int = 2 ** 14, pack: bool = False):
    """Replace large float leaves with code-resident :class:`QuantizedLeaf`.

    Stacked ``blocks``/``enc_blocks`` leaves get per-layer scales (finer
    than a whole-stack amax, and what the per-layer dequant-at-use needs).
    Leaves smaller than ``min_numel`` (biases, norms) stay float.
    """
    def one(path, p):
        if (not hasattr(p, "dtype")
                or not jnp.issubdtype(p.dtype, jnp.floating)
                or p.ndim == 0 or p.size < min_numel):
            return p
        per_layer = _path_head(path) in _STACKED_KEYS and p.ndim > 1
        return _quantize_leaf(p, k_x, absolute, per_layer, pack)

    return jax.tree_util.tree_map_with_path(one, params)


def is_quantized(params) -> bool:
    return any(_is_qleaf(l) for l in
               jax.tree.leaves(params, is_leaf=_is_qleaf))


# Leaf names whose contraction the model expresses as ``x @ w`` (or an
# embed lookup / tied ``x @ w.T``): these stay code-resident through the
# gather and dispatch to repro.comm.matmul. Everything else (conv taps,
# MoE expert stacks, meta-token banks, norms) is consumed elementwise or
# via einsum and still dequantizes whole.
_MATMUL_KEYS = frozenset({
    "q", "k", "v", "o", "w_gate", "w_up", "w_down", "router",
    "in_proj", "out_proj", "embed", "unembed",
})


def _path_name(path) -> Optional[str]:
    if not path:
        return None
    k = path[-1]
    return getattr(k, "key", getattr(k, "name", None))


def _fused_ok(path, leaf, kind: str) -> bool:
    """True when this quantized leaf can stay as codes for the fused
    matmul: a known projection name AND 2-D logical weight. Inside the
    scan ("blocks"/"enc_blocks") codes arrive sliced but the aux shape is
    still the stacked (L, K, N), so 2-D-when-sliced means len(shape) == 3;
    higher-rank stacks (MoE experts, meta banks) fall through to
    ``dequantize()``."""
    if _path_name(path) not in _MATMUL_KEYS:
        return False
    want = 2 if kind == "static" else 3
    return len(leaf.shape) == want


def make_dequant_gather(inner=None, fused: bool = True):
    """A ``ShardCtx.param_gather`` hook for code-resident params. The
    "static" pass leaves scan-stacked subtrees quantized so ``lax.scan``
    carries the codes and each layer decodes only its own slice.

    With ``fused`` (the default since the fused dequant-matmul landed),
    matmul-shaped leaves - attention/MLP/SSM projections, routers,
    embed/unembed - are ALSO left as codes and their ``x @ w`` sites
    dispatch to ``repro.comm.matmul.dequant_matmul``; only conv taps,
    expert stacks, and other non-matmul leaves are materialized. Pass
    ``fused=False`` for the pre-PR-7 dequantize-everything semantics.
    ``inner``: optional downstream gather to compose with (mesh serving).
    """
    def deq(leaf):
        return leaf.dequantize() if _is_qleaf(leaf) else leaf

    def gather(subtree, kind: str):
        def one(path, leaf):
            if kind == "static" and _path_head(path) in _STACKED_KEYS:
                return leaf  # decoded per-layer inside the scan
            if fused and _is_qleaf(leaf) and _fused_ok(path, leaf, kind):
                return leaf  # codes feed the fused matmul directly
            return deq(leaf)
        out = jax.tree_util.tree_map_with_path(one, subtree,
                                               is_leaf=_is_qleaf)
        return inner(out, kind) if inner is not None else out

    return gather


def params_nbytes(params) -> int:
    """Actual resident bytes of a parameter tree (codes + scales for
    quantized leaves, array bytes otherwise) - what the example and tests
    assert against, instead of printing a theoretical "~/4"."""
    total = 0
    for leaf in jax.tree.leaves(params, is_leaf=_is_qleaf):
        total += leaf.nbytes if _is_qleaf(leaf) else int(leaf.nbytes)
    return total


def cache_nbytes(cache) -> int:
    """Resident bytes of a decode cache (fixed lanes or paged pool +
    tables alike) - the number the fleet benchmark equalizes when it
    compares paged vs fixed-lane serving at equal cache memory."""
    return sum(int(leaf.nbytes) for leaf in jax.tree.leaves(cache))
