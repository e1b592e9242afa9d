"""The quantized wire of Algorithm 3: every cross-worker collective ships
bit-packed uint8 payloads (plus f32 scales), never raw floats.

All compression goes through the ``repro.comm`` codec registry; this
module owns only the mesh topology - which rows move where. The fused
codec entry points (``comm.encode_rows*`` / ``comm.decode_rows``) emit
and consume the exact payload arrays the collectives move, so no
unpacked code tensor is ever materialized between quantize and the wire.

Two worker-axis channels (both error-compensated in ``repro.dist.step``):

  * **update exchange** (worker -> server): each worker fuse-encodes its
    update ``Delta_t + e_t`` for the whole model-shard into per-chunk
    payload rows and all-to-alls them, so worker ``w`` (the "server" for
    chunk ``w``) receives every worker's packed codes for its chunk.
    Per leaf this moves ``n_workers * codec.payload_nbytes(c)`` bytes
    per device.
  * **weight broadcast** (server -> worker): each server encodes its
    updated master chunk with the weight codec (Q_x wire lanes) and
    all-gathers the payload, so every worker reassembles Q_x(x_{t+1})
    for the full shard. The ``efadam`` mode adds server-side error
    feedback on this channel.

One model-axis channel:

  * **weight gather** (FSDP / serve): per-layer all_gather of weight
    shards, optionally int8 (per-shard amax scale) - the serve path's
    "int8 weight gather" and the train path's ``model_gather_quant``.

All functions that touch ``jax.lax`` collectives must run inside
``shard_map``; the codec helpers are pure and unit-tested directly
(``tests/test_packing.py``, ``tests/test_comm_codecs.py``).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import comm
from repro.comm.bits import pack_rows, unpack_rows  # noqa: F401  (compat)
from repro.opt import grids


def wire_bits_for_log(k_g: int) -> int:
    """Packed lane width of the log-grid wire (codec-derived)."""
    return comm.LogCodec(k_g=k_g).bits


amax_scale = grids.amax_scale  # shared zero-guarded scale (one definition)


LANES = 128


def lane_view(x: jax.Array, lead: int = 0) -> jax.Array:
    """View the trailing axis of ``x`` (after ``lead`` leading axes) as
    rows of 128 lanes when it divides: ``(..., n) -> (..., n/128, 128)``.

    A TPU tiles the two minor axes of an array in (8, 128) tiles, so a
    few long rows - ``(n_workers, c)`` wire rows - are laid out unlike
    the flat tensors and kernel tiles they come from and go to: XLA
    copies them element by element, and at yi-6b widths that copy was
    tens of MB of unrolled code per leaf. In the lane view every row is
    a run of whole tiles, so the reshapes on either side move no data.
    Values and order are unchanged; a row that does not divide stays as
    it is."""
    if x.ndim != lead + 1 or x.shape[-1] % LANES or x.shape[-1] == LANES:
        return x
    return x.reshape(x.shape[:-1] + (x.shape[-1] // LANES, LANES))


# ---------------------------------------------------------------------------
# worker-axis collectives (inside shard_map)
# ---------------------------------------------------------------------------

def worker_index(axes: Sequence[str], sizes: Sequence[int]) -> jax.Array:
    """Flat worker id, row-major over the worker axes."""
    idx = jnp.int32(0)
    for a, s in zip(axes, sizes):
        idx = idx * s + jax.lax.axis_index(a)
    return idx


def gather_rows(x: jax.Array, axes: Sequence[str]) -> jax.Array:
    """All-gather one per-worker value -> (n_workers, *x.shape), rows in
    flat worker order (same order as worker_index)."""
    r = lane_view(x)[None]
    for a in reversed(tuple(axes)):
        r = jax.lax.all_gather(r, a, axis=0, tiled=True)
    return r.reshape(r.shape[:1] + x.shape)


def exchange_rows(rows: jax.Array, axes: Sequence[str],
                  sizes: Sequence[int]) -> jax.Array:
    """All-to-all of worker-ownership rows: send row j to worker j; the
    result's row i is worker i's row for *this* worker. Implemented as one
    transposing all_to_all per worker axis."""
    axes = tuple(axes)
    if not axes:
        return rows
    nw = int(np.prod(sizes))
    x = lane_view(rows, 1)
    x = x.reshape(tuple(sizes) + x.shape[1:])
    for i, a in enumerate(axes):
        x = jax.lax.all_to_all(x, a, split_axis=i, concat_axis=i)
    return x.reshape((nw,) + rows.shape[1:])


# ---------------------------------------------------------------------------
# codec-backed channels: the wire arrays are codec payload rows
# ---------------------------------------------------------------------------

def exchange_decode(payload_rows: jax.Array, scale, codec: comm.Codec,
                    c: int, axes: Sequence[str], sizes: Sequence[int],
                    *, backend: Optional[str] = None) -> jax.Array:
    """Update-exchange channel for one leaf: my per-chunk payload rows
    (from ``comm.encode_rows*``) -> all_to_all -> fused decode of every
    worker's codes for MY chunk with its source scale. Returns
    ``(n_workers, c)`` dequantized rows."""
    assert payload_rows.dtype == jnp.uint8
    recv = exchange_rows(payload_rows, axes, sizes)
    scales = gather_rows(scale, axes)
    return comm.decode_rows(recv, scales, codec, c, backend=backend)


# ---------------------------------------------------------------------------
# per-tier channels (repro.dist.topology): flat tiers route through the
# legacy collectives above op-for-op; hierarchical tiers keep the slow
# (inter/node) links to n_inter rows per leaf
# ---------------------------------------------------------------------------

def exchange_rows_tiered(rows: jax.Array, tiers) -> jax.Array:
    """Tier-aware ``exchange_rows``. Flat: all_to_all over every worker
    axis, unchanged. Hierarchical: gradients were intra-reduced first,
    so every device of a node holds bit-identical rows - each device
    slices the ``n_inter`` rows destined for its intra position
    (``w = node * n_intra + intra``, row-major) and all-to-alls them
    across the node axes only. The slow tier moves ``n_inter`` rows per
    leaf instead of ``n_workers``; the result's row ``k`` is node
    ``k``'s row for this worker's chunk."""
    if not tiers.intra_axes:
        return exchange_rows(rows, tiers.inter_axes, tiers.inter_sizes)
    j = worker_index(tiers.intra_axes, tiers.intra_sizes)
    grid = rows.reshape((tiers.n_inter, tiers.n_intra) + rows.shape[1:])
    mine = jax.lax.dynamic_index_in_dim(grid, j, axis=1, keepdims=False)
    return exchange_rows(mine, tiers.inter_axes, tiers.inter_sizes)


def exchange_decode_tiered(payload_rows: jax.Array, scale,
                           codec: comm.Codec, c: int, tiers,
                           *, backend: Optional[str] = None) -> jax.Array:
    """Tier-aware ``exchange_decode``: payload all-to-all over the
    exchange (inter) tier, source scales gathered over the same tier.
    Returns ``(n_inter, c)`` dequantized rows - one row per exchange
    peer (``n_inter == n_workers`` on a flat topology)."""
    assert payload_rows.dtype == jnp.uint8
    recv = exchange_rows_tiered(payload_rows, tiers)
    scales = gather_rows(scale, tiers.inter_axes)
    return comm.decode_rows(recv, scales, codec, c, backend=backend)


def gather_rows_tiered(x: jax.Array, tiers) -> jax.Array:
    """Tier-aware ``gather_rows``: (n_workers, *x.shape) in flat worker
    order. Hierarchical topologies gather the inter (node) axes first -
    only ``n_inter`` rows cross the slow tier - then fan the stacked
    rows out within each node over the fast links."""
    if not tiers.intra_axes:
        return gather_rows(x, tiers.inter_axes)
    r = gather_rows(x, tiers.inter_axes)     # (n_inter, ...)
    r = gather_rows(r, tiers.intra_axes)     # (n_intra, n_inter, ...)
    r = jnp.swapaxes(r, 0, 1)                # flat (node, intra) order
    return r.reshape((tiers.n_inter * tiers.n_intra,) + x.shape)


def broadcast_decode(payload: jax.Array, scale, codec: comm.Codec, c: int,
                     axes: Sequence[str],
                     *, backend: Optional[str] = None) -> jax.Array:
    """Weight-broadcast channel for one leaf: my chunk's packed payload
    -> all_gather -> fused decode of every chunk with its source scale.
    Returns ``(n_workers, c)`` dequantized rows."""
    assert payload.dtype == jnp.uint8
    rows = gather_rows(payload, axes)
    scales = gather_rows(scale, axes)
    return comm.decode_rows(rows, scales, codec, c, backend=backend)


def broadcast_decode_tiered(payload: jax.Array, scale, codec: comm.Codec,
                            c: int, tiers,
                            *, backend: Optional[str] = None) -> jax.Array:
    """Tier-aware ``broadcast_decode``: hierarchical topologies run the
    payload/scale gathers inter-first (``gather_rows_tiered``), so each
    chunk's packed codes cross the slow tier once per node instead of
    once per device. Returns ``(n_workers, c)`` dequantized rows in flat
    worker order either way."""
    assert payload.dtype == jnp.uint8
    rows = gather_rows_tiered(payload, tiers)
    scales = gather_rows_tiered(scale, tiers)
    return comm.decode_rows(rows, scales, codec, c, backend=backend)


# ---------------------------------------------------------------------------
# model-axis weight gather (FSDP / serve), optionally int8
# ---------------------------------------------------------------------------

def gather_shard(leaf: jax.Array, ax: int, n_shards: int,
                 axis_name: str = "model") -> jax.Array:
    """Plain full-precision all_gather of a weight shard along `ax`."""
    if n_shards <= 1:
        return leaf
    return jax.lax.all_gather(leaf, axis_name, axis=ax, tiled=True)


def quantized_gather_shard(leaf: jax.Array, ax: int, n_shards: int,
                           k_x: int, absolute: bool,
                           axis_name: str = "model") -> jax.Array:
    """Int8 weight gather: quantize the local shard (per-shard scale),
    all_gather codes + scales, dequantize each received segment with its
    source scale. With n_shards == 1 this degenerates to local Q_x."""
    codec = comm.UniformCodec(k_x=k_x, absolute=absolute, wire_bits=8)
    leaf32 = leaf.astype(jnp.float32)
    scale = codec.compute_scale(leaf32)
    # int8 on the wire: the clip above guarantees the int8 range
    codes = codec.quantize(leaf32, scale).astype(jnp.int8)
    if n_shards <= 1:
        return codec.dequantize(codes, scale)
    seg = jax.lax.all_gather(codes, axis_name, axis=0,
                             tiled=False)          # (n_shards, *shard)
    scales = jax.lax.all_gather(scale, axis_name)  # (n_shards,)
    bshape = (n_shards,) + (1,) * leaf.ndim
    deq = codec.dequantize(seg, scales.reshape(bshape))
    out = jnp.moveaxis(deq, 0, ax)                 # (..., n_shards, loc, ...)
    shape = list(leaf.shape)
    shape[ax] = shape[ax] * n_shards
    return out.reshape(shape)
