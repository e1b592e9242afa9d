"""Shared pieces of the per-mode distributed updaters.

A mode is a ~50-line plugin: it owns the per-leaf optimizer math (via the
``repro.opt`` engine) and *declares* its update-exchange wire as a
``repro.comm`` codec, while ``repro.dist.step`` owns the mode-independent
worker-step template (weight broadcast -> fwd/bwd -> engine update ->
update exchange).

Updater contract: ``updater(g, m, v, e, chunk, meta, a_t, th_t, key,
idx)`` with the flat per-shard gradient/moments, this worker's master
chunk and its LeafMeta, the scheduled scalars, a per-(leaf, worker,
step) PRNG key, and the leaf's flat index (``metas_flat`` order - what
per-leaf wire plans key on); returns ``(new_chunk, m', v', e')``, or
``(new_chunk, m', v', e', stats_row)`` when the mode sets
``emits_stats`` (one ``adapt.stats`` row per leaf, reduced and ringed
by the step template).

Topology (``repro.dist.topology``): tiered modes open their updater
with :func:`tier_grad_mean` and route the exchange through the
``*_tiered`` collectives. On a flat topology both degenerate to the
legacy ops, so flat results stay bit-identical.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import comm
from repro.dist import collectives as C
from repro.dist.topology import Tiers, flat_tiers
from repro.opt import engine, grids


@dataclasses.dataclass(frozen=True)
class WorkerCtx:
    """Static worker-axis geometry + engine backend for one train step.

    ``tiers`` is the resolved topology (``repro.dist.topology.Tiers``);
    ``None`` means flat over all worker axes (``ctx_tiers`` resolves
    it), so pre-topology callers constructing a WorkerCtx directly keep
    their behavior."""
    worker_axes: Tuple[str, ...]
    wsizes: Tuple[int, ...]
    n_workers: int
    backend: Optional[str] = None   # engine backend; None = auto
    tiers: Optional[Tiers] = None


def ctx_tiers(ctx: WorkerCtx) -> Tiers:
    """The context's resolved tiers, defaulting to flat."""
    if ctx.tiers is not None:
        return ctx.tiers
    return flat_tiers(ctx.worker_axes, ctx.wsizes)


@dataclasses.dataclass(frozen=True)
class ModeSpec:
    """One optimizer mode: updater factory + wire declaration + state
    layout.

    ``wire_codec(grad_k)`` names the update-exchange codec; the byte
    accounting behind ``train.loop.comm_bytes_per_step`` derives from it
    (``wire_nbytes`` below - packed codes only, scale side-channels
    excluded), so the figure agrees byte-for-byte with the payload the
    collectives actually move. ``extra_state`` adds chunk-sized state
    leaves; ``broadcast_ef`` turns on server-side error feedback on the
    weight-broadcast channel (the ``efadam`` mode).

    ``per_leaf`` (adaptive modes) maps ``(tc, leaf_idx) -> Codec`` so
    different leaves ride different lanes; ``leaf_codec`` /
    ``leaf_wire_nbytes`` are the indexed entry points every accounting
    and bucketing path goes through - they fall back to the uniform
    ``wire_codec`` when no per-leaf plan is declared. ``emits_stats``
    marks updaters returning a trailing ``adapt.stats`` row.

    ``tiered``: the updater understands hierarchical topologies (intra
    fp reduce + inter-only exchange). ``dp_adam`` opts out - its psum
    over all worker axes is the same reduction on any topology, so
    tiering it would double-count the intra contributions; accounting
    keeps its wire on the inter tier at flat semantics.
    """
    name: str
    chunk_sharded_moments: bool
    make_updater: Callable          # (tc, ctx: WorkerCtx) -> updater
    wire_codec: Callable            # (grad_k) -> comm.Codec
    extra_state: Tuple[str, ...] = ()
    broadcast_ef: bool = False
    per_leaf: Optional[Callable] = None   # (tc, leaf_idx) -> comm.Codec
    emits_stats: bool = False
    tiered: bool = True

    def wire_nbytes(self, c: int, n_workers: int, grad_k=None) -> int:
        """Per-device, per-leaf update-exchange payload bytes - the
        single source of truth, derived from the declared codec."""
        return n_workers * self.wire_codec(grad_k).payload_nbytes(c)

    def leaf_codec(self, tc, idx: int) -> comm.Codec:
        """Wire codec for leaf ``idx`` (metas_flat order)."""
        if self.per_leaf is not None:
            return self.per_leaf(tc, idx)
        return self.wire_codec(tc.grad_k)

    def leaf_wire_nbytes(self, tc, idx: int, c: int, n_workers: int) -> int:
        """Per-device update-exchange payload bytes for leaf ``idx``."""
        return n_workers * self.leaf_codec(tc, idx).payload_nbytes(c)

    def leaf_tier_nbytes(self, tc, idx: int, c: int, numel: int,
                         n_workers: int, tiers: Optional[Tiers]) -> dict:
        """Per-device update-path bytes for leaf ``idx`` split by link
        tier: ``inter`` is the all-to-all'd payload (packed codes),
        ``intra`` the fp rows the hierarchical gradient pre-reduce
        gathers (``tier_grad_mean``: ``n_intra`` f32 rows of the shard).
        Flat topologies and non-``tiered`` modes report everything on
        the inter tier - exactly ``leaf_wire_nbytes``."""
        if not self.tiered or tiers is None or not tiers.intra_axes:
            return {"inter": self.leaf_wire_nbytes(tc, idx, c, n_workers),
                    "intra": 0}
        codec = self.leaf_codec(tc, idx)
        return {"inter": tiers.n_inter * codec.payload_nbytes(c),
                "intra": tiers.n_intra * numel * 4}


def identity_codec(grad_k=None) -> comm.Codec:
    """Wire declaration of the uncompressed (f32 rows) modes."""
    return comm.IdentityCodec()


def worker_mean(rows):
    """Mean over worker rows via pairwise (tree) summation: with n a
    power of two and identical rows (the paper's identical-worker
    equivalence), the result is bit-exact - a sequential reduce
    (((x+x)+x)+x) is not, and its ulp bias flips quantizer codes. The
    rows are summed in their 128-lane view (``collectives.lane_view``),
    which moves no data on a TPU; the values are the same."""
    def psum_rows(x):
        k = x.shape[0]
        if k == 1:
            return x[0]
        h = k // 2
        return psum_rows(x[:h]) + psum_rows(x[h:])
    mean = psum_rows(C.lane_view(rows, 1)) / rows.shape[0]
    return mean.reshape(rows.shape[1:])


def tier_grad_mean(g, tiers: Optional[Tiers]):
    """Hierarchical pre-reduce: all-gather this leaf's flat gradient
    over the intra (fast) axes and tree-mean the rows, so every device
    of a node continues the step with the bit-identical node-mean
    gradient (moments, EF residuals and quantizer codes then agree
    across the node - the exchange can ship one row per node).

    ``worker_mean``'s pairwise tree keeps the mean deterministic and,
    with a power-of-two node width, exact for identical rows - a psum
    would leave reduction order (and therefore ulps) to the compiler.
    Identity on flat tiers."""
    if tiers is None or not tiers.intra_axes:
        return g
    return worker_mean(C.gather_rows(g, tiers.intra_axes))


def blockwise_exchange(de, codec, meta, ctx: WorkerCtx,
                       tiers: Optional[Tiers] = None):
    """The blockwise wire shared by ``ef_sgd`` and the adaptive 2-bit
    lanes: sign codes packed to the codec's lane width with a per-block
    scale side-channel, EF residual against this worker's own
    dequantized codes. The payload all-to-all and the scale gather run
    over the exchange (inter) tier; the received codes are rescaled by
    the *source* worker's scale columns for my chunk. Returns
    ``(recv_rows, e2)`` with ``recv_rows`` of shape ``(n_src, c)``
    (``n_src = n_inter``; ``n_workers`` when flat)."""
    tiers = tiers if tiers is not None else ctx_tiers(ctx)
    n = de.shape[0]
    block = codec.block
    codes2d, scale_b = engine.quantize_blockwise(de, block,
                                                 backend=ctx.backend)
    deq_own = grids.blockwise_dequantize(codes2d, scale_b).reshape(-1)[:n]
    e2 = de - deq_own
    rows = comm.pad_rows(codes2d.reshape(-1)[:n], ctx.n_workers)
    payload = comm.pack_rows(rows, codec.bits)
    codes_rows = comm.unpack_rows(
        C.exchange_rows_tiered(payload, tiers), codec.bits, meta.c)
    scales = C.gather_rows(scale_b, tiers.inter_axes)      # (n_src, nb)
    elem = jnp.repeat(scales, block, axis=1)               # (n_src, nb*block)
    c = meta.c
    total = ctx.n_workers * c
    if elem.shape[1] < total:
        elem = jnp.pad(elem, ((0, 0), (0, total - elem.shape[1])))
    # the scale columns of MY chunk: w indexes over all worker axes -
    # chunk ownership is flat regardless of topology.
    w = C.worker_index(ctx.worker_axes, ctx.wsizes)
    n_src = codes_rows.shape[0]
    scale_cols = jax.lax.dynamic_slice(
        elem, (jnp.int32(0), w * c), (n_src, c))
    return codes_rows.astype(jnp.float32) * scale_cols, e2
