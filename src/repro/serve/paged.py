"""Paged KV cache: one physical page pool + per-slot page tables.

Fixed-lane serving reserves a whole ``(layers, slots, max_seq, ...)``
cache lane per slot, so memory - not compute - caps concurrency: a slot
holding an 8-token request pins the same bytes as one holding a
``max_seq``-token request. Here the cache is a single physical pool of
``num_pages`` pages of ``page_size`` tokens each, and every slot owns
only the pages its tokens actually occupy: concurrency is bounded by
*tokens in flight*, not ``slots * max_seq``. This is the serving
analogue of the paper's bytes-for-throughput tradeoff - spend cache
bytes only on information that exists.

Layout (per layer, carried through the decode ``lax.scan``):

  * pool  ``pk``/``pv``: (num_pages, page_size, n_kv_heads, head_dim)
  * table ``ptab``: (slots, max_seq // page_size) int32 global page ids;
    ``num_pages`` (one past the last page) is the RELEASED sentinel - a
    freed slot's writes scatter out of bounds (dropped) and its view
    columns are masked invalid, so a recycled page can never be
    corrupted by its previous owner.

``gather_pages`` materializes a slot's contiguous cache view from its
table - the one new device primitive paging needs. It follows the
``repro.comm.matmul`` pattern exactly: a jnp gather reference that is
the bitwise oracle, a Pallas kernel (scalar-prefetched page table drives
the block index map, one page copy per grid step) for TPU, interpret
mode elsewhere, and an explicit ``backend=`` always wins. Decode then
runs the unchanged ``decode_attention`` math over the view, which is
how paged decode stays bitwise identical to fixed-lane decode: the view
equals the lane at every valid position and masking kills the rest.

``PagePool`` is the host-side allocator the scheduler drives: a free
list (LIFO, deterministic), ``alloc``/``free`` by page count, and exact
occupancy accounting for admission and preemption decisions. It holds
no device state - the device sees only ``ptab`` rows.
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.comm import codec as C


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# gather: page pool + table -> contiguous per-slot view
# ---------------------------------------------------------------------------

def _gather_jnp(pool, ptab):
    """Reference: one gather over the page axis. (B, npag) indices into a
    (P, ps, K, hd) pool -> (B, npag*ps, K, hd) view."""
    B, npag = ptab.shape
    _, ps, K, hd = pool.shape
    view = jnp.take(pool, ptab, axis=0)          # (B, npag, ps, K, hd)
    return view.reshape(B, npag * ps, K, hd)


def _gather_body(tab_ref, pool_ref, o_ref):
    # the page id was already consumed by the index map; the body is a
    # straight VMEM copy of one page. pool block (1, ps, K, hd) lands in
    # out block (1, 1, ps, K, hd).
    del tab_ref
    o_ref[0] = pool_ref[...]


def _gather_pallas(pool, ptab, *, interpret):
    B, npag = ptab.shape
    P, ps, K, hd = pool.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, npag),
        in_specs=[pl.BlockSpec((1, ps, K, hd),
                               lambda b, j, tab: (tab[b, j], 0, 0, 0))],
        out_specs=pl.BlockSpec((1, 1, ps, K, hd),
                               lambda b, j, tab: (b, j, 0, 0, 0)),
    )
    out = pl.pallas_call(
        _gather_body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, npag, ps, K, hd), pool.dtype),
        interpret=interpret,
        name="paged_gather",
    )(ptab, pool)
    return out.reshape(B, npag * ps, K, hd)


def _pallas_covers(pool, ptab) -> bool:
    # one page per grid step: any in-range table works; degenerate pools
    # (empty page axis) fall back
    return pool.shape[0] > 0 and ptab.shape[1] > 0


def gather_pages(pool, ptab, *, backend: Optional[str] = None) -> jax.Array:
    """Contiguous cache view of each slot's pages.

    pool: (num_pages, page_size, K, hd) physical pages (one layer).
    ptab: (B, npag) int32 page ids; entries are clipped into the pool, so
        RELEASED-sentinel rows read *some* page - callers mask those view
        columns invalid (``decode_attention``'s ``extra_valid``), exactly
        like fixed-lane masking of positions beyond ``total_len``.

    Returns (B, npag * page_size, K, hd). Bitwise identical to the jnp
    gather on every backend (a gather moves bytes; there is nothing to
    round), asserted by ``tests/test_paged.py``.
    """
    ptab = jnp.clip(jnp.asarray(ptab, jnp.int32), 0, pool.shape[0] - 1)
    bk = C.resolve_backend(backend, pool.size, tile=pool.size // max(
        pool.shape[0], 1))
    if bk == "pallas" and _pallas_covers(pool, ptab):
        return _gather_pallas(pool, ptab, interpret=_interpret())
    return _gather_jnp(pool, ptab)


# ---------------------------------------------------------------------------
# host-side page allocator
# ---------------------------------------------------------------------------

def pages_for(ntokens: int, page_size: int) -> int:
    """Pages needed to hold ``ntokens`` cache rows."""
    return max(0, -(-int(ntokens) // int(page_size)))


class PagePool:
    """Free-list allocator over the physical page pool (host state only).

    LIFO free list: allocation order is deterministic for a given
    request schedule, and reuse cycles deliberately fragment the id
    space - the device never cares (the table indirection absorbs it),
    which ``tests/test_paged.py`` exercises directly.
    """

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 1 or page_size < 1:
            raise ValueError("PagePool needs num_pages >= 1, page_size >= 1")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self._free: List[int] = list(range(num_pages - 1, -1, -1))

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.num_pages - len(self._free)

    def pages_for(self, ntokens: int) -> int:
        return pages_for(ntokens, self.page_size)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Pop ``n`` pages, or None (and no change) when the pool can't
        cover the request - the scheduler then queues or preempts."""
        if n > len(self._free):
            return None
        taken = [self._free.pop() for _ in range(n)]
        return taken

    def free(self, pages: List[int]) -> None:
        for p in pages:
            if not 0 <= p < self.num_pages:
                raise ValueError(f"freeing foreign page {p}")
        self._free.extend(pages)
        if len(self._free) > self.num_pages:
            raise RuntimeError("double free: free list exceeds the pool")

    def nbytes(self, n_layers: int, page_bytes: int) -> int:
        """Physical pool bytes (all layers) for sizing comparisons."""
        return n_layers * self.num_pages * page_bytes
