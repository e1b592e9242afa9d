"""Continuous-batching serve sessions over a fixed pool of decode slots.

``ServeSession`` replaces the old batch-synchronous ``Engine.generate``:

  * ``submit(Request) -> handle`` claims a free slot (or queues); new
    requests join mid-flight as others finish - the batch never drains to
    restart. Requests carry an SLO class (``interactive`` > ``standard``
    > ``batch``): the pending queue is priority-ordered and, under pool
    pressure, a higher-class arrival preempts the lowest-class occupant
    (requeue-and-recompute by default, or ``preempt_mode="kill"`` which
    surfaces ``finish_reason="preempted"``).
  * ``step()`` runs ONE jitted decode step over all slots: token embedding,
    attention against each slot's own cache prefix (per-slot positions -
    slot i attends exactly its ``pos_i`` written entries, never padding or
    a previous occupant's rows), and sampling (greedy + per-slot
    temperature via a temperature vector and per-slot PRNG keys) all inside
    the compiled step. The host dispatches and moves on: zero per-token
    device->host transfers.
  * ``drain()`` runs until every submitted request finished and returns
    ``{handle: Result}``.

Decode state keeps a fixed shape - (slots,) control vectors + the cache -
so exactly one compiled decode step is reused for the whole session, with
the state buffers donated through it. The cache is either fixed-lane
(``(layers, slots, max_seq, ...)``) or, with ``paged=True``, a physical
page pool + per-slot page table (``repro.serve.paged``): slots then pin
only the pages their tokens occupy, so concurrency is bounded by tokens
in flight rather than ``slots * max_seq``, and admission validates page
availability up front - ``finish_reason="cache_full"`` cannot happen
while the pool has free pages.

Admission (local sessions) runs **chunked prefill** by default: the
prompt advances through ``model.decode_chunk`` in fixed-size chunks, one
chunk interleaved before each decode dispatch, so a long prompt never
stalls the decode batch and the per-prompt-length jit cache collapses to
exactly two chunk shapes (mid/final). ``prefill="whole"`` restores the
legacy one-shot batched prefill (fixed lanes only, compiled per prompt
length); mesh ``decode_fn`` sessions and SSD chunk-misaligned prompts
fall back to injecting the prompt through the decode step one token per
dispatch.

The decode callable is pluggable: the default wraps
``model.decode_step`` locally (dequantizing ``QuantizedParams`` per layer
at use); pass ``decode_fn=`` from ``repro.dist.serve.make_serve_step`` to
run the same session over a mesh - single-device and sharded serving are
one API (paged state is local-only for now; the mesh decode over a
sharded page pool lives in ``repro.dist.serve``'s cache specs).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import ShardCtx
from repro.perf import aot
from repro.perf.profiling import span
from repro.serve.paged import PagePool
from repro.serve.quantized import is_quantized, make_dequant_gather

# SLO classes, higher = more urgent. The queue is ordered by (class,
# arrival); preemption only ever evicts a strictly lower class.
SLO_PRIORITY = {"batch": 0, "standard": 1, "interactive": 2}

_PAGED_LEAVES = ("pk", "pv", "ptab")


def _session_call(method):
    """A public session method. While the device has no step queued -
    from a sync's return to the next decode or prefill dispatch - the
    time spent inside such methods goes to ``stats["idle_host_ns"]``; the
    caller's own time between calls is never counted."""
    @functools.wraps(method)
    def call(self, *args, **kwargs):
        if self._in_call:
            return method(self, *args, **kwargs)
        self._in_call = True
        if self._idle:
            self._idle_t0 = time.perf_counter_ns()
        try:
            return method(self, *args, **kwargs)
        finally:
            self._in_call = False
            self._count_idle(dispatched=False)
    return call


def _raw_key(key: jax.Array) -> jax.Array:
    """Normalize legacy (2,) uint32 / new-style typed PRNG keys to the raw
    uint32 data the per-slot key buffer stores."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    key = jnp.asarray(key, jnp.uint32)
    if key.shape != (2,):
        raise ValueError("ServeSession needs a threefry PRNG key "
                         f"(2 uint32 words); got key data {key.shape}")
    return key


@dataclasses.dataclass
class Request:
    prompt: Sequence[int]
    max_new_tokens: int = 16
    temperature: float = 0.0
    slo: str = "standard"           # "interactive" | "standard" | "batch"


@dataclasses.dataclass
class Result:
    tokens: List[int]
    prompt_len: int
    handle: int = -1
    # "length" | "eos" | "cache_full" | "preempted"
    finish_reason: str = "length"
    # submit -> return of the first sync after which the host held the
    # request's first token (None if it never had one)
    first_token_s: Optional[float] = None


class ServeSession:
    """Slot-scheduled continuous-batching session.

    model: repro.models.model.Model (token-input decoder LM).
    params: the model's parameter tree; may contain ``QuantizedLeaf``
        leaves from ``quantize_params`` (local decode path only).
    slots: number of concurrent decode lanes (the fixed batch width).
    max_seq: per-slot cache length; a request needs
        ``len(prompt) + max_new_tokens - 1 <= max_seq``.
    eos_id: optional token id that finishes a request early.
    decode_fn: optional ``(params, inputs, cache, pos) -> (logits, cache)``
        override, e.g. from ``dist.serve.make_serve_step(..., "decode")``.
    paged: replace the fixed cache lanes with a page pool + page tables
        (``page_size`` tokens per page, ``num_pages`` physical pages -
        default ``slots * max_seq / page_size``, i.e. fixed-lane-equal
        memory). Local decode path only; requires
        ``max_seq % page_size == 0``. Decode over the paged view is
        bitwise identical to fixed-lane decode.
    prefill: admission mode - "auto" (chunked locally, injection on a
        mesh), "chunked", "whole" (legacy batched prefill, fixed lanes
        only), or "inject". Chunked admission advances ``prefill_chunk``
        prompt tokens per session step, interleaved with decode.
    preempt_mode: "requeue" re-admits a preempted request from its prompt
        with its original sampling key (identical tokens to an
        unpreempted run); "kill" returns the partial generation with
        ``finish_reason="preempted"``.
    sync_interval: while requests are queued AND a slot may have finished
        early (EOS configured), harvest every N steps. Without an EOS the
        scheduler knows each slot's earliest possible finish step
        host-side and harvests only then - O(requests) syncs, never
        O(tokens); with an empty queue the steady-state loop never syncs.
    aot_dir: AOT artifact directory (``repro.perf.aot``) for the compiled
        decode step, keyed on (model config digest, slots, max_seq, paged
        geometry, sample mode, quantization, arg signature). A warm dir
        makes the first dispatch skip trace+lower+compile; local decode
        path only. ``stats`` records ``compilations`` vs ``aot_loads``.

    ``stats`` holds integer counters that only grow: ``dispatches``
    (decode steps), ``chunk_dispatches``, ``syncs``, ``admitted`` and
    ``preemptions``; ``sync_drain_ns``, the host's wait in a sync for the
    dispatched steps, and ``fetch_ns``, its copy of the slots' rows after
    that (the device is idle then); ``idle_host_ns``, the time inside the
    session's public methods from a sync's return to the next decode or
    prefill dispatch; and ``decode_tokens``, the tokens decode dispatches
    emitted, counted at each sync from every slot's change in ``gen``
    since the previous one (a prefill's first token is not counted;
    tokens of a request preempted and requeued between two syncs are not
    counted either). The phases are ``repro.perf.profiling`` spans named
    ``serve.<phase>``: ``submit``, ``schedule``, ``prefill_chunk``,
    ``decode``, ``harvest``, ``sync.drain``, ``sync.fetch``, ``collect``
    and ``first_token``; per-request ones carry the request's handle.
    """

    def __init__(self, model, params, *, slots: int = 8, max_seq: int = 256,
                 eos_id: Optional[int] = None,
                 decode_fn: Optional[Callable] = None,
                 base_key: Optional[jax.Array] = None, seed: int = 0,
                 sync_interval: int = 8, aot_dir: Optional[str] = None,
                 fused_matmul: bool = True,
                 paged: bool = False, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 prefill: str = "auto", prefill_chunk: int = 32,
                 preempt_mode: str = "requeue"):
        cfg = model.cfg
        if cfg.input_mode != "tokens" or cfg.arch_type == "encdec":
            raise ValueError("ServeSession serves token-input decoder LMs")
        self.model, self.cfg = model, cfg
        self.slots, self.max_seq, self.eos_id = slots, max_seq, eos_id
        self.sync_interval = max(1, sync_interval)
        self.params = params
        self._local = decode_fn is None
        self.paged = bool(paged)
        if self.paged:
            if not self._local:
                raise ValueError("paged sessions use the local decode path; "
                                 "mesh paged decode runs through "
                                 "dist.serve cache specs directly")
            if cfg.arch_type == "ssm":
                raise ValueError("pure-SSM models hold no KV cache to page")
            if max_seq % page_size:
                raise ValueError(f"max_seq={max_seq} must be a multiple of "
                                 f"page_size={page_size}")
            self.page_size = int(page_size)
            self.num_pages = int(num_pages if num_pages is not None
                                 else slots * (max_seq // page_size))
            self._pool = PagePool(self.num_pages, self.page_size)
        else:
            self.page_size = self.num_pages = 0
            self._pool = None
        if prefill not in ("auto", "chunked", "whole", "inject"):
            raise ValueError(f"unknown prefill mode {prefill!r}")
        if prefill == "whole" and self.paged:
            raise ValueError("whole-prompt prefill fills a dense lane; "
                             "paged sessions admit chunked (or inject)")
        self._prefill_mode = prefill
        self.prefill_chunk = max(1, int(prefill_chunk))
        if preempt_mode not in ("requeue", "kill"):
            raise ValueError(f"unknown preempt_mode {preempt_mode!r}")
        self.preempt_mode = preempt_mode
        # fused_matmul: quantized projections contract straight from codes
        # (repro.comm.matmul); False restores dequantize-then-matmul.
        # Bitwise-identical tokens either way - this is a perf knob.
        self.fused_matmul = bool(fused_matmul) and is_quantized(params)
        self._ctx = (ShardCtx(param_gather=make_dequant_gather(
                         fused=fused_matmul))
                     if is_quantized(params) else ShardCtx())
        if decode_fn is None:
            ctx = self._ctx
            decode_fn = lambda p, i, c, pos: model.decode_step(p, i, c, pos,
                                                               ctx)
        elif is_quantized(params):
            raise ValueError("QuantizedParams require the local decode path;"
                             " a mesh decode_fn brings its own weight wire")
        self._decode = decode_fn
        self._prefill_fns: Dict[int, Callable] = {}  # keyed by prompt len
        # two step variants: sessions whose admitted requests are all
        # greedy never pay (or compile) the categorical sampling pass
        self._step_greedy = jax.jit(self._build_step(sample=False),
                                    donate_argnums=(1,))
        self._step_sample = jax.jit(self._build_step(sample=True),
                                    donate_argnums=(1,))
        self._admit_fn = jax.jit(self._build_admit(), donate_argnums=(0,))
        self._stage_fn = jax.jit(self._build_stage(), donate_argnums=(0,))
        self._release_fn = jax.jit(self._build_release(), donate_argnums=(0,))
        self._chunk_fns: Dict[bool, Callable] = {}   # is_last -> jitted
        self._aot_dir = aot_dir if self._local else None
        self._step_ready: Dict[bool, Callable] = {}  # sample -> executable
        self._state = self._init_state()
        self._base_key = _raw_key(base_key if base_key is not None
                                  else jax.random.PRNGKey(seed))
        self._hot: set = set()          # handles in slots with temp > 0
        self._slot_handle: List[Optional[int]] = [None] * slots
        self._slot_done_step = [0] * slots   # earliest possible finish
        self._slot_pages: List[Optional[List[int]]] = [None] * slots
        self._prefill_q: "collections.OrderedDict[int, dict]" = \
            collections.OrderedDict()   # slot -> chunked-admission progress
        self._pending: List[int] = []   # handles, (priority, arrival) order
        self._requests: Dict[int, Request] = {}
        self._req_key: Dict[int, jax.Array] = {}   # stable across preemption
        self._results: Dict[int, Result] = {}
        # per-request timing, held only while the request is in flight:
        # its submit stamp until the host holds its first token, then the
        # first-token time until its result is made
        self._submit_ns: Dict[int, int] = {}
        self._first_token_s: Dict[int, float] = {}
        # each slot's ``gen`` as the last sync saw it (or as its admission
        # set it): the base from which decode tokens are counted
        self._gen_seen = [0] * slots
        # idle accounting (see _session_call): set by a sync, cleared by
        # the next decode or prefill dispatch
        self._idle, self._idle_t0, self._in_call = False, 0, False
        self._next_handle = 0
        self._admit_seq = 0             # submissions since the last reseed
        self._steps = 0
        self.stats = {"dispatches": 0, "syncs": 0, "admitted": 0,
                      "compilations": 0, "aot_loads": 0,
                      "preemptions": 0, "chunk_dispatches": 0,
                      "max_inflight": 0, "sync_drain_ns": 0, "fetch_ns": 0,
                      "idle_host_ns": 0, "decode_tokens": 0}

    # ------------------------------------------------------------------
    # device-side state + compiled programs
    # ------------------------------------------------------------------

    def _init_state(self):
        B, S = self.slots, self.max_seq
        pool = (self.num_pages, self.page_size) if self.paged else None
        cache = self.model.init_cache(B, max_seq_local=S, page_pool=pool)
        z = lambda dt: jnp.zeros((B,), dt)
        return dict(cache=cache, cur=z(jnp.int32), pos=z(jnp.int32),
                    plen=z(jnp.int32), gen=z(jnp.int32),
                    max_new=z(jnp.int32), active=z(bool),
                    temp=z(jnp.float32),
                    rng=jnp.zeros((B, 2), jnp.uint32),
                    prompt=jnp.zeros((B, S), jnp.int32),
                    out=jnp.zeros((B, S), jnp.int32))

    def _claim_cache(self, cache, slot, ptab_row):
        """Slot-reuse reclaim, in-jit: zero only the recurrent lanes (SSM
        state, conv tail) - per-slot attention masking already makes a
        previous occupant's K/V rows unreachable, so the old whole-lane
        zeroing was pure wasted bandwidth - and install the slot's page
        table row when paged."""
        cache = dict(cache)
        for name in cache:
            if name in ("ssm", "conv"):
                cache[name] = cache[name].at[:, slot].set(0)
        if self.paged:
            cache["ptab"] = cache["ptab"].at[slot].set(ptab_row)
        return cache

    def _build_admit(self):
        S, paged = self.max_seq, self.paged

        def admit(st, slot, prompt, plen, max_new, temp, key, ptab_row):
            st = dict(st)
            st["prompt"] = st["prompt"].at[slot].set(prompt)
            st["cur"] = st["cur"].at[slot].set(prompt[0])
            st["pos"] = st["pos"].at[slot].set(0)
            st["plen"] = st["plen"].at[slot].set(plen)
            st["gen"] = st["gen"].at[slot].set(0)
            st["max_new"] = st["max_new"].at[slot].set(max_new)
            st["active"] = st["active"].at[slot].set(True)
            st["temp"] = st["temp"].at[slot].set(temp)
            st["rng"] = st["rng"].at[slot].set(key)
            st["cache"] = self._claim_cache(st["cache"], slot, ptab_row)
            return st
        return admit

    def _build_stage(self):
        """Claim a slot for chunked admission: recurrent lanes zeroed and
        the page-table row installed, but the slot stays inactive with
        ``pos = max_seq`` so interleaved decode steps neither advance it
        nor write into its (paged) cache while chunks are in flight."""
        S = self.max_seq

        def stage(st, slot, ptab_row):
            st = dict(st)
            st["active"] = st["active"].at[slot].set(False)
            st["pos"] = st["pos"].at[slot].set(S)
            st["gen"] = st["gen"].at[slot].set(0)
            st["cache"] = self._claim_cache(st["cache"], slot, ptab_row)
            return st
        return stage

    def _build_release(self):
        """Free a slot in-jit (harvest page reclaim / preemption): decode
        writes for the row are suppressed (paged: RELEASED-sentinel page
        table + out-of-view position drop the scatters, so recycled pages
        can never be corrupted by the previous owner)."""
        S, paged, P = self.max_seq, self.paged, self.num_pages

        def release(st, slot):
            st = dict(st)
            st["active"] = st["active"].at[slot].set(False)
            st["pos"] = st["pos"].at[slot].set(S)
            if paged:
                cache = dict(st["cache"])
                npag = cache["ptab"].shape[1]
                cache["ptab"] = cache["ptab"].at[slot].set(
                    jnp.full((npag,), P, jnp.int32))
                st["cache"] = cache
            return st
        return release

    def _build_prefill(self, plen: int):
        """Legacy admission via one batched prefill over the whole prompt:
        fills the slot's cache lane and emits the first generated token.
        Compiled once per distinct prompt length (``prefill="whole"``);
        chunked admission replaces this with two chunk-shaped programs."""
        model, S, eos, ctx = self.model, self.max_seq, self.eos_id, self._ctx

        def prefill(params, st, slot, prompt, max_new, temp, key):
            batch = {"tokens": prompt[None], "targets": prompt[None],
                     "mask": jnp.ones((1, plen), jnp.float32)}
            logits, lane = model.prefill(params, batch, max_seq_local=S,
                                         ctx=ctx)
            lg = logits[0, plen - 1].astype(jnp.float32)
            greedy = jnp.argmax(lg).astype(jnp.int32)
            k_next, k_draw = jax.random.split(key)
            sampled = jax.random.categorical(
                k_draw, lg / jnp.maximum(temp, 1e-6)).astype(jnp.int32)
            hot = temp > 0.0
            t0 = jnp.where(hot, sampled, greedy)
            st = dict(st)
            st["cache"] = {
                k: st["cache"][k].at[:, slot].set(
                    lane[k][:, 0].astype(st["cache"][k].dtype))
                for k in st["cache"]}
            st["prompt"] = st["prompt"].at[slot].set(
                jnp.zeros((S,), jnp.int32).at[:plen].set(prompt))
            st["cur"] = st["cur"].at[slot].set(t0)
            st["pos"] = st["pos"].at[slot].set(plen)
            st["plen"] = st["plen"].at[slot].set(plen)
            st["gen"] = st["gen"].at[slot].set(1)
            st["out"] = st["out"].at[slot, 0].set(t0)
            st["max_new"] = st["max_new"].at[slot].set(max_new)
            done = max_new <= 1
            if eos is not None:
                done |= t0 == jnp.int32(eos)
            st["active"] = st["active"].at[slot].set(~done)
            st["temp"] = st["temp"].at[slot].set(temp)
            st["rng"] = st["rng"].at[slot].set(
                jnp.where(hot, k_next, key))
            return st
        return prefill

    def _build_chunk(self, is_last: bool):
        """One chunked-prefill dispatch for one slot: advance the slot's
        cache by ``prefill_chunk`` prompt tokens via ``model.decode_chunk``.
        The final chunk additionally samples the first generated token
        with exactly the whole-prefill key discipline (one split, draw on
        one half, store the other), so chunked admissions reproduce the
        same per-request sampling streams on fixed-lane and paged
        sessions alike."""
        model, S, eos, ctx = self.model, self.max_seq, self.eos_id, self._ctx

        def chunk(params, st, slot, tokens, start, nvalid, max_new, temp,
                  key):
            st = dict(st)
            cache = st["cache"]
            lane = {}
            for name in cache:
                if name in ("pk", "pv"):
                    lane[name] = cache[name]
                elif name == "ptab":
                    lane[name] = jax.lax.dynamic_slice_in_dim(
                        cache[name], slot, 1, axis=0)
                else:
                    lane[name] = jax.lax.dynamic_slice_in_dim(
                        cache[name], slot, 1, axis=1)
            lg, new_lane = model.decode_chunk(
                params, {"token": tokens[None]}, lane,
                start[None], nvalid[None], ctx)
            newc = {}
            for name in cache:
                if name in ("pk", "pv"):
                    newc[name] = new_lane[name]
                elif name == "ptab":
                    newc[name] = cache[name]   # rows set at staging
                else:
                    newc[name] = jax.lax.dynamic_update_slice_in_dim(
                        cache[name], new_lane[name], slot, axis=1)
            st["cache"] = newc
            if is_last:
                lgf = lg[0].astype(jnp.float32)
                greedy = jnp.argmax(lgf).astype(jnp.int32)
                k_next, k_draw = jax.random.split(key)
                sampled = jax.random.categorical(
                    k_draw, lgf / jnp.maximum(temp, 1e-6)).astype(jnp.int32)
                hot = temp > 0.0
                t0 = jnp.where(hot, sampled, greedy)
                plen = start + nvalid
                st["cur"] = st["cur"].at[slot].set(t0)
                st["pos"] = st["pos"].at[slot].set(plen)
                st["plen"] = st["plen"].at[slot].set(plen)
                st["gen"] = st["gen"].at[slot].set(1)
                st["out"] = st["out"].at[slot, 0].set(t0)
                st["max_new"] = st["max_new"].at[slot].set(max_new)
                done = max_new <= 1
                if eos is not None:
                    done |= t0 == jnp.int32(eos)
                st["active"] = st["active"].at[slot].set(~done)
                st["temp"] = st["temp"].at[slot].set(temp)
                st["rng"] = st["rng"].at[slot].set(
                    jnp.where(hot, k_next, key))
            return st
        return chunk

    def _can_prefill_whole(self, plen: int) -> bool:
        if not self._local or plen < 2:
            return False
        if self.cfg.arch_type in ("ssm", "hybrid"):
            # the SSD chunked scan needs the sequence to tile its chunk
            return plen % self.cfg.ssm.chunk == 0
        return True

    def _admission_mode(self, plen: int) -> str:
        if self._prefill_mode == "inject" or not self._local:
            return "inject"
        if self._prefill_mode == "whole":
            return "whole" if self._can_prefill_whole(plen) else "inject"
        # "auto"/"chunked": chunked wherever the architecture allows
        if self.cfg.arch_type in ("ssm", "hybrid"):
            c = self.prefill_chunk
            # decode_chunk has no per-token SSD masking: every dispatched
            # chunk must be full and SSD-chunk-aligned
            if c % self.cfg.ssm.chunk == 0 and plen % c == 0:
                return "chunked"
            if not self.paged and self._can_prefill_whole(plen):
                return "whole"
            return "inject"
        return "chunked"

    def _build_step(self, sample: bool):
        decode, eos, S = self._decode, self.eos_id, self.max_seq

        def step(params, st):
            B = st["cur"].shape[0]
            active, pos = st["active"], st["pos"]
            logits, new_cache = decode(params, {"token": st["cur"][:, None]},
                                       st["cache"], pos)

            # cache retention: fixed lanes revert inactive slots' writes
            # (leaves are (layers, B, ...)); the paged pool and tables pass
            # through - released rows already dropped their scatters, and a
            # finished-but-unharvested row's rewrite is idempotent (same
            # frozen inputs -> same bytes into its own pages)
            cache = {}
            for name, new in new_cache.items():
                if name in _PAGED_LEAVES:
                    cache[name] = new
                else:
                    a = active.reshape((1, B) + (1,) * (new.ndim - 2))
                    cache[name] = jnp.where(a, new, st["cache"][name])

            # sampling lives INSIDE the compiled step: greedy argmax plus
            # (when any admitted request is hot) per-slot temperature/
            # categorical on per-slot PRNG streams
            logits = logits.astype(jnp.float32)
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if sample:
                keys = jax.vmap(jax.random.split)(st["rng"])  # (B, 2, 2)
                hot = st["temp"] > 0.0
                scaled = logits / jnp.maximum(st["temp"], 1e-6)[:, None]
                sampled = jax.vmap(jax.random.categorical)(
                    keys[:, 1], scaled).astype(jnp.int32)
                tok = jnp.where(hot, sampled, greedy)
                rng = jnp.where(hot[:, None], keys[:, 0], st["rng"])
            else:
                tok, rng = greedy, st["rng"]

            nxt = pos + 1
            in_prompt = nxt < st["plen"]
            prompt_next = jnp.take_along_axis(
                st["prompt"], jnp.clip(nxt, 0, S - 1)[:, None], axis=1)[:, 0]
            emit = active & ~in_prompt                 # tok was generated
            rows = jnp.arange(B)
            gidx = jnp.clip(st["gen"], 0, S - 1)
            out = st["out"].at[rows, gidx].set(
                jnp.where(emit, tok, st["out"][rows, gidx]))
            gen = st["gen"] + emit.astype(jnp.int32)
            done = emit & (gen >= st["max_new"])
            if eos is not None:
                done |= emit & (tok == jnp.int32(eos))
            done |= active & (nxt >= S)                # cache full
            alive = active & ~done
            cur = jnp.where(in_prompt, prompt_next, tok)
            cur = jnp.where(alive, cur, st["cur"])
            pos = jnp.where(alive, jnp.minimum(nxt, S - 1), pos)
            return dict(cache=cache, cur=cur, pos=pos, plen=st["plen"],
                        gen=gen, max_new=st["max_new"], active=alive,
                        temp=st["temp"], rng=rng, prompt=st["prompt"],
                        out=out)
        return step

    # ------------------------------------------------------------------
    # scheduler API
    # ------------------------------------------------------------------

    @property
    def free_slots(self) -> int:
        return sum(h is None for h in self._slot_handle)

    @property
    def inflight(self) -> int:
        return sum(h is not None for h in self._slot_handle)

    @property
    def queued(self) -> int:
        return len(self._pending)

    @property
    def free_pages(self) -> int:
        return self._pool.free_pages if self.paged else 0

    def _request_pages(self, req: Request) -> int:
        # cache rows actually written: prompt + all generated tokens but
        # the last (which is emitted, never fed back)
        return self._pool.pages_for(len(req.prompt) + req.max_new_tokens - 1)

    @_session_call
    def submit(self, req: Request) -> int:
        """Queue a request; returns its handle. Claims a free slot
        immediately when one is available (preempting a lower SLO class
        under slot/page pressure)."""
        plen = len(req.prompt)
        if plen < 1:
            raise ValueError("empty prompt")
        if req.slo not in SLO_PRIORITY:
            raise ValueError(f"unknown SLO class {req.slo!r}; expected one "
                             f"of {sorted(SLO_PRIORITY)}")
        if plen + req.max_new_tokens - 1 > self.max_seq:
            raise ValueError(
                f"prompt_len={plen} + max_new={req.max_new_tokens} - 1 "
                f"exceeds max_seq={self.max_seq}")
        if self.paged and self._request_pages(req) > self.num_pages:
            raise ValueError(
                f"request needs {self._request_pages(req)} pages; the pool "
                f"holds {self.num_pages}")
        h = self._next_handle
        self._next_handle += 1
        with span("serve.submit", handle=h):
            self._submit_ns[h] = time.perf_counter_ns()
            self._requests[h] = req
            # fold on the submission ordinal since the last (re)seed:
            # identical (requests, key) sequences after a reseed() draw
            # identical sampling streams, and the key survives
            # preemption-requeue so a resumed request replays its draws
            self._req_key[h] = jax.random.fold_in(self._base_key,
                                                  self._admit_seq)
            self._admit_seq += 1
            self._enqueue(h)
            self._schedule()
        return h

    def _enqueue(self, h: int):
        """Insert into the pending queue ordered by (SLO class desc,
        arrival asc) - handles are arrival-ordered, so a preempted request
        resumes ahead of later arrivals in its class."""
        pr = SLO_PRIORITY[self._requests[h].slo]
        keyf = lambda hh: (-SLO_PRIORITY[self._requests[hh].slo], hh)
        lo = 0
        me = (-pr, h)
        while lo < len(self._pending) and keyf(self._pending[lo]) < me:
            lo += 1
        self._pending.insert(lo, h)

    def _schedule(self, allow_harvest: bool = True):
        """Admit from the head of the priority queue while resources
        allow. Under pressure, first collect any already-finished slots
        (so a completed request is never "preempted"), then preempt
        strictly-lower-SLO occupants."""
        if not self._pending:
            return
        with span("serve.schedule"):
            while self._pending:
                h = self._pending[0]
                req = self._requests[h]
                if self._try_admit(h, req):
                    self._pending.pop(0)
                    continue
                if allow_harvest and self.inflight:
                    allow_harvest = False
                    if self._collect_finished():
                        continue
                if not self._try_preempt_for(req):
                    break

    def _try_admit(self, handle: int, req: Request) -> bool:
        free = [s for s, owner in enumerate(self._slot_handle)
                if owner is None]
        if not free:
            return False
        pages = None
        if self.paged:
            pages = self._pool.alloc(self._request_pages(req))
            if pages is None:
                return False
        self._admit(free[0], handle, req, pages)
        return True

    def _try_preempt_for(self, req: Request) -> bool:
        """Reclaim slot+pages from the lowest-SLO, most-recently-admitted
        occupant strictly below ``req``'s class. Returns False (nothing
        touched) when no such victim exists or even evicting all of them
        could not seat the request."""
        pr = SLO_PRIORITY[req.slo]
        victims = [(SLO_PRIORITY[self._requests[h].slo], -h, s)
                   for s, h in enumerate(self._slot_handle)
                   if h is not None and h in self._requests
                   and SLO_PRIORITY[self._requests[h].slo] < pr]
        if self.preempt_mode == "kill":
            # killed handles leave self._requests; look them up anyway
            victims = [(SLO_PRIORITY[self._requests[h].slo], -h, s)
                       for s, h in enumerate(self._slot_handle)
                       if h is not None
                       and SLO_PRIORITY[self._requests[h].slo] < pr]
        if not victims:
            return False
        if self.paged:
            reclaim = sum(len(self._slot_pages[s] or ())
                          for _, _, s in victims)
            if self._pool.free_pages + reclaim < self._request_pages(req):
                return False
        victims.sort()
        self._preempt(victims[0][2])
        return True

    def _preempt(self, slot: int):
        h = self._slot_handle[slot]
        self.stats["preemptions"] += 1
        mid_prefill = slot in self._prefill_q
        if self.preempt_mode == "kill":
            if mid_prefill:
                req = self._requests.pop(h)
                self._results[h] = Result(tokens=[],
                                          prompt_len=len(req.prompt),
                                          handle=h,
                                          finish_reason="preempted")
            else:
                snap = self._sync()
                n = int(snap["gen"][slot])
                req = self._requests.pop(h)
                self._results[h] = Result(
                    tokens=[int(t) for t in snap["out"][slot, :n]],
                    prompt_len=len(req.prompt), handle=h,
                    finish_reason="preempted",
                    first_token_s=self._first_token_s.get(h))
            self._forget(h)
        else:
            # requeue-and-recompute: the request (and its sampling key)
            # goes back to the head of its SLO class
            self._enqueue(h)
        self._free_slot(slot, release=True)

    def _free_slot(self, slot: int, release: bool):
        h = self._slot_handle[slot]
        self._slot_handle[slot] = None
        self._slot_done_step[slot] = 0
        self._prefill_q.pop(slot, None)
        self._hot.discard(h)
        if self.paged and self._slot_pages[slot] is not None:
            self._pool.free(self._slot_pages[slot])
            self._slot_pages[slot] = None
        if release:
            self._state = self._release_fn(self._state, slot)

    def _admit(self, slot: int, handle: int, req: Request,
               pages: Optional[List[int]]):
        plen = len(req.prompt)
        key = self._req_key[handle]
        if self.paged:
            npag = self.max_seq // self.page_size
            row = np.full((npag,), self.num_pages, np.int32)
            row[:len(pages)] = pages
            ptab_row = jnp.asarray(row)
            self._slot_pages[slot] = pages
        else:
            ptab_row = jnp.zeros((1,), jnp.int32)  # unused placeholder
        self._slot_handle[slot] = handle
        mode = self._admission_mode(plen)
        # whole prefill leaves gen at 1 (its first token); staging and
        # injection at 0
        self._gen_seen[slot] = int(mode == "whole")
        if mode == "whole":
            fn = self._prefill_fns.get(plen)
            if fn is None:
                fn = jax.jit(self._build_prefill(plen), donate_argnums=(1,))
                self._prefill_fns[plen] = fn
            self._state = fn(
                self.params, self._state, jnp.int32(slot),
                jnp.asarray(np.asarray(req.prompt, np.int32)),
                jnp.int32(req.max_new_tokens),
                jnp.float32(req.temperature), key)
            self._count_idle(dispatched=True)
            self._finalize_admission(slot, handle, req,
                                     remaining=req.max_new_tokens - 1)
        elif mode == "chunked":
            self._state = self._stage_fn(self._state, jnp.int32(slot),
                                         ptab_row)
            self._prefill_q[slot] = dict(
                handle=handle, tokens=np.asarray(req.prompt, np.int32),
                next=0, plen=plen, max_new=req.max_new_tokens,
                temp=req.temperature, key=key)
            nchunks = -(-plen // self.prefill_chunk)
            # provisional bound until the final chunk lands
            self._slot_done_step[slot] = (self._steps + nchunks
                                          + req.max_new_tokens)
            self._advance_prefill()    # first chunk goes out immediately
        else:
            prompt = np.zeros((self.max_seq,), np.int32)
            prompt[:plen] = np.asarray(req.prompt, np.int32)
            self._state = self._admit_fn(
                self._state, jnp.int32(slot), jnp.asarray(prompt),
                jnp.int32(plen), jnp.int32(req.max_new_tokens),
                jnp.float32(req.temperature), key, ptab_row)
            self._finalize_admission(slot, handle, req,
                                     remaining=plen + req.max_new_tokens - 1)
        self.stats["admitted"] += 1
        self.stats["max_inflight"] = max(self.stats["max_inflight"],
                                         self.inflight)

    def _finalize_admission(self, slot: int, handle: int, req: Request,
                            remaining: int):
        self._slot_done_step[slot] = self._steps + remaining
        if req.temperature > 0:
            self._hot.add(handle)

    def _chunk_fn(self, is_last: bool) -> Callable:
        fn = self._chunk_fns.get(is_last)
        if fn is None:
            fn = jax.jit(self._build_chunk(is_last), donate_argnums=(1,))
            self._chunk_fns[is_last] = fn
        return fn

    def _advance_prefill(self):
        """Dispatch ONE prompt chunk for the oldest mid-prefill slot.
        ``step()`` calls this before every decode dispatch, so long
        prompts stream in without ever stalling the decode batch."""
        if not self._prefill_q:
            return
        slot, pp = next(iter(self._prefill_q.items()))
        c = self.prefill_chunk
        lo = pp["next"]
        hi = min(lo + c, pp["plen"])
        tok = np.zeros((c,), np.int32)
        tok[:hi - lo] = pp["tokens"][lo:hi]
        is_last = hi >= pp["plen"]
        fn = self._chunk_fn(is_last)
        with span("serve.prefill_chunk", handle=pp["handle"],
                  is_last=is_last):
            self._state = fn(self.params, self._state, jnp.int32(slot),
                             jnp.asarray(tok), jnp.int32(lo),
                             jnp.int32(hi - lo), jnp.int32(pp["max_new"]),
                             jnp.float32(pp["temp"]), pp["key"])
        self._count_idle(dispatched=True)
        pp["next"] = hi
        self.stats["chunk_dispatches"] += 1
        if is_last:
            self._gen_seen[slot] = 1       # the final chunk's first token
            del self._prefill_q[slot]
            h = pp["handle"]
            self._finalize_admission(
                slot, h, self._requests[h],
                remaining=max(0, pp["max_new"] - 1))

    def _step_callable(self, sample: bool) -> Callable:
        """The ready-to-dispatch decode step: first use per variant loads
        the AOT artifact (or compiles and exports one) - restarts with a
        warm ``aot_dir`` never trace or compile the decode step."""
        fn = self._step_ready.get(sample)
        if fn is None:
            jitted = self._step_sample if sample else self._step_greedy
            facts = {"program": "serve_decode", "model_cfg": self.cfg,
                     "slots": self.slots, "max_seq": self.max_seq,
                     "eos": self.eos_id, "sample": sample,
                     "quantized": is_quantized(self.params),
                     "fused_matmul": self.fused_matmul,
                     "paged": self.paged, "page_size": self.page_size,
                     "num_pages": self.num_pages,
                     "prefill": self._prefill_mode,
                     "prefill_chunk": self.prefill_chunk}
            fn = aot.load_or_compile(jitted, (self.params, self._state),
                                     aot_dir=self._aot_dir, facts=facts,
                                     stats=self.stats)
            self._step_ready[sample] = fn
        return fn

    def compiled_step(self, sample: bool = False):
        """The decode-step executable (greedy or sampling variant) at the
        session's parameter and state shapes, for inspecting its memory
        use and kernels; after a dispatch of that variant it is found
        already compiled in this process."""
        fn = self._step_callable(sample)
        if hasattr(fn, "as_text"):
            return fn
        return fn.lower(self.params, self._state).compile()

    @_session_call
    def step(self):
        """One decode step for every slot (a single device dispatch),
        preceded by at most one chunked-prefill dispatch. While the
        pending queue is non-empty, finished slots are harvested as soon
        as one *can* have finished (plus every ``sync_interval`` steps
        when an EOS may end a request early), so queued requests claim
        slots mid-flight without a per-token host sync."""
        self._advance_prefill()
        fn = self._step_callable(bool(self._hot))
        with span("serve.decode"):
            self._state = fn(self.params, self._state)
        self._count_idle(dispatched=True)
        self.stats["dispatches"] += 1
        self._steps += 1
        if self._pending:
            bound = min((self._slot_done_step[s]
                         for s, h in enumerate(self._slot_handle)
                         if h is not None), default=0)
            if self._steps >= bound or (
                    self.eos_id is not None
                    and self._steps % self.sync_interval == 0):
                self.harvest()

    def _count_idle(self, dispatched: bool):
        """Charge the idle time since ``_idle_t0`` (see _session_call);
        a decode or prefill dispatch ends the idle stretch."""
        if self._idle:
            self.stats["idle_host_ns"] += (time.perf_counter_ns()
                                           - self._idle_t0)
            self._idle = not dispatched

    def _sync(self):
        """Wait for every dispatched step, then copy the slots' control
        rows to the host; count the decode tokens and stamp the first
        tokens that the copy brings."""
        self.stats["syncs"] += 1
        self._count_idle(dispatched=False)
        rows = {k: self._state[k] for k in ("active", "gen", "plen", "out")}
        with span("serve.sync.drain", self.stats, "sync_drain_ns"):
            jax.block_until_ready(rows)
        with span("serve.sync.fetch", self.stats, "fetch_ns"):
            snap = jax.device_get(rows)
        now = time.perf_counter_ns()
        self._idle, self._idle_t0 = True, now
        for s, h in enumerate(self._slot_handle):
            if h is None:
                continue
            g = int(snap["gen"][s])
            self.stats["decode_tokens"] += g - self._gen_seen[s]
            self._gen_seen[s] = g
            if g and h in self._submit_ns:
                self._first_token_s[h] = (now - self._submit_ns.pop(h)) / 1e9
                with span("serve.first_token", handle=h):
                    pass
        return snap

    @_session_call
    def harvest(self) -> List[int]:
        """Collect finished slots into results, free them (returning their
        pages to the pool), and admit queued requests. Returns the handles
        that completed on this call."""
        finished = self._collect_finished()
        self._schedule(allow_harvest=False)
        return finished

    def _forget(self, h: int):
        """Drop a finished request's key and timing."""
        self._req_key.pop(h, None)
        self._submit_ns.pop(h, None)
        self._first_token_s.pop(h, None)

    def _collect_finished(self) -> List[int]:
        with span("serve.harvest"):
            snap = self._sync()
            finished = []
            with span("serve.collect"):
                for s in range(self.slots):
                    h = self._slot_handle[s]
                    if h is None or snap["active"][s] or s in self._prefill_q:
                        continue
                    n = int(snap["gen"][s])
                    req = self._requests.pop(h)   # bounded host state: one
                    reason = "length"             # per in-flight request
                    if n < req.max_new_tokens:
                        reason = ("eos" if self.eos_id is not None and n > 0
                                  and int(snap["out"][s, n - 1]) == self.eos_id
                                  else "cache_full")
                    self._results[h] = Result(
                        tokens=[int(t) for t in snap["out"][s, :n]],
                        prompt_len=int(snap["plen"][s]), handle=h,
                        finish_reason=reason,
                        first_token_s=self._first_token_s.get(h))
                    self._forget(h)
                    self._free_slot(s, release=self.paged)
                    finished.append(h)
        return finished

    @_session_call
    def drain(self, max_steps: Optional[int] = None) -> Dict[int, Result]:
        """Step until every submitted request has finished; returns the
        results not yet delivered as ``{handle: Result}``. Results are
        handed out once (here or via ``result()``) - the session holds no
        per-request state afterwards, so long-running sessions stay
        bounded."""
        outstanding = self.inflight + self.queued
        budget = (max_steps if max_steps is not None
                  else (outstanding + self.slots) * 2 * self.max_seq
                  + self.max_seq)
        while self.inflight or self._pending:
            if budget <= 0:
                raise RuntimeError("drain exceeded its step budget")
            if self._prefill_q:
                # one chunk advances per step: burst exactly through the
                # outstanding chunks, then recompute bounds
                burst = sum(-(-(pp["plen"] - pp["next"])
                              // self.prefill_chunk) or 1
                            for pp in self._prefill_q.values())
            elif self._pending:
                # step() harvests on its own bound-aware cadence
                burst = 8
            elif self.eos_id is not None:
                burst = self.sync_interval  # poll for early finishes
            else:
                # no EOS: slots finish exactly at their known bound - step
                # straight there and harvest once (O(requests) syncs)
                nxt = min(self._slot_done_step[s]
                          for s, h in enumerate(self._slot_handle)
                          if h is not None)
                burst = max(1, nxt - self._steps)
            burst = min(burst, budget)
            for _ in range(burst):
                self.step()
            budget -= burst
            if not self._pending:
                self.harvest()
        out, self._results = self._results, {}
        return out

    def reseed(self, key: jax.Array):
        """Set the base sampling key for subsequently admitted requests
        (restarting the per-submission key sequence, so the same requests
        under the same key reproduce their draws)."""
        self._base_key = _raw_key(key)
        self._admit_seq = 0

    def result(self, handle: int) -> Optional[Result]:
        """Pop a finished request's result (None while still running)."""
        return self._results.pop(handle, None)
