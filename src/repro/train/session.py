"""TrainSession: the async, resumable, prefetching training substrate.

One session owns the training hot loop for BOTH drivers:

  * the distributed path (``repro.dist.step`` ``StepArtifacts`` - any
    ``TrainConfig.mode``): ``TrainSession.from_artifacts(art, batches)``
  * the single-machine path (``repro.core.qadam`` optimizers):
    ``TrainSession.from_optimizer(opt, loss_fn, params, batches)``

replacing the three partially-overlapping drivers that used to exist
(``train.loop.train``'s per-step and scan-chunk branches, the
``opt.multistep`` chunked drivers, and the ad-hoc ``launch.train`` loop -
all now thin shims over this class). The hot loop never stalls on the
host in steady state:

  * **prefetch** - a background host thread pulls numpy batches from the
    generator, stacks scan chunks, and stages them to device
    (``device_put`` with the step's shardings), ``prefetch`` batches deep
    (double-buffered by default). The critical path just picks up
    pre-placed buffers.
  * **device-resident metrics** - per-step losses land in a device ring
    buffer written *inside* the jitted step; the host harvests them with
    one ``device_get`` per log boundary, never per step. ``stats`` counts
    ``dispatches`` and ``syncs`` exactly like ``ServeSession`` so tests
    can assert steady-state training performs ZERO host syncs.
  * **scan chunking** - ``scan_chunk > 1`` compiles K steps into one
    ``lax.scan`` program (state buffers donated), one Python dispatch per
    chunk.
  * **async checkpoints** - at a checkpoint boundary the session snapshots
    the state on device (``jnp.copy`` - an async dispatch, not a sync)
    and hands the snapshot to a writer thread; ``checkpoint/store`` makes
    each write atomic (temp dir + rename) with keep-last-N pruning.
  * **auto-resume** - ``resume(ckpt_dir)`` restores the step counter, the
    optimizer/PRNG state, and the data-stream position (the manifest
    records batches consumed; the fresh generator is fast-forwarded), so
    resumed training is bit-identical to never having stopped
    (``tests/test_train_session.py`` asserts it).
"""
from __future__ import annotations

import dataclasses
import math
import queue
import threading
import time
from typing import Any, Callable, Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import store
from repro.perf import aot
from repro.perf.profiling import span


@dataclasses.dataclass
class SessionConfig:
    log_every: int = 10        # history/log cadence; 0 = never harvest
    eval_every: int = 0
    eval_fn: Optional[Callable] = None   # eval_fn(state) -> loggable
    ckpt_every: int = 0
    ckpt_dir: Optional[str] = None
    ckpt_keep: int = 3         # keep-last-N versioned checkpoints
    ckpt_async: bool = True    # background writer thread
    # repro.comm codec spec for compressed optimizer-moment snapshots
    # (e.g. "uniform_amax:7:w8"); None = raw f32. Master weights and
    # counters always stay exact; see repro.checkpoint.store.
    ckpt_codec: Optional[str] = None
    scan_chunk: int = 1        # K steps per compiled dispatch
    prefetch: int = 2          # staged batches in flight; 0 = synchronous
    check_finite: bool = True  # raise on non-finite harvested loss
    # stats-ring coverage in steps (modes with ``emits_stats``, e.g. the
    # adaptive controller's replan window). The per-step gradient-stats
    # rows stay device-resident for at least this many steps between
    # ``harvest_stats()`` calls; 0 sizes the ring off log_every alone.
    stats_ring: int = 0
    # AOT step artifacts (repro.perf.aot): serialized compiled train
    # steps keyed on (config digest, mesh, mode, codec, arg signature).
    # A warm dir skips trace+lower+compile entirely on restart; None
    # keeps plain jit (which still hits the persistent XLA cache where
    # one is enabled, see repro.perf.cache).
    aot_dir: Optional[str] = None


def stack_batches(batch_list):
    """Stack a list of same-shape batch pytrees along a new leading axis
    (the scan axis)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *batch_list)


def _stack_host(batch_list):
    """Host-side (numpy) stack for the prefetch thread."""
    return jax.tree.map(
        lambda *xs: np.stack([np.asarray(x) for x in xs]), *batch_list)


# ---------------------------------------------------------------------------
# the two unified training programs
# ---------------------------------------------------------------------------

class _DistProgram:
    """Distributed path: wraps ``dist.step.StepArtifacts``. State is the
    chunk-sharded dict (master/m/v/e/count); checkpoints store it as-is
    and restore onto the mesh with the original shardings."""

    def __init__(self, art):
        self.art = art
        self._shardings = None

    def init_state(self, key):
        return self.art.init_state(key)

    def step_fn(self):
        return self.art.step_fn

    def place(self, batch, stacked: bool):
        from repro.dist.step import batch_shardings
        if self._shardings is None:
            self._shardings = batch_shardings(self.art, batch,
                                              stacked=stacked)
        return jax.device_put(batch, self._shardings)

    def to_ckpt(self, state):
        return state

    def from_ckpt(self, tree):
        return tree

    def ckpt_shardings(self, state):
        return jax.tree.map(lambda x: x.sharding, state)

    def ring_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(self.art.mesh, PartitionSpec())

    def step_count(self, state):
        return state["count"]

    def stats_shape(self):
        """``(n_leaves, N_FIELDS)`` when the mode emits per-leaf stats
        rows (adaptive), else None (no stats ring allocated)."""
        from repro.dist.modes import get_mode
        if not get_mode(self.art.config.mode).emits_stats:
            return None
        from repro.adapt import stats as astats
        n_leaves = len(jax.tree_util.tree_leaves(self.art.layout._leaves))
        return (n_leaves, astats.N_FIELDS)

    def step_token(self):
        """Hashable token the compiled-step cache keys on besides k: the
        TrainConfig, so swapping artifacts (a new adaptive bit plan)
        never reuses the previous plan's executable."""
        return self.art.config

    def aot_facts(self):
        """What the compiled step's machine code depends on beyond the
        argument signature: the mode/codec config and mesh geometry."""
        mesh = self.art.mesh
        return {"program": "dist", "config": self.art.config,
                "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
                "n_workers": self.art.n_workers,
                "worker_axes": self.art.worker_axes}


class _SingleProgram:
    """Single-machine path: a ``repro.core.qadam``-style Optimizer plus a
    ``loss_fn(forward_params, batch)``. State is
    ``{"params": ..., "opt": QAdamState}``."""

    def __init__(self, opt, loss_fn):
        self.opt, self.loss_fn = opt, loss_fn

    def init_state(self, params):
        # private copy: the session donates its state buffers into each
        # dispatch, which would delete the caller's params in place.
        # device_put commits the buffers so dispatch #2 (whose inputs are
        # committed jit outputs) reuses dispatch #1's executable.
        params = jax.device_put(jax.tree.map(jnp.copy, params))
        return {"params": params, "opt": jax.device_put(
            self.opt.init(params))}

    def step_fn(self):
        from repro.core.qadam import apply_updates
        opt, loss_fn = self.opt, self.loss_fn

        def step(state, batch):
            p, s = state["params"], state["opt"]
            fp = opt.forward_params(p, s)
            loss, g = jax.value_and_grad(loss_fn)(fp, batch)
            upd, s2 = opt.update(g, s, p)
            return {"params": apply_updates(p, upd), "opt": s2}, \
                {"loss": loss}
        return step

    def place(self, batch, stacked: bool):
        return jax.device_put(batch)

    def to_ckpt(self, state):
        return {"params": state["params"], "opt": state["opt"]._asdict()}

    def from_ckpt(self, tree):
        from repro.core.qadam import QAdamState
        return {"params": tree["params"], "opt": QAdamState(**tree["opt"])}

    def ckpt_shardings(self, state):
        return None

    def ring_sharding(self):
        return jax.local_devices()[0]

    def step_count(self, state):
        return state["opt"].count

    def stats_shape(self):
        return None

    def step_token(self):
        return None

    def aot_facts(self):
        return {"program": "single",
                "opt": type(self.opt).__name__,
                "opt_cfg": getattr(self.opt, "cfg", None),
                "loss_fn": getattr(self.loss_fn, "__qualname__",
                                   repr(self.loss_fn))}


# ---------------------------------------------------------------------------
# background batch prefetcher
# ---------------------------------------------------------------------------

class _Prefetcher:
    """Pulls host batches from the generator and stages them to device on
    a background thread, ``depth`` staged dispatches ahead. Work is
    demand-driven: the session enqueues the exact dispatch sizes it will
    run (so scan chunks group deterministically and the consumed-batch
    count stays exact for resume). ``depth == 0`` degrades to synchronous
    inline pulls."""

    def __init__(self, batches: Iterator, place: Callable, depth: int,
                 stacked: bool):
        self._batches, self._place, self.depth = batches, place, depth
        self._stacked = stacked   # chunked sessions scan a leading axis
        if depth > 0:
            self._plan: queue.Queue = queue.Queue()
            self._out: queue.Queue = queue.Queue(maxsize=depth)
            self._stop = threading.Event()
            self._thread = threading.Thread(
                target=self._fill, name="train-prefetch", daemon=True)
            self._thread.start()

    def _pull(self, k: int):
        if not self._stacked:
            b = next(self._batches)
            return self._place(b, stacked=False)
        # always stack under a scan program - a tail dispatch of k=1
        # still needs its leading scan axis
        b = _stack_host([next(self._batches) for _ in range(k)])
        return self._place(b, stacked=True)

    def _put(self, item) -> bool:
        while not self._stop.is_set():
            try:
                self._out.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _fill(self):
        while not self._stop.is_set():
            try:
                k = self._plan.get(timeout=0.1)
            except queue.Empty:
                continue
            try:
                item = self._pull(k)
            except BaseException as e:  # surfaced on the consumer side
                self._put(e)
                return
            if not self._put(item):
                return

    def request(self, sizes: List[int]):
        if self.depth > 0:
            for k in sizes:
                self._plan.put(k)

    def get(self, k: int):
        if self.depth <= 0:
            return self._pull(k)
        item = self._out.get()
        if isinstance(item, BaseException):
            raise item
        return item

    def close(self):
        if self.depth > 0:
            self._stop.set()
            while True:     # unblock a producer stuck on a full queue
                try:
                    self._out.get_nowait()
                except queue.Empty:
                    break
            self._thread.join(timeout=2.0)


# ---------------------------------------------------------------------------
# the session
# ---------------------------------------------------------------------------

def _abstract(x):
    """Shape/dtype/sharding of an array argument (other leaves as-is)."""
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
    return x


class TrainSession:
    """Async training session over one program (dist or single-machine).

    Typical use::

        sess = TrainSession.from_artifacts(art, batches, cfg)
        sess.resume(cfg.ckpt_dir)      # no-op when no checkpoint exists
        sess.run(1000)                 # 1000 more steps
        sess.close()

    ``run(n)`` executes exactly ``n`` optimizer steps (``n`` batches).
    ``history`` collects ``{"step", "loss"}`` entries at log boundaries
    and ``{"step", "eval"}`` entries at eval boundaries (each eval gets
    its OWN entry pinned to its own step - the old loop misattached evals
    to the most recent log entry). ``stats`` mirrors ``ServeSession``:
    ``dispatches`` (compiled step calls), ``syncs`` (host device_gets on
    the critical path - zero in steady state), ``steps``, ``ckpts``, and
    ``batch_wait_ns``, the host's wait for the prefetcher's next batch.
    The phases are ``repro.perf.profiling`` spans: ``train.batch_wait``,
    ``train.dispatch``, ``train.harvest`` (the loss-ring sync) and
    ``train.checkpoint``.
    """

    def __init__(self, program, batches: Iterator,
                 cfg: Optional[SessionConfig] = None, *,
                 init_arg=None, state=None, log: Callable = print):
        self.cfg = cfg or SessionConfig()
        self._program = program
        self._batches = batches
        self._log = log
        self._state = state if state is not None \
            else program.init_state(init_arg)
        self._ckpt_shardings = program.ckpt_shardings(self._state)
        self.chunk = max(1, self.cfg.scan_chunk)
        for name, every in (("log_every", self.cfg.log_every),
                            ("eval_every", self.cfg.eval_every),
                            ("ckpt_every", self.cfg.ckpt_every)):
            if every and self.chunk > 1 and every % self.chunk:
                raise ValueError(
                    f"{name}={every} must be a multiple of "
                    f"scan_chunk={self.chunk}")
        # device loss ring: sized so every unharvested step since the
        # last log boundary stays resident (one extra chunk of slack for
        # boundary-misaligned tails). Stats-emitting modes share the
        # slot geometry, so the cover also spans the stats window.
        cover = max(self.cfg.log_every, self.cfg.stats_ring, 1)
        self._ring_len = self.chunk * (math.ceil(cover / self.chunk) + 1)
        # committed placement (replicated over the program's mesh): an
        # uncommitted jnp.zeros ring would differ from the (committed)
        # dispatch outputs in the jit cache key and force a silent
        # recompile of the whole step on the second dispatch
        self._ring = jax.device_put(jnp.zeros((self._ring_len,),
                                              jnp.float32),
                                    program.ring_sharding())
        # device stats ring (modes with ``emits_stats``): per-step
        # (n_leaves, N_FIELDS) rows written inside the compiled step,
        # harvested in one sync at replan/log boundaries
        sshape = program.stats_shape()
        self._sring = None if sshape is None else jax.device_put(
            jnp.zeros((self._ring_len,) + tuple(sshape), jnp.float32),
            program.ring_sharding())
        self._slot = 0
        self._segments: List[tuple] = []   # (first_step, slot, k) pending
        self._stat_segments: List[tuple] = []
        self._steps_by_k: Dict[Any, Callable] = {}
        self._step = 0                     # optimizer steps executed
        self._prefetch: Optional[_Prefetcher] = None
        # extra JSON-safe entries merged into every checkpoint manifest
        # next to "batches_consumed" - the adaptive controller keeps the
        # live bit plan + stats-EMA here so --adaptive --resume restores
        # the plan (see repro.adapt.controller.AdaptiveController.resume)
        self.ckpt_extra: Dict[str, Any] = {}
        self.history: List[Dict[str, Any]] = []
        # compilations / aot_loads account for every step executable this
        # session built vs loaded ready-made (tests assert a warm AOT dir
        # means a zero-compilation session)
        self.stats = {"dispatches": 0, "syncs": 0, "steps": 0, "ckpts": 0,
                      "compilations": 0, "aot_loads": 0, "batch_wait_ns": 0}
        self._ckpt_q: Optional[queue.Queue] = None
        self._ckpt_thread: Optional[threading.Thread] = None
        self._ckpt_err: Optional[BaseException] = None
        self._closed = False
        self._last_dispatch = None

    # -- constructors ---------------------------------------------------

    @classmethod
    def from_artifacts(cls, art, batches: Iterator,
                       cfg: Optional[SessionConfig] = None, *, key=None,
                       state=None, log: Callable = print) -> "TrainSession":
        """Distributed session over ``dist.step.make_train_step``
        artifacts (any mode: qadam / dp_adam / terngrad / ef_sgd)."""
        key = key if key is not None else jax.random.PRNGKey(0)
        return cls(_DistProgram(art), batches, cfg, init_arg=key,
                   state=state, log=log)

    @classmethod
    def from_optimizer(cls, opt, loss_fn: Callable, params,
                       batches: Iterator,
                       cfg: Optional[SessionConfig] = None, *,
                       log: Callable = print) -> "TrainSession":
        """Single-machine session over a ``repro.core.qadam``-style
        optimizer and ``loss_fn(forward_params, batch) -> scalar``."""
        return cls(_SingleProgram(opt, loss_fn), batches, cfg,
                   init_arg=params, log=log)

    # -- compiled step plumbing ----------------------------------------

    def _built_step(self, k: int, args: tuple) -> Callable:
        """Compiled ``(state, ring[, sring], slot, batch) -> (state,
        ring[, sring])`` for a k-step dispatch; state and ring buffers
        are donated, the loss (and, for stats-emitting modes, the
        per-leaf stats row) lands in its ring INSIDE the compiled
        program (no host sync).

        The cache key carries the program's ``step_token`` (the dist
        TrainConfig), so a ``swap_artifacts`` plan switch builds a new
        executable instead of reusing the old plan's. With
        ``cfg.aot_dir`` the executable is loaded from / exported to an
        AOT artifact keyed on the program facts + ``args`` signature
        (see ``repro.perf.aot``); ``stats["compilations"]`` vs
        ``stats["aot_loads"]`` records which path ran."""
        ckey = (k, self._program.step_token())
        fn = self._steps_by_k.get(ckey)
        if fn is not None:
            return fn
        step_fn = self._program.step_fn()
        with_stats = self._sring is not None
        if k == 1 and self.chunk == 1:
            def wrapped(state, ring, slot, batch):
                state, metrics = step_fn(state, batch)
                return state, ring.at[slot].set(metrics["loss"])

            def wrapped_s(state, ring, sring, slot, batch):
                state, metrics = step_fn(state, batch)
                sring = jax.lax.dynamic_update_slice(
                    sring, metrics["gstats"][None], (slot, 0, 0))
                return state, ring.at[slot].set(metrics["loss"]), sring
        else:
            def wrapped(state, ring, slot, batches):
                def body(s, b):
                    s2, m = step_fn(s, b)
                    return s2, m["loss"]
                state, losses = jax.lax.scan(body, state, batches)
                return state, jax.lax.dynamic_update_slice(
                    ring, losses, (slot,))

            def wrapped_s(state, ring, sring, slot, batches):
                def body(s, b):
                    s2, m = step_fn(s, b)
                    return s2, (m["loss"], m["gstats"])
                state, (losses, rows) = jax.lax.scan(body, state, batches)
                ring = jax.lax.dynamic_update_slice(ring, losses, (slot,))
                sring = jax.lax.dynamic_update_slice(
                    sring, rows, (slot, 0, 0))
                return state, ring, sring
        # pin the output shardings to the input state's: on small meshes
        # GSPMD canonicalizes size-1-axis specs to replicated on the way
        # out, and the sharding flip would silently recompile the whole
        # step on the SECOND dispatch
        state_sh = jax.tree.map(lambda x: x.sharding, self._state)
        if with_stats:
            out_sh = (state_sh, self._ring.sharding, self._sring.sharding)
            jitted = jax.jit(wrapped_s, donate_argnums=(0, 1, 2),
                             out_shardings=out_sh)
        else:
            out_sh = (state_sh, self._ring.sharding)
            jitted = jax.jit(wrapped, donate_argnums=(0, 1),
                             out_shardings=out_sh)
        facts = dict(self._program.aot_facts(), k=k, chunk=self.chunk,
                     ring_len=self._ring_len)
        fn = aot.load_or_compile(jitted, args, aot_dir=self.cfg.aot_dir,
                                 facts=facts, stats=self.stats)
        self._steps_by_k[ckey] = fn
        return fn

    def _sync(self, x):
        self.stats["syncs"] += 1
        return jax.device_get(x)

    # -- loss ring ------------------------------------------------------

    @staticmethod
    def _push_segment(segments: List[tuple], first_step: int, slot: int,
                      k: int) -> List[tuple]:
        lo, hi = slot, slot + k
        segments = [s for s in segments
                    if s[1] + s[2] <= lo or s[1] >= hi]
        segments.append((first_step, slot, k))
        return segments

    def _record_segment(self, first_step: int, slot: int, k: int):
        self._segments = self._push_segment(self._segments, first_step,
                                            slot, k)
        if self._sring is not None:
            self._stat_segments = self._push_segment(
                self._stat_segments, first_step, slot, k)

    def harvest_losses(self) -> List[tuple]:
        """Pull every still-resident per-step loss off the device in ONE
        host sync; returns ``[(step, loss), ...]`` and clears the pending
        ring segments."""
        if not self._segments:
            return []
        with span("train.harvest"):
            vals = self._sync(self._ring)
        out = []
        for first, slot, k in self._segments:
            for j in range(k):
                out.append((first + j, float(vals[slot + j])))
        self._segments.clear()
        out.sort()
        if self.cfg.check_finite:
            for s, v in out:
                if not np.isfinite(v):
                    raise FloatingPointError(f"loss diverged at step {s}")
        return out

    def harvest_stats(self) -> List[tuple]:
        """Pull every still-resident per-step gradient-stats row off the
        device in ONE host sync; returns ``[(step, (n_leaves, N_FIELDS)
        ndarray), ...]`` sorted by step and clears the pending stats
        segments. Empty for modes without ``emits_stats``."""
        if self._sring is None or not self._stat_segments:
            return []
        vals = self._sync(self._sring)
        out = []
        for first, slot, k in self._stat_segments:
            for j in range(k):
                out.append((first + j, np.asarray(vals[slot + j])))
        self._stat_segments.clear()
        out.sort(key=lambda t: t[0])
        return out

    # -- adaptive replans ----------------------------------------------

    def swap_artifacts(self, art):
        """Swap in new ``StepArtifacts`` (same model/mesh/state layout,
        different TrainConfig - the adaptive controller's new bit plan)
        at a dispatch boundary. The live state buffers carry over
        untouched - masters, moments and EF residuals continue bitwise
        from the previous plan - and the next dispatch compiles (or
        AOT-loads) the new plan's executable under its own cache key."""
        if not isinstance(self._program, _DistProgram):
            raise ValueError("swap_artifacts requires a dist session")
        old = self._program.art
        if (art.mesh is not old.mesh or art.n_workers != old.n_workers
                or art.worker_axes != old.worker_axes):
            raise ValueError("swap_artifacts cannot change mesh geometry")
        self._program.art = art

    # -- checkpointing --------------------------------------------------

    def _ensure_writer(self):
        if self._ckpt_thread is not None:
            return
        self._ckpt_q = queue.Queue()

        def writer():
            while True:
                item = self._ckpt_q.get()
                try:
                    if item is None:
                        return
                    tree, step, extra = item
                    store.save(self.cfg.ckpt_dir, tree, step=step,
                               keep=self.cfg.ckpt_keep, extra=extra,
                               codec=self.cfg.ckpt_codec)
                except BaseException as e:   # re-raised on the main thread
                    self._ckpt_err = e
                finally:
                    self._ckpt_q.task_done()

        self._ckpt_thread = threading.Thread(
            target=writer, name="train-ckpt-writer", daemon=True)
        self._ckpt_thread.start()

    def checkpoint(self, step: Optional[int] = None):
        """Snapshot the live state on device (async copy - the hot loop
        keeps going) and write it out. With ``cfg.ckpt_async`` the
        npz/manifest write (including the device->host transfer) happens
        on the writer thread, off the critical path."""
        if self._ckpt_err is not None:
            err, self._ckpt_err = self._ckpt_err, None
            raise err
        if not self.cfg.ckpt_dir:
            raise ValueError("SessionConfig.ckpt_dir is not set")
        step = self._step if step is None else step
        with span("train.checkpoint", step=step):
            # device-side copy: the live buffers are donated into the next
            # dispatch, the snapshot stays valid for the writer
            snap = jax.tree.map(jnp.copy, self._state)
            tree = self._program.to_ckpt(snap)
            extra = {"batches_consumed": self._step, **self.ckpt_extra}
            self.stats["ckpts"] += 1
            if self.cfg.ckpt_async:
                self._ensure_writer()
                self._ckpt_q.put((tree, step, extra))
            else:
                store.save(self.cfg.ckpt_dir, tree, step=step,
                           keep=self.cfg.ckpt_keep, extra=extra,
                           codec=self.cfg.ckpt_codec)

    def wait_for_checkpoints(self):
        """Block until every queued async checkpoint hit disk."""
        if self._ckpt_q is not None:
            self._ckpt_q.join()
        if self._ckpt_err is not None:
            err, self._ckpt_err = self._ckpt_err, None
            raise err

    def resume(self, ckpt_dir: Optional[str] = None,
               step: Optional[int] = None) -> int:
        """Restore the latest (or given) checkpoint under ``ckpt_dir``
        (default ``cfg.ckpt_dir``): state, step counter, and data-stream
        position - the generator is fast-forwarded past every batch the
        checkpointed run consumed, so continuing is bit-identical to an
        uninterrupted run. Returns the restored step (0 when no
        checkpoint exists). Must be called before the first ``run()``."""
        if self._step:
            raise RuntimeError("resume() must precede run()")
        d = ckpt_dir or self.cfg.ckpt_dir
        if not d:
            raise ValueError("no checkpoint directory given")
        found = store.latest_step(d) if step is None else step
        if found is None:
            return 0
        like = self._program.to_ckpt(self._state)
        tree = store.restore(d, like, shardings=self._ckpt_shardings,
                             step=found)
        self._state = self._program.from_ckpt(tree)
        extra = store.read_extra(d, step=found)
        consumed = int(extra.get("batches_consumed", found))
        for _ in range(consumed):
            next(self._batches)
        self._step = consumed
        return found

    # -- the hot loop ---------------------------------------------------

    def _boundary_hits(self, i0: int, k: int, every: int) -> List[int]:
        if every <= 0:
            return []
        return [s for s in range(i0 + 1, i0 + k + 1) if s % every == 0]

    def run(self, steps: int) -> List[Dict[str, Any]]:
        """Run exactly ``steps`` more optimizer steps; returns (the tail
        of) ``history``. Steady-state dispatches perform zero host
        syncs; the host only reads the device at log/eval boundaries."""
        if self._closed:
            raise RuntimeError("session is closed")
        if steps <= 0:
            return []
        if self._prefetch is None:
            self._prefetch = _Prefetcher(self._batches,
                                         self._program.place,
                                         self.cfg.prefetch,
                                         stacked=self.chunk > 1)
        q, r = divmod(steps, self.chunk)
        plan = [self.chunk] * q + ([r] if r else [])
        self._prefetch.request(plan)
        hist_start = len(self.history)
        run_start = self._step
        t0 = time.perf_counter()
        for di, k in enumerate(plan):
            with span("train.batch_wait", self.stats, "batch_wait_ns"):
                batch = self._prefetch.get(k)
            if self._slot + k > self._ring_len:
                self._slot = 0
            sl, i0 = self._slot, self._step
            if self._sring is None:
                args = (self._state, self._ring, sl, batch)
            else:
                args = (self._state, self._ring, self._sring, sl, batch)
            self._last_dispatch = (k, batch)
            with span("train.dispatch", step=i0 + 1):
                out = self._built_step(k, args)(*args)
            if self._sring is None:
                self._state, self._ring = out
            else:
                self._state, self._ring, self._sring = out
            self._record_segment(i0 + 1, sl, k)
            self._slot += k
            self._step += k
            self.stats["dispatches"] += 1
            self.stats["steps"] += k
            log_hits = self._boundary_hits(i0, k, self.cfg.log_every)
            last = di == len(plan) - 1
            if self.cfg.log_every > 0 and (log_hits or di == 0 or last):
                want = set(log_hits)
                if di == 0 or last:
                    want.add(i0 + k)
                dt = time.perf_counter() - t0
                rate = dt / max(1, self._step - run_start)
                for s, v in self.harvest_losses():
                    if s in want:
                        self.history.append({"step": s, "loss": v})
                        self._log(f"step {s:5d}  loss {v:.4f}  "
                                  f"({rate:.2f}s/step)")
            # eval/ckpt cadences fire per boundary crossed, but are
            # pinned to the TRUE post-dispatch step (self._step): with a
            # tail-misaligned run() a boundary can fall mid-dispatch, and
            # labeling post-dispatch state with the earlier boundary step
            # would break the bit-identical resume contract. Cadences are
            # validated as chunk multiples, so at most one hit each.
            if self.cfg.eval_fn is not None and \
                    self._boundary_hits(i0, k, self.cfg.eval_every):
                ev = self.cfg.eval_fn(self._state)
                self.history.append({"step": self._step, "eval": ev})
                self._log(f"  eval @{self._step}: {ev}")
            if self.cfg.ckpt_every and self.cfg.ckpt_dir and \
                    self._boundary_hits(i0, k, self.cfg.ckpt_every):
                self.checkpoint()
        return self.history[hist_start:]

    # -- accessors / lifecycle ------------------------------------------

    @property
    def state(self):
        """The live train-state pytree (valid between dispatches)."""
        return self._state

    def compiled_step(self):
        """The executable of the most recent ``run`` dispatch, for
        inspecting its memory use and kernels. Lowering at the recorded
        argument shapes finds the program already compiled in this
        process, so nothing is compiled again."""
        if self._last_dispatch is None:
            raise RuntimeError("no step has been dispatched yet")
        k, batch = self._last_dispatch
        # the live state and rings have the donated inputs' shapes
        rings = ((self._ring,) if self._sring is None
                 else (self._ring, self._sring))
        sig = jax.tree.map(_abstract,
                           (self._state, *rings, self._slot, batch))
        fn = self._built_step(k, sig)
        return fn if hasattr(fn, "as_text") else fn.lower(*sig).compile()

    @property
    def step(self) -> int:
        return self._step

    def close(self):
        """Stop the prefetch thread and flush pending checkpoints."""
        if self._closed:
            return
        self._closed = True
        if self._prefetch is not None:
            self._prefetch.close()
        self.wait_for_checkpoints()
        if self._ckpt_q is not None:
            self._ckpt_q.put(None)
            self._ckpt_thread.join(timeout=5.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# ---------------------------------------------------------------------------
# single-machine chunked step builders (canonical home; ``opt.multistep``
# re-exports these as the compat surface)
# ---------------------------------------------------------------------------

def make_chunked_update(opt, donate: bool = True) -> Callable:
    """K pure optimizer updates per call: ``fn(params, state, gstack)``
    with ``gstack`` a gradient pytree stacked over a leading step axis.
    Returns (params, state)."""
    from repro.core.qadam import apply_updates

    def chunk(params, state, gstack):
        def body(carry, g):
            p, s = carry
            upd, s2 = opt.update(g, s, p)
            return (apply_updates(p, upd), s2), None
        (p2, s2), _ = jax.lax.scan(body, (params, state), gstack)
        return p2, s2
    return jax.jit(chunk, donate_argnums=(0, 1) if donate else ())


def make_chunked_train_step(opt, loss_fn: Callable,
                            donate: bool = True) -> Callable:
    """K full steps (Q_x forward params -> grad -> engine update -> apply)
    per call: ``fn(params, state, batches)`` with ``batches`` a batch
    pytree stacked over a leading step axis. Returns
    (params, state, per-step losses)."""
    from repro.core.qadam import apply_updates

    def chunk(params, state, batches):
        def body(carry, batch):
            p, s = carry
            fp = opt.forward_params(p, s)
            loss, g = jax.value_and_grad(loss_fn)(fp, batch)
            upd, s2 = opt.update(g, s, p)
            return (apply_updates(p, upd), s2), loss
        (p2, s2), losses = jax.lax.scan(body, (params, state), batches)
        return p2, s2, losses
    return jax.jit(chunk, donate_argnums=(0, 1) if donate else ())
