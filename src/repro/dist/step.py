"""Distributed QAdam-EF train step (Algorithms 2+3): quantized parameter
server over the mesh's worker axes, context/model parallelism over its
model axis.

This module owns the mode-independent worker-step TEMPLATE:

  1. weight broadcast: every server quantizes its chunk with Q_x, packed
     8-bit codes are all-gathered over the worker axes, each worker
     reassembles Q_x(x_t) for its model shard (small leaves ride f32).
  2. forward/backward at Q_x(x_t) (Assumption 3), sequence sharded over
     the model axis, per-layer FSDP weight gather; each worker gets the
     gradient of *its own* mean loss.
  3. per-worker engine update (``repro.opt.engine``; fused Pallas on TPU).
  4. update exchange: each mode's wire (packed codes all-to-all for the
     quantized modes) so each server receives all workers' updates for
     its chunk; it averages the dequantized deltas into its master chunk.

Steps 3-4 are the per-mode plugins in ``repro.dist.modes`` ("qadam" - the
paper, "dp_adam", "terngrad", "ef_sgd"); the serve step lives in
``repro.dist.serve``.

State layout (matches ``repro.launch.dryrun`` and the equivalence tests):
every leaf of the train state is *chunked* - shape
``worker_sizes + (n_model_shards, X)`` sharded
``P(*worker_axes, "model", None)`` - so each device holds a 1-D slice:

  * ``master``: worker w's f32 chunk of model-shard m (X = chunk size c).
    Worker w is the Algorithm-2 "server" for its chunk.
  * ``m, v, e``: per-worker Adam moments / EF residual. Workers see
    different gradients, so each keeps moments for the *whole* shard
    (X = shard numel); in ``dp_adam`` mode gradients are averaged first
    and the moments are chunk-sharded like ``master`` (ZeRO-style).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.shard_map import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import comm
from repro.adapt import stats as astats
from repro.core.qadam import QAdamConfig, _alpha_t, _theta_t
from repro.dist import sharding as SH
from repro.dist import collectives as C
from repro.dist import topology as T
from repro.dist.modes import WorkerCtx, get_mode
from repro.models.layers import ShardCtx

MODEL_AXIS = "model"


# ---------------------------------------------------------------------------
# configs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TrainConfig:
    alpha: float = 1e-3
    beta: float = 0.99
    theta: float = 0.999
    eps: float = 1e-5
    schedule: str = "constant"          # "sqrt" | "constant" | "halving:K"
    grad_k: Optional[int] = 6           # log-grid k_g; None = f32 wire
    weight_k: Optional[int] = None      # uniform k_x; None = f32 broadcast
    weight_absolute: bool = True        # paper's absolute [-0.5,0.5] grid
    weight_q_min_numel: int = 2 ** 14   # small leaves skip Q_x (biases/norms)
    error_feedback: bool = True
    mode: str = "qadam"                 # any repro.dist.modes name
    # per-leaf wire plan (adaptive mode): one registry codec spec per
    # state leaf in metas_flat order, e.g. ("log:6", "blockwise:256",
    # ...). TrainConfig is a static jit argument and rides in the AOT
    # facts, so every distinct plan is its own compiled/cached step.
    bit_plan: Optional[Tuple[str, ...]] = None
    # update-exchange bucketing: leaves are fenced (optimization_barrier)
    # and dispatched to the wire in buckets of about this many payload
    # bytes instead of behind one whole-tree end-of-step barrier, so XLA
    # may overlap an early bucket's quantized exchange with the rest of
    # the backward. <= 0 restores the single whole-tree fence.
    exchange_bucket_bytes: int = 4 << 20
    worker_axes: Tuple[str, ...] = ("pod", "data")
    # link-tier topology (repro.dist.topology): FlatTopology keeps
    # today's single-tier wire; HierarchicalTopology(nodes, d) runs an
    # fp intra-node gradient reduce and keeps the quantized+EF exchange
    # on the node axis only. A frozen dataclass field, so every
    # topology is its own jit/AOT cache key like any config change.
    topology: T.Topology = T.FlatTopology()
    batch_dim_shardable: bool = True
    model_gather_quant: Optional[int] = None  # int8 FSDP gather bits
    fused_kernels: Optional[bool] = None      # None = auto (TPU only)
    seed: int = 0

    @property
    def engine_backend(self) -> Optional[str]:
        """repro.opt.engine backend for the update core."""
        if self.fused_kernels is None:
            return None
        return "pallas" if self.fused_kernels else "jnp"


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    weight_k: Optional[int] = None      # int8 weight-gather bits
    weight_absolute: bool = False
    worker_axes: Tuple[str, ...] = ("pod", "data")
    batch_dim_shardable: bool = True


@dataclasses.dataclass(frozen=True)
class LeafMeta:
    """Per-leaf wire geometry. `shp` is the local model-shard shape,
    `numel` its element count, `c` the per-worker chunk length."""
    shp: Tuple[int, ...]
    c: int
    numel: int
    dim: int
    stacked: bool
    shape: Tuple[int, ...]

    @property
    def full_numel(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1


def _leaf_meta(layout: SH.Layout, n_workers: int):
    """Tree of LeafMeta mirroring the parameter tree."""
    def one(l, d, s):
        shp = SH.local_shard_shape(tuple(l.shape), d, s, layout.n_shards)
        n = int(np.prod(shp)) if shp else 1
        return LeafMeta(shp=shp, c=SH.chunk_size(n, n_workers), numel=n,
                        dim=d, stacked=s, shape=tuple(l.shape))
    return jax.tree.map(one, layout._leaves, layout.dims, layout.stacked)


class StepArtifacts(NamedTuple):
    init_state: Callable
    step_fn: Callable
    layout: SH.Layout
    n_workers: int
    worker_axes: Tuple[str, ...]
    mesh: Any
    config: Any
    # resolved topology (topology.Tiers); None on artifacts built by
    # older callers - accounting treats that as flat.
    tiers: Any = None


def weight_wire_codec(tc, full_numel: int) -> comm.Codec:
    """The weight-broadcast channel's codec for one leaf - THE source of
    truth for what moves on channel 2 (``comm_bytes_per_step`` and the
    dryrun accounting read the same function). Small / unquantized
    leaves ride f32 (identity)."""
    if tc.weight_k is None or full_numel < tc.weight_q_min_numel:
        return comm.IdentityCodec()
    return comm.uniform_wire_codec(tc.weight_k, tc.weight_absolute)


def _exchange_buckets(metas_flat, mode, tc, n_workers, tiers=None):
    """Group consecutive leaves into wire buckets of about
    ``tc.exchange_bucket_bytes`` payload each. Each bucket gets its own
    gradient fence, so the first bucket's quantized exchange can be
    scheduled while the backward of later leaves is still running;
    ``<= 0`` collapses to one whole-tree bucket (the pre-bucketing
    end-of-step barrier). Bucket fill counts the payload that actually
    crosses the exchange (inter) tier, so hierarchical topologies pack
    ~``devices_per_node`` times more leaves per dispatch."""
    if tc.exchange_bucket_bytes <= 0 or len(metas_flat) <= 1:
        return [list(range(len(metas_flat)))]
    buckets, cur, cur_bytes = [], [], 0
    for i, meta in enumerate(metas_flat):
        cur.append(i)
        cur_bytes += mode.leaf_tier_nbytes(tc, i, meta.c, meta.numel,
                                           n_workers, tiers)["inter"]
        if cur_bytes >= tc.exchange_bucket_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
    if cur:
        buckets.append(cur)
    return buckets


def state_template(art: StepArtifacts):
    """Sharded ShapeDtypeStructs of ``art.init_state``'s output - the one
    description of the chunked state layout (master/m/v/e plus the
    mode's ``extra_state``) that dryrun lowering, resume plumbing and
    tests consume instead of hand-reconstructing shapes."""
    tc = art.config
    mode = get_mode(tc.mode)
    ms = dict(zip(art.mesh.axis_names, art.mesh.devices.shape))
    Nm = int(ms.get(MODEL_AXIS, 1))
    wdims = tuple(ms[a] for a in art.worker_axes)
    spec = P(*art.worker_axes, MODEL_AXIS, None) if MODEL_AXIS in ms \
        else P(*art.worker_axes, None, None)
    sh = NamedSharding(art.mesh, spec)
    metas = _leaf_meta(art.layout, art.n_workers)

    def sds(meta, x):
        return jax.ShapeDtypeStruct(wdims + (Nm, x), jnp.float32,
                                    sharding=sh)

    def tree(xfn):
        return jax.tree.map(lambda _, m: sds(m, xfn(m)),
                            art.layout._leaves, metas)

    moment_x = (lambda m: m.c) if mode.chunk_sharded_moments \
        else (lambda m: m.numel)
    state = {"master": tree(lambda m: m.c)}
    for k in ("m", "v", "e"):
        state[k] = tree(moment_x)
    for k in mode.extra_state:
        state[k] = tree(lambda m: m.c)
    state["count"] = jax.ShapeDtypeStruct(
        (), jnp.int32, sharding=NamedSharding(art.mesh, P()))
    return state


def batch_shardings(art: StepArtifacts, batch, stacked: bool = False):
    """NamedShardings the train step expects for a (host numpy) batch -
    lets a prefetcher stage batches onto the mesh ahead of dispatch so
    the step consumes pre-placed buffers. ``stacked``: the batch carries
    a leading scan-chunk axis (replicated)."""
    ex = jax.tree.map(lambda x: x[0], batch) if stacked else batch
    ms = dict(zip(art.mesh.axis_names, art.mesh.devices.shape))
    Nm = int(ms.get(MODEL_AXIS, 1))
    Wb, cp = _batch_geometry(ex, Nm, art.worker_axes, art.n_workers,
                             art.config.batch_dim_shardable)
    specs = _batch_specs(ex, Wb, cp)
    if stacked:
        specs = {k: P(None, *s) for k, s in specs.items()}
    return {k: NamedSharding(art.mesh, s) for k, s in specs.items()}


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------

def _dims_by_path(layout: SH.Layout):
    flat = jax.tree_util.tree_flatten_with_path(layout.dims)[0]
    dims = {SH._path_keys(p): d for p, d in flat}
    st = {SH._path_keys(p): s for p, s in
          jax.tree_util.tree_flatten_with_path(layout.stacked)[0]}
    return dims, st


def _make_param_gather(layout: SH.Layout, Nm: int, expert_local: bool,
                       quant_k: Optional[int], quant_absolute: bool,
                       quant_min_numel: int = 0,
                       stacked_at_static: bool = False):
    """ctx.param_gather hook: reconstruct full weights from model-axis
    shards, leaving expert tensors local when the MoE layer is sharded.

    ``stacked_at_static`` (serve): gather the scan-stacked ``blocks``
    leaves whole during the "static" pass - the Q_x scale is then one
    per-shard amax across all layers of a leaf (matching the serve
    equivalence reference), and the per-layer gather inside the scan
    becomes a no-op. Training keeps the per-layer (FSDP-style) gather.
    """
    dims_by_path, stacked_by_path = _dims_by_path(layout)

    def gather_leaf(dim: int, stacked: bool, leaf):
        if dim == SH.REPLICATED:
            return leaf
        ax = SH.axis_of(dim, stacked)
        if dim == SH.EXPERT_MARKER and expert_local:
            if quant_k is not None and leaf.size >= quant_min_numel:
                # keep resident experts on the same Q_x wire semantics
                return C.quantized_gather_shard(leaf, ax, 1, quant_k,
                                                quant_absolute)
            return leaf
        if quant_k is not None and leaf.size * Nm >= quant_min_numel:
            return C.quantized_gather_shard(leaf, ax, Nm, quant_k,
                                            quant_absolute)
        return C.gather_shard(leaf, ax, Nm)

    def gather(subtree, kind: str):
        if Nm <= 1 and quant_k is None:
            return subtree
        if stacked_at_static and kind != "static":
            return subtree  # already gathered whole in the static pass

        def one(path, leaf):
            keys = SH._path_keys(path)
            if kind == "static":
                if keys and keys[0] in SH._STACKED_KEYS:
                    if not stacked_at_static:
                        return leaf  # per-layer gather inside the scan
                    return gather_leaf(dims_by_path[keys],
                                       stacked_by_path[keys], leaf)
                full = keys
            else:
                full = (kind,) + keys
            return gather_leaf(dims_by_path[full], False, leaf)

        return jax.tree_util.tree_map_with_path(one, subtree)

    return gather


def _batch_geometry(batch, Nm: int, worker_axes, n_workers: int,
                    shardable: bool):
    """Static decisions: shard batch over workers / sequence over model."""
    if "tokens" in batch:
        B, S = batch["tokens"].shape
    else:
        B, S = batch["embeds"].shape[:2]
    Wb = worker_axes if (shardable and worker_axes
                         and B % n_workers == 0) else ()
    cp = Nm > 1 and S % Nm == 0
    if "audio" in batch and batch["audio"].shape[1] % Nm != 0:
        cp = False
    return Wb, cp


def _batch_specs(batch, Wb, cp):
    b0 = Wb if Wb else None
    sa = MODEL_AXIS if cp else None
    specs = {}
    for k, v in batch.items():
        ent = [None] * v.ndim
        ent[0] = b0
        if v.ndim >= 2:
            ent[1] = sa
        specs[k] = P(*ent)
    return specs


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------

def make_train_step(model, mesh, tc: TrainConfig) -> StepArtifacts:
    ms = dict(zip(mesh.axis_names, mesh.devices.shape))
    worker_axes, wsizes, n_workers = SH.worker_info(mesh, tc.worker_axes)
    Nm = int(ms.get(MODEL_AXIS, 1))
    model_in_mesh = MODEL_AXIS in ms

    pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    layout = SH.build_layout(pshapes, Nm)
    metas = _leaf_meta(layout, n_workers)
    qcfg = QAdamConfig(alpha=tc.alpha, beta=tc.beta, theta=tc.theta,
                       eps=tc.eps, schedule=tc.schedule)
    mode = get_mode(tc.mode)
    topo = tc.topology if tc.topology is not None else T.FlatTopology()
    # non-tiered modes (dp_adam) run flat collectives on any topology;
    # resolving their tiers flat keeps the updater/accounting honest.
    tiers = topo.tiers(worker_axes, wsizes) if mode.tiered \
        else T.flat_tiers(worker_axes, wsizes)
    updater = mode.make_updater(tc, WorkerCtx(
        worker_axes=worker_axes, wsizes=wsizes, n_workers=n_workers,
        backend=tc.engine_backend, tiers=tiers))

    treedef = jax.tree_util.tree_structure(layout._leaves)
    metas_flat = treedef.flatten_up_to(metas)
    if tc.bit_plan is not None and len(tc.bit_plan) != len(metas_flat):
        raise ValueError(
            f"bit_plan has {len(tc.bit_plan)} specs for "
            f"{len(metas_flat)} state leaves")
    buckets = _exchange_buckets(metas_flat, mode, tc, n_workers, tiers)
    chunk_sharded = mode.chunk_sharded_moments  # moments chunked vs full-shard
    state_spec = P(*worker_axes, MODEL_AXIS, None) if model_in_mesh \
        else P(*worker_axes, None, None)

    def _state_x(meta):  # per-leaf trailing dim of m/v/e
        return meta.c if chunk_sharded else meta.numel

    # ---------------- init ----------------
    def init_state(key):
        params = model.init(key)
        p_flat = treedef.flatten_up_to(params)
        sh = NamedSharding(mesh, state_spec)
        master, zs, chunk_zs = [], [], []
        for p, meta in zip(p_flat, metas_flat):
            rows = [SH.flatten_pad(
                SH.shard_of(p, meta.dim, meta.stacked, Nm, mi)
                .reshape(-1).astype(jnp.float32), n_workers)
                for mi in range(Nm)]
            arr = jnp.stack(rows, axis=1)            # (n_workers, Nm, c)
            master.append(jax.device_put(
                arr.reshape(wsizes + (Nm, meta.c)), sh))
            # m/v/e exist for every mode even where unused (terngrad
            # reads none, ef_sgd skips v): the chunked state layout is a
            # fixed contract with repro.launch.dryrun (state_template)
            # and with checkpoint round-trips.
            zs.append(jax.device_put(
                jnp.zeros(wsizes + (Nm, _state_x(meta)), jnp.float32), sh))
            chunk_zs.append(jax.device_put(
                jnp.zeros(wsizes + (Nm, meta.c), jnp.float32), sh))
        mtree = jax.tree_util.tree_unflatten(treedef, master)
        ztree = jax.tree_util.tree_unflatten(treedef, zs)
        ctree = jax.tree_util.tree_unflatten(treedef, chunk_zs)
        zero = lambda t: jax.tree.map(jnp.copy, t)
        state = {"master": mtree, "m": zero(ztree), "v": zero(ztree),
                 "e": zero(ztree),
                 "count": jax.device_put(jnp.zeros((), jnp.int32),
                                         NamedSharding(mesh, P()))}
        for k in mode.extra_state:   # efadam: server broadcast residual
            state[k] = zero(ctree)
        return state

    # ---------------- weight-broadcast channel ----------------
    def chunks_to_shard(chunk, meta, es=None):
        """My master chunk -> full f32 shard over the codec wire.

        With ``es`` (the ``broadcast_ef`` modes), the server sends
        ``Q(chunk + es)`` and keeps the residual; the returned es' feeds
        the next step. Identity-codec leaves broadcast f32 rows (their
        residual is exactly zero)."""
        codec = weight_wire_codec(tc, meta.full_numel)
        if isinstance(codec, comm.IdentityCodec):
            rows = C.gather_rows_tiered(chunk, tiers)
            return SH.unflatten_chunked(rows, meta.shp), es
        send = chunk if es is None else chunk + es
        scale = codec.compute_scale(send)
        payload, e_new = comm.encode_rows_ef(send, scale, codec, 1,
                                             backend=tc.engine_backend)
        rows = C.broadcast_decode_tiered(payload[0], scale, codec, meta.c,
                                         tiers, backend=tc.engine_backend)
        return (SH.unflatten_chunked(rows, meta.shp),
                e_new if es is not None else None)

    # ---------------- the sharded step ----------------
    def _impl(state, batch, cp: bool):
        masters = [x.reshape(m.c) for x, m in
                   zip(treedef.flatten_up_to(state["master"]), metas_flat)]
        ms_ = [x.reshape(_state_x(m)) for x, m in
               zip(treedef.flatten_up_to(state["m"]), metas_flat)]
        vs_ = [x.reshape(_state_x(m)) for x, m in
               zip(treedef.flatten_up_to(state["v"]), metas_flat)]
        es_ = [x.reshape(_state_x(m)) for x, m in
               zip(treedef.flatten_up_to(state["e"]), metas_flat)]
        t = state["count"] + 1
        a_t = _alpha_t(qcfg, t)
        th_t = _theta_t(qcfg, t)

        # 1. weight broadcast: chunks -> Q_x(x_t) shards. broadcast_ef
        # modes thread the per-chunk server residual through the codec.
        # The ``qadam.*`` scopes name the step's phases in every op's
        # metadata (the backward shows as ``transpose(jvp(qadam.loss))``).
        with jax.named_scope("qadam.broadcast"):
            if mode.broadcast_ef:
                srv = [x.reshape(m.c) for x, m in
                       zip(treedef.flatten_up_to(state["es"]), metas_flat)]
                pairs = [chunks_to_shard(ch, m, es)
                         for ch, m, es in zip(masters, metas_flat, srv)]
                new_es = [p[1] for p in pairs]
            else:
                pairs = [chunks_to_shard(ch, m)
                         for ch, m in zip(masters, metas_flat)]
                new_es = None
        xs = [p[0] for p in pairs]
        # fence the forward/backward off from the channel/update code so
        # XLA compiles it like a standalone value_and_grad: its float
        # rounding then matches the single-machine reference path instead
        # of shifting with unrelated fusion decisions.
        xs = jax.lax.optimization_barrier(xs)
        x_tree = jax.tree_util.tree_unflatten(treedef, xs)

        # 2. forward/backward at Q_x(x_t)
        ctx = ShardCtx(
            cp_axis=MODEL_AXIS if cp else None,
            cp_size=Nm if cp else 1, dp_axes=worker_axes,
            param_gather=_make_param_gather(
                layout, Nm, expert_local=cp,
                quant_k=tc.model_gather_quant, quant_absolute=False,
                quant_min_numel=2 ** 14))
        maxes = (MODEL_AXIS,) if model_in_mesh and Nm > 1 else ()
        all_axes = worker_axes + maxes

        def lfn(pt):
            with jax.named_scope("qadam.loss"):
                s, nt = model.loss(pt, batch, ctx)
            if tc.mode == "dp_adam":
                # local sum / global count; the weight-gather transpose
                # already sums model-axis contributions, the worker-axis
                # average happens on chunk rows in the dp_adam updater.
                gden = jax.lax.psum(nt, all_axes) if all_axes else nt
                return s / gden, (s, nt)
            # per-worker mean loss (Algorithm 2). psum's transpose is psum,
            # so a psum'd objective over-counts cotangents by the axis
            # size - divide it back out (value is unused, only grads).
            sw = jax.lax.psum(s, maxes) if maxes else s
            nw_ = jax.lax.psum(nt, maxes) if maxes else nt
            return sw / nw_ / (Nm if maxes else 1), (s, nt)

        grads, (s_loc, n_loc) = jax.grad(lfn, has_aux=True)(x_tree)
        loss = (jax.lax.psum(s_loc, all_axes) /
                jax.lax.psum(n_loc, all_axes)) if all_axes \
            else s_loc / n_loc

        gs = []
        for g, meta in zip(treedef.flatten_up_to(grads), metas_flat):
            g = g.reshape(-1).astype(jnp.float32)
            if Nm > 1 and meta.dim == SH.REPLICATED:
                # replicated leaves skip the gather, so their grads miss
                # the gather-transpose psum over the model axis
                g = jax.lax.psum(g, MODEL_AXIS)
            gs.append(g)

        # fence the forward/backward off from the channel/update code so
        # XLA compiles it like a standalone value_and_grad (float
        # rounding then matches the single-machine reference path) - but
        # per BUCKET rather than one whole-tree end-of-step barrier: a
        # bucket's quantized exchange only waits on its own gradients,
        # so the wire of early buckets can overlap the remaining
        # backward.
        for bucket in buckets:
            fenced = jax.lax.optimization_barrier([gs[i] for i in bucket])
            for i, g in zip(bucket, fenced):
                gs[i] = g

        # 3+4. per-worker engine update + per-mode quantized exchange.
        # The PRNG folds the *inter-tier* worker index: flat tiers make
        # it the plain flat worker id (unchanged), hierarchical tiers
        # fold the node id only, so a node's devices draw identical
        # stochastic codes for their identical node-mean gradients.
        base = jax.random.fold_in(jax.random.PRNGKey(tc.seed), t)
        widx = C.worker_index(tiers.inter_axes, tiers.inter_sizes)
        new_m, new_mm, new_vv, new_ee, stat_rows = [], [], [], [], []
        for i, meta in enumerate(metas_flat):
            key = jax.random.fold_in(jax.random.fold_in(base, i), widx)
            out = updater(gs[i], ms_[i], vs_[i], es_[i],
                          masters[i], meta, a_t, th_t, key, i)
            if mode.emits_stats:
                nc, nm, nv, ne, row = out
                stat_rows.append(row)
            else:
                nc, nm, nv, ne = out
            lead = (1,) * (len(worker_axes) + 1)
            new_m.append(nc.reshape(lead + (meta.c,)))
            new_mm.append(nm.reshape(lead + (_state_x(meta),)))
            new_vv.append(nv.reshape(lead + (_state_x(meta),)))
            new_ee.append(ne.reshape(lead + (_state_x(meta),)))

        unf = lambda ls: jax.tree_util.tree_unflatten(treedef, ls)
        new_state = {"master": unf(new_m), "m": unf(new_mm),
                     "v": unf(new_vv), "e": unf(new_ee), "count": t}
        if mode.broadcast_ef:
            lead = (1,) * (len(worker_axes) + 1)
            new_state["es"] = unf([
                es.reshape(lead + (m.c,))
                for es, m in zip(new_es, metas_flat)])
        metrics = {"loss": loss}
        if mode.emits_stats:
            rows = jnp.stack(stat_rows)          # (n_leaves, N_FIELDS)
            metrics["gstats"] = (astats.reduce_stats(rows, all_axes)
                                 if all_axes else rows)
        return new_state, metrics

    def step_fn(state, batch):
        Wb, cp = _batch_geometry(batch, Nm, worker_axes, n_workers,
                                 tc.batch_dim_shardable)
        sspec = {"master": jax.tree.map(lambda _: state_spec,
                                        layout._leaves),
                 "count": P()}
        for k in ("m", "v", "e") + mode.extra_state:
            sspec[k] = jax.tree.map(lambda _: state_spec, layout._leaves)
        bspec = _batch_specs(batch, Wb, cp)
        mspec = {"loss": P()}
        if mode.emits_stats:
            mspec["gstats"] = P()
        fn = shard_map(functools.partial(_impl, cp=cp), mesh=mesh,
                       in_specs=(sspec, bspec),
                       out_specs=(sspec, mspec),
                       check_rep=False)
        return fn(state, batch)

    return StepArtifacts(init_state=init_state, step_fn=step_fn,
                         layout=layout, n_workers=n_workers,
                         worker_axes=worker_axes, mesh=mesh, config=tc,
                         tiers=tiers)


def __getattr__(name):
    # compat: the serve step moved to repro.dist.serve
    if name in ("make_serve_step", "_cache_specs_for"):
        from repro.dist import serve
        return getattr(serve, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
