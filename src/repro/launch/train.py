"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke \
      --steps 50 --data 2 --model 2 --grad-bits 4 --weight-bits 7

Runs QAdam-EF distributed training (Algorithms 2+3) on a local mesh (or
the production mesh under a real TPU runtime) through ``TrainSession``:
batches are prefetched and staged to device on a background thread,
losses stay device-resident between log boundaries, and checkpoints are
written asynchronously. `--mode dp_adam` gives the conventional
data-parallel Adam baseline; `--no-ef` ablates error feedback;
`--grad-bits/--weight-bits 0` turn each quantized channel off.

`--steps` is the TOTAL step budget: with `--resume`, the session restores
the newest checkpoint under `--ckpt-dir` (step counter, optimizer/PRNG
state, and data-stream position - bit-identical to never stopping) and
runs only the remaining steps. `--adaptive --resume` additionally
restores the checkpointed bit plan and stats EMA.

`--topology NxD` exchanges quantized updates hierarchically
(``repro.dist.topology``): fp gradients reduce over the fast intra-node
tier first, the quantized+EF exchange crosses only the node tier.
`--multihost` initializes ``jax.distributed`` for one-process-per-host
runs; CI simulates hosts with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``. `--profile DIR`
records the run's profile, with the session's `train.*` spans and the
step's `qadam.*` scopes, into DIR.
"""
from __future__ import annotations

import argparse
import json

import numpy as np


def _run_adaptive(args, model, mesh, tc):
    """--adaptive path: drive the run through the repro.adapt
    controller (stats ring -> bit allocation -> codec swaps at replan
    boundaries) instead of a plain session."""
    import jax
    import math
    from repro.adapt.controller import AdaptConfig, AdaptiveController
    from repro.data.pipeline import batch_for_model

    batches = batch_for_model(model.cfg, args.seq, args.global_batch,
                              seed=args.seed)
    sc = session_config(args)
    acfg = AdaptConfig(budget_ratio=args.adapt_budget,
                       replan_every=args.replan_every,
                       ema_decay=args.adapt_ema)
    ctl = AdaptiveController(model, mesh, tc, batches, acfg, sc,
                             key=jax.random.PRNGKey(args.seed),
                             verify=args.adapt_verify)
    print(f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"workers={ctl.art.n_workers}")
    try:
        start = ctl.resume(args.ckpt_dir) if args.resume else 0
        if start:
            print(f"resumed from step {start} ({args.ckpt_dir}), "
                  f"plan restored: "
                  f"{_plan_summary(ctl.tc.bit_plan) if ctl.tc.bit_plan else 'initial log grid'}")
        remaining = args.steps - start
        if remaining <= 0:
            print(f"nothing to do: checkpoint at step {start} >= "
                  f"--steps {args.steps}")
            return
        ctl.run(remaining)
        windows = math.ceil(remaining / args.replan_every)
        if args.adapt_verify:
            # every plan already passed accounted == measured (see
            # AdaptiveController verify); here: the only host syncs are
            # the per-window stats harvests + the log-boundary loss
            # harvests - nothing per step.
            expected = windows if args.log_every == 0 else None
            if expected is not None:
                assert ctl.stats["syncs"] == expected, \
                    (f"{ctl.stats['syncs']} syncs != {expected} "
                     f"replan windows: a per-step host sync crept in")
            print(f"adapt-verify OK: {len(ctl.plan_log)} plans exact, "
                  f"{ctl.stats['syncs']} syncs / {windows} windows")
        losses = [h for h in ctl.session.history if "loss" in h]
        if not losses:
            losses = [{"step": s, "loss": v}
                      for s, v in ctl.session.harvest_losses()]
    finally:
        ctl.close()
    print(f"session stats: {ctl.stats}")
    for e in ctl.plan_log:
        a2a = e["comm"]["update_exchange_bytes"]
        print(f"plan @{e['step']}: a2a {a2a/1e6:.3f}MB/step "
              f"({'initial log grid' if e['bit_plan'] is None else ''}"
              f"{'' if e['bit_plan'] is None else _plan_summary(e['bit_plan'])})")
    if args.history_out:
        with open(args.history_out, "w") as f:
            json.dump({"arch": args.arch, "history": ctl.session.history,
                       "plan_log": [
                           {"step": e["step"], "comm": e["comm"],
                            "bit_plan": (list(e["bit_plan"])
                                         if e["bit_plan"] else None)}
                           for e in ctl.plan_log],
                       "stats": ctl.stats}, f, indent=1)
    if losses:
        print("final loss:", losses[-1]["loss"])


def _plan_summary(plan):
    counts = {}
    for spec in plan:
        counts[spec] = counts.get(spec, 0) + 1
    return " ".join(f"{s}x{n}" for s, n in sorted(counts.items()))


def session_config(args):
    """The ``TrainSession`` settings the command line asks for."""
    from repro.train.session import SessionConfig
    return SessionConfig(log_every=args.log_every,
                         ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir,
                         ckpt_keep=args.ckpt_keep,
                         ckpt_codec=args.ckpt_codec,
                         scan_chunk=args.scan_chunk,
                         prefetch=args.prefetch, aot_dir=args.aot_dir)


def train_config(args, topo):
    """The ``TrainConfig`` the command line asks for."""
    from repro.dist.step import TrainConfig
    return TrainConfig(
        alpha=args.alpha, beta=args.beta, theta=args.theta,
        schedule=args.schedule,
        grad_k=args.grad_bits or None,
        weight_k=args.weight_bits or None,
        weight_absolute=args.weight_absolute,
        model_gather_quant=args.model_gather_quant or None,
        error_feedback=not args.no_ef,
        worker_axes=("pod", "data"),
        topology=topo,
        mode="adaptive" if args.adaptive else args.mode)


def make_session(model, mesh, tc, args, *, log=print):
    """Build the train step, its wire accounting and the session that
    drives it over the synthetic batch stream: the launcher's main path
    (``chip_smoke.py`` runs it too). Returns ``(art, comm, session)``."""
    import jax
    from repro.data.pipeline import batch_for_model
    from repro.dist.step import make_train_step
    from repro.train.loop import comm_bytes_per_step
    from repro.train.session import TrainSession
    art = make_train_step(model, mesh, tc)
    comm = comm_bytes_per_step(art, tc)
    batches = batch_for_model(model.cfg, args.seq, args.global_batch,
                              seed=args.seed)
    sess = TrainSession.from_artifacts(art, batches, session_config(args),
                                       key=jax.random.PRNGKey(args.seed),
                                       log=log)
    return art, comm, sess


def build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100,
                    help="total step budget (resume counts toward it)")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--data", type=int, default=1, help="data axis size")
    ap.add_argument("--model", type=int, default=1, help="model axis size")
    ap.add_argument("--pod", type=int, default=0, help="pod axis size")
    ap.add_argument("--topology", default=None, metavar="SPEC",
                    help="worker exchange topology: 'flat' (default) or "
                         "'NxD' = HierarchicalTopology(nodes=N, "
                         "devices_per_node=D); NxD implies --pod N "
                         "--data D when those are left default")
    ap.add_argument("--multihost", action="store_true",
                    help="initialize jax.distributed before device "
                         "queries (one process per host)")
    ap.add_argument("--coordinator", default=None, metavar="ADDR",
                    help="--multihost coordinator address host:port")
    ap.add_argument("--num-processes", type=int, default=None,
                    help="--multihost total process count")
    ap.add_argument("--process-id", type=int, default=None,
                    help="--multihost rank of this process")
    ap.add_argument("--tune-buckets", action="store_true",
                    help="sweep exchange_bucket_bytes against measured "
                         "step time before training and run with the "
                         "winner (perf.autotune.tune_exchange_buckets)")
    ap.add_argument("--alpha", type=float, default=1e-3)
    ap.add_argument("--beta", type=float, default=0.99)
    ap.add_argument("--theta", type=float, default=0.999)
    ap.add_argument("--schedule", default="constant")
    ap.add_argument("--grad-bits", type=int, default=6,
                    help="log-grid k_g; 0 = fp32 wire")
    ap.add_argument("--weight-bits", type=int, default=6,
                    help="uniform k_x; 0 = bf16 wire")
    ap.add_argument("--weight-absolute", action="store_true",
                    help="the paper's absolute [-0.5,0.5] grid")
    ap.add_argument("--model-gather-quant", type=int, default=0,
                    help="int8 FSDP gather bits (beyond-paper), 0=off")
    ap.add_argument("--no-ef", action="store_true")
    ap.add_argument("--mode", default="qadam",
                    choices=["qadam", "efadam", "dp_adam", "terngrad",
                             "ef_sgd", "adaptive"])
    ap.add_argument("--adaptive", action="store_true",
                    help="runtime-adaptive per-leaf bit allocation "
                         "(repro.adapt): stats-driven replans every "
                         "--replan-every steps under --adapt-budget")
    ap.add_argument("--adapt-budget", type=float, default=0.6,
                    help="a2a byte budget as a fraction of the fixed "
                         "log:6 wire")
    ap.add_argument("--replan-every", type=int, default=25)
    ap.add_argument("--adapt-ema", type=float, default=0.8,
                    help="stats EMA decay per step")
    ap.add_argument("--adapt-verify", action="store_true",
                    help="assert exact byte accounting at every plan "
                         "and zero steady-state host syncs")
    ap.add_argument("--scan-chunk", type=int, default=1,
                    help=">1: lax.scan this many steps per compiled call")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="batches staged to device ahead (0 = sync pulls)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--ckpt-keep", type=int, default=3,
                    help="versioned checkpoints kept (keep-last-N)")
    ap.add_argument("--ckpt-codec", default=None,
                    help="repro.comm codec spec for compressed moment "
                         "snapshots, e.g. uniform_amax:7:w8 (lossy)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest checkpoint under --ckpt-dir")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--history-out", default=None)
    ap.add_argument("--no-compile-cache", action="store_true",
                    help="run without the persistent XLA compilation "
                         "cache (see repro.perf.cache for where it lives)")
    ap.add_argument("--aot-dir", default=None, metavar="DIR",
                    help="AOT step-artifact dir: restart/resume loads the "
                         "serialized compiled train step instead of "
                         "tracing+compiling (repro.perf.aot)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="record a jax.profiler profile of the run into DIR")
    return ap


def main(argv=None):
    ap = build_parser()
    args = ap.parse_args(argv)
    if args.resume and not args.ckpt_dir:
        ap.error("--resume requires --ckpt-dir")
    args.adaptive = args.adaptive or args.mode == "adaptive"
    if args.multihost:
        if not (args.coordinator and args.num_processes is not None
                and args.process_id is not None):
            ap.error("--multihost requires --coordinator, "
                     "--num-processes and --process-id")
        import jax
        jax.distributed.initialize(args.coordinator, args.num_processes,
                                   args.process_id)
    from repro.perf import profiling
    with profiling.trace(args.profile, enabled=args.profile is not None):
        _train(ap, args)


def _train(ap, args):
    import jax
    from repro import perf
    if not args.no_compile_cache:
        print(f"compile cache: {perf.enable_persistent_cache()}")
    from repro.configs import get_config
    from repro.models.model import Model
    from repro.launch.mesh import make_local_mesh
    from repro.data.pipeline import batch_for_model

    from repro.dist import topology as T
    topo = T.parse_topology(args.topology)
    if isinstance(topo, T.HierarchicalTopology):
        n, d = topo.nodes, topo.devices_per_node
        if args.pod == 0 and args.data == 1:
            # NxD picks the mesh too: pod = node axis, data = intra axis
            args.pod, args.data = n, d
        elif max(args.pod, 1) * args.data != n * d:
            ap.error(f"--topology {args.topology} needs {n * d} workers "
                     f"but --pod/--data give "
                     f"{max(args.pod, 1) * args.data}")

    cfg = get_config(args.arch, smoke=args.smoke)
    model = Model(cfg)
    mesh = make_local_mesh(data=args.data, model=args.model, pod=args.pod)
    tc = train_config(args, topo)
    if args.tune_buckets:
        from repro.perf.autotune import tune_exchange_buckets
        # probe batch from a fresh same-seed generator: the training
        # stream position is untouched
        probe = next(batch_for_model(cfg, args.seq, args.global_batch,
                                     seed=args.seed))
        rep = tune_exchange_buckets(model, mesh, tc, probe)
        tc = rep["config"]
        print(f"tuned exchange bucket: {rep['best']} B "
              f"(speedup {rep['speedup']:.2f}x vs default "
              f"{rep['default']} B)")
    if args.adaptive:
        _run_adaptive(args, model, mesh, tc)
        return
    art, comm, sess = make_session(model, mesh, tc, args)
    print(f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"workers={art.n_workers}")
    print(f"comm/device/step: exchange={comm['update_exchange_bytes']/1e6:.2f}MB "
          f"broadcast={comm['weight_broadcast_bytes']/1e6:.2f}MB")
    if comm["tiers"]["intra"]["total"]:
        print(f"  per tier: inter={comm['tiers']['inter']['total']/1e6:.2f}MB "
              f"intra={comm['tiers']['intra']['total']/1e6:.2f}MB")
    try:
        start = sess.resume(args.ckpt_dir) if args.resume else 0
        if start:
            print(f"resumed from step {start} ({args.ckpt_dir})")
        remaining = args.steps - start
        if remaining <= 0:
            print(f"nothing to do: checkpoint at step {start} >= "
                  f"--steps {args.steps}")
            return
        sess.run(remaining)
        losses = [h for h in sess.history if "loss" in h]
        if not losses:   # --log-every 0: nothing harvested during run
            losses = [{"step": s, "loss": v}
                      for s, v in sess.harvest_losses()]
    finally:
        sess.close()
    history = sess.history
    print(f"session stats: {sess.stats}")
    if args.history_out:
        with open(args.history_out, "w") as f:
            json.dump({"arch": args.arch, "history": history,
                       "comm": comm, "stats": sess.stats}, f, indent=1)
    if losses:
        print("final loss:", losses[-1]["loss"])


if __name__ == "__main__":
    main()
