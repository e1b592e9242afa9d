"""Chip smoke test: QAdam-EF training and code-resident serving on one TPU
at yi-6b's published widths, through the launchers' own entry points.

    python chip_smoke.py              # one chip: train phase, serve phase
    python chip_smoke.py --chips 4    # four chips: Algorithms 2+3 over four
                                      # workers vs Algorithm 1 on one chip

Run it from a checkout (it imports ``src/repro``). Without a TPU it exits
non-zero before any phase; any failed check raises and exits non-zero.
The last line of standard output is one JSON object naming the device.

Cuts are of depth and of the vocabulary only; every width stays as
published (d_model 4096, 32 heads / 4 KV heads x 128, d_ff 11008):

* train: 2 of 32 layers, and 16,000 of 64,000 vocabulary rows - one
  chip's share of a vocabulary split four ways; the batches draw their
  ids from the slice. The train state keeps fp32 master, m, v and e for
  every parameter (about 16 B each plus fp32 gradients), so 2 layers
  (477M parameters) is what fits 16 GB with a 2048-token sequence.
* serve: 8 of 32 layers at the full vocabulary. ``Model.init`` makes
  fp32 weights, which are dropped once quantized to code-resident
  int8 (k_x = 6) leaves.
* four chips: 1 layer and the train phase's vocabulary slice, one
  2048-token sequence per worker, the same on every worker. Each worker
  keeps m, v and e for the whole model (Algorithm 3), and Algorithm 1
  needs all of it on the first chip; one layer keeps both within 16 GB.
"""
from __future__ import annotations

import argparse
import dataclasses
import faulthandler
import functools
import gc
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

TRAIN_LAYERS, TRAIN_VOCAB, TRAIN_SEQ, TRAIN_BATCH = 2, 16_000, 2048, 1
SERVE_LAYERS = 8
FOUR_WORKER_LAYERS = 1
# the code-resident prefill may differ from the dequantize-then-float one
# only in each projection's f32 summation order (the fused kernel adds K
# in tiles); both round every projection to bf16 (steps of 2^-8), so an
# order change flips at most one such rounding per activation. Eight
# layers of those flips move the largest logit by a few bf16 steps; 2^-5
# of max |logit| (8 steps) bounds that, while a wrong scale, code or
# tile (errors of whole quantizer steps or more) lands far outside.
PREFILL_REL_TOL = 2.0 ** -5
# the train step's first loss and the plain model loss at the same
# quantized weights run one bf16 forward in two differently fused
# programs: logits differ by bf16 roundings (2^-8 relative) on some
# elements, which moves a mean over thousands of tokens of ~10.4 by far
# less than 0.02; a broken wire, shard or kernel moves it by whole units.
FIRST_LOSS_TOL = 0.02
# four workers with identical batches vs Algorithm 1 on one chip. Losses:
# step 1 sees identical weights, so only reduction order differs (the
# mean of four identical rows is exact, the loss psum is not ordered
# like one device's sum): relative 1e-3. Masters: the two programs fuse
# the same bf16 forward/backward differently, so their gradients differ
# by bf16 roundings (2^-8 relative). That moves each leaf's exchange
# scale, amax |alpha * m / sqrt(v) + e|, by a few such roundings, and
# with it the update of EVERY element of the leaf (same code, scale off
# by ~2^-8..2^-6 relative); it can also flip the code of an element
# that sits on a log-grid boundary, moving that one element by at most
# one grid step, itself at most the scale <= alpha / sqrt(1 - theta) ~
# 32 * alpha. So: every element within 32 * alpha per step, and the
# masters' movement from the initial weights agrees in RMS within 2^-6
# (scale roundings) plus sqrt(flip fraction) (flips of at most their own
# update size): at most 1e-3 of elements flipping gives sqrt(1e-3) ~
# 0.032, 0.05 in all. A wrong code, scale or wire is off by O(1) there.
EQUIV_LOSS_RTOL = 1e-3
EQUIV_UPDATE_RTOL = 0.05


class SmokeFailure(AssertionError):
    """A check of the smoke run failed."""


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}")


def _import_repro():
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit("chip_smoke.py: run it from a checkout of the "
                         "repository (src/repro not found)")
    sys.path.insert(0, src)


def device_summary():
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def peak_bytes(device=None):
    """``peak_bytes_in_use`` of a device - the most it has held since the
    process started - or None where the backend reports no memory
    statistics (the CPU)."""
    import jax
    stats = (device or jax.devices()[0]).memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def in_use_bytes():
    import jax
    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("bytes_in_use")


def pallas_kernels(compiled):
    """Names of the Pallas kernels in a compiled program."""
    import re
    txt = compiled.as_text()
    names = set()
    for line in txt.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.search(r'op_name="[^"]*?([A-Za-z0-9_]+)/pallas_call', line)
            if m:
                names.add(m.group(1))
    return names


def check_kernels(compiled, expected, what):
    found = pallas_kernels(compiled)
    missing = sorted(set(expected) - found)
    print(f"  {what} kernels: {sorted(found)}")
    check(not missing, f"{what} runs every expected Pallas kernel"
          + (f" (missing {missing})" if missing else ""))


def train_cut(cfg):
    """The cut yi-6b for the train phase (see the module docstring)."""
    return dataclasses.replace(cfg, n_layers=TRAIN_LAYERS,
                               vocab_size=TRAIN_VOCAB)


def _train_args(seq, batch, steps, seed=0):
    from repro.launch import train as launch_train
    return launch_train.build_parser().parse_args([
        "--arch", "yi-6b", "--mode", "qadam", "--grad-bits", "4",
        "--weight-bits", "6", "--seq", str(seq),
        "--global-batch", str(batch), "--steps", str(steps),
        "--log-every", "1", "--seed", str(seed)])


def _reference_first_loss(model, tc, batch, seed):
    """The plain model loss at the step's first weights: the initial
    parameters through the weight-broadcast codec's Q_x (one worker, so
    each leaf's chunk is the whole leaf)."""
    import jax
    from repro.dist.step import weight_wire_codec

    def qx(p):
        codec = weight_wire_codec(tc, p.size)
        x = p.astype("float32")
        scale = codec.compute_scale(x)
        return codec.dequantize(codec.quantize(x, scale), scale)

    @jax.jit
    def loss(params, batch):
        s, n = model.loss(jax.tree.map(qx, params), batch)
        return s / n

    return float(loss(model.init(jax.random.PRNGKey(seed)), batch))


def train_phase(cfg, *, seq=TRAIN_SEQ, batch=TRAIN_BATCH, steps=5,
                expect_kernels=True):
    """QAdam-EF (grad_k=4, weight_k=6, error feedback) through
    ``launch.train.make_session``: checks losses, EF residuals, wire
    bytes and kernels; returns a summary."""
    import jax
    import jax.numpy as jnp
    from repro.adapt.controller import measured_exchange_bytes
    from repro.data.pipeline import batch_for_model
    from repro.dist import topology
    from repro.launch import train as launch_train
    from repro.launch.mesh import make_local_mesh
    from repro.models.model import Model

    args = _train_args(seq, batch, steps)
    model = Model(cfg)
    mesh = make_local_mesh(data=1, model=1)
    tc = launch_train.train_config(args, topology.parse_topology(None))
    first_batch = next(batch_for_model(cfg, seq, batch, seed=args.seed))
    ref_loss = _reference_first_loss(model, tc, first_batch, args.seed)
    gc.collect()

    art, comm, sess = launch_train.make_session(
        model, mesh, tc, args, log=lambda m: print(f"  {m}"))
    try:
        sess.run(1)
        e = jax.tree.leaves(sess.state["e"])
        e_finite = all(bool(jnp.all(jnp.isfinite(x))) for x in e)
        e_max = max(float(jnp.max(jnp.abs(x))) for x in e)
        sess.run(steps - 1)
        losses = [h["loss"] for h in sess.history if "loss" in h]
        compiled = sess.compiled_step()
        measured = measured_exchange_bytes(art, tc)
    finally:
        sess.close()
    print(f"  losses: {losses}")
    check(len(losses) == steps and all(math.isfinite(v) for v in losses),
          f"all {steps} losses finite")
    check(abs(losses[0] - ref_loss) <= FIRST_LOSS_TOL,
          f"first loss {losses[0]:.4f} within {FIRST_LOSS_TOL} of the plain "
          f"model loss at the same weights {ref_loss:.4f} "
          f"(ln V = {math.log(cfg.vocab_size):.4f})")
    check(e_finite and e_max > 0,
          f"EF residual e finite and not all zero after step 1 "
          f"(max |e| = {e_max:.3e})")
    check(measured == comm["update_exchange_bytes"],
          f"measured exchange payload {measured} B == comm_bytes_per_step "
          f"{comm['update_exchange_bytes']} B")
    if expect_kernels:
        check_kernels(compiled, ("adam_ef_moments", "codec_ef_encode",
                                 "codec_decode"), "train step")
    mem = compiled.memory_analysis()
    peak = peak_bytes()
    print(f"  train step memory: arguments "
          f"{getattr(mem, 'argument_size_in_bytes', None)} B, temporaries "
          f"{getattr(mem, 'temp_size_in_bytes', None)} B")
    print(f"  train peak_bytes_in_use: {peak}")
    return {"losses": losses, "peak_bytes_in_use": peak,
            "exchange_bytes": measured}


def _serve_args(max_seq, max_new, slots, seed=0):
    from repro.launch import serve as launch_serve
    return launch_serve.build_parser().parse_args([
        "--arch", "yi-6b", "--quantized", "--k-x", "6",
        "--slots", str(slots), "--max-seq", str(max_seq),
        "--max-new", str(max_new), "--seed", str(seed)])


def serve_phase(cfg, *, requests=8, slots=4, prompt_lens=(128, 512),
                max_new=32, expect_kernels=True):
    """Code-resident (k_x=6, packed, fused matmul) continuous batching
    through ``launch.serve``; checks completions, prefill logits against
    the dequantize-then-float path, and kernels."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch import serve as launch_serve
    from repro.models.layers import ShardCtx
    from repro.models.model import Model
    from repro.serve import Request, params_nbytes
    from repro.serve.quantized import make_dequant_gather

    lo, hi = prompt_lens
    args = _serve_args(hi + max_new, max_new, slots)
    model = Model(cfg)
    params = launch_serve.serve_params(model, args,
                                       log=lambda m: print(f"  {m}"))
    sess = launch_serve.make_session(model, params, args)
    rng = np.random.default_rng(args.seed)
    reqs = [Request(prompt=[int(t) for t in
                            rng.integers(1, cfg.vocab_size,
                                         size=int(rng.integers(lo, hi + 1)))],
                    max_new_tokens=max_new) for _ in range(requests)]
    handles = [sess.submit(r) for r in reqs]
    results = sess.drain()
    got = [len(results[h].tokens) for h in handles]
    print(f"  prompt lengths {[len(r.prompt) for r in reqs]}, "
          f"new tokens {got}, stats {sess.stats}")
    check(all(n == max_new for n in got),
          f"all {requests} requests finished with {max_new} tokens")
    decode = sess.compiled_step()

    def prefill(fused, tokens):
        ctx = ShardCtx(param_gather=make_dequant_gather(fused=fused))
        fn = jax.jit(lambda p, b: model.forward(p, b, ctx)[0])
        compiled = fn.lower(params, tokens).compile()
        return compiled, compiled(params, tokens).astype(jnp.float32)

    # the shortest and the longest prompt: two token streams, and two
    # sequence lengths through attention
    by_len = sorted(reqs, key=lambda r: len(r.prompt))
    diffs, maxes = [], []
    for prompt in (by_len[0].prompt, by_len[-1].prompt):
        tokens = {"tokens": jnp.asarray([prompt], jnp.int32)}
        fused_prefill, got_logits = prefill(True, tokens)
        _, ref_logits = prefill(False, tokens)
        diff = float(jnp.max(jnp.abs(got_logits - ref_logits)))
        ref_max = float(jnp.max(jnp.abs(ref_logits)))
        diffs.append(diff)
        maxes.append(ref_max)
        check(bool(jnp.all(jnp.isfinite(got_logits)))
              and diff <= PREFILL_REL_TOL * ref_max,
              f"code-resident prefill logits ({len(prompt)} tokens) agree "
              f"with dequantize-then-float: max |diff| {diff:.4e} <= "
              f"{PREFILL_REL_TOL} * max |logit| {ref_max:.4e} "
              f"({diff / ref_max * 256:.2f} bf16 steps of 2^-8)")
    if expect_kernels:
        check_kernels(decode, ("dequant_matmul",), "decode step")
        check_kernels(fused_prefill, ("dequant_matmul",), "prefill")
    peak = peak_bytes()
    print(f"  serve peak_bytes_in_use: {peak} (since the process started; "
          f"bytes_in_use at the serve phase's end {in_use_bytes()}, "
          f"resident weights {params_nbytes(params)} B)")
    return {"tokens": got, "prefill_max_abs_diff": diffs,
            "prefill_max_abs_logit": maxes, "peak_bytes_in_use": peak}


def four_worker_phase(cfg, *, workers=4, seq=TRAIN_SEQ, steps=3,
                      alpha=1e-3):
    """Algorithms 2+3 over ``workers`` workers on mesh (workers, 1) with
    the same batch on every worker, against single-machine Algorithm 1
    (``core.qadam``) on the first device (paper section 3.2)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.qadam import QAdamConfig, apply_updates, qadam
    from repro.data.pipeline import batch_for_model
    from repro.dist.step import TrainConfig, make_train_step
    from repro.launch.mesh import make_local_mesh
    from repro.models.model import Model

    model = Model(cfg)
    one = {k: jnp.asarray(v) for k, v in
           next(batch_for_model(cfg, seq, 1, seed=0)).items()}
    batch = {k: jnp.concatenate([v] * workers) for k, v in one.items()}
    mesh = make_local_mesh(data=workers, model=1)
    # the paper's absolute Q_x grid: a chunk's codes then equal the whole
    # tensor's (an amax grid would take one scale per worker chunk)
    tc = TrainConfig(alpha=alpha, grad_k=4, weight_k=6,
                     weight_absolute=True, worker_axes=("pod", "data"))
    art = make_train_step(model, mesh, tc)
    state = art.init_state(jax.random.PRNGKey(0))
    step = jax.jit(art.step_fn, donate_argnums=(0,))
    dist_losses = []
    for i in range(steps):
        state, metrics = step(state, batch)
        dist_losses.append(float(metrics["loss"]))
        print(f"  {workers}-worker step {i + 1} loss {dist_losses[-1]:.4f}")
    peaks = [peak_bytes(d) for d in jax.devices()]
    master = [np.asarray(x) for x in jax.tree.leaves(state["master"])]
    del state, step
    gc.collect()

    opt = qadam(QAdamConfig(alpha=alpha, beta=tc.beta, theta=tc.theta,
                            eps=tc.eps, schedule=tc.schedule,
                            grad_q="log:4", weight_q="uniform:6",
                            weight_q_min_numel=tc.weight_q_min_numel))

    def mean_loss(p):
        s, n = model.loss(p, one)
        return s / n

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def ref_step(params, ostate):
        loss, grads = jax.value_and_grad(mean_loss)(
            opt.forward_params(params, ostate))
        upd, ostate = opt.update(grads, ostate, params)
        return apply_updates(params, upd), ostate, loss

    params = model.init(jax.random.PRNGKey(0))
    init = [np.asarray(x, np.float32).reshape(-1)
            for x in jax.tree.leaves(params)]
    ostate = opt.init(params)
    ref_losses = []
    for i in range(steps):
        params, ostate, loss = ref_step(params, ostate)
        ref_losses.append(float(loss))
        print(f"  Algorithm 1 step {i + 1} loss {ref_losses[-1]:.4f}")

    differ = total = 0
    worst = sq_diff = sq_moved = 0.0
    for got, ref, x0 in zip(master, jax.tree.leaves(params), init):
        ref = np.asarray(ref, np.float32).reshape(-1)
        got = got.reshape(-1)[:ref.size]     # (workers, 1, c) chunks
        d = np.abs(got - ref)
        worst = max(worst, float(d.max()))
        differ += int((d > 1e-6).sum())
        total += ref.size
        sq_diff += float(np.sum(d.astype(np.float64) ** 2))
        sq_moved += float(np.sum((ref - x0).astype(np.float64) ** 2))
    rel = math.sqrt(sq_diff / sq_moved)
    print(f"  {workers}-worker losses:   {dist_losses}")
    print(f"  Algorithm 1 losses: {ref_losses}")
    print(f"  per-device peak_bytes_in_use (after the {workers}-worker "
          f"steps): {peaks}")
    print(f"  masters: max |diff| {worst:.3e}, {differ} of {total} elements "
          f"differ by more than 1e-6; RMS diff / RMS movement from the "
          f"initial weights {rel:.3e}")
    check(all(abs(a - b) <= EQUIV_LOSS_RTOL * abs(b)
              for a, b in zip(dist_losses, ref_losses)),
          f"losses agree within relative {EQUIV_LOSS_RTOL}")
    check(worst <= 32 * alpha * steps,
          f"masters agree elementwise: max |diff| {worst:.3e} <= "
          f"{32 * alpha * steps:.3e}")
    check(rel <= EQUIV_UPDATE_RTOL,
          f"the masters' movement agrees: RMS diff / RMS movement "
          f"{rel:.3e} <= {EQUIV_UPDATE_RTOL}")
    return {"losses": dist_losses, "ref_losses": ref_losses,
            "master_max_abs_diff": worst, "master_differ": differ,
            "master_update_rel_diff": rel, "peak_bytes_in_use": peaks}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-worker equivalence phase")
    args = ap.parse_args(argv)
    # lines reach a pipe as they are printed, and a crash in native code
    # still leaves the Python stack on stderr
    sys.stdout.reconfigure(line_buffering=True)
    faulthandler.enable()
    _import_repro()
    import jax

    device = device_summary()
    if device["platform"] != "tpu":
        print(f"chip_smoke.py: no TPU found (JAX sees "
              f"{device['platform']}); nothing was run", file=sys.stderr)
        return 2
    if device["count"] < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {device['count']}", file=sys.stderr)
        return 2
    from repro import perf
    from repro.configs import get_config
    print(f"device: {device}")
    print(f"compile cache: {perf.enable_persistent_cache()}")
    base = get_config("yi-6b")
    cut = train_cut(base)
    if args.chips == 4:
        cut = dataclasses.replace(cut, n_layers=FOUR_WORKER_LAYERS)
        print(f"[four workers] cuts: {cut.n_layers} of {base.n_layers} "
              f"layers, vocab {cut.vocab_size} of {base.vocab_size} (one "
              f"chip's share of a four-way split), seq {TRAIN_SEQ} per "
              f"worker")
        four_worker_phase(cut)
    else:
        print(f"[train] cuts: {cut.n_layers} of {base.n_layers} layers, "
              f"vocab {cut.vocab_size} of {base.vocab_size} (one chip's "
              f"share of a four-way split), seq {TRAIN_SEQ}, batch "
              f"{TRAIN_BATCH}")
        train_phase(cut)
        gc.collect()
        jax.clear_caches()
        print(f"  bytes_in_use after the train phase: {in_use_bytes()}")
        serve_cfg = dataclasses.replace(base, n_layers=SERVE_LAYERS)
        print(f"[serve] cuts: {serve_cfg.n_layers} of {base.n_layers} "
              f"layers, full vocab {serve_cfg.vocab_size}")
        serve_phase(serve_cfg)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
