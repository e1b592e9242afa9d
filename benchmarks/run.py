"""Benchmark harness - one entry per paper table/figure + system benches.

  PYTHONPATH=src python -m benchmarks.run               # all, CSV to stdout
  PYTHONPATH=src python -m benchmarks.run --only kernels
  PYTHONPATH=src python -m benchmarks.run --suite comm --trace

Benches (name -> paper artifact):
  table2_cifar100_analogue  - Table 2 protocol (QADAM vs TernGrad vs
                              blockwise-EF vs WQuan) on the synthetic
                              classification task, reduced steps
  table3_cifar10_analogue   - Table 3 protocol, second seed/task split
  fig34_convergence         - Figures 3/4: loss-vs-step curves per method
  comm_cost                 - the 'Comm'/'Size' columns: wire bytes per
                              step/model at each quantization level
  kernels                   - Pallas kernel micro-bench (interpret mode on
                              CPU: correctness-path timing, not TPU perf)
  startup                   - cold vs warm jit startup through the
                              persistent compile cache + AOT artifacts
  roofline                  - reads results/dryrun_single.jsonl and emits
                              the three roofline terms per (arch x shape)

Output format: ``name,us_per_call,derived,ratio`` CSV rows; ``ratio`` is
a machine-readable dimensionless figure (fused-vs-legacy speedup,
warm-vs-cold) on rows where us_per_call alone is meaningless, else
empty.

``--trace [--trace-dir D]`` wraps the run in ``jax.profiler.trace``
with one ``TraceAnnotation`` per bench, so a regression like PR-5's
fused log decode (0.23x: per-element exp2 on unpacked codes) shows up
as a named hot region in the timeline instead of surviving five PRs.
Profiler overhead distorts absolute timings (10x+ on CPU interpret
runs), so never combine ``--trace`` with the ``BENCH_ASSERT_*`` gates
or a baseline snapshot - traced runs are for reading timelines.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))


def _time_call(fn, *args, reps=5, warmup=2):
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6  # us


# --------------------------------------------------------------------------

def bench_kernels(emit):
    import jax.numpy as jnp
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    for numel in (1 << 16, 1 << 20):
        x = jnp.asarray(rng.normal(size=(numel,)).astype(np.float32))
        us = _time_call(lambda v: ops.quantize_log(v, 6)[0], x)
        emit(f"kernel_quantize_log_{numel}", us, f"{numel}el")
        codes, scale = ops.quantize_log(x, 6)
        us = _time_call(lambda c: ops.dequantize_log(c, scale, 6), codes)
        emit(f"kernel_dequantize_log_{numel}", us, f"{numel}el")
        m = jnp.zeros_like(x)
        us = _time_call(
            lambda g: ops.adam_ef_step(g, m, m, m, 1e-3, 0.99, 0.9, 1e-5,
                                       6)[2], x)
        emit(f"kernel_adam_ef_{numel}", us, f"{numel}el")
    bench_opt_step(emit)


def _time_chain(fn, p, s, k_steps, reps=5, warmup=2):
    """Time fn(p, s) -> (p, s) with the state *chained* through calls, so
    buffer donation is exercised for real (each call consumes the
    previous call's output). Returns us per optimizer step."""
    import jax
    for r in range(warmup + reps):
        if r == warmup:
            jax.block_until_ready(p)
            t0 = time.perf_counter()
        p, s = fn(p, s)
    jax.block_until_ready(p)
    return (time.perf_counter() - t0) / (reps * k_steps) * 1e6


def bench_opt_step(emit, k_steps=16):
    """Single-machine qadam() through the engine: the per-step jax.jit
    loop vs the lax.scan-chunked, buffer-donating multi-step. Reports
    us/step for each; the scan path amortizes Python dispatch + jit-cache
    lookup + per-step host sync, so it must come out faster."""
    import jax
    import jax.numpy as jnp
    from repro.core.qadam import QAdamConfig, qadam, apply_updates
    from repro.opt.multistep import make_chunked_update

    rng = np.random.default_rng(1)
    for numel in (1 << 14, 1 << 18):
        params = {"w": jnp.asarray(rng.normal(size=(numel,), scale=0.1)
                                   .astype(np.float32))}
        gstack = jnp.asarray(rng.normal(size=(k_steps, numel))
                             .astype(np.float32))
        opt = qadam(QAdamConfig(alpha=1e-3, grad_q="log:6"))
        state0 = opt.init(params)

        @jax.jit
        def one_step(p, s, g):
            upd, s2 = opt.update({"w": g}, s, p)
            return apply_updates(p, upd), s2

        def loop_k(p, s):
            for i in range(k_steps):
                p, s = one_step(p, s, gstack[i])
            return p, s

        us = _time_chain(loop_k, params, state0, k_steps)
        emit(f"opt_qadam_loop{k_steps}_{numel}", us, f"{numel}el_per_step")

        chunk = make_chunked_update(opt, donate=True)
        us = _time_chain(lambda p, s: chunk(p, s, {"w": gstack}),
                         jax.tree.map(jnp.copy, params), state0, k_steps)
        emit(f"opt_qadam_scan{k_steps}_{numel}", us, f"{numel}el_per_step")


def bench_serve(emit, requests=8, slots=4, prompt_len=16, max_new=32,
                rounds=3):
    """ServeSession decode throughput (tok/s): fp32-resident vs
    code-resident (k_x=6, packed) through the fused dequant-matmul, and
    the same codes through the unfused dequantize-then-matmul path. The
    three sessions are timed in interleaved rounds (medians per tag) so
    machine noise hits every variant equally - the qx6/fp32 ratio is a
    GATED compare.py floor (>= 1.0: residency must also be a speed win),
    not just a report. Smoke-scale on CPU: the numbers track the serving
    hot path (one fused jit step per token, no per-token host sync), not
    TPU perf."""
    import jax
    from repro.configs import get_config
    from repro.models.model import Model
    from repro.serve import (Request, ServeSession, params_nbytes,
                             quantize_params)

    cfg = get_config("yi-6b", smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    qparams = quantize_params(params, k_x=6, min_numel=2 ** 10, pack=True)
    rng = np.random.default_rng(0)

    sessions = {
        "fp32": ServeSession(model, params, slots=slots, max_seq=128, seed=0),
        "qx6": ServeSession(model, qparams, slots=slots, max_seq=128, seed=0),
        "qx6_nofuse": ServeSession(model, qparams, slots=slots, max_seq=128,
                                   seed=0, fused_matmul=False),
    }
    # compile warmup: same prompt length as the timed requests, so the
    # per-length prefill executable is cached before the clock starts
    for sess in sessions.values():
        sess.submit(Request(prompt=list(range(1, prompt_len + 1)),
                            max_new_tokens=4))
        sess.drain()

    def one_round(sess):
        reqs = [Request(prompt=list(rng.integers(1, cfg.vocab_size,
                                                 size=prompt_len)),
                        max_new_tokens=max_new) for _ in range(requests)]
        t0 = time.perf_counter()
        hs = [sess.submit(r) for r in reqs]
        res = sess.drain()
        dt = time.perf_counter() - t0
        return dt, sum(len(res[h].tokens) for h in hs)

    times = {tag: [] for tag in sessions}
    toks = 0
    for _ in range(rounds):
        for tag, sess in sessions.items():
            dt, toks = one_round(sess)
            times[tag].append(dt)
    us = {tag: float(np.median(ts)) / toks * 1e6
          for tag, ts in times.items()}

    def tok_s(tag):
        return 1e6 / us[tag]

    emit("serve_session_fp32", us["fp32"],
         f"{tok_s('fp32'):.1f}tok_s_{requests}req_{slots}slots")
    # the headline: packed code-resident serving at least as fast as fp32
    emit("serve_session_qx6", us["qx6"],
         f"{tok_s('qx6'):.1f}tok_s_{us['fp32'] / us['qx6']:.2f}x_vs_fp32",
         us["fp32"] / us["qx6"])
    emit("serve_session_qx6_nofuse", us["qx6_nofuse"],
         f"{tok_s('qx6_nofuse'):.1f}tok_s_"
         f"{us['fp32'] / us['qx6_nofuse']:.2f}x_vs_fp32",
         us["fp32"] / us["qx6_nofuse"])
    emit("serve_fused_speedup_qx6", 0.0,
         f"{us['qx6_nofuse'] / us['qx6']:.2f}x_vs_unfused",
         us["qx6_nofuse"] / us["qx6"])
    emit("serve_resident_ratio", 0.0,
         f"{params_nbytes(qparams) / params_nbytes(params):.3f}x_fp32_measured",
         params_nbytes(qparams) / params_nbytes(params))


def bench_fleet(emit, n_requests=36, seed=0):
    """The paged-cache headline: an arrival-process-driven request fleet
    served by a fixed-lane session and a paged session holding EXACTLY
    the same cache bytes (fixed: 4 slots x 96 tokens; paged: the same
    384 tokens as 24 x 16-token pages fanned over 12 slots). Mixed
    prompt lengths and SLO classes arrive on a deterministic Poisson
    process (seeded numpy, identical schedule for both sessions); the
    driver submits on schedule and steps the session, exactly like a
    serving loop. Gated compare.py floors: paged tokens/s >= fixed
    (``serve_paged_toks``), paged peak concurrency >= 2x fixed
    (``serve_paged_concurrency``), and paged p99 TTFT within 1.5x of
    fixed (``serve_ttft_p99``). Smoke-scale on CPU."""
    import jax
    from repro.configs import get_config
    from repro.models.model import Model
    from repro.serve import Request, ServeSession, cache_nbytes

    cfg = get_config("yi-6b", smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    rng = np.random.default_rng(seed)
    slos = ["interactive", "standard", "batch"]
    sched = []
    step_at = 0
    for i in range(n_requests):
        step_at += int(rng.poisson(0.8))
        sched.append((step_at,
                      [int(t) for t in rng.integers(1, cfg.vocab_size,
                                                    size=rng.integers(4, 25))],
                      int(rng.integers(6, 13)), slos[i % 3]))

    max_seq, ps = 96, 16
    def make(paged):
        if paged:
            return ServeSession(model, params, slots=12, max_seq=max_seq,
                                seed=seed, paged=True, page_size=ps,
                                num_pages=24, prefill_chunk=8)
        return ServeSession(model, params, slots=4, max_seq=max_seq,
                            seed=seed, prefill_chunk=8)

    def run(paged):
        sess = make(paged)
        # compile warmup: a long prompt exercises both chunk shapes
        # (mid + final), the decode step, and the release path; the jits
        # live on the session instance, so warm the instance we time
        sess.submit(Request(prompt=list(range(1, 21)), max_new_tokens=3))
        sess.drain()
        sess.stats["max_inflight"] = 0
        sess._steps = 0
        t0 = time.perf_counter()
        it = iter(sched)
        nxt = next(it, None)
        submitted = []
        while nxt is not None:
            while nxt is not None and nxt[0] <= sess._steps:
                _, prompt, max_new, slo = nxt
                submitted.append(sess.submit(Request(
                    prompt=prompt, max_new_tokens=max_new, slo=slo)))
                nxt = next(it, None)
            if nxt is None:
                break
            if sess.inflight or sess.queued:
                sess.step()
            else:
                sess._steps += 1       # idle tick waiting for an arrival
        res = sess.drain()             # finish everything in flight
        dt = time.perf_counter() - t0
        toks = sum(len(res[h].tokens) for h in submitted)
        ttfts = sorted(res[h].first_token_s for h in submitted)
        p99 = ttfts[min(len(ttfts) - 1, int(len(ttfts) * 0.99))]
        return dict(dt=dt, toks=toks, tok_s=toks / dt, p99=p99,
                    peak=sess.stats["max_inflight"],
                    bytes=cache_nbytes(sess._state["cache"]))

    fx = run(paged=False)
    pg = run(paged=True)
    # pool bytes must match the fixed lanes (the page tables are the only
    # extra, a few hundred int32s)
    mem_ratio = pg["bytes"] / fx["bytes"]

    emit("serve_fleet_fixed", 1e6 / fx["tok_s"],
         f"{fx['tok_s']:.1f}tok_s_peak{fx['peak']}_p99ttft"
         f"{fx['p99'] * 1e3:.0f}ms")
    emit("serve_paged_toks", 1e6 / pg["tok_s"],
         f"{pg['tok_s']:.1f}tok_s_{pg['tok_s'] / fx['tok_s']:.2f}x_vs_fixed_"
         f"mem{mem_ratio:.3f}x", pg["tok_s"] / fx["tok_s"])
    emit("serve_paged_concurrency", 0.0,
         f"peak{pg['peak']}_vs_{fx['peak']}_at_equal_cache_mem",
         pg["peak"] / max(fx["peak"], 1))
    emit("serve_ttft_p99", pg["p99"] * 1e6,
         f"{pg['p99'] * 1e3:.0f}ms_vs_{fx['p99'] * 1e3:.0f}ms_fixed",
         fx["p99"] / max(pg["p99"], 1e-9))


def bench_train(emit, steps=24, chunk=8):
    """TrainSession steps/s vs the legacy blocking per-step loop (which
    pulled+converted a batch and forced a `float(loss)` host sync every
    step), plus the session's measured host-sync count. Smoke-scale on
    CPU: tracks the hot-loop host overhead the session removes, not TPU
    step time."""
    import jax
    from repro.configs import get_config
    from repro.models.model import Model
    from repro.launch.mesh import make_local_mesh
    from repro.dist.step import make_train_step, TrainConfig
    from repro.train.session import SessionConfig, TrainSession
    from repro.data.pipeline import batch_for_model

    cfg = get_config("yi-6b", smoke=True)
    model = Model(cfg)
    mesh = make_local_mesh(data=1, model=1)
    tc = TrainConfig(alpha=3e-3, grad_k=6, weight_k=None, worker_axes=())
    art = make_train_step(model, mesh, tc)

    def batches():
        return batch_for_model(cfg, 64, 4, seed=0)

    # legacy loop: per-step dispatch, sync batch conversion, per-step
    # float() host sync
    step = jax.jit(art.step_fn, donate_argnums=(0,))
    state = art.init_state(jax.random.PRNGKey(0))
    it = batches()
    state, m = step(state, next(it))   # compile
    float(m["loss"])
    t0 = time.perf_counter()
    syncs = 0
    for _ in range(steps):
        state, m = step(state, next(it))
        _ = float(m["loss"])           # the old loop's per-step sync
        syncs += 1
    dt = time.perf_counter() - t0
    emit("train_loop_blocking", dt / steps * 1e6,
         f"{steps / dt:.1f}steps_s_{syncs}syncs")

    # session: prefetch thread + device loss ring, per-step dispatch
    sess = TrainSession.from_artifacts(
        art, batches(), SessionConfig(log_every=0, prefetch=2),
        log=lambda *_: None)
    sess.run(2)                        # compile + prime the prefetcher
    t0 = time.perf_counter()
    sess.run(steps)
    dt = time.perf_counter() - t0
    emit("train_session_step1", dt / steps * 1e6,
         f"{steps / dt:.1f}steps_s_{sess.stats['syncs']}syncs")
    sess.close()

    # session: scan-chunked (K steps per dispatch) on top of prefetch
    sess = TrainSession.from_artifacts(
        art, batches(), SessionConfig(log_every=0, prefetch=2,
                                      scan_chunk=chunk),
        log=lambda *_: None)
    sess.run(chunk)                    # compile
    t0 = time.perf_counter()
    sess.run(steps)
    dt = time.perf_counter() - t0
    emit(f"train_session_scan{chunk}", dt / steps * 1e6,
         f"{steps / dt:.1f}steps_s_{sess.stats['syncs']}syncs")
    sess.close()


def bench_startup(emit, steps=2):
    """Cold vs warm startup through repro.perf's AOT step artifacts: a
    fresh AOT dir, then a TrainSession and a ServeSession built TWICE
    against it. Cold pays trace + lower + compile (+ export); warm
    deserializes the compiled step. The persistent XLA cache is off for
    the whole bench, so nothing a previous run left there can make the
    cold sessions warm; it is restored afterwards. Rows are
    setup-through-first-work wall time; the speedup rows are the
    machine-independent signal.

    Set BENCH_ASSERT_STARTUP=1 (the CI startup-smoke gate) to hard-fail
    unless warm < cold and the warm sessions report zero compilations.
    """
    import shutil
    import tempfile

    import jax
    from repro import perf
    from repro.configs import get_config
    from repro.models.model import Model
    from repro.launch.mesh import make_local_mesh
    from repro.dist.step import make_train_step, TrainConfig
    from repro.train.session import SessionConfig, TrainSession
    from repro.data.pipeline import batch_for_model
    from repro.serve import Request, ServeSession

    cfg = get_config("yi-6b", smoke=True)
    model = Model(cfg)
    mesh = make_local_mesh(data=1, model=1)
    tc = TrainConfig(alpha=3e-3, grad_k=6, weight_k=None, worker_axes=())
    art = make_train_step(model, mesh, tc)
    params = model.init(jax.random.PRNGKey(0))

    tmp = tempfile.mkdtemp(prefix="bench_startup_")
    cache_was_on = jax.config.jax_enable_compilation_cache
    perf.disable_persistent_cache()
    try:
        def train_once():
            t0 = time.perf_counter()
            sess = TrainSession.from_artifacts(
                art, batch_for_model(cfg, 64, 4, seed=0),
                SessionConfig(log_every=0, prefetch=0,
                              aot_dir=os.path.join(tmp, "aot_train")),
                log=lambda *_: None)
            sess.run(steps)
            dt = time.perf_counter() - t0
            stats = dict(sess.stats)
            sess.close()
            return dt, stats

        cold, st_c = train_once()
        warm, st_w = train_once()
        emit("startup_train_cold", cold * 1e6,
             f"{st_c['compilations']}compiles_{steps}steps")
        emit("startup_train_warm", warm * 1e6,
             f"{st_w['aot_loads']}aot_loads_{steps}steps")
        emit("startup_train_speedup", 0.0, f"{cold / warm:.2f}x_warm",
             cold / warm)

        def serve_once():
            t0 = time.perf_counter()
            sess = ServeSession(model, params, slots=2, max_seq=64, seed=0,
                                aot_dir=os.path.join(tmp, "aot_serve"))
            sess.submit(Request(prompt=[1, 2, 3], max_new_tokens=4))
            sess.drain()
            return time.perf_counter() - t0, dict(sess.stats)

        s_cold, sst_c = serve_once()
        s_warm, sst_w = serve_once()
        emit("startup_serve_cold", s_cold * 1e6,
             f"{sst_c['compilations']}compiles")
        emit("startup_serve_warm", s_warm * 1e6,
             f"{sst_w['aot_loads']}aot_loads")
        emit("startup_serve_speedup", 0.0, f"{s_cold / s_warm:.2f}x_warm",
             s_cold / s_warm)

        if os.environ.get("BENCH_ASSERT_STARTUP"):
            assert warm < cold, (
                f"warm TrainSession no faster: {warm:.2f}s vs {cold:.2f}s")
            assert st_w["compilations"] == 0 and st_w["aot_loads"] >= 1, (
                f"warm TrainSession recompiled: {st_w}")
            assert s_warm < s_cold, (
                f"warm ServeSession no faster: {s_warm:.2f}s vs {s_cold:.2f}s")
            assert sst_w["compilations"] == 0 and sst_w["aot_loads"] >= 1, (
                f"warm ServeSession recompiled: {sst_w}")
    finally:
        if cache_was_on:
            perf.enable_persistent_cache()
        shutil.rmtree(tmp, ignore_errors=True)


def bench_comm_codec(emit, numel=1 << 20, steps=6):
    """The fused codec stack vs the legacy three-pass path it replaced.

    * encode: one fused program (amax + quantize + bit-pack; single
      kernel launch on TPU, one XLA program on CPU) vs three separately
      dispatched passes with the code tensor materialized in between -
      at a 4MB (1M-element f32) buffer, the paper's bucket size.
    * decode: fused unpack+dequant vs two passes.
    * end-to-end: dist train step (qadam vs efadam two-way) at 4MB
      exchange buckets, smoke scale - tracks dispatch/fusion overhead of
      the wire path, not TPU perf.

    Set BENCH_ASSERT_FUSED=1 to hard-fail if fused is slower than
    legacy (the CI kernels-bench gate).
    """
    import jax
    import jax.numpy as jnp
    from repro import comm
    from repro.comm import bits as cbits
    from repro.opt import grids

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=numel, scale=0.2).astype(np.float32))
    gbytes = numel * 4 / 1e9
    checks = []

    for spec in ("log:6", "uniform:7:wire"):
        cd = comm.get_codec(spec)
        tag = spec.replace(":", "_")

        fused_enc = jax.jit(
            lambda v, cd=cd: cd._encode_impl(v, key=None, backend="jnp"))
        us_f = _time_call(lambda v: fused_enc(v).payload, x)
        emit(f"comm_encode_fused_{tag}", us_f,
             f"{gbytes / (us_f / 1e6):.2f}GB_s_4MB")

        # the pre-codec wire: amax pass, quantize pass, pack pass - each
        # its own dispatch, codes materialized between them
        amax_fn = jax.jit(grids.amax_scale)
        if spec.startswith("log"):
            quant_fn = jax.jit(lambda v, s: grids.log_quantize(v, s, 6))
        else:
            quant_fn = jax.jit(lambda v, s: jnp.clip(
                grids.uniform_quantize(v, s, 7), -127, 127))
        pack_fn = jax.jit(lambda c, b=cd.bits: cbits.pack_flat(c, b))

        # default-arg binding: the gate times these AFTER the loop, and
        # late-bound closures would make every check run the last spec
        def legacy_enc(v, a=amax_fn, q=quant_fn, p=pack_fn,
                       is_log=spec.startswith("log")):
            s = a(v) if is_log else jnp.float32(0.5)
            c = q(v, s)
            return p(c)

        us_l = _time_call(legacy_enc, x)
        emit(f"comm_encode_legacy3_{tag}", us_l,
             f"{gbytes / (us_l / 1e6):.2f}GB_s_4MB")
        emit(f"comm_encode_speedup_{tag}", 0.0, f"{us_l / us_f:.2f}x",
             us_l / us_f)
        checks.append(("encode", spec,
                       lambda v, f=fused_enc: f(v).payload, legacy_enc, x))

        wb = fused_enc(x)
        fused_dec = jax.jit(
            lambda w, cd=cd: cd._decode_impl(w, backend="jnp"))
        us_fd = _time_call(fused_dec, wb)
        emit(f"comm_decode_fused_{tag}", us_fd,
             f"{gbytes / (us_fd / 1e6):.2f}GB_s_4MB")

        unpack_fn = jax.jit(
            lambda p, b=cd.bits: cbits.unpack_flat(p, b, numel))
        if spec.startswith("log"):
            deq_fn = jax.jit(lambda c, s: grids.log_dequantize(c, s, 6))
        else:
            deq_fn = jax.jit(lambda c, s: grids.uniform_dequantize(c, s, 7))
        legacy_dec = lambda w, u=unpack_fn, d=deq_fn: d(u(w.payload),
                                                       w.scale)
        us_ld = _time_call(legacy_dec, wb)
        emit(f"comm_decode_legacy2_{tag}", us_ld,
             f"{gbytes / (us_ld / 1e6):.2f}GB_s_4MB")
        emit(f"comm_decode_speedup_{tag}", 0.0, f"{us_ld / us_fd:.2f}x",
             us_ld / us_fd)
        checks.append(("decode", spec, fused_dec, legacy_dec, wb))

    if os.environ.get("BENCH_ASSERT_FUSED"):
        # The gate guards against STRUCTURAL regressions of the fused
        # path - e.g. the XLA loop-fusion bug where the packer's strided
        # reads re-ran the transcendental quantize per lane group (2x
        # wall time; fixed with an optimization_barrier in the codec).
        # Budgets are per (direction, grid), not a blanket grace: the
        # PR-5 log-DECODE regression (0.23x: per-element exp2 on
        # unpacked codes) sat comfortably under the old uniform 1.5x
        # check because only the encode direction was asserted tightly.
        # Since the SMEM dequant LUT, fused log decode does zero
        # transcendentals while legacy still pays exp2 per element, so
        # its budget is 1.0 - fused must win outright. Encode and the
        # uniform paths keep 1.5x: on CPU those compare dispatch/fusion
        # overhead, and XLA's fused-loop codegen jitters the
        # transcendental-bound paths by up to ~1.3x either way.
        budgets = {("encode", "log"): 1.5, ("decode", "log"): 1.0,
                   ("encode", "uniform"): 1.5, ("decode", "uniform"): 1.5}
        for kind, spec, f_fn, l_fn, arg in checks:
            grid = "log" if spec.startswith("log") else "uniform"
            budget = budgets[(kind, grid)]
            fs, ls = [], []
            for _ in range(7):
                fs.append(_time_call(f_fn, arg, reps=3, warmup=1))
                ls.append(_time_call(l_fn, arg, reps=3, warmup=1))
            med_f = sorted(fs)[len(fs) // 2]
            med_l = sorted(ls)[len(ls) // 2]
            assert med_f <= med_l * budget, (
                f"fused {kind} over budget ({budget}x) vs legacy for "
                f"{spec}: median {med_f:.1f}us vs {med_l:.1f}us")

    # end-to-end dist step at 4MB exchange buckets, qadam vs efadam
    from repro.configs import get_config
    from repro.models.model import Model
    from repro.dist.step import make_train_step, TrainConfig
    from repro.data.pipeline import batch_for_model

    cfg = get_config("yi-6b", smoke=True)
    model = Model(cfg)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    batch = next(batch_for_model(cfg, 64, 4, seed=0))
    for mode in ("qadam", "efadam"):
        tc = TrainConfig(grad_k=6, weight_k=7, mode=mode,
                         exchange_bucket_bytes=4 << 20,
                         worker_axes=("data",))
        art = make_train_step(model, mesh, tc)
        state = art.init_state(jax.random.PRNGKey(0))
        step = jax.jit(art.step_fn, donate_argnums=(0,))
        state, _ = step(state, batch)          # compile
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = step(state, batch)
        jax.block_until_ready(m["loss"])
        us = (time.perf_counter() - t0) / steps * 1e6
        emit(f"comm_dist_step_{mode}_4MB", us, "smoke_1dev")


def bench_comm_cost(emit):
    """Wire bytes for ResNet-101-sized (162.9MB fp32) and VGG16-sized
    (512.3MB) models at the paper's quantization levels - reproduces the
    Comm/Size columns of Tables 2-3 analytically through our codec."""
    from repro.core.packing import packed_nbytes

    for model_name, fp32_mb in (("resnet101", 162.9), ("vgg16", 512.3)):
        n = fp32_mb * 1e6 / 4
        for bits, tag in ((32, "fp32"), (4, "log_k6_4bit"),
                          (3, "3bit"), (2, "2bit"), (1, "sign")):
            mb = packed_nbytes(int(n), bits) / 1e6
            emit(f"comm_{model_name}_{tag}", 0.0, f"{mb:.2f}MB_per_iter")
        for k_x, tag in ((7, "8bit"), (6, "7bit"), (3, "4bit")):
            mb = packed_nbytes(int(n), k_x + 1) / 1e6
            emit(f"size_{model_name}_kx{k_x}", 0.0, f"{mb:.2f}MB_model")


def _table_protocol(emit, table, seeds, steps):
    import jax
    sys.path.insert(0, os.path.join(ROOT, "examples"))
    import paper_repro as pr
    from repro.core.qadam import (QAdamConfig, qadam, terngrad_sgd, ef_sgdm,
                                  wquan)
    from repro.data.pipeline import ClsDataConfig, classification_dataset

    data = classification_dataset(
        ClsDataConfig(seed=1 if table == 2 else 2))
    xte, yte = data[2], data[3]
    methods = {
        "qadam_fp32": (lambda: qadam(QAdamConfig(alpha=2e-3, grad_q=None)),
                       None),
        "qadam_3bit": (lambda: qadam(QAdamConfig(alpha=2e-3,
                                                 grad_q="log:2")), None),
        "qadam_2bit": (lambda: qadam(QAdamConfig(alpha=2e-3,
                                                 grad_q="log:1")), None),
        "qadam_3bit_qx5": (lambda: qadam(QAdamConfig(
            alpha=2e-3, grad_q="log:2", weight_q="uniform_amax:5")), None),
        "terngrad": (lambda: terngrad_sgd(alpha=2e-2), None),
        "blockwise_ef": (lambda: ef_sgdm(alpha=2e-3, beta=0.9,
                                         grad_q="blockwise:256"), None),
        "wquan_post_k5": (lambda: qadam(QAdamConfig(alpha=2e-3,
                                                    grad_q=None)), 5),
    }
    for name, (builder, wq_after) in methods.items():
        accs = []
        t0 = time.perf_counter()
        for s in range(seeds):
            p = pr.run(builder(), steps, data, jax.random.PRNGKey(s + table),
                       seed=s * 100 + table, n_workers=4)
            if wq_after is not None:
                p = wquan(p, k_x=wq_after, absolute=False)
            accs.append(pr.accuracy(p, xte, yte))
        us = (time.perf_counter() - t0) * 1e6 / max(1, seeds)
        emit(f"table{table}_{name}", us,
             f"acc={np.mean(accs) * 100:.2f}pm{np.std(accs) * 100:.2f}")


def bench_table2(emit):
    _table_protocol(emit, 2, seeds=2, steps=150)


def bench_table3(emit):
    _table_protocol(emit, 3, seeds=2, steps=150)


def bench_fig34(emit, steps=120):
    """Figures 3-4: convergence curves (train loss every 20 steps)."""
    import jax
    sys.path.insert(0, os.path.join(ROOT, "examples"))
    import paper_repro as pr
    from repro.core.qadam import QAdamConfig, qadam, apply_updates
    from repro.data.pipeline import (ClsDataConfig, classification_dataset,
                                     classification_batches)

    data = classification_dataset(ClsDataConfig(seed=3))
    xtr, ytr = data[0], data[1]
    for name, gq, ef in (("fp32", None, True), ("log2bit_ef", "log:1", True),
                         ("log2bit_noef", "log:1", False)):
        opt = qadam(QAdamConfig(alpha=2e-3, grad_q=gq, error_feedback=ef))
        params = pr.mlp_init(jax.random.PRNGKey(0), xtr.shape[1], 256,
                             int(ytr.max()) + 1)
        state = opt.init(params)
        gfun = jax.jit(jax.value_and_grad(pr.loss_fn))
        it = classification_batches(xtr, ytr, 128, seed=0)
        curve = []
        for t in range(steps):
            x, y = next(it)
            fp = opt.forward_params(params, state)
            loss, g = gfun(fp, x, y)
            upd, state = opt.update(g, state, params)
            params = apply_updates(params, upd)
            if t % 20 == 0:
                curve.append(round(float(loss), 4))
        emit(f"fig34_{name}", 0.0, "curve=" + "|".join(map(str, curve)))


def bench_adapt(emit, steps=250, seeds=2, workers=4, replan_every=25,
                budget=0.6):
    """Runtime-adaptive bit allocation (repro.adapt) vs the paper's
    fixed k_g=6 wire on the multi-worker protocol: measured payload
    bytes/step and final test loss for each arm. The two ratio rows are
    GATED compare.py floors: the adaptive wire must come in at or under
    ``budget``x the fixed bytes (adapt_bytes_reduction >= 1/budget)
    while holding final loss within 1% (adapt_loss_parity >= 0.99)."""
    import jax
    sys.path.insert(0, os.path.join(ROOT, "examples"))
    import paper_repro as pr
    from repro.data.pipeline import ClsDataConfig, classification_dataset

    data = classification_dataset(ClsDataConfig(seed=1))
    arms = {}
    for name, adaptive in (("fixed_kg6", False), ("adaptive", True)):
        losses, bps = [], []
        t0 = time.perf_counter()
        for s in range(seeds):
            _, info = pr.run_quantized(
                steps, data, jax.random.PRNGKey(s), seed=s * 100,
                n_workers=workers, adaptive=adaptive, budget_ratio=budget,
                replan_every=replan_every)
            losses.append(info["final_test_loss"])
            bps.append(info["bytes_per_step"])
        us = (time.perf_counter() - t0) * 1e6 / max(1, seeds)
        arms[name] = (float(np.mean(losses)), float(np.mean(bps)))
        emit(f"adapt_{name}", us,
             f"loss={arms[name][0]:.4f}_{arms[name][1] / 1e3:.1f}KB_step")
    (fl, fb), (al, ab) = arms["fixed_kg6"], arms["adaptive"]
    emit("adapt_bytes_reduction", 0.0,
         f"{fb / ab:.3f}x_fewer_bytes_budget{budget}", fb / ab)
    emit("adapt_loss_parity", 0.0,
         f"fixed{fl:.4f}_vs_adaptive{al:.4f}", fl / al)


def bench_dist(emit, steps=6, warmup=2):
    """Flat vs hierarchical parameter-server topology on a simulated
    2-node x 4-device mesh (needs
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``). Two rows
    are GATED compare.py floors: ``dist_hier_inter_bytes`` (the 2x4
    hierarchy must ship <= 0.27x flat's inter-node wire bytes; the
    registry accounting says exactly 1/devices_per_node = 0.25x) and
    ``dist_bucket_tuned`` (the bucket the exchange tuner picks must not
    lose to the config default - the incumbent joins the sweep, so
    >= 1.0 by construction)."""
    import jax
    if jax.device_count() < 8:
        emit("dist_skipped", 0.0,
             f"needs_8_devices_have_{jax.device_count()}")
        return
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.dist import topology as T
    from repro.dist.step import make_train_step, TrainConfig
    from repro.models.model import Model
    from repro.perf.autotune import tune_exchange_buckets
    from repro.train.loop import comm_bytes_per_step

    model = Model(get_config("yi-6b", smoke=True))
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:8]).reshape(2, 4, 1),
        ("pod", "data", "model"))
    rng = np.random.default_rng(0)
    tok = jnp.asarray(rng.integers(0, model.cfg.vocab_size,
                                   size=(8, 32)).astype(np.int32))
    batch = {"tokens": tok, "targets": tok}

    cfgs, times = {}, {}
    for name, topo in (("flat", T.FlatTopology()),
                       ("hier", T.HierarchicalTopology(2, 4))):
        tc = TrainConfig(worker_axes=("pod", "data"), topology=topo)
        art = make_train_step(model, mesh, tc)
        state = art.init_state(jax.random.PRNGKey(0))
        step = jax.jit(art.step_fn, donate_argnums=(0,))
        for _ in range(warmup):
            state, metrics = step(state, batch)
        jax.block_until_ready(metrics["loss"])
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step(state, batch)
        jax.block_until_ready(metrics["loss"])
        times[name] = (time.perf_counter() - t0) / steps * 1e6
        cfgs[name] = (art, tc)
        del state

    fb = comm_bytes_per_step(*cfgs["flat"])["tiers"]["inter"]["total"]
    hb = comm_bytes_per_step(*cfgs["hier"])["tiers"]["inter"]["total"]
    emit("dist_step_flat_2x4", times["flat"], "smoke_8dev")
    emit("dist_step_hier_2x4", times["hier"],
         f"{times['flat'] / times['hier']:.2f}x_vs_flat")
    emit("dist_hier_inter_bytes", 0.0,
         f"hier{hb}B_vs_flat{fb}B_per_step", fb / hb)
    rep = tune_exchange_buckets(model, mesh, cfgs["hier"][1], batch,
                                candidates=(0, 1 << 20), steps=3,
                                warmup=1)
    emit("dist_bucket_tuned", rep["timings_s"][rep["best"]] * 1e6,
         f"bucket{rep['best']}B_{rep['speedup']:.2f}x_vs_default",
         rep["speedup"])


def bench_roofline(emit):
    path = os.path.join(ROOT, "results", "dryrun_single.jsonl")
    if not os.path.exists(path):
        emit("roofline_missing", 0.0, "run repro.launch.dryrun first")
        return
    with open(path) as f:
        for line in f:
            r = json.loads(line)
            if r.get("skipped") or r.get("error"):
                continue
            t = r["roofline"]
            ur = r.get("useful_flops_ratio")
            emit(f"roofline_{r['arch']}_{r['shape']}", 0.0,
                 f"c={t['compute_s']:.4f}s;m={t['memory_s']:.4f}s;"
                 f"x={t['collective_s']:.4f}s;bound={r['bottleneck']};"
                 f"useful={round(ur, 3) if ur else 'na'}")


BENCHES = {
    "kernels": bench_kernels,
    "comm_codec": bench_comm_codec,
    "comm_cost": bench_comm_cost,
    "serve": bench_serve,
    "fleet": bench_fleet,
    "train": bench_train,
    "startup": bench_startup,
    "table2_cifar100_analogue": bench_table2,
    "table3_cifar10_analogue": bench_table3,
    "fig34_convergence": bench_fig34,
    "adapt": bench_adapt,
    "dist": bench_dist,
    "roofline": bench_roofline,
}

# named suites: coarse groups for CI jobs / snapshot baselines
SUITES = {
    "serve": ["serve"],
    "fleet": ["fleet"],
    "train": ["train"],
    "comm": ["comm_codec", "comm_cost"],
    "kernels": ["kernels", "comm_codec", "comm_cost"],
    "startup": ["startup"],
    "adapt": ["adapt"],
    "dist": ["dist"],
    "paper": ["table2_cifar100_analogue", "table3_cifar10_analogue",
              "fig34_convergence", "comm_cost"],
    "all": list(BENCHES),
}


# suites dominated by host allocation (session scheduling, request
# bookkeeping, numpy batch staging) where glibc malloc contention shows
# up as run-to-run noise; tcmalloc flattens it (SNIPPETS 1/2 preload the
# same library for exactly these loops)
HOST_ALLOC_HEAVY = {"serve", "fleet", "train", "startup"}


def _check_tcmalloc(names) -> None:
    if not HOST_ALLOC_HEAVY & set(names):
        return
    if "tcmalloc" in os.environ.get("LD_PRELOAD", ""):
        return
    import glob
    hits = sorted(glob.glob("/usr/lib/*/libtcmalloc*.so*")
                  + glob.glob("/usr/lib/libtcmalloc*.so*"))
    if not hits:
        return                     # not installed: nothing to suggest
    print(f"# warning: host-alloc-heavy bench without tcmalloc; numbers "
          f"may carry malloc noise. Re-run with\n"
          f"#   LD_PRELOAD={hits[0]} "
          f"TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD=60000000000",
          file=sys.stderr, flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma list of benches")
    ap.add_argument("--suite", default=None, choices=sorted(SUITES),
                    help="named bench group (overrides --only)")
    ap.add_argument("--trace", action="store_true",
                    help="wrap the run in jax.profiler.trace with one "
                         "TraceAnnotation per bench")
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="trace output dir (default results/traces)")
    args, _ = ap.parse_known_args()
    if args.suite:
        names = SUITES[args.suite]
    elif args.only:
        names = args.only.split(",")
    else:
        names = list(BENCHES)
    _check_tcmalloc(names)

    print("name,us_per_call,derived,ratio")

    def emit(name, us, derived, ratio=None):
        cell = "" if ratio is None else f"{ratio:.4f}"
        print(f"{name},{us:.1f},{derived},{cell}", flush=True)

    from repro.perf import profiling
    with profiling.trace(args.trace_dir, enabled=args.trace) as tdir:
        for n in names:
            with profiling.span(f"bench:{n}"):
                BENCHES[n](emit)
    if tdir:
        print(f"# trace: {tdir}", file=sys.stderr)


if __name__ == "__main__":
    main()
