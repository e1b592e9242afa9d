"""Serving launcher: continuous-batching ServeSession with (optionally)
code-resident Q_x weights (the paper's 'Size' column, held as int codes).

  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke \
      --requests 8 --slots 4 --max-new 16 --quantized

Submitting more requests than slots exercises the scheduler: queued
requests claim slots mid-flight as earlier ones finish. `--profile DIR`
records the run's profile, with the session's `serve.*` spans, into DIR.
"""
from __future__ import annotations

import argparse
import time


def serve_params(model, args, *, log=print):
    """Random weights from ``--seed``; with ``--quantized`` the fp32 tree
    is replaced by code-resident leaves and dropped, so only the codes
    stay on the device."""
    import jax
    from repro.serve import params_nbytes, quantize_params
    params = model.init(jax.random.PRNGKey(args.seed))
    fp_bytes = params_nbytes(params)
    if not args.quantized:
        log(f"arch={args.arch} params={fp_bytes / 1e6:.1f}MB fp32")
        return params
    params = quantize_params(params, k_x=args.k_x, pack=not args.no_pack)
    q_bytes = params_nbytes(params)
    log(f"arch={args.arch} params={fp_bytes / 1e6:.1f}MB fp32 -> "
        f"{q_bytes / 1e6:.1f}MB resident codes "
        f"({q_bytes / fp_bytes:.2f}x, measured)")
    return params


def make_session(model, params, args):
    """The ``ServeSession`` the command line asks for (the launcher's
    main path; ``chip_smoke.py`` runs it too)."""
    from repro.serve import ServeSession
    return ServeSession(model, params, slots=args.slots,
                        max_seq=args.max_seq, seed=args.seed,
                        aot_dir=args.aot_dir,
                        fused_matmul=not args.no_fused_matmul,
                        paged=args.paged, page_size=args.page_size,
                        num_pages=args.num_pages,
                        prefill_chunk=args.prefill_chunk)


def build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--quantized", action="store_true",
                    help="code-resident Q_x weights (packed codes + scales;"
                         " projections run the fused dequant-matmul)")
    ap.add_argument("--k-x", type=int, default=6)
    ap.add_argument("--no-pack", action="store_true",
                    help="keep codes unpacked (one int8/int16 per code)"
                         " instead of the registry's 3/4/6-bit lanes")
    ap.add_argument("--no-fused-matmul", action="store_true",
                    help="dequantize-then-matmul instead of contracting"
                         " straight from codes (debug/perf comparison)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: one physical page pool + per-slot"
                         " page tables; concurrency is bounded by tokens in"
                         " flight, not slots * max_seq")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per page (max_seq must be a multiple)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="physical pool pages (default: fixed-lane-equal"
                         " memory, slots * max_seq / page_size)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="chunked-prefill tokens per admission dispatch")
    ap.add_argument("--slo-mix", action="store_true",
                    help="tag requests round-robin interactive/standard/"
                         "batch to exercise priority admission+preemption")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-compile-cache", action="store_true",
                    help="run without the persistent XLA compilation "
                         "cache (see repro.perf.cache for where it lives)")
    ap.add_argument("--aot-dir", default=None, metavar="DIR",
                    help="AOT artifact dir for the compiled decode step "
                         "(repro.perf.aot): warm restarts skip compilation")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="record a jax.profiler profile of the run into DIR")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    from repro.perf import profiling
    with profiling.trace(args.profile, enabled=args.profile is not None):
        _serve(args)


def _serve(args):
    from repro import perf
    if not args.no_compile_cache:
        print(f"compile cache: {perf.enable_persistent_cache()}")
    import numpy as np
    from repro.configs import get_config
    from repro.models.model import Model
    from repro.serve import Request

    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.arch_type == "encdec" or cfg.input_mode != "tokens":
        raise SystemExit("serve CLI demo supports token-input decoder LMs")
    model = Model(cfg)
    session = make_session(model, serve_params(model, args), args)
    if args.paged:
        print(f"paged cache: {session.num_pages} pages x "
              f"{session.page_size} tokens "
              f"({session.num_pages * session.page_size} tokens vs "
              f"{args.slots * args.max_seq} fixed-lane)")
    rng = np.random.default_rng(args.seed)
    slos = ["interactive", "standard", "batch"]
    reqs = [Request(prompt=list(rng.integers(1, cfg.vocab_size,
                                             size=args.prompt_len)),
                    max_new_tokens=args.max_new,
                    temperature=args.temperature,
                    slo=slos[i % 3] if args.slo_mix else "standard")
            for i in range(args.requests)]
    t0 = time.time()
    handles = [session.submit(r) for r in reqs]
    results = session.drain()
    dt = time.time() - t0
    total_new = sum(len(results[h].tokens) for h in handles)
    first = [results[h].first_token_s for h in handles
             if results[h].first_token_s is not None] or [float("nan")]
    print(f"generated {total_new} tokens over {args.requests} requests on "
          f"{args.slots} slots in {dt:.2f}s ({total_new / dt:.1f} tok/s); "
          f"first token p50 {np.percentile(first, 50):.3f}s "
          f"p95 {np.percentile(first, 95):.3f}s; stats={session.stats}")
    for i, h in enumerate(handles):
        r = results[h]
        print(f"  req{i}: {r.tokens[:12]}{'...' if len(r.tokens) > 12 else ''}"
              f" [{r.finish_reason}]")


if __name__ == "__main__":
    main()
