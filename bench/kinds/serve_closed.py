"""Closed-loop serving traffic through the launcher's ``ServeSession``.

``clients`` callers each keep one request in the session and send the
next as soon as the host holds the previous result. Requests are greedy,
with no EOS, so each runs for exactly its drawn output length. Lengths
come in blocks of ``block`` requests: every block holds the same sizes -
the quantiles of a clamped log-normal - in an order drawn from the
traffic's ``order_seed``, the same for every run: which requests a
window catches whole depends on that order, and a seed of its own would
change the work by up to 16 % (the token ids and weights come from the
run's seed). Token ids are uniform over the vocabulary.

Set-up makes the benchmark's weights (bfloat16, one jitted call), codes
them through the program's ``quantize_params``, builds the session with
``launch.serve.make_session``, runs ``warmup`` requests that use every
shape the window will (both prefill-chunk programs, the decode step, the
page release) and then fills the session: every client sends, and
set-up ends when ``clients`` requests have completed. The window drives
``submit`` / ``step`` / ``result`` only; it opens and closes on a
``harvest``, which waits for every step dispatched before it.

The window's work is the tokens its decode dispatches generated. With no
EOS a request's tokens come one per decode dispatch, the last from the
dispatch after which its result is harvested (the session harvests a
slot as soon as it can have finished), the first - from the last prompt
chunk - together with the second. So once every request in flight at
the close has finished (the loop runs on, untimed), each request's
tokens are placed among the dispatches, and those of the window counted.

After that the session is freed and the reference recomputes the logits
of a sample of the requests that generated in the window (drawn from the
seed, the longest among them) over each prompt with its served tokens;
the widest gap between the reference's best logit and that of a served
token is compared with its limit.
"""
from __future__ import annotations

import collections
import gc
import statistics

import numpy as np

from bench import harness, program, weights
from bench import trace as tr
from bench.reference import model as ref_model
from bench.reference import serve as ref_serve


# ---------------------------------------------------------------------------
# requests
# ---------------------------------------------------------------------------

def _sizes(dist: dict, n: int) -> np.ndarray:
    nd = statistics.NormalDist()
    q = (np.arange(n) + 0.5) / n
    z = np.array([nd.inv_cdf(float(p)) for p in q])
    x = np.round(float(dist["median"]) * np.exp(float(dist["sigma"]) * z))
    return np.clip(x, int(dist["min"]), int(dist["max"])).astype(np.int64)


def requests(traffic: dict, vocab: int, seed: int):
    """Endless (prompt ids, output length) pairs: the sizes in one order
    for every seed, the token ids from the seed."""
    order = np.random.default_rng(int(traffic["order_seed"]))
    rng = np.random.default_rng(seed)
    n = int(traffic["block"])
    plens, outs = _sizes(traffic["prompt"], n), _sizes(traffic["output"], n)
    while True:
        for p, o in zip(order.permutation(plens), order.permutation(outs)):
            yield rng.integers(1, vocab, size=int(p)).tolist(), int(o)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def _serve_args(conf, traffic, seed):
    from repro.launch import serve as launch_serve
    s = conf["serve"]
    argv = ["--arch", conf["program"]["arch"], "--k-x", str(s["k_x"]),
            "--slots", str(traffic["slots"]),
            "--max-seq", str(traffic["max_seq"]), "--seed", str(seed),
            "--prefill-chunk", str(traffic["prefill_chunk"])]
    if s.get("quantized", True):
        argv.append("--quantized")
    if traffic.get("paged", True):
        argv += ["--paged", "--page-size", str(traffic["page_size"]),
                 "--num-pages", str(traffic["num_pages"])]
    return launch_serve.build_parser().parse_args(argv)


def build(cell, seed):
    import jax
    import jax.numpy as jnp
    from repro.launch import serve as launch_serve
    from repro.models.model import Model
    from repro.serve import quantize_params

    conf, traffic = cell.config, cell.traffic
    cfg = program.model_config(conf)
    shapes = program.shapes(conf)
    key = weights.seed_key(seed)
    # the program's own layout must match the benchmark's
    program.seeded_model(cfg, shapes, key, jnp.bfloat16)
    args = _serve_args(conf, traffic, seed)
    params = weights.make_params(shapes, key, jnp.bfloat16)
    if args.quantized:
        params = quantize_params(params, k_x=args.k_x,
                                 pack=not args.no_pack)
    jax.block_until_ready(params)
    sess = launch_serve.make_session(Model(cfg), params, args)
    return sess, params


# A finished request: send and harvest times, prompt, served tokens, finish
# reason, and the session's decode dispatches when it was harvested.
Done = collections.namedtuple(
    "Done", "sent done prompt tokens reason dispatch")


def generated(d: Done, dispatches: int) -> int:
    """Tokens of ``d`` generated by the session's first ``dispatches``
    decode dispatches (see the module's docstring)."""
    n = len(d.tokens)
    first = d.dispatch - n + 2      # the dispatch of tokens 1 and 2
    return 0 if dispatches < first else min(n, dispatches - first + 2)


class Loop:
    """The closed loop: ``clients`` callers over one session."""

    def __init__(self, sess, gen, clients):
        from repro.serve import Request
        self.sess, self.gen, self.Request = sess, gen, Request
        self.clients = clients
        self.out = {}         # handle -> (send time, prompt)
        self.done = []        # Done, in harvest order

    def send(self):
        prompt, n = next(self.gen)
        with tr.span("submit"):
            h = self.sess.submit(self.Request(prompt=prompt,
                                              max_new_tokens=n))
        self.out[h] = (harness.now(), prompt)

    def start(self):
        for _ in range(self.clients):
            self.send()

    def _collect(self):
        t, d = harness.now(), self.sess.stats["dispatches"]
        finished = []
        for h in list(self.out):
            r = self.sess.result(h)
            if r is not None:
                finished.append((h, r))
        for h, r in finished:
            sent, prompt = self.out.pop(h)
            self.done.append(Done(sent, t, prompt, r.tokens,
                                  r.finish_reason, d))
            self.send()
        return len(finished)

    def step(self):
        with tr.span("step"):
            self.sess.step()
        return self._collect()

    def sync(self):
        """Wait for every dispatched step (the session's harvest)."""
        with tr.span("sync"):
            self.sess.harvest()
        return self._collect()

    def pages_used(self) -> float:
        return 1.0 - self.sess.free_pages / self.sess.num_pages


def warmup(sess, traffic, vocab):
    """Requests that touch every program the window uses: a prompt of
    more than one chunk (the mid and the final chunk program), the decode
    step and the release of a finished slot's pages."""
    from repro.serve import Request
    c = int(traffic["prefill_chunk"])
    rng = np.random.default_rng(0)
    for plen in (c + 3, 5):
        sess.submit(Request(prompt=rng.integers(1, vocab, plen).tolist(),
                            max_new_tokens=4))
    sess.drain()


def run(cell, seed, seconds, trace, *, t_start, trace_dir=None,
        keep_trace=False, control=None):
    """One run; with ``control`` (a precision below float32) the result
    also holds ``control``: the check with the reference at that
    precision put in the program's place."""
    import jax

    conf, traffic = cell.config, cell.traffic
    device = jax.devices()[0]
    counter = harness.CompileCounter()
    t_build = harness.now()
    sess, params = build(cell, seed)
    vocab = int(conf["run"]["vocab_size"])
    t_warm = harness.now()
    warmup(sess, traffic, vocab)
    t_fill = harness.now()
    loop = Loop(sess, requests(traffic, vocab, seed), int(traffic["clients"]))
    loop.start()
    while len(loop.done) < int(traffic["clients"]):
        loop.step()
    loop.sync()
    memory_setup = harness.peak_memory([device])
    compiled_before = counter.n
    setup_s = harness.now() - t_start
    harness.note(f"set-up: start {t_build - t_start:.1f} s, build "
                 f"{t_warm - t_build:.1f} s, warm-up {t_fill - t_warm:.1f} s, "
                 f"fill {harness.now() - t_fill:.1f} s; {compiled_before} "
                 f"compilations; peak memory {memory_setup}")

    pages, in_use = [], []
    with tr.window(trace_dir if trace else None) as tw:
        with tr.span("serve_window"):
            stats0 = dict(sess.stats)
            t0 = harness.now()
            t_end = t0 + seconds
            while harness.now() < t_end:
                loop.step()
                pages.append(loop.pages_used())
                in_use.append(harness.memory_in_use([device]))
            loop.sync()
            window_s = harness.now() - t0
    d0, d1 = stats0["dispatches"], sess.stats["dispatches"]
    stats = {k: sess.stats[k] - stats0.get(k, 0) for k in sess.stats}
    # the window's own peak: the most in use after any of its steps (the
    # process's peak_bytes_in_use is set-up's, when it codes the weights)
    memory = max((m for m in in_use if m is not None), default=None)
    memory_life = harness.peak_memory([device])
    window_compiles = counter.n - compiled_before
    open_at_close = set(loop.out)
    steps_after = 0
    while open_at_close & set(loop.out):
        loop.step()
        steps_after += 1
        if steps_after > 2 * int(traffic["max_seq"]):
            raise RuntimeError("requests open at the close never finished")
    del sess, params
    gc.collect()

    spans = [(d, generated(d, d0), generated(d, d1)) for d in loop.done]
    spans = [(d, g0, g1) for d, g0, g1 in spans if g1 > g0]
    served = [d for d, _, _ in spans]
    out_tokens = sum(g1 - g0 for _, g0, g1 in spans)
    failed = sum(1 for d in served if d.reason != "length")
    dev = dict(harness.device_info(1), memory_peak_bytes=memory)
    breakdown = None
    if trace:
        summary = tr.summarize(tw.path, devices=1,
                               kernels=harness.kernels_of(cell))
        dev.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        breakdown = summary["breakdown"]
        rec = Record(cell=cell, window_s=window_s,
                     spans=[(len(d.prompt), g0 + 1, g1)
                            for d, g0, g1 in spans],
                     stats=stats, pages_used=float(np.mean(pages)),
                     trace=summary, peaks=harness.peaks(dev["kind"]))
        metrics = harness.read_per_layer(cell, rec)
        if not keep_trace:
            tr.cleanup(tw.path)
    else:
        metrics = {"serve_tok_s": {"value": out_tokens / window_s,
                                   "unit": "tokens/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
    harness.note(f"window: {d1 - d0} decode dispatches, {out_tokens} "
                 f"tokens of {len(served)} requests, {window_s:.3f} s, "
                 f"pages in use {float(np.mean(pages)):.4f}, session stats "
                 f"{stats}, compilations inside the window "
                 f"{window_compiles}; memory in use in the window up to "
                 f"{memory}, the process's peak {memory_life}; "
                 f"{steps_after} steps after the close")
    checked = sample(served, seed, int(traffic["check_tokens"]))
    gaps, low = served_gaps(cell, seed, checked,
                            precision=control or "float32")
    limit = cell.limits["served_logit_gap"]
    out = dict(checks=[harness.check("served_logit_gap", _widest(gaps),
                                     limit)],
               attempted=len(served), failed=failed, metrics=metrics,
               device=dev, breakdown=breakdown)
    if control:
        out["control"] = [harness.check("served_logit_gap", _widest(low),
                                        limit)]
    return out


def _widest(gaps):
    return max(float(g.max()) for g in gaps) if gaps else float("inf")


def sample(done, seed, min_tokens):
    """Finished requests to check: the longest (prompt and output) and
    others drawn from the seed until ``min_tokens`` served tokens."""
    if not done:
        return []
    order = sorted(range(len(done)),
                   key=lambda i: -(len(done[i].prompt)
                                   + len(done[i].tokens)))
    picked = [order[0]]
    rng = np.random.default_rng(seed)
    rest = [int(i) for i in rng.permutation(order[1:])]
    while rest and sum(len(done[i].tokens) for i in picked) < min_tokens:
        picked.append(rest.pop(0))
    return [done[i] for i in picked]


def served_gaps(cell, seed, checked, *, precision="float32"):
    """Per request: the reference's best logit minus the served token's,
    at every served position; with ``precision`` below float32 also the
    gap of the token that precision ranks first (the control)."""
    import jax
    import jax.numpy as jnp
    conf = cell.config
    dm = ref_model.dims(conf["run"])
    shapes = ref_model.param_shapes(dm)
    params = weights.make_params(shapes, weights.seed_key(seed),
                                 jnp.bfloat16)
    kw = dict(k_x=int(conf["serve"]["k_x"]),
              min_numel=int(conf["serve"]["min_numel"]),
              length=int(cell.traffic["max_seq"]))
    served, control = [], []
    with jax.default_matmul_precision("highest"):
        for d in checked:
            prompt, toks = d.prompt, d.tokens
            seq = list(prompt) + list(toks[:-1])
            ref = ref_serve.logits(params, seq, dm, **kw)[len(prompt) - 1:]
            best = jnp.max(ref, axis=-1)
            tok = jnp.asarray(toks, jnp.int32)
            served.append(np.asarray(
                best - jnp.take_along_axis(ref, tok[:, None], 1)[:, 0]))
            if precision != "float32":
                low = ref_serve.logits(params, seq, dm, precision=precision,
                                       **kw)[len(prompt) - 1:]
                pick = jnp.argmax(low, axis=-1)
                control.append(np.asarray(
                    best - jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]))
    return served, control


class Record:
    """What a per-layer reader of a serving cell sees."""

    kind = "serve"

    def __init__(self, *, cell, window_s, spans, stats, pages_used, trace,
                 peaks):
        self.cell, self.window_s, self.trace = cell, window_s, trace
        self.peaks, self.stats = peaks, stats
        # (prompt length, first, last): the tokens first..last (from 1)
        # of each request that the window's dispatches generated
        self.spans = spans
        self.pages_used = pages_used     # mean share of the page pool
        self.dims = ref_model.dims(cell.config["run"])
        self.serve = cell.config["serve"]
        self.traffic = cell.traffic
        self.chips = 1
