"""Training traffic: QAdam-EF steps through the launcher's ``TrainSession``.

Set-up builds one session (``launch.train`` config, ``dist.step`` step,
``train.session.TrainSession``) on the benchmark's weights and batches,
drives it through its first three steps - reading each step's loss, the
first gradient from the optimizer's state and the masters' change after
step 3 - and times a few more to size the window. The window then runs a
fixed number of steps on the same session and ends on
``block_until_ready`` of the state. Once it has closed and the session is
freed, the plain reference follows the same three steps from the same
weights and batches, and the readings are compared leaf by leaf.

Traffic keys: ``seq`` tokens per row, ``workers`` (the mesh's data axis,
one chip each), ``rows_per_worker``, and the token stream's ``zipf_a``
and ``copy_period``.
"""
from __future__ import annotations

import functools
import gc
import math

import numpy as np

from bench import harness, program, weights
from bench.reference import model as ref_model
from bench.reference import qadam as ref_qadam
from bench.reference import train as ref_train

SETUP_STEPS = 3     # steps the reference follows
PROBE_STEPS = 3     # timed in set-up to size the window


# ---------------------------------------------------------------------------
# the batches (a copy of the program's synthetic token stream:
# Zipf-distributed ids with a copy pattern, so attention has work to do)
# ---------------------------------------------------------------------------

def batches(traffic: dict, vocab: int, seed: int):
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    probs = ranks ** -float(traffic["zipf_a"])
    probs /= probs.sum()
    B = int(traffic["workers"]) * int(traffic["rows_per_worker"])
    S, P = int(traffic["seq"]), int(traffic["copy_period"])
    half = P // 2
    while True:
        toks = rng.choice(vocab, size=(B, S + 1), p=probs)
        for start in range(0, S + 1 - P, P):
            toks[:, start + half:start + P] = toks[:, start:start + half]
        toks = toks.astype(np.int32)
        yield {"tokens": np.ascontiguousarray(toks[:, :-1]),
               "targets": np.ascontiguousarray(toks[:, 1:]),
               "mask": np.ones((B, S), np.float32)}


class _Recorded:
    """Hands the batches on, keeping a copy of the first ``n``."""

    def __init__(self, it, n):
        self.it, self.n, self.kept = it, n, []

    def __iter__(self):
        return self

    def __next__(self):
        b = next(self.it)
        if len(self.kept) < self.n:
            self.kept.append({k: v.copy() for k, v in b.items()})
        return b


# ---------------------------------------------------------------------------
# readings from the program's state
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _norm_fns():
    """Jitted readings of the program's state, compiled once per process
    (no seed-dependent constant, so the compile cache serves every run)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def mean_norms(m):
        return [jnp.linalg.norm(jnp.mean(x, axis=(0, 1)))
                for x in jax.tree.leaves(m)]

    @jax.jit
    def change_norms(master, x0):
        out = []
        for x, a in zip(jax.tree.leaves(master), jax.tree.leaves(x0)):
            flat = a.reshape(-1)
            a = jnp.pad(flat, (0, x.size - flat.size)).reshape(x.shape)
            out.append(jnp.sqrt(jnp.sum(jnp.square(x - a))))
        return out
    return mean_norms, change_norms


def _grad_norms(m_tree, beta):
    """Every leaf's norm of the workers' mean first gradient, from m
    after step 1 (m = (1 - beta) g; leaves are (workers, 1, numel),
    sharded over the workers)."""
    return [float(n) / (1.0 - beta) for n in _norm_fns()[0](m_tree)]


def _update_norms(master_tree, shapes, key):
    """Every leaf's norm of the masters' change from the benchmark's
    weights. Master leaves are (workers, 1, c): the flat leaf, zero padded
    to workers x c, one chunk per worker."""
    x0 = weights.make_params(shapes, key)
    norms = _norm_fns()[1](master_tree, x0)
    del x0
    return [float(n) for n in norms]


def compare(prog: dict, ref: dict, limits: dict, *, min_grad_share=1e-3):
    """The compared numbers, each beside its limit (see PERF.md)."""
    def worst_gap(got, want, counted):
        want_c = [w for w, c in zip(want, counted) if c]
        med = float(np.median(want_c))
        return max(abs(g - w) / max(w, med)
                   for g, w, c in zip(got, want, counted) if c)

    every = [True] * len(ref["grad_norms"])
    med_g = float(np.median(ref["grad_norms"]))
    moved = [g >= min_grad_share * med_g for g in ref["grad_norms"]]
    harness.note(f"leaves left out of update_norm_gap: "
                 f"{moved.count(False)} of {len(moved)}")
    loss_gap = max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"]))
    values = {"loss_gap": loss_gap,
              "grad_norm_gap": worst_gap(prog["grad_norms"],
                                         ref["grad_norms"], every),
              "update_norm_gap": worst_gap(prog["update_norms"],
                                           ref["update_norms"], moved)}
    return [harness.check(k, values[k], limits[k]) for k in values]


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def _train_args(conf, traffic, seed):
    from repro.launch import train as launch_train
    t = conf["train"]
    W = int(traffic["workers"])
    argv = ["--arch", conf["program"]["arch"], "--mode", t["mode"],
            "--grad-bits", str(t["grad_bits"]),
            "--weight-bits", str(t["weight_bits"]),
            "--alpha", repr(t["alpha"]), "--beta", repr(t["beta"]),
            "--theta", repr(t["theta"]), "--seq", str(traffic["seq"]),
            "--global-batch", str(W * int(traffic["rows_per_worker"])),
            "--data", str(W), "--log-every", "0", "--seed", str(seed)]
    if not t.get("error_feedback", True):
        argv.append("--no-ef")
    return launch_train.build_parser().parse_args(argv)


def build(cell, seed):
    """The session, ready for its first step, with what the readings
    need."""
    import jax
    from repro.dist import topology
    from repro.dist.step import make_train_step
    from repro.launch import train as launch_train
    from repro.launch.mesh import make_local_mesh
    from repro.train.session import TrainSession

    conf, traffic = cell.config, cell.traffic
    W = int(traffic["workers"])
    shapes = program.shapes(conf)
    key = weights.seed_key(seed)
    cfg = program.model_config(conf)
    model = program.seeded_model(cfg, shapes, key, np.float32)
    args = _train_args(conf, traffic, seed)
    tc = launch_train.train_config(args, topology.parse_topology(None))
    if tc.eps != conf["train"]["eps"]:
        raise harness.SetupError("bench: the program's Adam eps differs from "
                                 "the configuration's")
    mesh = make_local_mesh(data=W, model=1)
    feed = _Recorded(batches(traffic, cfg.vocab_size, seed), SETUP_STEPS)
    art = make_train_step(model, mesh, tc)
    sess = TrainSession.from_artifacts(art, feed,
                                       launch_train.session_config(args),
                                       key=key, log=lambda m: None)
    return sess, feed, shapes, key, tc


def first_steps(sess, shapes, key, beta):
    """Drive the session through its first three steps; the program's
    readings."""
    losses = []
    sess.run(1)
    losses += [v for _, v in sess.harvest_losses()]
    grad_norms = _grad_norms(sess.state["m"], beta)
    for _ in range(SETUP_STEPS - 1):
        sess.run(1)
        losses += [v for _, v in sess.harvest_losses()]
    update_norms = _update_norms(sess.state["master"], shapes, key)
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": update_norms}


def reference(cell, seed, kept, *, precision="float32", fault=None):
    import jax
    conf, traffic = cell.config, cell.traffic
    dm = ref_model.dims(conf["run"])
    shapes = ref_model.param_shapes(dm)
    key = weights.seed_key(seed)
    hp = ref_qadam.Hyper.from_config(conf["train"])
    devices = jax.devices()[:int(traffic["workers"])]
    with jax.default_matmul_precision("highest"):
        return ref_train.run(lambda: weights.make_params(shapes, key),
                             kept, dm, hp, devices, precision=precision,
                             fault=fault)


def run(cell, seed, seconds, trace, *, t_start, trace_dir=None,
        keep_trace=False):
    import jax
    from bench import trace as tr

    conf, traffic = cell.config, cell.traffic
    W = int(traffic["workers"])
    devices = jax.devices()[:W]
    counter = harness.CompileCounter()
    t_build = harness.now()
    sess, feed, shapes, key, tc = build(cell, seed)
    t_first = harness.now()
    prog = first_steps(sess, shapes, key, tc.beta)
    t0 = harness.now()
    sess.run(PROBE_STEPS)
    jax.block_until_ready(sess.state)
    step_s = (harness.now() - t0) / PROBE_STEPS
    n = max(2, int(round(seconds / step_s)))
    compiled_before = counter.n
    setup_s = harness.now() - t_start
    harness.note(f"set-up: start {t_build - t_start:.1f} s, build "
                 f"{t_first - t_build:.1f} s, checked steps "
                 f"{t0 - t_first:.1f} s, probe {PROBE_STEPS * step_s:.1f} s; "
                 f"{compiled_before} compilations")

    with tr.window(trace_dir if trace else None) as tw:
        t0 = harness.now()
        with tr.span("train_window"):
            sess.run(n)
            jax.block_until_ready(sess.state)
        window_s = harness.now() - t0
    last = [v for _, v in sess.harvest_losses()]
    failed = sum(1 for v in last if not math.isfinite(v))
    memory = harness.peak_memory(devices)
    window_compiles = counter.n - compiled_before
    sess.close()
    kept = feed.kept
    del sess, feed
    gc.collect()

    tokens = n * W * int(traffic["rows_per_worker"]) * int(traffic["seq"])
    device = dict(harness.device_info(W), memory_peak_bytes=memory)
    breakdown = None
    if trace:
        summary = tr.summarize(tw.path, devices=W,
                               kernels=harness.kernels_of(cell))
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        breakdown = summary["breakdown"]
        rec = Record(cell=cell, window_s=window_s, steps=n, tokens=tokens,
                     trace=summary, peaks=harness.peaks(device["kind"]),
                     leaves=[int(np.prod(s)) for s in _shape_leaves(shapes)])
        metrics = harness.read_per_layer(cell, rec)
        if not keep_trace:
            tr.cleanup(tw.path)
    else:
        metrics = {"train_tok_s": {"value": tokens / window_s / W,
                                   "unit": "tokens/s/chip"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
    ref = reference(cell, seed, kept)
    checks = compare(prog, ref, cell.limits)
    harness.note(f"compilations inside the window: {window_compiles}")
    return dict(checks=checks, attempted=n, failed=failed, metrics=metrics,
                device=device, breakdown=breakdown)


def _shape_leaves(shapes):
    import jax
    return jax.tree.leaves(shapes, is_leaf=lambda x: isinstance(x, tuple))


class Record:
    """What a per-layer reader of a training cell sees."""

    kind = "train"

    def __init__(self, *, cell, window_s, steps, tokens, trace, peaks,
                 leaves):
        self.cell, self.window_s, self.steps = cell, window_s, steps
        self.tokens, self.trace, self.peaks = tokens, trace, peaks
        self.leaves = leaves             # element count of every leaf
        self.dims = ref_model.dims(cell.config["run"])
        self.chips = int(cell.traffic["workers"])
        self.train = cell.config["train"]
        self.seq = int(cell.traffic["seq"])
