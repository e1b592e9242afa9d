"""The program's spans and counters (``repro.perf.profiling``): the serving
session's harvest counters, decode-token count and first-token time, its
``serve.*`` spans in a recorded profile, the training session's batch
wait, and the train step's ``qadam.*`` scopes in the compiled program's
metadata."""
import os
import re
import sys
import time

import jax
import pytest

from repro.configs import get_config
from repro.models.model import Model
from repro.perf import profiling
from repro.serve import Request, ServeSession

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NEW_COUNTERS = ("sync_drain_ns", "fetch_ns", "idle_host_ns", "decode_tokens")
# (prompt length, output length): more requests than slots, prompts of
# one to three chunks
TRAFFIC = ((20, 12), (5, 9), (11, 10), (3, 1), (17, 6))


@pytest.fixture(scope="module")
def yi():
    cfg = get_config("yi-6b", smoke=True)
    model = Model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _session(yi, **kw):
    _, model, params = yi
    kw = dict(dict(slots=2, max_seq=64, paged=True, page_size=16,
                   prefill_chunk=8), **kw)
    return ServeSession(model, params, **kw)


def _request(plen, n):
    return Request(prompt=list(range(1, plen + 1)), max_new_tokens=n)


class TestSpan:
    def test_adds_elapsed_ns_to_its_counter(self):
        stats = {"wait_ns": 5}
        with profiling.span("test.wait", stats, "wait_ns", handle=3):
            time.sleep(0.002)
        assert isinstance(stats["wait_ns"], int)
        assert stats["wait_ns"] >= 5 + 2_000_000

    def test_without_stats_is_a_bare_annotation(self):
        assert isinstance(profiling.span("test.plain", handle=1),
                          jax.profiler.TraceAnnotation)


class TestServeCounters:
    def test_counters_across_harvests(self, yi):
        """Greedy, no EOS, harvested mid-run and at the end: the decode
        tokens are every token but each request's first; every new counter
        is an int that never falls; fetches and decode tokens are counted
        only at syncs, and host idle only after one."""
        sess = _session(yi)
        t0 = time.perf_counter_ns()
        handles = [sess.submit(_request(p, n)) for p, n in TRAFFIC]
        obs = [dict(sess.stats)]
        results = {}
        for i in range(30):
            sess.step()
            obs.append(dict(sess.stats))
            if i in (6, 14):
                sess.harvest()
                obs.append(dict(sess.stats))
            for h in handles:
                r = sess.result(h)
                if r is not None:
                    results[h] = r
        results.update(sess.drain())
        obs.append(dict(sess.stats))
        elapsed = time.perf_counter_ns() - t0

        assert sorted(results) == handles
        assert all(r.finish_reason == "length" for r in results.values())
        assert [len(results[h].tokens) for h in handles] == \
            [n for _, n in TRAFFIC]
        assert sess.stats["decode_tokens"] == sum(
            len(r.tokens) - 1 for r in results.values())
        for k in NEW_COUNTERS:
            vals = [o[k] for o in obs]
            assert all(isinstance(v, int) for v in vals), k
            assert vals == sorted(vals), k
        assert sess.stats["idle_host_ns"] > 0
        # drain, fetch and idle host time never overlap
        assert sum(sess.stats[k] for k in ("sync_drain_ns", "fetch_ns",
                                           "idle_host_ns")) <= elapsed
        for a, b in zip(obs, obs[1:]):
            if b["syncs"] == a["syncs"]:
                for k in ("fetch_ns", "sync_drain_ns", "decode_tokens"):
                    assert b[k] == a[k], k
            else:
                assert b["fetch_ns"] > a["fetch_ns"]
        # a call that follows a call without a sync, and syncs nothing
        # itself, finds a step already queued: no idle time
        quiet = 0
        for a, b, c in zip(obs, obs[1:], obs[2:]):
            if a["syncs"] == b["syncs"] == c["syncs"]:
                quiet += 1
                assert c["idle_host_ns"] == b["idle_host_ns"]
        assert quiet > 5

    def test_first_token_time(self, yi):
        """``first_token_s`` ends at the return of the first sync after the
        request's final prompt chunk: no earlier than that sync's start,
        no later than the harvest that hands out the result; after
        ``drain()`` the session keeps no per-request timing."""
        sess = _session(yi)
        finals, syncs = {}, []
        chunk_fn, sync = sess._chunk_fn, sess._sync

        def traced_chunk_fn(is_last):
            fn = chunk_fn(is_last)
            if not is_last:
                return fn

            def final(params, state, slot, *args):
                finals[sess._slot_handle[int(slot)]] = time.perf_counter_ns()
                return fn(params, state, slot, *args)
            return final

        def traced_sync():
            syncs.append(time.perf_counter_ns())
            return sync()

        sess._chunk_fn, sess._sync = traced_chunk_fn, traced_sync
        sent, handed = {}, {}
        for p, n in TRAFFIC:
            t0 = time.perf_counter_ns()
            h = sess.submit(_request(p, n))
            sent[h] = (t0, time.perf_counter_ns())
        for i in range(200):
            if len(handed) == len(sent):
                break
            sess.step()
            if i % 3 == 2:
                sess.harvest()
            t = time.perf_counter_ns()
            for h in sent:
                r = sess.result(h)
                if r is not None:
                    handed[h] = (r, t)
        assert len(handed) == len(sent)
        sess.drain()
        assert not sess._submit_ns and not sess._first_token_s
        for h, (r, t_handed) in handed.items():
            lo, hi = sent[h]
            first_ns = r.first_token_s * 1e9
            assert lo + first_ns <= t_handed
            after_final = min(t for t in syncs if t > finals[h])
            assert hi + first_ns >= after_final
        # a request with more than one token is handed out at a later
        # harvest than the one that brought its first token
        assert any(r.first_token_s * 1e9 + sent[h][1] < t
                   for h, (r, t) in handed.items() if len(r.tokens) > 3)


class TestServeSpans:
    def test_profile_holds_the_session_phases(self, yi, tmp_path):
        if ROOT not in sys.path:
            sys.path.insert(0, ROOT)
        from bench import trace as tr
        sess = _session(yi)
        sess.submit(_request(5, 2))
        sess.drain()                 # compiled outside the profile
        with profiling.trace(str(tmp_path)):
            for p, n in TRAFFIC:
                sess.submit(_request(p, n))
            sess.drain()
        host, _ = tr.load(str(tmp_path))
        spans = {}
        for s, e, name, _ in host:
            if name.startswith("serve."):
                spans.setdefault(name, []).append((s, e))
        assert set(spans) == {
            "serve.submit", "serve.schedule", "serve.prefill_chunk",
            "serve.decode", "serve.harvest", "serve.sync.drain",
            "serve.sync.fetch", "serve.collect", "serve.first_token"}
        assert len(spans["serve.submit"]) == len(TRAFFIC)
        assert len(spans["serve.first_token"]) == len(TRAFFIC)
        assert len(spans["serve.sync.drain"]) == \
            len(spans["serve.sync.fetch"]) == len(spans["serve.harvest"])
        for (ds, de), (fs, fe) in zip(sorted(spans["serve.sync.drain"]),
                                      sorted(spans["serve.sync.fetch"])):
            assert de <= fs
            assert any(hs <= ds and fe <= he
                       for hs, he in spans["serve.harvest"])


class TestTrainTracing:
    def test_step_scopes_in_compiled_metadata(self):
        from repro.data.pipeline import batch_for_model
        from repro.dist.step import TrainConfig, make_train_step
        cfg = get_config("yi-6b", smoke=True)
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        art = make_train_step(Model(cfg), mesh, TrainConfig(
            grad_k=4, weight_k=6, worker_axes=("data",)))
        state = jax.eval_shape(art.init_state, jax.random.PRNGKey(0))
        batch = next(batch_for_model(cfg, 16, 2, seed=5))
        text = jax.jit(art.step_fn).lower(state, batch).compile().as_text()
        names = set(re.findall(r'op_name="([^"]*)"', text))
        for scope in ("qadam.broadcast", "jvp(qadam.loss)",
                      "transpose(jvp(qadam.loss))", "qadam.update",
                      "qadam.exchange"):
            assert any(scope in n for n in names), scope

    def test_session_counts_batch_wait(self):
        import jax.numpy as jnp
        from repro.core.qadam import QAdamConfig, qadam
        from repro.train.session import SessionConfig, TrainSession

        def batches():
            while True:
                yield {"x": jnp.ones((4,), jnp.float32)}

        params = {"w": jnp.zeros((4,), jnp.float32)}
        opt = qadam(QAdamConfig(alpha=1e-2))
        loss = lambda p, b: jnp.sum((p["w"] - b["x"]) ** 2)
        with TrainSession.from_optimizer(
                opt, loss, params, batches(),
                SessionConfig(log_every=0, prefetch=1)) as sess:
            sess.run(3)
            wait = sess.stats["batch_wait_ns"]
            assert isinstance(wait, int) and wait > 0
            sess.run(2)
            assert sess.stats["batch_wait_ns"] > wait
