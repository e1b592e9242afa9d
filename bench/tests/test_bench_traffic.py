"""Each traffic generator reproduces itself from the seed and changes
with it; the serving mix is the same sizes in the same order for every
seed, and the serving loop places every token among the dispatches."""
import itertools

import numpy as np

from bench.tests import tiny
from bench import harness
from bench.kinds import serve_closed, train


def _take(gen, n):
    return list(itertools.islice(gen, n))


def test_train_batches_follow_the_seed():
    t = harness.find_cell("yi6b-train-1chip").traffic
    a = _take(train.batches(t, 16000, 2 ** 31 + 5), 2)
    b = _take(train.batches(t, 16000, 2 ** 31 + 5), 2)
    c = _take(train.batches(t, 16000, 2 ** 31 + 6), 2)
    for x, y in zip(a, b):
        for k in x:
            np.testing.assert_array_equal(x[k], y[k])
    assert not np.array_equal(a[0]["tokens"], c[0]["tokens"])
    # consecutive rows differ, targets are the next tokens
    assert not np.array_equal(a[0]["tokens"], a[1]["tokens"])
    np.testing.assert_array_equal(a[0]["tokens"][:, 1:],
                                  a[0]["targets"][:, :-1])
    assert a[0]["tokens"].shape == (1, 2048)


def test_serve_requests_follow_the_seed():
    t = harness.find_cell("mistral7b-int8-closed96").traffic
    n = int(t["block"])
    a = _take(serve_closed.requests(t, 32000, 7), n)
    b = _take(serve_closed.requests(t, 32000, 7), n)
    c = _take(serve_closed.requests(t, 32000, 2 ** 31 + 7), n)
    assert a == b
    assert a != c
    # every seed serves the same sizes in the same order; ids differ
    assert [(len(p), o) for p, o in a] == [(len(p), o) for p, o in c]
    assert sorted(len(p) for p, _ in a) == sorted(
        serve_closed._sizes(t["prompt"], n).tolist())
    lens = [len(p) for p, _ in a]
    outs = [o for _, o in a]
    assert min(lens) >= t["prompt"]["min"] and max(lens) <= t["prompt"]["max"]
    assert min(outs) >= t["output"]["min"] and max(outs) <= t["output"]["max"]
    assert abs(float(np.median(lens)) - t["prompt"]["median"]) < 0.1 * \
        t["prompt"]["median"]
    # every request fits a slot's cache
    assert max(lens) + max(outs) - 1 <= t["max_seq"]


def test_serve_tokens_are_placed_among_the_dispatches():
    """``generated`` gives, after every decode dispatch, the tokens each
    request holds in the session's own state; a first token from a last
    prompt chunk sent between two decode dispatches counts with the
    later one."""
    import jax

    c = tiny.cell("mistral7b-int8-closed96")
    seed = 11
    sess, _ = serve_closed.build(c, seed)
    vocab = int(c.config["run"]["vocab_size"])
    serve_closed.warmup(sess, c.traffic, vocab)
    loop = serve_closed.Loop(sess, serve_closed.requests(c.traffic, vocab,
                                                         seed),
                             int(c.traffic["clients"]))
    loop.start()
    seen = []      # (dispatches, [(prompt, tokens the session holds)])
    for _ in range(60):
        loop.step()
        gen = jax.device_get(sess._state["gen"])
        held = [(tuple(loop.out[h][1]), int(gen[s]))
                for s, h in enumerate(sess._slot_handle)
                if h is not None and h in loop.out]
        seen.append((sess.stats["dispatches"], held))
    done = {tuple(d.prompt): d for d in loop.done}
    assert len(loop.done) >= 8
    checked = 0
    for dispatches, held in seen:
        for prompt, n in held:
            if prompt in done:
                d = done[prompt]
                g = serve_closed.generated(d, dispatches)
                assert g == n or (g, n) == (0, 1) and \
                    serve_closed.generated(d, dispatches + 1) == 2
                checked += 1
    for d in loop.done:
        assert serve_closed.generated(d, d.dispatch) == len(d.tokens)
    assert checked > 20
