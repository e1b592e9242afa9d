"""A run whose timed path is broken underneath comes out not correct:
for each fault a cell can have, at a size the CPU holds (the chip check
skipped, the rest of the run as on the chip)."""
import pytest

from bench.tests import tiny


def _correct(out):
    return all(c["ok"] for c in out["checks"])


@pytest.fixture()
def unchanged_state(monkeypatch):
    """The train step returns the state it was given."""
    from repro.dist import step as dstep
    make = dstep.make_train_step

    def broken(*a, **kw):
        art = make(*a, **kw)
        return art._replace(step_fn=lambda s, b: (s, art.step_fn(s, b)[1]))
    monkeypatch.setattr(dstep, "make_train_step", broken)


@pytest.fixture()
def half_batch(monkeypatch):
    """The loss is the mean over the first half of each row's tokens."""
    from repro.models import model as mm
    loss = mm.Model.loss

    def broken(self, params, batch, ctx=mm.ShardCtx()):
        S = batch["mask"].shape[1]
        mask = batch["mask"].at[:, S // 2:].set(0)
        return loss(self, params, dict(batch, mask=mask), ctx)
    monkeypatch.setattr(mm.Model, "loss", broken)


@pytest.fixture()
def altered_token(monkeypatch):
    """The decode step emits the token after the greedy one."""
    import jax.numpy as jnp
    from repro.models import model as mm
    decode = mm.Model.decode_step

    def broken(self, *a, **kw):
        logits, cache = decode(self, *a, **kw)
        return jnp.roll(logits, 1, axis=-1), cache
    monkeypatch.setattr(mm.Model, "decode_step", broken)


def test_train_sound_run_is_correct():
    assert _correct(tiny.run(tiny.cell("yi6b-train-1chip")))


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_train_fault_is_caught(fault, request):
    request.getfixturevalue(fault)
    out = tiny.run(tiny.cell("yi6b-train-1chip"))
    assert not _correct(out), out["checks"]


def test_serve_sound_run_is_correct():
    assert _correct(tiny.run(tiny.cell("mistral7b-int8-closed96")))


def test_serve_altered_token_is_caught(altered_token):
    out = tiny.run(tiny.cell("mistral7b-int8-closed96"))
    assert not _correct(out), out["checks"]
