"""The plain reference against the program at a size the CPU holds: the
model's logits and loss, the quantizer grids, and QAdam-EF's first steps
through the benchmark's own set-up (float32 on both sides)."""
import numpy as np
import pytest

from bench.tests import tiny
from bench import program, weights
from bench.kinds import train
from bench.reference import model as M
from bench.reference import qadam as Q


@pytest.fixture(scope="module")
def setup():
    import jax
    import jax.numpy as jnp
    c = tiny.cell("yi6b-train-1chip")
    c.config["program"]["dtype"] = "float32"
    dm = M.dims(c.config["run"])
    shapes = M.param_shapes(dm)
    key = weights.seed_key(11)
    model = program.seeded_model(program.model_config(c.config), shapes,
                                 key, jnp.float32)
    batch = next(train.batches(dict(c.traffic, seq=48), dm["V"], 3))
    return c, dm, model, model.init(), jax.tree.map(jnp.asarray, batch)


def test_logits_and_loss(setup):
    import jax
    _, dm, model, params, batch = setup
    with jax.default_matmul_precision("highest"):
        got = model.forward(params, batch)[0]
        want = M.forward(params, batch["tokens"], dm)
        s, n = model.loss(params, batch)
        ref_loss = M.mean_loss(params, batch, dm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert abs(float(s / n) - float(ref_loss)) < 1e-5


@pytest.mark.parametrize("k", [4, 6])
def test_grids_match_the_program(k):
    import jax
    import jax.numpy as jnp
    from repro.opt import grids
    x = jax.random.normal(jax.random.PRNGKey(k), (4096,)) ** 3
    s = jnp.max(jnp.abs(x))
    np.testing.assert_array_equal(
        np.asarray(Q.log_grid(x, s, k)),
        np.asarray(grids.log_dequantize(grids.log_quantize(x, s, k), s, k)))
    np.testing.assert_array_equal(
        np.asarray(Q.uniform_grid(x, s, k)),
        np.asarray(grids.uniform_dequantize(grids.uniform_quantize(x, s, k),
                                            s, k)))


def test_first_qadam_steps_match(setup):
    c = setup[0]
    sess, feed, shapes, key, tc = train.build(c, 5)
    prog = train.first_steps(sess, shapes, key, tc.beta)
    sess.close()
    ref = train.reference(c, 5, feed.kept)
    got = {x["name"]: x["value"] for x in train.compare(prog, ref,
                                                        c.limits)}
    # float32 on both sides: rounding order only
    assert got["loss_gap"] < 1e-4
    assert got["grad_norm_gap"] < 1e-4
    assert got["update_norm_gap"] < 1e-3
