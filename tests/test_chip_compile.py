"""Compile-only checks of the main-path Pallas kernels for a described TPU
v5e at yi-6b widths (d_model 4096, d_ff 11008, 4 KV heads x 128).

Nothing runs: the TPU compiler that is installed here compiles for a chip
that is described, not attached, and refuses what the chip would refuse
(blocks off the (8, 128) tiling, scalars outside SMEM, more VMEM than a
kernel may use). Each test also checks that the named kernel is really in
the compiled program. The topology is described inside a module fixture,
so every test worker collects the same tests and only the worker that
runs this file loads the TPU library.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.shard_map import shard_map
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.comm import bits as B
from repro.comm import kernels as K
from repro.comm import matmul as MM
from repro.kernels import adam_ef as AK
from repro.opt import grids
from repro.serve import paged

D_MODEL, D_FF, KV_HEADS, HEAD_DIM = 4096, 11008, 4, 128
LEAF = D_MODEL * D_FF          # one MLP projection
# generated code of one leaf's exchange, and of a whole one-layer step.
# A wire row or kernel tile laid out unlike the flat tensor makes XLA
# copy it with unrolled code: 68-70 MB per leaf and 746 MB for the
# four-device step at these widths, which took five minutes and 25 GB
# of host memory to compile. Without such copies they are 0.5-0.8 MB
# and 31-35 MB.
LEAF_CODE_BYTES = 8 << 20
STEP_CODE_BYTES = 96 << 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_cache(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_cache):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def tpu_paths(monkeypatch):
    """Steer the auto backends (``jax.default_backend()`` still names
    the CPU here) to the paths they take on a TPU: Pallas kernels,
    compiled rather than interpreted."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


def _has_kernel(text, name):
    return any('custom_call_target="tpu_custom_call"' in line
               and f"/{name}/pallas_call" in line
               for line in text.splitlines())


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("k,n", [(D_MODEL, D_FF), (D_FF, D_MODEL)])
@pytest.mark.parametrize("k_x", [6, 4])   # int8 codes; packed 6-bit lanes
def test_fused_dequant_matmul(one_chip, k, n, k_x):
    bits = B.lane_bits_for(2 ** k_x)
    pack = bits if bits < 8 else 0
    if pack:   # quantize_params pads packed rows to whole blocks
        blk = B.block_codes(pack)
        width = -(-n // blk) * B.block_nbytes(pack)
        codes = _sds(one_chip, (k, width), jnp.uint8)
    else:
        codes = _sds(one_chip, (k, n), jnp.int8)
    for rows in (4, 512):      # decode slots, a prefill chunk
        text = _compile(
            lambda x, c, s: MM._matmul_pallas(
                x, c, s, k_x=k_x, pack_bits=pack, n=n, w_dtype="bfloat16",
                cast_dtype="bfloat16", transpose=False, interpret=False),
            _sds(one_chip, (rows, k), jnp.bfloat16), codes,
            _sds(one_chip, (), jnp.float32))
        assert _has_kernel(text, "dequant_matmul")


def _codec_rows(bits):
    """Payload rows of one LEAF, and the rows of its 128-lane floats."""
    rows = -(-LEAF // (K.lanes_in(bits) * K.ENC_ROWS)) * K.ENC_ROWS
    return rows, rows * K.planes(bits)


@pytest.mark.parametrize("kind,bits,k,static", [
    ("log", 4, 4, False),          # the exchange's Q_g, amax scale
    ("uniform", 8, 6, False),      # the weight wire, amax scale
    ("uniform", 8, 6, True),       # the paper's absolute Q_x grid
])
def test_codec_encode(one_chip, kind, bits, k, static):
    _, frows = _codec_rows(bits)
    scale = jnp.float32(0.5) if static else None
    text = _compile(
        lambda x: K.encode_pallas(x, kind, bits, k, scale=scale,
                                  interpret=False),
        _sds(one_chip, (frows, K.LANES), jnp.float32))
    assert _has_kernel(text, "codec_encode")


@pytest.mark.parametrize("kind,bits,k", [("log", 4, 4), ("uniform", 8, 6)])
def test_codec_ef_encode(one_chip, kind, bits, k):
    _, frows = _codec_rows(bits)
    lut = grids.log_dequant_table(k, bits) if kind == "log" else None
    text = _compile(
        lambda x, s: K.ef_encode_pallas(x, s, kind, bits, k, lut=lut,
                                        interpret=False),
        _sds(one_chip, (frows, K.LANES), jnp.float32),
        _sds(one_chip, (), jnp.float32))
    assert _has_kernel(text, "codec_ef_encode")


@pytest.mark.parametrize("kind,bits,k", [("log", 4, 4), ("uniform", 8, 6)])
def test_codec_decode_rows(one_chip, kind, bits, k):
    rows, _ = _codec_rows(bits)
    workers = 4                    # one scale per source worker row
    rows = -(-rows // (workers * K.ENC_ROWS)) * workers * K.ENC_ROWS
    lut = grids.log_dequant_table(k, bits) if kind == "log" else None
    text = _compile(
        lambda p, s: K.decode_pallas(
            p, s, kind, bits, k, tiles_per_scale=rows // workers // K.ENC_ROWS,
            lut=lut, interpret=False),
        _sds(one_chip, (rows, K.lanes_out(bits)), jnp.uint8),
        _sds(one_chip, (workers,), jnp.float32))
    assert _has_kernel(text, "codec_decode")


def test_adam_ef_moments(one_chip):
    rows = LEAF // K.LANES
    f32 = lambda: _sds(one_chip, (rows, K.LANES), jnp.float32)
    text = _compile(
        lambda g, m, v, e, hp: AK.adam_moments_pallas(g, m, v, e, hp,
                                                      interpret=False),
        f32(), f32(), f32(), f32(), _sds(one_chip, (4,), jnp.float32))
    assert _has_kernel(text, "adam_ef_moments")


def test_adam_ef_quantize(one_chip):
    rows = LEAF // K.LANES
    text = _compile(
        lambda d, s: AK.ef_quantize_pallas(d, s, 4, interpret=False),
        _sds(one_chip, (rows, K.LANES), jnp.float32),
        _sds(one_chip, (), jnp.float32))
    assert _has_kernel(text, "adam_ef_quantize")


def test_paged_gather(one_chip):
    slots, pages_per_slot, page = 4, 64, 16
    text = _compile(
        lambda pool, tab: paged._gather_pallas(pool, tab, interpret=False),
        _sds(one_chip, (slots * pages_per_slot, page, KV_HEADS, HEAD_DIM),
             jnp.bfloat16),
        _sds(one_chip, (slots, pages_per_slot), jnp.int32))
    assert _has_kernel(text, "paged_gather")


@pytest.mark.parametrize("workers", [1, 4])
def test_exchange_leaf_code_size(topo, no_cache, tpu_paths, workers):
    """One MLP leaf through the paper's exchange (Adam+EF, Q_g encode,
    all-to-all, decode, mean) on ``workers`` described chips, state in
    and out in the train state's (1, 1, X) layout."""
    from repro.dist import topology as T
    from repro.dist.modes import WorkerCtx, get_mode
    from repro.dist.step import LeafMeta, TrainConfig
    mesh = Mesh(np.array(topo.devices[:workers]).reshape(workers, 1),
                ("data", "model"))
    c = -(-LEAF // workers)
    tc = TrainConfig(grad_k=4, weight_k=6, worker_axes=("data",))
    upd = get_mode("qadam").make_updater(tc, WorkerCtx(
        worker_axes=("data",), wsizes=(workers,), n_workers=workers,
        tiers=T.flat_tiers(("data",), (workers,))))
    meta = LeafMeta(shp=(D_MODEL, D_FF), c=c, numel=LEAF, dim=0,
                    stacked=False, shape=(D_MODEL, D_FF))

    def leaf(g, m, v, e, chunk):
        out = upd(g.reshape(-1), m.reshape(-1), v.reshape(-1),
                  e.reshape(-1), chunk.reshape(-1), meta, 1e-3, 0.999,
                  jax.random.PRNGKey(0), 0)
        return tuple(x.reshape(1, 1, -1) for x in out)

    spec = P("data", "model", None)
    fn = shard_map(leaf, mesh=mesh, in_specs=(spec,) * 5,
                   out_specs=(spec,) * 4, check_rep=False)
    sh = NamedSharding(mesh, spec)
    arg = lambda x: jax.ShapeDtypeStruct((workers, 1, x), jnp.float32,
                                         sharding=sh)
    compiled = jax.jit(fn).lower(arg(LEAF), arg(LEAF), arg(LEAF),
                                 arg(LEAF), arg(c)).compile()
    text = compiled.as_text()
    for name in ("adam_ef_moments", "codec_ef_encode", "codec_decode"):
        assert _has_kernel(text, name)
    code = compiled.memory_analysis().generated_code_size_in_bytes
    assert code < LEAF_CODE_BYTES, code


def test_four_worker_train_step(topo, no_cache, tpu_paths):
    """The four-worker QAdam-EF step of ``chip_smoke.py --chips 4`` at
    yi-6b widths (one layer, a 4,096-row vocabulary) compiles for a
    described v5e 2x2 with its kernels, bounded code and its state
    spread over the four chips."""
    from repro.configs import get_config
    from repro.dist.step import (TrainConfig, batch_shardings,
                                 make_train_step, state_template)
    from repro.models.model import Model
    cfg = dataclasses.replace(get_config("yi-6b"), n_layers=1,
                              vocab_size=4096)
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    art = make_train_step(Model(cfg), mesh, TrainConfig(
        grad_k=4, weight_k=6, worker_axes=("pod", "data")))
    seq = 2048
    batch = {"tokens": np.zeros((4, seq), np.int32),
             "targets": np.zeros((4, seq), np.int32),
             "mask": np.zeros((4, seq), np.float32)}
    shard = batch_shardings(art, batch)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=shard[k])
             for k, v in batch.items()}
    compiled = jax.jit(art.step_fn, donate_argnums=(0,)).lower(
        state_template(art), batch).compile()
    text = compiled.as_text()
    for name in ("adam_ef_moments", "codec_ef_encode", "codec_decode"):
        assert _has_kernel(text, name)
    mem = compiled.memory_analysis()
    assert mem.generated_code_size_in_bytes < STEP_CODE_BYTES, \
        mem.generated_code_size_in_bytes
    # per device: a quarter of the fp32 masters, but m, v and e of the
    # whole model (Algorithm 3) - well under one chip's 16 GB
    assert mem.argument_size_in_bytes < 4 << 30
