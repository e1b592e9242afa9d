"""Fused dequant-matmul (repro.comm.matmul): the contract is BITWISE
equality with dequantize-then-jnp.dot at every supported lane width
(3/4/6-bit packed, 8/16-bit raw), per-tensor and per-layer scales, both
backends, both orientations, plus the row-gather (embedding) path and
the shape-fallback rules.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import matmul as MM
from repro.serve.quantized import quantize_params

# k_x -> registry lane width: 3/4/6-bit lanes pack, 8/16-bit stay raw
KX_CASES = [(1, 3), (2, 4), (4, 6), (6, 8), (14, 16)]
BACKENDS = ["jnp", "pallas"]  # pallas = interpret mode off-TPU


def _leaf(k_x, shape, *, stacked=False, key=0):
    w = jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)
    params = {"blocks": {"w": w}} if stacked else {"w": w}
    q = quantize_params(params, k_x=k_x, min_numel=1, pack=True)
    return (w, q["blocks"]["w"] if stacked else q["w"])


class TestBitwiseParity:
    @pytest.mark.parametrize("k_x,bits", KX_CASES)
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_per_tensor(self, k_x, bits, backend):
        _, leaf = _leaf(k_x, (40, 384))
        assert leaf.pack_bits == (bits if bits < 8 else 0)
        x = jax.random.normal(jax.random.PRNGKey(1), (5, 40), jnp.float32)
        ref = jax.jit(lambda x: x @ leaf.dequantize().astype(x.dtype))(x)
        got = jax.jit(lambda x: leaf.astype(x.dtype).matmul(
            x, backend=backend))(x)
        assert got.dtype == ref.dtype
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    @pytest.mark.parametrize("k_x,bits", KX_CASES)
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_per_layer_scales(self, k_x, bits, backend):
        # stacked (L, K, N) leaf: one amax scale per layer, shape (L,)
        _, leaf = _leaf(k_x, (3, 24, 256), stacked=True)
        assert leaf.scale.shape == (3,)
        x = jax.random.normal(jax.random.PRNGKey(2), (3, 4, 24), jnp.float32)
        deq = leaf.dequantize()  # (L, K, N)
        ref = jnp.stack([x[l] @ deq[l].astype(x.dtype) for l in range(3)])
        got = leaf.astype(x.dtype).matmul(x, backend=backend)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    @pytest.mark.parametrize("k_x,bits", KX_CASES)
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_transpose(self, k_x, bits, backend):
        # tied-embedding head orientation: logits = x @ W.T, W (V, d)
        _, leaf = _leaf(k_x, (256, 48))
        x = jax.random.normal(jax.random.PRNGKey(3), (6, 48), jnp.float32)
        ref = jax.jit(lambda x: x @ leaf.dequantize().astype(x.dtype).T)(x)
        got = jax.jit(lambda x: leaf.astype(x.dtype).matmul_t(
            x, backend=backend))(x)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_rows_padded_to_whole_blocks(self, backend):
        # a packed row of at least one block is padded to whole blocks
        # (600 -> 1024 6-bit codes); the output is cut back to n
        w, leaf = _leaf(4, (24, 600))
        assert leaf.codes.shape == (24, 2 * 384)
        assert leaf.dequantize().shape == w.shape
        x = jax.random.normal(jax.random.PRNGKey(11), (3, 24), jnp.float32)
        ref = x @ leaf.dequantize().astype(x.dtype)
        got = leaf.astype(x.dtype).matmul(x, backend=backend)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    def test_reflection_dispatch(self):
        # models write ``x @ w.astype(x.dtype)``; jax arrays defer to the
        # leaf's __rmatmul__, so that exact spelling hits the fused path
        _, leaf = _leaf(6, (32, 128))
        x = jax.random.normal(jax.random.PRNGKey(4), (2, 32), jnp.float32)
        ref = x @ leaf.dequantize().astype(x.dtype)
        got = jax.jit(lambda x: x @ leaf.astype(x.dtype))(x)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    def test_cast_chain_bf16(self):
        # dequant -> leaf dtype -> activation dtype must stay two casts;
        # bf16 activations catch any collapsed-cast shortcut
        _, leaf = _leaf(2, (32, 256))
        x = jax.random.normal(jax.random.PRNGKey(5), (3, 32), jnp.bfloat16)
        ref = x @ leaf.dequantize().astype(x.dtype)
        for backend in BACKENDS:
            got = leaf.astype(x.dtype).matmul(x, backend=backend)
            assert got.dtype == ref.dtype
            np.testing.assert_array_equal(
                np.asarray(ref, np.float32), np.asarray(got, np.float32))

    def test_batched_lead_dims(self):
        # (B, S, K) activations flatten through the same kernel
        _, leaf = _leaf(2, (32, 128))
        x = jax.random.normal(jax.random.PRNGKey(6), (2, 5, 32), jnp.float32)
        ref = x @ leaf.dequantize().astype(x.dtype)
        for backend in BACKENDS:
            got = leaf.astype(x.dtype).matmul(x, backend=backend)
            np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


class TestKTiles:
    def test_long_k_accumulates_in_f32(self):
        # K past one K tile: the kernel sums K tiles in an f32 scratch,
        # which reorders the reduction - equal up to f32 rounding
        k = 2 * MM._MAX_K_TILE
        _, leaf = _leaf(4, (k, 512))      # 6-bit lanes, one whole block
        x = jax.random.normal(jax.random.PRNGKey(10), (4, k), jnp.float32)
        ref = x @ leaf.dequantize().astype(x.dtype)
        got = leaf.astype(x.dtype).matmul(x, backend="pallas")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


class TestTake:
    @pytest.mark.parametrize("k_x", [2, 6])
    def test_row_gather_matches_full_dequant(self, k_x):
        _, leaf = _leaf(k_x, (64, 96))
        idx = jnp.asarray([[0, 63, 7], [12, 12, 1]])
        ref = leaf.dequantize()[idx]
        got = jax.jit(leaf.take)(idx)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    def test_cast_applied(self):
        _, leaf = _leaf(2, (16, 96))
        idx = jnp.asarray([3, 1])
        got = leaf.astype(jnp.bfloat16).take(idx)
        assert got.dtype == jnp.bfloat16
        ref = leaf.dequantize()[idx].astype(jnp.bfloat16)
        np.testing.assert_array_equal(
            np.asarray(ref, np.float32), np.asarray(got, np.float32))


class TestFallbacks:
    def test_uncovered_width_falls_back_bitwise(self):
        # n=100 is not a multiple of mm_cols(): the pallas request must
        # silently take the dequantize-then-matmul path, same bits out
        _, leaf = _leaf(6, (24, 100))
        x = jax.random.normal(jax.random.PRNGKey(7), (4, 24), jnp.float32)
        ref = x @ leaf.dequantize().astype(x.dtype)
        got = leaf.astype(x.dtype).matmul(x, backend="pallas")
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    def test_auto_backend_is_jnp_off_tpu(self):
        if jax.default_backend() == "tpu":
            pytest.skip("auto resolves to pallas on TPU")
        _, leaf = _leaf(6, (24, 128))
        x = jax.random.normal(jax.random.PRNGKey(8), (4, 24), jnp.float32)
        ref = x @ leaf.dequantize().astype(x.dtype)
        got = leaf.astype(x.dtype).matmul(x)  # backend=None -> auto
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


class TestMmCols:
    def test_set_and_clear_override(self):
        bk = jax.default_backend()
        assert MM.mm_cols() == MM.MM_COLS
        try:
            MM.set_mm_cols(256, backend=bk)
            assert MM.mm_cols() == 256
        finally:
            MM.set_mm_cols(None, backend=bk)
        assert MM.mm_cols() == MM.MM_COLS

    def test_rejects_non_multiple_of_128(self):
        with pytest.raises(ValueError):
            MM.set_mm_cols(96)

    def test_wider_tile_still_bitwise(self):
        _, leaf = _leaf(2, (32, 512))
        x = jax.random.normal(jax.random.PRNGKey(9), (4, 32), jnp.float32)
        ref = x @ leaf.dequantize().astype(x.dtype)
        try:
            MM.set_mm_cols(256)
            got = leaf.astype(x.dtype).matmul(x, backend="pallas")
        finally:
            MM.set_mm_cols(None)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
