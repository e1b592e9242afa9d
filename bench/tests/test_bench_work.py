"""Each per-layer reader's operation and byte count against numbers
worked out by hand at one shape (yi-6b widths, 2 layers, vocab 16,000,
sequence 2048; Mistral-7B widths, 8 layers for serving)."""
import types

import pytest

from bench.tests import tiny  # noqa: F401  (puts the checkout on the path)
from bench import harness, work

YI = {"d": 4096, "H": 32, "K": 4, "hd": 128, "f": 11008, "V": 16000, "L": 2,
      "theta": 5e6, "eps": 1e-5}
MI = {"d": 4096, "H": 32, "K": 8, "hd": 128, "f": 14336, "V": 32000, "L": 8,
      "theta": 1e6, "eps": 1e-5}
PEAKS = {"bf16_flop_s": 197e12, "hbm_byte_s": 819e9}


def test_layer_and_train_flops():
    # q, o: 4096 x 4096 each; k, v: 4096 x 512 each; mlp: 3 x 4096 x 11008
    assert work.layer_matmul_params(YI) == (2 * 16_777_216 + 2 * 2_097_152
                                            + 135_266_304)
    # forward: 2 x 2048 x (2 x 173,015,040 + 4096 x 16,000) projections
    # = 1,685,774,663,680; attention 2 layers x 4 x 32 x 128 x
    # (2048 x 2049 / 2) = 68,753,031,168; backward twice the forward
    assert work.sequence_forward_flops(YI, 2048) == 1_754_527_694_848
    assert work.train_flops_per_token(YI, 2048) == pytest.approx(
        3 * 1_754_527_694_848 / 2048)


def test_served_flops():
    # prompt 3, output 2: 4 positions fed, head at 2 of them
    lp = work.layer_matmul_params(MI)
    want = (2 * 4 * 8 * lp + 2 * 2 * 4096 * 32000
            + 8 * 4 * 32 * 128 * (1 + 2 + 3 + 4))
    assert work.served_flops(MI, 3, 1, 2) == want
    # token 1 alone: the prompt's 3 positions, the head once
    assert work.served_flops(MI, 3, 1, 1) == (
        2 * 3 * 8 * lp + 2 * 4096 * 32000 + 8 * 4 * 32 * 128 * (1 + 2 + 3))
    # tokens 2 and 3 of a 3-token prompt: positions 3 and 4 fed back
    assert work.served_flops(MI, 3, 2, 3) == (
        2 * 2 * 8 * lp + 2 * 2 * 4096 * 32000 + 8 * 4 * 32 * 128 * (4 + 5))
    # a request split anywhere counts the same as whole
    whole = work.served_flops(MI, 700, 1, 50)
    for k in (1, 2, 30, 49):
        assert work.served_flops(MI, 700, 1, k) + \
            work.served_flops(MI, 700, k + 1, 50) == whole


def _rec(**kw):
    return types.SimpleNamespace(peaks=PEAKS, **kw)


def test_mfu_train():
    r = _rec(kind="train", tokens=2048 * 10, window_s=2.0, chips=1,
             dims=YI, seq=2048)
    # 10 steps of 3 x 1.7545e12 operations in 2 s on one 197 TFLOP/s chip
    assert harness.reader("mfu.train").read(r) == pytest.approx(
        100 * 10 * 3 * 1_754_527_694_848 / 2.0 / 197e12)


def test_adam_ef_roofline():
    r = _rec(kind="train", steps=4, chips=1, leaves=[1000, 24],
             trace={"kernel_s": {"adam_ef_moments": 2e-3}})
    # 28 B x 1024 parameters x 4 steps / 819 GB/s over 2 ms
    assert harness.reader("adam_ef_moments_roofline").read(r) == \
        pytest.approx(100 * 28 * 1024 * 4 / 819e9 / 2e-3)


def test_codec_roofline():
    train = {"grad_bits": 4, "weight_bits": 6, "weight_min_numel": 100}
    r = _rec(kind="train", steps=1, chips=2, leaves=[400, 50], train=train,
             trace={"kernel_s": {"codec_ef_encode": 1e-6,
                                 "codec_decode": 1e-6}})
    # 4-bit update lanes, 8-bit weight lanes (|code| <= 63 needs 8 bits);
    # N = 450, coded N_x = 400, W = 2:
    # (8.5 + 4.5) x 450 + 5 x 400 / 2 + 5 x 400 = 8,850 B per worker
    assert harness.reader("codec_roofline").read(r) == pytest.approx(
        100 * 8850 * 2 / 819e9 / 2e-6)


def test_dequant_matmul_roofline():
    traffic = {"slots": 32, "prefill_chunk": 256}
    r = _rec(kind="serve", dims=MI, traffic=traffic,
             stats={"dispatches": 1, "chunk_dispatches": 0, "admitted": 0},
             trace={"kernel_s": {"dequant_matmul": 1.0}})
    # a decode step at 32 rows is bound by bytes: int8 codes of 8 layers x
    # (2 x 4096 x 4096 + 2 x 4096 x 1024 + 3 x 4096 x 14336) and of the
    # head 4096 x 32000, plus bf16 activations in and out
    shapes = [(4096, 4096), (4096, 1024), (4096, 1024), (4096, 4096),
              (4096, 14336), (4096, 14336), (14336, 4096)]
    nbytes = 8 * sum(k * n + 32 * k * 2 + 32 * n * 2 for k, n in shapes) \
        + 4096 * 32000 + 32 * 4096 * 2 + 32 * 32000 * 2
    assert harness.reader("dequant_matmul_roofline").read(r) == \
        pytest.approx(100 * nbytes / 819e9, rel=1e-9)


def test_paged_gather_roofline():
    r = _rec(kind="serve", dims=MI,
             spans=[(100, 1, 3), (600, 1, 2), (50, 3, 4)],
             traffic={"prefill_chunk": 256},
             trace={"kernel_s": {"paged_gather": 1e-3}})
    # prompt 100, tokens 1-3: one chunk at 0 reads nothing; tokens 2 and
    # 3, fed tokens 1 and 2, read the 100 and 101 positions before them.
    # Prompt 600, tokens 1-2: chunks at 0, 256, 512 read 0 + 256 + 512;
    # token 2 reads 600. Prompt 50, tokens 3-4 only: 51 and 52.
    # Each position: 8 layers x 8 heads x 128 x 2 (k, v) x 2 B
    nbytes = (100 + 101 + 256 + 512 + 600 + 51 + 52) * 8 * 8 * 128 * 2 * 2
    assert harness.reader("paged_gather_roofline").read(r) == \
        pytest.approx(100 * nbytes / 819e9 / 1e-3)


def test_kv_pages_used():
    r = _rec(kind="serve", pages_used=0.9375)
    assert harness.reader("kv_pages_used").read(r) == pytest.approx(93.75)
    assert harness.reader("kv_pages_used").read(
        _rec(kind="train")) is None


def test_dequant_matmul_roofline_prefill():
    traffic = {"slots": 32, "prefill_chunk": 256}
    r = _rec(kind="serve", dims=MI, traffic=traffic,
             stats={"dispatches": 0, "chunk_dispatches": 2, "admitted": 1},
             trace={"kernel_s": {"dequant_matmul": 1.0}})
    # a 256-row chunk is bound by operations (2 x 256 x K x N at 197
    # TFLOP/s beats K x N bytes at 819 GB/s); the head of one row, once
    # per admission, by its 4096 x 32000 code bytes
    shapes = [(4096, 4096), (4096, 1024), (4096, 1024), (4096, 4096),
              (4096, 14336), (4096, 14336), (14336, 4096)]
    chunk = 8 * sum(2 * 256 * k * n for k, n in shapes) / 197e12
    head = (4096 * 32000 + 4096 * 2 + 32000 * 2) / 819e9
    assert harness.reader("dequant_matmul_roofline").read(r) == \
        pytest.approx(100 * (2 * chunk + head), rel=1e-9)


def test_trace_shares_and_silence():
    t = {"busy_s": 0.75, "window_s": 1.0, "collective_exposed_s": 0.02,
         "kernel_s": {}}
    r = _rec(kind="train", trace=t, steps=4, chips=4)
    assert harness.reader("idle_share.train").read(r) == pytest.approx(25.0)
    # no kernel time: no roofline
    assert harness.reader("adam_ef_moments_roofline").read(
        _rec(kind="train", trace=t, steps=4, chips=1, leaves=[1])) is None
    s = _rec(kind="serve", trace=t, spans=[(5, 1, 5)], window_s=1.0,
             chips=1, dims=MI)
    assert harness.reader("idle_share.serve").read(s) == pytest.approx(25.0)
    assert harness.reader("mfu.serve").read(s) == pytest.approx(
        100 * work.served_flops(MI, 5, 1, 5) / 197e12)
