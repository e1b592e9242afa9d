"""Model FLOP/s utilisation of training: the operations the forward and
backward passes require for the window's tokens (``bench/work.py``), over
the window's seconds, the chips and the chip's bf16 peak, in percent."""
from bench import work

KERNELS = ()


def read(r):
    if r.kind != "train":
        return None
    flops = r.tokens * work.train_flops_per_token(r.dims, r.seq)
    return 100.0 * flops / r.window_s / (r.chips * r.peaks["bf16_flop_s"])
