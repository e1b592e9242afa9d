"""Model FLOP/s utilisation of serving: the operations the tokens that
the window's dispatches generated require (a request's first token its
prompt's positions, each later one its fed-back predecessor, through
every layer, and the head; attention as half the square;
``bench/work.py``), over the window's seconds and the chip's bf16 peak,
in percent."""
from bench import work

KERNELS = ()


def read(r):
    if r.kind != "serve" or not r.spans:
        return None
    flops = sum(work.served_flops(r.dims, p, a, b) for p, a, b in r.spans)
    return 100.0 * flops / r.window_s / (r.chips * r.peaks["bf16_flop_s"])
