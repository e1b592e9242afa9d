"""Share of the decode lanes the window's decode dispatches ran that
emitted a token, in percent: the window's
``ServeSession.stats["decode_tokens"]`` over its ``dispatches`` times the
cell's slots. Lanes that emit nothing are free slots and slots staged
for admission, waiting for their prompt's chunks. A program that does
not count decode tokens reports nothing."""
KERNELS = ()


def read(r):
    if r.kind != "serve":
        return None
    tokens, steps = r.stats.get("decode_tokens"), r.stats.get("dispatches")
    if tokens is None or not steps:
        return None
    return 100.0 * tokens / (steps * int(r.traffic["slots"]))
