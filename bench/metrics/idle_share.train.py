"""Share of the traced training window in which no operation ran on the
device (union of the device's op intervals, averaged over the chips),
in percent."""
KERNELS = ()


def read(r):
    if r.kind != "train" or r.trace is None:
        return None
    t = r.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
