"""Share of the traced serving window in which no operation ran on the
device, in percent."""
KERNELS = ()


def read(r):
    if r.kind != "serve" or r.trace is None:
        return None
    t = r.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
