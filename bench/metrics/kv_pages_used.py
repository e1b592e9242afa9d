"""Share of the KV page pool's pages that requests hold, the mean over
the window's decode steps (read from the session's free-page count after
each step), in percent: the cache memory the traffic really uses."""
KERNELS = ()


def read(r):
    if r.kind != "serve" or r.pages_used is None:
        return None
    return 100.0 * r.pages_used
