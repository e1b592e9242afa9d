"""Share of the serving window in which the session's own harvest and
admission held the device idle, in percent: the window's
``ServeSession.stats["fetch_ns"]`` (the copy of the slots' rows after a
sync has waited for every dispatched step) plus ``idle_host_ns`` (the
session's time from a sync's return to its next decode or prefill
dispatch), over the window's seconds. Measured where the work happens,
with the profiler off as well as on; a program that keeps neither counter
reports nothing."""
KERNELS = ()


def read(r):
    if r.kind != "serve":
        return None
    fetch, idle = r.stats.get("fetch_ns"), r.stats.get("idle_host_ns")
    if fetch is None or idle is None:
        return None
    return 100.0 * (fetch + idle) / 1e9 / r.window_s
