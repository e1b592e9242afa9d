"""Roofline share of the Adam+EF moments kernel (``adam_ef_moments``).

Per step every worker passes over its whole model shard once: it reads
the gradient, m, v and e and writes m, v and d = alpha m / sqrt(v + eps)
+ e, all float32: 28 bytes per parameter, at about 2 operations per
byte - bound by memory. Least time = bytes / peak HBM bandwidth; the
share is that over the kernel's summed device time, in percent."""
KERNELS = ("adam_ef_moments",)
BYTES_PER_PARAM = 7 * 4


def read(r):
    if r.kind != "train" or r.trace is None:
        return None
    t = r.trace["kernel_s"].get("adam_ef_moments", 0.0)
    if t <= 0:
        return None
    nbytes = BYTES_PER_PARAM * sum(r.leaves) * r.steps * r.chips
    return 100.0 * nbytes / r.peaks["hbm_byte_s"] / t
