"""Roofline share of the paged KV gather kernel (``paged_gather``).

What attention needs of the cache is to read the keys and values of
every earlier position once per dispatch: a request of prompt p reads,
for its first token, in each prefill chunk starting at position s, the
s positions before the chunk, and, for its i-th token (i >= 2), fed its
predecessor at position p + i - 2, the p + i - 2 positions before it.
Each position is layers x KV heads x head size x 2 (keys and values) x 2
bytes (bf16). Summed over the tokens that the window's dispatches
generated; least time = bytes / HBM bandwidth (bound by memory: a gather
does no arithmetic); the share is that over the kernel's summed device
time, in percent."""
KERNELS = ("paged_gather",)


def positions_read(prompt, first, last, chunk):
    """Cached positions the dispatches of tokens ``first`` .. ``last``
    (from 1) of one request need to read."""
    prefill = sum(range(0, prompt, chunk)) if first == 1 else 0
    lo, hi = prompt + max(first, 2) - 2, prompt + last - 2
    decode = (hi - lo + 1) * (lo + hi) // 2 if hi >= lo else 0
    return prefill + decode


def read(r):
    if r.kind != "serve" or r.trace is None or not r.spans:
        return None
    t = r.trace["kernel_s"].get("paged_gather", 0.0)
    if t <= 0:
        return None
    dm = r.dims
    per_pos = dm["L"] * dm["K"] * dm["hd"] * 2 * 2
    chunk = int(r.traffic["prefill_chunk"])
    positions = sum(positions_read(p, a, b, chunk) for p, a, b in r.spans)
    return 100.0 * positions * per_pos / r.peaks["hbm_byte_s"] / t
