"""Roofline share of the fused dequant-matmul kernel (``dequant_matmul``)
in serving.

Every decode step contracts the slots' rows with every projection of
every layer and with the head; every prefill chunk contracts its rows
with every projection, and an admission needs the head for the last
prompt position once (the program's chunks each compute it; only one
is needed). A call of rows x
K x N needs 2 rows K N operations and reads the codes (K N bytes at int8
lanes) and the activations and writes the result (bf16). Least time per
call = the larger of operations / bf16 peak and bytes / HBM bandwidth;
the share is the least time of the window's calls over the kernel's
summed device time, in percent. The calls are counted from the session's
counters of decode and chunk dispatches and of admissions in the
window."""
KERNELS = ("dequant_matmul",)


def _least(rows, K, N, pk):
    ops = 2.0 * rows * K * N
    nbytes = K * N * 1 + rows * K * 2 + rows * N * 2
    return max(ops / pk["bf16_flop_s"], nbytes / pk["hbm_byte_s"])


def _shapes(dm):
    d, H, K, hd, f = dm["d"], dm["H"], dm["K"], dm["hd"], dm["f"]
    return [(d, H * hd), (d, K * hd), (d, K * hd), (H * hd, d),
            (d, f), (d, f), (f, d)]


def read(r):
    if r.kind != "serve" or r.trace is None:
        return None
    t = r.trace["kernel_s"].get("dequant_matmul", 0.0)
    if t <= 0:
        return None
    dm, pk = r.dims, r.peaks
    slots, chunk = int(r.traffic["slots"]), int(r.traffic["prefill_chunk"])
    step = dm["L"] * sum(_least(slots, K, N, pk) for K, N in _shapes(dm)) \
        + _least(slots, dm["d"], dm["V"], pk)
    chunk_t = dm["L"] * sum(_least(chunk, K, N, pk) for K, N in _shapes(dm))
    head_t = _least(1, dm["d"], dm["V"], pk)
    least = (r.stats["dispatches"] * step
             + r.stats["chunk_dispatches"] * chunk_t
             + r.stats["admitted"] * head_t)
    return 100.0 * least / t
