"""Roofline share of the codec kernels (``codec_ef_encode`` and
``codec_decode``) of the train step.

Bytes each worker's step needs, for a model of N parameters over W
workers, with b_g / b_x the lane bits of the update / weight codes:

* update encode: read d (4 B), write the codes (b_g / 8 B) and the new
  residual e (4 B), per parameter of the shard: (8 + b_g / 8) N;
* update decode: read W rows of codes for this worker's chunk of N / W
  and write them as float32 rows: (b_g / 8 + 4) N;
* weight encode: read this worker's master chunk and write its codes:
  (4 + b_x / 8) N / W;
* weight decode: read every chunk's codes, write the float32 weights:
  (b_x / 8 + 4) N.

All bound by memory (a few operations per byte). Least time = bytes /
peak HBM bandwidth; the share is that over the kernels' summed device
time, in percent."""
KERNELS = ("codec_ef_encode", "codec_decode")


def _lane_bits(max_abs_code):
    for b in (2, 3, 4, 6, 8, 16):
        if max_abs_code <= 2 ** (b - 1) - 1:
            return b
    return 32


def read(r):
    if r.kind != "train" or r.trace is None:
        return None
    t = sum(r.trace["kernel_s"].get(k, 0.0) for k in KERNELS)
    if t <= 0:
        return None
    W = r.chips
    bg = _lane_bits(r.train["grad_bits"] + 1)        # log grid: |c| <= k+1
    bx = _lane_bits(2 ** r.train["weight_bits"] - 1)  # uniform grid lanes
    coded = [n for n in r.leaves if n >= r.train["weight_min_numel"]]
    N, Nx = sum(r.leaves), sum(coded)
    per_step = ((8 + bg / 8) * N + (bg / 8 + 4) * N
                + (4 + bx / 8) * Nx / W + (bx / 8 + 4) * Nx)
    nbytes = per_step * r.steps * W
    return 100.0 * nbytes / r.peaks["hbm_byte_s"] / t
