"""Bit-lane packing: the byte layout every wire, residency, and checkpoint
payload in the repo ships.

Supported lane widths are ``SUPPORTED_BITS`` = (2, 3, 4, 6, 8, 16). The
odd widths pack across byte boundaries in *groups*: ``lcm(bits, 8)`` bits
of codes become whole bytes, so a group of ``group_codes(bits)`` codes
maps to ``group_nbytes(bits)`` bytes (3-bit: 8 codes -> 3 bytes; 6-bit:
4 codes -> 3 bytes). The members of a group are ``PLANE`` = 128 codes
apart (one vreg of lanes), see :data:`PLANE`; within a group, codes are
biased by ``2^(bits-1)`` below 8 bits (two's complement at 8 and 16)
and shifted in little-endian order. 8-bit lanes are the int8 view.

Everything here is pure jnp arithmetic (no dtype views), so the *same*
functions run inside the fused Pallas kernel bodies
(``repro.comm.kernels``) and in the jnp reference backend - the two
backends cannot drift.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

SUPPORTED_BITS = (2, 3, 4, 6, 8, 16)


def group_codes(bits: int) -> int:
    """Codes per whole-byte packing group: lcm(bits, 8) / bits."""
    return math.lcm(bits, 8) // bits


def group_nbytes(bits: int) -> int:
    """Bytes per packing group: lcm(bits, 8) / 8."""
    return math.lcm(bits, 8) // 8


def payload_nbytes(numel: int, bits: int) -> int:
    """Exact payload bytes for ``numel`` codes at a lane width: whole
    groups only (the tail group is padded with zero codes). Pure
    accounting - any positive width is accepted (the analytic 'Comm'
    tables quote 1-bit sign and 32-bit f32 rows); actual pack/unpack is
    restricted to SUPPORTED_BITS."""
    if bits <= 0:
        raise ValueError(f"bits={bits} must be positive")
    g, b = group_codes(bits), group_nbytes(bits)
    return -(-int(numel) // g) * b


def lane_bits_for(max_abs_code: int) -> int:
    """Smallest supported lane whose signed range [-(2^(b-1)),
    2^(b-1)-1] holds codes with |c| <= max_abs_code."""
    for b in SUPPORTED_BITS:
        if max_abs_code <= 2 ** (b - 1) - 1:
            return b
    raise ValueError(f"codes of magnitude {max_abs_code} exceed 16 bits")


def _bias(bits: int) -> int:
    # <8-bit lanes use the historical biased-unsigned layout; 8/16-bit
    # lanes are two's complement (the low bits of an int8/int16 view).
    return (1 << (bits - 1)) if bits < 8 else 0


# Codes of one packing group sit PLANE apart, not side by side: a block
# of ``group_codes * PLANE`` codes is read as ``group_codes`` planes of
# PLANE lanes, and writes ``group_nbytes`` byte planes of PLANE lanes.
# Packing then only slices whole 128-lane vregs - no lane-strided access,
# which the TPU's kernel compiler cannot lower. A row's last, partial
# block uses planes of ``w < PLANE`` lanes, so the payload stays exactly
# ``payload_nbytes`` bytes.
PLANE = 128

# Version of the byte layout above. Anything that stores payloads apart
# from the code that packed them (codec-compressed checkpoints, compiled
# steps kept on disk) records it and refuses another: 1 put a group's
# codes side by side and each 16-bit lane's two bytes together; 2 puts
# them PLANE apart in byte planes.
LAYOUT = 2


def block_codes(bits: int) -> int:
    """Codes in one whole packing block (``group_codes`` planes)."""
    return group_codes(bits) * PLANE


def block_nbytes(bits: int) -> int:
    """Payload bytes of one whole packing block."""
    return group_nbytes(bits) * PLANE


def lane_values(codes, bits: int):
    """Signed codes -> the unsigned lane values a group packs (int32)."""
    return (codes.astype(jnp.int32) + _bias(bits)) & ((1 << bits) - 1)


def signed_codes(u, bits: int):
    """Inverse of :func:`lane_values` -> codes (int8, or int16 for
    16-bit lanes)."""
    if bits < 8:
        codes = u - _bias(bits)
    else:
        half = 1 << (bits - 1)
        codes = ((u + half) & ((1 << bits) - 1)) - half
    return codes.astype(jnp.int16 if bits == 16 else jnp.int8)


def pack_planes(planes, bits: int):
    """The ``group_codes`` lane-value planes of a group (equal shapes,
    int32) -> its ``group_nbytes`` byte planes (int32 values 0..255).
    Plane-wise, so a kernel can feed it planes it read with a stride."""
    val = planes[0]
    for j in range(1, group_codes(bits)):   # <= 24 bits per group
        val = val | (planes[j] << (j * bits))
    return [(val >> (8 * b)) & 0xFF for b in range(group_nbytes(bits))]


def unpack_planes(byte_planes, bits: int):
    """Inverse of :func:`pack_planes` -> ``group_codes`` lane-value
    planes (int32)."""
    val = byte_planes[0]
    for b in range(1, group_nbytes(bits)):
        val = val | (byte_planes[b] << (8 * b))
    mask = (1 << bits) - 1
    return [(val >> (j * bits)) & mask for j in range(group_codes(bits))]


def _pack_block(u, bits: int, w: int):
    """(..., g*w) lane values -> (..., nb*w) byte values (int32)."""
    g = group_codes(bits)
    out = pack_planes([u[..., j * w:(j + 1) * w] for j in range(g)], bits)
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=-1)


def _unpack_block(p, bits: int, w: int):
    """(..., nb*w) byte values -> (..., g*w) lane values (int32)."""
    nb = group_nbytes(bits)
    out = unpack_planes([p[..., b * w:(b + 1) * w] for b in range(nb)],
                        bits)
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=-1)


def _by_blocks(x, fn, per_in: int):
    """Apply ``fn(block, w)`` to a row's whole blocks (``per_in`` input
    lanes each) and to its tail block; concatenate the results."""
    rows, L = x.shape
    full, rest = divmod(L, per_in)
    if full == 1 and rest == 0:          # one block per row (kernel tiles)
        return fn(x, PLANE)
    parts = []
    if full:
        blk = fn(x[:, :full * per_in].reshape(rows, full, per_in), PLANE)
        parts.append(blk.reshape(rows, -1))
    if rest:
        parts.append(fn(x[:, full * per_in:], rest * PLANE // per_in))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)


def pack_lanes(codes2d: jax.Array, bits: int) -> jax.Array:
    """(R, L) signed int codes -> (R, L*bits/8) uint8, each row packed
    independently. L must be a multiple of group_codes(bits)."""
    if bits not in SUPPORTED_BITS:
        raise ValueError(f"bits={bits} not in {SUPPORTED_BITS}")
    L = codes2d.shape[1]
    assert L % group_codes(bits) == 0, (L, bits)
    u = lane_values(codes2d, bits)
    out = _by_blocks(u, lambda b, w: _pack_block(b, bits, w),
                     block_codes(bits))
    return out.astype(jnp.uint8)


def unpack_lanes(payload2d: jax.Array, bits: int, L: int) -> jax.Array:
    """Inverse of pack_lanes -> (R, L) codes (int8, or int16 for 16-bit
    lanes). The payload may hold more codes than L (rows padded to whole
    blocks); the first L are returned."""
    if bits not in SUPPORTED_BITS:
        raise ValueError(f"bits={bits} not in {SUPPORTED_BITS}")
    u = _by_blocks(payload2d.astype(jnp.int32),
                   lambda b, w: _unpack_block(b, bits, w), block_nbytes(bits))
    if u.shape[1] != L:
        u = u[:, :L]
    return signed_codes(u, bits)


# ---------------------------------------------------------------------------
# kernel tile views: the fused codec kernels see every row as whole
# blocks. A tail block's g planes of w lanes are spread to PLANE-lane
# planes (zero-filled) on the way in and gathered back on the way out,
# so a kernel that packs whole blocks writes exactly pack_lanes' bytes.
# ---------------------------------------------------------------------------

def _split_tail(L: int, bits: int):
    g = group_codes(bits)
    lg = -(-L // g) * g
    full, rest = divmod(lg, block_codes(bits))
    return lg, full, rest // g


def to_blocks(x_rows: jax.Array, bits: int, nblk: int) -> jax.Array:
    """(R, L) values -> (R, nblk * block_codes) in whole-block layout."""
    R, L = x_rows.shape
    g, blk = group_codes(bits), block_codes(bits)
    lg, full, w = _split_tail(L, bits)
    x = jnp.pad(x_rows, ((0, 0), (0, lg - L))) if lg != L else x_rows
    parts = [x[:, :full * blk]]
    if w:
        t = x[:, full * blk:].reshape(R, g, w)
        parts.append(jnp.pad(t, ((0, 0), (0, 0), (0, PLANE - w)))
                     .reshape(R, blk))
    used = full + (1 if w else 0)
    parts.append(jnp.zeros((R, (nblk - used) * blk), x.dtype))
    return jnp.concatenate(parts, axis=-1)


def from_blocks(y: jax.Array, L: int, bits: int) -> jax.Array:
    """Inverse of :func:`to_blocks` on decoded values -> (R, L)."""
    R = y.shape[0]
    g, blk = group_codes(bits), block_codes(bits)
    lg, full, w = _split_tail(L, bits)
    parts = [y[:, :full * blk]]
    if w:
        t = y[:, full * blk:(full + 1) * blk].reshape(R, g, PLANE)
        parts.append(t[:, :, :w].reshape(R, g * w))
    return jnp.concatenate(parts, axis=-1)[:, :L]


def payload_from_blocks(p: jax.Array, L: int, bits: int) -> jax.Array:
    """Whole-block payload rows of L codes -> (R, payload_nbytes(L))."""
    R = p.shape[0]
    nb, bb = group_nbytes(bits), block_nbytes(bits)
    _, full, w = _split_tail(L, bits)
    parts = [p[:, :full * bb]]
    if w:
        t = p[:, full * bb:(full + 1) * bb].reshape(R, nb, PLANE)
        parts.append(t[:, :, :w].reshape(R, nb * w))
    return jnp.concatenate(parts, axis=-1)


def payload_to_blocks(p: jax.Array, L: int, bits: int,
                      nblk: int) -> jax.Array:
    """Inverse of :func:`payload_from_blocks`: (R, payload_nbytes(L))
    -> (R, nblk * block_nbytes) whole-block payload rows."""
    R = p.shape[0]
    nb, bb = group_nbytes(bits), block_nbytes(bits)
    _, full, w = _split_tail(L, bits)
    parts = [p[:, :full * bb]]
    if w:
        t = p[:, full * bb:].reshape(R, nb, w)
        parts.append(jnp.pad(t, ((0, 0), (0, 0), (0, PLANE - w)))
                     .reshape(R, bb))
    used = full + (1 if w else 0)
    parts.append(jnp.zeros((R, (nblk - used) * bb), p.dtype))
    return jnp.concatenate(parts, axis=-1)


# ---------------------------------------------------------------------------
# flat / row-chunked views (the shapes the wire and residency paths use)
# ---------------------------------------------------------------------------

def pack_flat(codes: jax.Array, bits: int) -> jax.Array:
    """Any-shape codes -> flat uint8 payload of payload_nbytes(numel)."""
    flat = codes.reshape(-1)
    numel = flat.shape[0]
    g = group_codes(bits)
    pad = (-numel) % g
    flat = jnp.pad(flat, (0, pad))
    return pack_lanes(flat.reshape(1, -1), bits).reshape(-1)


def unpack_flat(payload: jax.Array, bits: int, numel: int) -> jax.Array:
    """Inverse of pack_flat -> (numel,) codes."""
    return unpack_lanes(payload.reshape(1, -1), bits, numel)[0]


def pack_rows(codes_rows: jax.Array, bits: int) -> jax.Array:
    """(n_rows, c) codes -> (n_rows, payload_nbytes(c)) uint8; each row
    packed independently so chunk boundaries stay byte-aligned on the
    wire (the all_to_all moves whole rows)."""
    n_rows, c = codes_rows.shape
    g = group_codes(bits)
    pad = (-c) % g
    rows = jnp.pad(codes_rows, ((0, 0), (0, pad)))
    return pack_lanes(rows, bits)


def unpack_rows(payload_rows: jax.Array, bits: int, c: int) -> jax.Array:
    """Inverse of pack_rows -> (n_rows, c) codes."""
    return unpack_lanes(payload_rows, bits, c)


def pad_rows(x: jax.Array, n_rows: int) -> jax.Array:
    """Flatten and zero-pad into (n_rows, ceil(numel/n_rows)) ownership
    rows (the worker-chunk layout of Algorithm 2)."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    c = -(-n // n_rows)
    return jnp.pad(flat, (0, n_rows * c - n)).reshape(n_rows, c)


def packed_nbytes(numel: int, bits: int) -> int:
    """Compat alias (the historical ``repro.core.packing`` name)."""
    return payload_nbytes(numel, bits)
