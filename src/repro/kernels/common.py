"""Shared Pallas kernel utilities.

TPU is the target: every kernel is written as `pl.pallas_call` with explicit
BlockSpec VMEM tiling, MXU-aligned block shapes (multiples of 128 on matmul
dims), and VMEM scratch accumulators. On this CPU container the kernels
execute under `interpret=True` (the kernel body runs in Python), which is
how the allclose sweeps in tests/ validate them against the pure-jnp oracles
in each kernel's ref.py.

VMEM budgeting follows the paper's working-set rule (§9.2): block shapes are
chosen so the live tiles fit the per-core budget in `hal.TPU_V5E.onchip_bytes`
— a kernel whose live tiles exceed on-chip memory stalls on streaming, on
the ANE and on the TPU alike.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def pick_block(dim: int, preferred: int, align: int = 128) -> int:
    """Largest MXU-aligned block <= preferred that doesn't exceed dim
    (padded). Small dims fall back to the padded dim itself."""
    if dim >= preferred:
        return preferred
    if dim >= align:
        return (dim // align) * align
    # tiny dims: round up to a sublane-friendly size
    for candidate in (8, 16, 32, 64, 128):
        if dim <= candidate:
            return candidate
    return align


def vmem_bytes(*tiles: tuple[tuple[int, ...], int]) -> int:
    """Sum of (shape, dtype_bytes) tile footprints — checked against the
    VMEM budget in kernel wrappers."""
    total = 0
    for shape, nbytes in tiles:
        n = 1
        for s in shape:
            n *= s
        total += n * nbytes
    return total


def select_from_table(idx: jnp.ndarray, values) -> jnp.ndarray:
    """Gather-free table lookup: a log2(len) select tree over scalar table
    entries. TPU Pallas has no general gather from VMEM; for small tables
    (16-entry palettes, 32 LUT segments) a select tree is the native form —
    each level is one vectorized `where` on the index bits.

    idx: integer tile with values in [0, len(values)); values: list of
    scalars (or 0-d arrays). Returns a float32 tile.
    """
    n = len(values)
    assert n & (n - 1) == 0, "table length must be a power of two"
    vals = [jnp.asarray(v, jnp.float32) for v in values]
    level = [jnp.broadcast_to(v, idx.shape) for v in vals]
    bit = 0
    while len(level) > 1:
        b = (idx >> bit) & 1
        nxt = []
        for i in range(0, len(level), 2):
            nxt.append(jnp.where(b == 1, level[i + 1], level[i]))
        level = nxt
        bit += 1
    return level[0]


def round_to_fp16(x: jnp.ndarray) -> jnp.ndarray:
    """``x.astype(float16).astype(float32)`` for an fp32 tile, bit for bit,
    in int32 arithmetic only: Mosaic on v5e cannot pack fp32 into fp16
    (``tpu.pack_subelements``). Normal results round the mantissa to 10 bits
    half-to-even; results below 2^-14 round half-to-even onto the 2^-24
    subnormal grid; past 65504 they overflow to inf; inf and NaN pass
    through; the sign rides along."""
    i32 = jnp.int32
    bits = jax.lax.bitcast_convert_type(x, i32)
    sign = bits & i32(-(2 ** 31))
    mag = bits & i32(0x7FFFFFFF)
    lsb = (mag >> 13) & 1
    normal = (mag + i32(0x0FFF) + lsb) & i32(~0x1FFF)
    # subnormal: significand (implicit bit set) in units of 2^-24
    shift = jnp.clip(i32(126) - (mag >> 23), 0, 30)
    mant = (mag & i32(0x7FFFFF)) | i32(0x800000)
    q = mant >> shift
    rem = mant & ((i32(1) << shift) - 1)
    half = i32(1) << jnp.maximum(shift - 1, 0)
    q = q + ((rem > half) | ((rem == half) & ((q & 1) == 1))).astype(i32)
    sub = jax.lax.bitcast_convert_type(
        q.astype(jnp.float32) * jnp.float32(2.0 ** -24), i32)
    out = jnp.where(mag < i32(0x38800000), sub, normal)
    out = jnp.where(out > i32(0x477FE000), i32(0x7F800000), out)
    out = jnp.where(mag >= i32(0x7F800000), mag, out)
    return jax.lax.bitcast_convert_type(out | sign, jnp.float32)
