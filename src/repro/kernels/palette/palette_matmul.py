"""palette_matmul: int4 palette-LUT weights, dequantized inside the kernel.

The paper's headline compression result (§7.3): the int4 lookup-table form
*streams* on every ANE generation — four-bit indices cross DRAM and the
16-entry fp16 codebook reconstructs them at the multiplier input, 2.37x
faster than fp16 on a bandwidth-bound stack. The TPU-native transcription:
the packed nibbles cross HBM->VMEM (4x fewer weight bytes), and the
codebook lookup happens *in the kernel*, between the VMEM load and the MXU
dot — the multiplier-input reconstruction point, exactly.

TPU Pallas has no general VMEM gather, so the 16-entry lookup is a 4-level
select tree over the index bits (`select_from_table`) — each level one
vectorized where, fully VPU-resident.

Weight layout: pairs packed along K (low nibble = even row), so a (bk, bn)
dense block unpacks from a (bk/2, bn) packed block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compat
from repro.kernels.common import (cdiv, pad_to, pick_block,
                                  select_from_table)


def pack_kn(w: np.ndarray, iters: int = 12) -> tuple[np.ndarray, np.ndarray]:
    """Fit a 16-entry codebook (Lloyd) and pack indices along K, low nibble
    first. Returns (packed (K/2, N) uint8, lut (16,) float32)."""
    w = np.asarray(w, dtype=np.float32)
    assert w.ndim == 2 and w.shape[0] % 2 == 0
    flat = w.reshape(-1)
    code = np.quantile(flat, np.linspace(0, 1, 16)).astype(np.float32)
    for _ in range(iters):
        idx = np.argmin(np.abs(flat[:, None] - code[None, :]), axis=1)
        for c in range(16):
            sel = flat[idx == c]
            if sel.size:
                code[c] = sel.mean()
    code = np.sort(code)
    idx = np.argmin(np.abs(w[:, :, None] - code[None, None, :]),
                    axis=-1).astype(np.uint8)
    lo, hi = idx[0::2], idx[1::2]
    return (lo | (hi << 4)).astype(np.uint8), code


def unpack_dense(packed: jnp.ndarray, lut: jnp.ndarray) -> jnp.ndarray:
    """Reference dequantization (the FOLD path: dense fp16 materialized)."""
    lo = (packed & 0xF).astype(jnp.int32)
    hi = (packed >> 4).astype(jnp.int32)
    k2, n = packed.shape
    idx = jnp.stack([lo, hi], axis=1).reshape(k2 * 2, n)
    return lut[idx]


def _kernel(a_ref, w_ref, lut_ref, o_ref, acc_ref, *, nk, out_dtype):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    packed = w_ref[...]                          # (bk/2, bn) uint8 in VMEM
    lo = (packed & 0xF).astype(jnp.int32)
    hi = (packed >> 4).astype(jnp.int32)
    entries = [lut_ref[0, i] for i in range(16)]
    w_lo = select_from_table(lo, entries)        # dequant at the MXU input
    w_hi = select_from_table(hi, entries)
    bk2, bn = packed.shape
    w = jnp.stack([w_lo, w_hi], axis=1).reshape(bk2 * 2, bn)
    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], w.astype(a_ref.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k_idx == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def palette_matmul(
    a: jnp.ndarray,                 # (M, K)
    packed: jnp.ndarray,            # (K/2, N) uint8
    lut: jnp.ndarray,               # (16,)
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
) -> jnp.ndarray:
    m, k = a.shape
    k2, n = packed.shape
    assert k == 2 * k2, (a.shape, packed.shape)
    bm = pick_block(m, bm)
    bn = pick_block(n, bn)
    bk = max(16, pick_block(k, bk))
    ap = pad_to(pad_to(a, 0, bm), 1, bk)
    wp = pad_to(pad_to(packed, 0, bk // 2), 1, bn)
    nm, nn, nk = cdiv(ap.shape[0], bm), cdiv(wp.shape[1], bn), cdiv(ap.shape[1], bk)
    lut2 = lut.astype(jnp.float32).reshape(1, 16)

    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk, out_dtype=a.dtype),
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, 16), lambda i, j, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nm * bm, nn * bn), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=compat.interpret_mode(),
        **compat.pallas_call_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(ap, wp, lut2)
    return out[:m, :n]
