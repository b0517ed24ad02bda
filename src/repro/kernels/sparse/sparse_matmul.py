"""sparse_matmul: 50% pair-structured sparse weights, streamed compressed.

The paper's structured-sparsity form (§7.2/§7.3): a 1-bit keep mask plus the
packed fp16 nonzeros streams on every ANE generation — 1.55-1.64x faster at
0.43x the bytes on the M1. The TPU-native structure (DESIGN.md §2): exactly
one survivor per adjacent pair along K (like GPU 2:4 but 1:2), stored as

    values    (K/2, N)  fp16/bf16    — the packed nonzeros
    selector  (K/16, N) uint8        — one bit per pair, packed 8/byte

Both stream HBM->VMEM compressed (~0.53x dense bytes); the kernel unpacks
the selector bits with shift/mask (no gather) and reconstructs the dense
(bk, bn) tile at the MXU input — the multiplier-input reconstruction point.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compat
from repro.kernels.common import cdiv, pad_to, pick_block


def pack_pair_sparse(w: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Magnitude-based 1:2 structured pruning + packing.

    Returns (values (K/2, N) float16, selector (K/16, N) uint8)."""
    w = np.asarray(w, dtype=np.float32)
    assert w.ndim == 2 and w.shape[0] % 16 == 0, "K must be divisible by 16"
    k, n = w.shape
    pairs = w.reshape(k // 2, 2, n)
    sel = (np.abs(pairs[:, 1, :]) > np.abs(pairs[:, 0, :])).astype(np.uint8)
    vals = np.where(sel, pairs[:, 1, :], pairs[:, 0, :]).astype(np.float16)
    bits = sel.reshape(-1, 8, n)
    weights_of_bit = (1 << np.arange(8, dtype=np.uint8))[None, :, None]
    packed = (bits * weights_of_bit).sum(axis=1).astype(np.uint8)
    return vals, packed


def unpack_dense(values: jnp.ndarray, selector: jnp.ndarray) -> jnp.ndarray:
    """Reference reconstruction to a dense (K, N) weight."""
    k2, n = values.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (selector[:, None, :] >> shifts[None, :, None]) & 1
    sel = bits.reshape(-1, n)[:k2]
    v32 = values.astype(jnp.float32)
    lo = jnp.where(sel == 0, v32, 0.0)
    hi = jnp.where(sel == 1, v32, 0.0)
    return jnp.stack([lo, hi], axis=1).reshape(k2 * 2, n)


def _kernel(a_ref, v_ref, s_ref, o_ref, acc_ref, *, nk, out_dtype):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    vals = v_ref[...].astype(jnp.float32)        # (bk/2, bn)
    packed = s_ref[...]                          # (bk/16, bn) uint8
    bk2, bn = vals.shape
    # unpack 8 selector bits per byte along K (shift/mask, no gather)
    shifts = jax.lax.broadcasted_iota(jnp.uint8, (1, 8, 1), 1)
    bits = (packed[:, None, :] >> shifts) & 1    # (bk/16, 8, bn)
    sel = bits.reshape(bk2, bn)
    w_lo = jnp.where(sel == 0, vals, 0.0)
    w_hi = jnp.where(sel == 1, vals, 0.0)
    w = jnp.stack([w_lo, w_hi], axis=1).reshape(bk2 * 2, bn)
    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], w.astype(a_ref.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k_idx == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def sparse_matmul(
    a: jnp.ndarray,                 # (M, K)
    values: jnp.ndarray,            # (K/2, N)
    selector: jnp.ndarray,          # (K/16, N) uint8
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
) -> jnp.ndarray:
    m, k = a.shape
    k2, n = values.shape
    assert k == 2 * k2 and selector.shape == (k // 16, n)
    bm = pick_block(m, bm)
    bn = pick_block(n, bn)
    bk = max(16, pick_block(k, bk))
    ap = pad_to(pad_to(a, 0, bm), 1, bk)
    vp = pad_to(pad_to(values, 0, bk // 2), 1, bn)
    sp = pad_to(pad_to(selector, 0, bk // 16), 1, bn)
    nm, nn, nk = cdiv(ap.shape[0], bm), cdiv(vp.shape[1], bn), cdiv(ap.shape[1], bk)

    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk, out_dtype=a.dtype),
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk // 16, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nm * bm, nn * bn), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=compat.interpret_mode(),
        **compat.pallas_call_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(ap, vp, sp)
    return out[:m, :n]
