"""anemm: blocked matmul with a wide VMEM accumulator + ANE-mode epilogue.

The kernel is the paper's datapath transcribed to the MXU (§3.1/§3.2):

    inputs round to the narrow dtype on the way in            (HBM -> VMEM)
    products accumulate in a wide fp32 register               (VMEM scratch)
    optional per-channel scale and bias apply                 (epilogue)
    the accumulator OUTPUT PORT saturates at 2^15             (ANE mode)
    the store rounds to the narrow dtype (RTNE)               (VMEM -> HBM)

Grid: (M/bm, N/bn, K/bk) with K innermost ("arbitrary"); the fp32
accumulator lives in VMEM scratch across the K steps and is written out
exactly once — two rounding points bracketing the reduction, like the
engine.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hal
from repro.kernels import compat
from repro.kernels.act_lut.act_lut import lut_eval
from repro.kernels.common import cdiv, pad_to, pick_block


def _kernel(a_ref, b_ref, scale_ref, bias_ref, lut_refs, o_ref, acc_ref, *,
            nk: int, ane_mode: bool, out_dtype):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k_idx == nk - 1)
    def _epilogue():
        acc = acc_ref[...]
        if scale_ref is not None:
            acc = acc * scale_ref[...].astype(jnp.float32)
        if bias_ref is not None:
            acc = acc + bias_ref[...].astype(jnp.float32)
        if ane_mode:
            # the MAC output-port ceiling: |x| >= 2^15 -> +-inf (paper §3.7)
            acc = jnp.where(acc >= hal.ACCUM_OUT_CEILING, jnp.inf, acc)
            acc = jnp.where(acc <= -hal.ACCUM_OUT_CEILING, -jnp.inf, acc)
        if lut_refs is not None:
            # fused LUT activation (paper §3.5: the activation unit sits on
            # the producing op's output port, no extra dispatch/HBM trip).
            # Round to the out dtype first — the separate-op pipeline stores
            # the matmul and reloads it through act_lut's fp32 widening, so
            # this rounding is what makes fused == kernel-then-LUT, bit for
            # bit.
            acc = acc.astype(out_dtype).astype(jnp.float32)
            acc = lut_eval(acc, *lut_refs, ane_mode=True)
        o_ref[...] = acc.astype(out_dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "ane_mode", "epilogue"))
def anemm(
    a: jnp.ndarray,                 # (M, K)
    b: jnp.ndarray,                 # (K, N)
    scale: jnp.ndarray | None = None,   # (N,) per-output-channel
    bias: jnp.ndarray | None = None,    # (N,)
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    ane_mode: bool = False,
    epilogue: str | None = None,    # LUT activation fused at the output port
) -> jnp.ndarray:
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    out_dtype = a.dtype
    bm = pick_block(m, bm)
    bn = pick_block(n, bn)
    bk = pick_block(k, bk)
    ap = pad_to(pad_to(a, 0, bm), 1, bk)
    bp = pad_to(pad_to(b, 0, bk), 1, bn)
    nm, nn, nk = cdiv(ap.shape[0], bm), cdiv(bp.shape[1], bn), cdiv(ap.shape[1], bk)

    operands = [ap, bp]
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
    ]
    if scale is not None:
        operands.append(pad_to(scale.reshape(1, -1), 1, bn))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
    if bias is not None:
        operands.append(pad_to(bias.reshape(1, -1), 1, bn))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
    if epilogue is not None:
        from repro.kernels.act_lut.ops import lut_table_operands
        operands.extend(lut_table_operands(epilogue))
        in_specs.extend(
            pl.BlockSpec((1, c), lambda i, j, kk: (0, 0))
            for c in (33, 32, 32, 2))

    def kernel(*refs):
        a_ref, b_ref = refs[0], refs[1]
        idx = 2
        scale_ref = bias_ref = lut_refs = None
        if scale is not None:
            scale_ref = refs[idx]
            idx += 1
        if bias is not None:
            bias_ref = refs[idx]
            idx += 1
        if epilogue is not None:
            lut_refs = refs[idx:idx + 4]
            idx += 4
        o_ref, acc_ref = refs[-2], refs[-1]
        _kernel(a_ref, b_ref, scale_ref, bias_ref, lut_refs, o_ref, acc_ref,
                nk=nk, ane_mode=ane_mode, out_dtype=out_dtype)

    out = pl.pallas_call(
        kernel,
        grid=(nm, nn, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nm * bm, nn * bn), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=compat.interpret_mode(),
        **compat.pallas_call_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*operands)
    return out[:m, :n]
