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

`anemm_head` is the same datapath for the logits head: a few rows of
activations against a vocabulary-wide weight read where it lies — either
(K, N) or the tied embedding table as (N, K) — with an explicit (fp32)
output dtype, blocks sized for a skinny M and a very large N, and a ragged
last N block instead of a padded copy of the table.
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


# contracting dims of one (a block, b block) pair, by the weight's layout
_CONTRACT = {"kn": (((1,), (0,)), ((), ())), "nk": (((1,), (1,)), ((), ()))}


def _kernel(a_ref, b_ref, scale_ref, bias_ref, lut_refs, o_ref, acc_ref, *,
            nk: int, ane_mode: bool, out_dtype, layout: str = "kn"):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], _CONTRACT[layout],
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


# One weight tile of the head; the pipeline keeps two in flight.
HEAD_TILE_BYTES = 8 << 20


def head_blocks(m: int, k: int, n: int, itemsize: int) -> tuple[int, int]:
    """(bm, bn) for a logits head: few rows against a vocabulary-wide
    weight. The whole contraction is one tile (bk = K), so the grid walks N
    alone in steps of up to 2048 columns, each weight tile at most
    `HEAD_TILE_BYTES`: a 200064 x 3072 bf16 table is 196 steps of 6 MiB,
    not the 9,378 steps of the layer matmuls' 128 x 512 blocks."""
    bn = 2048
    while bn > 128 and bn * k * itemsize > HEAD_TILE_BYTES:
        bn //= 2
    if n < bn:
        bn = n if n < 128 else (n // 128) * 128
    return pick_block(m, 256), bn


@functools.partial(jax.jit, static_argnames=("layout", "out_dtype"))
def anemm_head(
    a: jnp.ndarray,                 # (M, K)
    w: jnp.ndarray,                 # (K, N) for layout "kn", (N, K) for "nk"
    *,
    layout: str,
    out_dtype=jnp.float32,
) -> jnp.ndarray:
    """out[M, N] = a @ (w or w.T) in `out_dtype`, the operands as stored.

    The weight is never copied: no transpose of an (N, K) table, no cast,
    no padding along N. The grid takes cdiv(N, bn) column blocks; the last
    one reads past N and only feeds output columns past N, which are never
    written. K is one block; only `a`, a few rows, is padded along M.
    Accumulation is the fp32 VMEM scratch of `anemm`."""
    if layout not in _CONTRACT:
        raise ValueError(f"layout {layout!r}: want 'kn' or 'nk'")
    m, k = a.shape
    n, k2 = w.shape if layout == "nk" else w.shape[::-1]
    if k != k2:
        raise ValueError(f"contraction mismatch: a {a.shape}, w {w.shape} "
                         f"({layout})")
    bm, bn = head_blocks(m, k, n, w.dtype.itemsize)
    ap = pad_to(a, 0, bm)
    nm, nn = ap.shape[0] // bm, cdiv(n, bn)
    w_spec = (pl.BlockSpec((bn, k), lambda i, j, kk: (j, 0)) if layout == "nk"
              else pl.BlockSpec((k, bn), lambda i, j, kk: (0, j)))
    out_bytes = jnp.dtype(out_dtype).itemsize
    # double-buffered a, w and out tiles plus the accumulator
    vmem = (2 * (bm * k * a.dtype.itemsize + bn * k * w.dtype.itemsize
                 + bm * bn * out_bytes) + bm * bn * 4)

    def kernel(a_ref, w_ref, o_ref, acc_ref):
        _kernel(a_ref, w_ref, None, None, None, o_ref, acc_ref, nk=1,
                ane_mode=False, out_dtype=out_dtype, layout=layout)

    out = pl.pallas_call(
        kernel,
        grid=(nm, nn, 1),
        in_specs=[pl.BlockSpec((bm, k), lambda i, j, kk: (i, 0)), w_spec],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nm * bm, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=compat.interpret_mode(),
        name="anemm_head",
        **compat.pallas_call_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem + (4 << 20)),
    )(ap, w)
    return out[:m]
