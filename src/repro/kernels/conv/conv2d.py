"""conv2d: direct NHWC convolution as KH*KW shifted MXU matmuls.

The engine the paper profiles is convolution-first (§3.1): the MAC array's
native datapath is a conv window sliding over a planar tensor, with the
per-channel scale/bias and the LUT activation unit sitting on the output
port so activations never round-trip through memory. This kernel is that
datapath on the MXU:

    each (kh, kw) tap is a strided spatial slice of the input tile
    contracted against the (Cin, Cout) weight plane — a plain matmul;
    the fp32 accumulator sums the KH*KW taps               (VMEM scratch)
    bias applies, ANE mode saturates the output port       (epilogue)
    the fused LUT activation evaluates in-register         (epilogue=)
    one store rounds to the narrow dtype                   (VMEM -> HBM)

Grid: one batch image per step ("parallel"); spatial extent stays whole in
VMEM (encoder stems and pooling pyramids are short-and-wide, well inside the
working-set budget). Channels pad to MXU-friendly multiples; `pad_explicit`
resolves SAME/VALID to explicit lo/hi pads shared with the jnp oracle.
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
from repro.kernels.common import pad_to


def out_extent(size: int, k: int, stride: int, padding: str) -> int:
    """Output spatial extent for one dim (SAME: ceil(size/s); VALID floor)."""
    if padding == "SAME":
        return -(-size // stride)
    if padding == "VALID":
        if size < k:
            raise ValueError(f"VALID conv: extent {size} < window {k}")
        return (size - k) // stride + 1
    raise ValueError(f"padding must be SAME or VALID, got {padding!r}")


def pad_explicit(size: int, k: int, stride: int,
                 padding: str) -> tuple[int, int]:
    """(lo, hi) explicit pads for one spatial dim — one formula, used by the
    kernel wrapper and the oracles, so SAME always means the same cells."""
    o = out_extent(size, k, stride, padding)
    if padding == "VALID":
        return (0, 0)
    total = max((o - 1) * stride + k - size, 0)
    return (total // 2, total - total // 2)


def _kernel(x_ref, w_ref, bias_ref, lut_refs, o_ref, acc_ref, *,
            kh: int, kw: int, sh: int, sw: int, oh: int, ow: int,
            ane_mode: bool, out_dtype):
    x = x_ref[0]                                   # (Hp, Wp, Cin)
    cin = x.shape[-1]
    cout = acc_ref.shape[-1]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    for i in range(kh):
        for j in range(kw):
            # tap (i, j): every output pixel reads x[i + sh*oy, j + sw*ox]
            patch = x[i:i + sh * (oh - 1) + 1:sh,
                      j:j + sw * (ow - 1) + 1:sw, :]
            acc_ref[...] += jax.lax.dot_general(
                patch.reshape(oh * ow, cin), w_ref[i * kw + j],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    acc = acc_ref[...]
    if bias_ref is not None:
        acc = acc + bias_ref[...].astype(jnp.float32)
    if ane_mode:
        # the MAC output-port ceiling: |x| >= 2^15 -> +-inf (paper §3.7)
        acc = jnp.where(acc >= hal.ACCUM_OUT_CEILING, jnp.inf, acc)
        acc = jnp.where(acc <= -hal.ACCUM_OUT_CEILING, -jnp.inf, acc)
    if lut_refs is not None:
        # fused LUT activation at the output port; round to the out dtype
        # first — the separate-op pipeline stores the conv and reloads it
        # through act_lut's fp32 widening, so this rounding is what makes
        # fused == kernel-then-LUT, bit for bit
        acc = acc.astype(out_dtype).astype(jnp.float32)
        acc = lut_eval(acc, *lut_refs, ane_mode=True)
    o_ref[...] = acc.reshape(1, oh, ow, cout).astype(out_dtype)


@functools.partial(jax.jit,
                   static_argnames=("stride", "padding", "ane_mode",
                                    "epilogue"))
def conv2d(
    x: jnp.ndarray,                    # (B, H, W, Cin) NHWC
    w: jnp.ndarray,                    # (KH, KW, Cin, Cout) HWIO
    bias: jnp.ndarray | None = None,   # (Cout,)
    *,
    stride: tuple[int, int] = (1, 1),
    padding: str = "SAME",
    ane_mode: bool = False,
    epilogue: str | None = None,       # LUT activation fused at the output
) -> jnp.ndarray:
    b, h, wd, cin = x.shape
    kh, kw, cin2, cout = w.shape
    assert cin == cin2, (cin, cin2)
    sh, sw = stride
    out_dtype = x.dtype
    oh = out_extent(h, kh, sh, padding)
    ow = out_extent(wd, kw, sw, padding)
    ph = pad_explicit(h, kh, sh, padding)
    pw = pad_explicit(wd, kw, sw, padding)
    xp = jnp.pad(x, ((0, 0), ph, pw, (0, 0)))
    # the tap slices only ever reach sh*(oh-1)+kh rows; crop VALID leftovers
    xp = xp[:, :sh * (oh - 1) + kh, :sw * (ow - 1) + kw, :]
    # MXU-friendly channel padding: contraction to a sublane multiple,
    # output channels to a lane multiple (zeros are exact for the matmul)
    xp = pad_to(xp, 3, 8)
    wp = pad_to(pad_to(w.reshape(kh * kw, cin, cout), 1, 8), 2, 128)
    cin_p, cout_p = wp.shape[1], wp.shape[2]
    hp, wp_w = xp.shape[1], xp.shape[2]

    operands = [xp, wp]
    in_specs = [
        pl.BlockSpec((1, hp, wp_w, cin_p), lambda bb: (bb, 0, 0, 0)),
        pl.BlockSpec((kh * kw, cin_p, cout_p), lambda bb: (0, 0, 0)),
    ]
    if bias is not None:
        operands.append(pad_to(bias.reshape(1, -1), 1, cout_p))
        in_specs.append(pl.BlockSpec((1, cout_p), lambda bb: (0, 0)))
    if epilogue is not None:
        from repro.kernels.act_lut.ops import lut_table_operands
        operands.extend(lut_table_operands(epilogue))
        in_specs.extend(pl.BlockSpec((1, c), lambda bb: (0, 0))
                        for c in (33, 32, 32, 2))

    def kernel(*refs):
        x_ref, w_ref = refs[0], refs[1]
        idx = 2
        bias_ref = lut_refs = None
        if bias is not None:
            bias_ref = refs[idx]
            idx += 1
        if epilogue is not None:
            lut_refs = refs[idx:idx + 4]
            idx += 4
        o_ref, acc_ref = refs[-2], refs[-1]
        _kernel(x_ref, w_ref, bias_ref, lut_refs, o_ref, acc_ref,
                kh=kh, kw=kw, sh=sh, sw=sw, oh=oh, ow=ow,
                ane_mode=ane_mode, out_dtype=out_dtype)

    out = pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, oh, ow, cout_p), lambda bb: (bb, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, oh, ow, cout_p), out_dtype),
        scratch_shapes=[pltpu.VMEM((oh * ow, cout_p), jnp.float32)],
        interpret=compat.interpret_mode(),
        **compat.pallas_call_params(dimension_semantics=("parallel",)),
    )(*operands)
    return out[..., :cout]
