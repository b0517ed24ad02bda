"""avg_pool / max_pool: NHWC window reductions over shifted strided slices.

Same datapath shape as the conv kernel (the engine's pooling unit shares the
conv address generator, §3.1): each (i, j) window tap is a strided spatial
slice of the VMEM-resident input tile; avg sums taps in fp32 and scales by
1/(wh*ww) at the output port, max folds taps with an elementwise maximum.
SAME padding contributes the reduction identity (0 for the avg sum — the
engine's count-include-pad semantics — and -inf for max), which is exactly
what `lax.reduce_window` does with the same explicit pads, so the oracle
match is bit-for-bit up to the single fp32 accumulation order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compat
from repro.kernels.common import pad_to
from repro.kernels.conv.conv2d import out_extent, pad_explicit


def _pool_kernel(x_ref, o_ref, *, wh, ww, sh, sw, oh, ow, kind, out_dtype):
    x = x_ref[0].astype(jnp.float32)               # (Hp, Wp, C)
    acc = None
    for i in range(wh):
        for j in range(ww):
            tap = x[i:i + sh * (oh - 1) + 1:sh,
                    j:j + sw * (ow - 1) + 1:sw, :]
            if acc is None:
                acc = tap
            elif kind == "avg":
                acc = acc + tap
            else:
                acc = jnp.maximum(acc, tap)
    if kind == "avg":
        acc = acc * (1.0 / (wh * ww))
    o_ref[...] = acc[None].astype(out_dtype)


def _pool(x, *, window, stride, padding, kind):
    b, h, w, c = x.shape
    wh, ww = window
    sh, sw = stride
    oh = out_extent(h, wh, sh, padding)
    ow = out_extent(w, ww, sw, padding)
    ph = pad_explicit(h, wh, sh, padding)
    pw = pad_explicit(w, ww, sw, padding)
    fill = 0.0 if kind == "avg" else -jnp.inf      # the reduction identity
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), ph, pw, (0, 0)),
                 constant_values=fill)
    xp = xp[:, :sh * (oh - 1) + wh, :sw * (ow - 1) + ww, :]
    xp = pad_to(xp, 3, 128)
    hp, wp, cp = xp.shape[1], xp.shape[2], xp.shape[3]

    out = pl.pallas_call(
        functools.partial(_pool_kernel, wh=wh, ww=ww, sh=sh, sw=sw,
                          oh=oh, ow=ow, kind=kind, out_dtype=x.dtype),
        grid=(b,),
        in_specs=[pl.BlockSpec((1, hp, wp, cp), lambda bb: (bb, 0, 0, 0))],
        out_specs=pl.BlockSpec((1, oh, ow, cp), lambda bb: (bb, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, oh, ow, cp), x.dtype),
        interpret=compat.interpret_mode(),
        **compat.pallas_call_params(dimension_semantics=("parallel",)),
    )(xp)
    return out[..., :c]


@functools.partial(jax.jit, static_argnames=("window", "stride", "padding"))
def avg_pool(x: jnp.ndarray, *, window: tuple[int, int],
             stride: tuple[int, int] | None = None,
             padding: str = "VALID") -> jnp.ndarray:
    """NHWC average pooling (count-include-pad, like the engine)."""
    return _pool(x, window=window, stride=stride or window, padding=padding,
                 kind="avg")


@functools.partial(jax.jit, static_argnames=("window", "stride", "padding"))
def max_pool(x: jnp.ndarray, *, window: tuple[int, int],
             stride: tuple[int, int] | None = None,
             padding: str = "VALID") -> jnp.ndarray:
    """NHWC max pooling."""
    return _pool(x, window=window, stride=stride or window, padding=padding,
                 kind="max")
