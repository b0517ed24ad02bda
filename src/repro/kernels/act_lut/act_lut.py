"""act_lut: 33-knot piecewise-linear activation evaluation (paper §3.5).

The engine evaluates every nonlinear activation through a 33-knot PWL table:
the input maps onto one of 32 segments, the bracketing segment evaluates as
slope*x + intercept, and values past the domain clamp to the end-knot
asymptote. A NaN coerces to the hi clamp (the +inf input coercion of §3.6).

The kernel is gather-free, as the VPU wants it:
  * segment index = sum of 32 vectorized (x >= knot_i) compares;
  * slope/intercept fetch = 5-level select tree over the 32 segment values.

Tables come from `core.numerics.build_lut`, the same fit the oracle uses, so
kernel-vs-oracle agreement is exact up to fp rounding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compat
from repro.kernels.common import (cdiv, pad_to, round_to_fp16,
                                  select_from_table)


def lut_eval(x, xs_ref, sl_ref, ic_ref, cl_ref, *, ane_mode: bool):
    """The in-kernel PWL evaluation, shared verbatim by this kernel and the
    fused `epilogue=` paths of anemm/conv — one body, so "fused" and
    "kernel-then-LUT" are bit-identical by construction. `x` is an fp32
    tile; the table refs are the (1, 33)/(1, 32)/(1, 32)/(1, 2) operands.
    Returns the fp32 result tile (callers round at their own store)."""
    if ane_mode:
        x = jnp.where(jnp.isnan(x), jnp.inf, x)       # NaN -> +inf coercion
    # segment index: 32 vectorized compares (knots 1..32), no gather
    idx = jnp.zeros(x.shape, jnp.int32)
    for i in range(1, 33):
        idx += (x >= xs_ref[0, i]).astype(jnp.int32)
    idx = jnp.clip(idx, 0, 31)
    slope = select_from_table(idx, [sl_ref[0, i] for i in range(32)])
    icept = select_from_table(idx, [ic_ref[0, i] for i in range(32)])
    y = slope * x + icept
    lo_clamp, hi_clamp = cl_ref[0, 0], cl_ref[0, 1]
    y = jnp.where(x < xs_ref[0, 0], lo_clamp, y)
    y = jnp.where(x > xs_ref[0, 32], hi_clamp, y)
    if ane_mode:
        y = round_to_fp16(y)                          # fp16 output port
    return y


def _kernel(x_ref, xs_ref, sl_ref, ic_ref, cl_ref, o_ref, *, ane_mode: bool):
    y = lut_eval(x_ref[...].astype(jnp.float32), xs_ref, sl_ref, ic_ref,
                 cl_ref, ane_mode=ane_mode)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("ane_mode", "block"))
def act_lut(
    x: jnp.ndarray,
    xs: jnp.ndarray,        # (33,) knot abscissae
    slopes: jnp.ndarray,    # (32,)
    icepts: jnp.ndarray,    # (32,)
    clamps: jnp.ndarray,    # (2,) lo/hi asymptotes
    *,
    ane_mode: bool = True,
    block: int = 1024,
) -> jnp.ndarray:
    shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    cols = min(block, max(n, 1))
    flat = pad_to(flat, 0, cols)
    rows = flat.shape[0] // cols
    x2 = flat.reshape(rows, cols)
    brows = min(8, rows)
    x2 = pad_to(x2, 0, brows)
    nr = cdiv(x2.shape[0], brows)

    out = pl.pallas_call(
        functools.partial(_kernel, ane_mode=ane_mode),
        grid=(nr,),
        in_specs=[
            pl.BlockSpec((brows, cols), lambda i: (i, 0)),
            pl.BlockSpec((1, 33), lambda i: (0, 0)),
            pl.BlockSpec((1, 32), lambda i: (0, 0)),
            pl.BlockSpec((1, 32), lambda i: (0, 0)),
            pl.BlockSpec((1, 2), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((brows, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=compat.interpret_mode(),
    )(x2, xs.reshape(1, 33).astype(jnp.float32),
      slopes.reshape(1, 32).astype(jnp.float32),
      icepts.reshape(1, 32).astype(jnp.float32),
      clamps.reshape(1, 2).astype(jnp.float32))
    return out.reshape(-1)[:n].reshape(shape)
