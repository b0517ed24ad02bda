"""Pure-jnp oracle for anemm."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import hal


def anemm_ref(a, b, scale=None, bias=None, *, ane_mode: bool = False,
              epilogue: str | None = None):
    acc = jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    if scale is not None:
        acc = acc * scale.astype(jnp.float32)[None, :]
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)[None, :]
    if ane_mode:
        acc = jnp.where(acc >= hal.ACCUM_OUT_CEILING, jnp.inf, acc)
        acc = jnp.where(acc <= -hal.ACCUM_OUT_CEILING, -jnp.inf, acc)
    out = acc.astype(a.dtype)
    if epilogue is not None:
        # same semantics as the fused kernel: the matmul result rounds to the
        # out dtype, then the LUT evaluates it through the fp32 widening
        from repro.kernels.act_lut.ops import lut_apply_ref
        out = lut_apply_ref(out, epilogue)
    return out


def anemm_head_ref(a, w, layout: str):
    """The head's product as `anemm_head` computes it: operands as stored,
    fp32 accumulate, fp32 out; `w` is (K, N) for "kn", (N, K) for "nk"."""
    dims = (((1,), (1 if layout == "nk" else 0,)), ((), ()))
    return jax.lax.dot_general(a, w, dims, preferred_element_type=jnp.float32)
