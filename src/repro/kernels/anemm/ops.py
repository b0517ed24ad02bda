"""jit'd public wrapper for anemm with training-grade gradients.

Forward runs the Pallas kernel; backward uses standard XLA matmuls (the
universal practice for matmul kernels — the transpose contractions are
themselves plain matmuls XLA already emits optimally). ANE mode is a
serving/emulation path and is non-differentiable by design: the saturation
epilogue has measure-zero gradient support.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.anemm.anemm import anemm as _anemm_kernel
from repro.kernels.anemm.anemm import anemm_head as _head_kernel


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def matmul(a: jnp.ndarray, b: jnp.ndarray, ane_mode: bool = False):
    return _anemm_kernel(a, b, ane_mode=ane_mode)


def _fwd(a, b, ane_mode):
    return matmul(a, b, ane_mode), (a, b)


def _bwd(ane_mode, res, g):
    a, b = res
    g = g.astype(jnp.float32)
    da = (g @ b.astype(jnp.float32).T).astype(a.dtype)
    db = (a.astype(jnp.float32).T @ g).astype(b.dtype)
    return da, db


matmul.defvjp(_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def head(a: jnp.ndarray, w: jnp.ndarray, layout: str):
    """The logits head: `a` (M, K) against the weight as stored — (K, N)
    for layout "kn", the tied (N, K) table for "nk" — fp32 out."""
    return _head_kernel(a, w, layout=layout, out_dtype=jnp.float32)


def _head_fwd(a, w, layout):
    return head(a, w, layout), (a, w)


def _head_bwd(layout, res, g):
    a, w = res
    g = g.astype(jnp.float32)
    a32, w32 = a.astype(jnp.float32), w.astype(jnp.float32)
    if layout == "nk":
        da, dw = g @ w32, g.T @ a32
    else:
        da, dw = g @ w32.T, a32.T @ g
    return da.astype(a.dtype), dw.astype(w.dtype)


head.defvjp(_head_fwd, _head_bwd)


def linear(a, b, scale=None, bias=None, *, ane_mode: bool = False,
           epilogue: str | None = None):
    """Inference-path linear with the fused epilogue (scale/bias/saturate,
    plus an optional LUT activation evaluated at the output port)."""
    return _anemm_kernel(a, b, scale, bias, ane_mode=ane_mode,
                         epilogue=epilogue)
