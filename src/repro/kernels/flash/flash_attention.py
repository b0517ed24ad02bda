"""flash: tiled attention with online softmax and an fp32 VMEM accumulator.

The fusion result of paper §9.6 transcribed to the MXU: fusing the score,
softmax, and value matmuls into one kernel keeps the (bq x bk) score tile as
the only live intermediate — the whole attention graph runs above the ridge
point instead of three bandwidth-bound dispatches. The running (max, denom)
pair is the same two-rounding-point structure as the wide accumulator:
scores in fp32, output rounded once at the store.

Grid: (B*H, Sq/bq, Skv/bk) with the KV axis innermost; m/l/acc live in VMEM
scratch across the KV steps. GQA maps q-head -> kv-head in the BlockSpec
index maps (no KV duplication in HBM). Causal + sliding-window masking via
block-position iota; fully-masked blocks short-circuit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compat
from repro.kernels.common import cdiv, pad_to

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            nk: int, bq: int, bk: int, causal: bool, window: int | None,
            skv: int, scale: float, out_dtype):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    allow = k_pos < skv                                 # kv padding
    if causal:
        allow &= k_pos <= q_pos
    if window is not None:
        allow &= (q_pos - k_pos) < window

    def compute():
        q = q_ref[0].astype(jnp.float32)                # (bq, d)
        k = k_ref[0].astype(jnp.float32)                # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(allow, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_new
        v = v_ref[0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    if causal or window is not None:
        # skip blocks that are entirely masked
        first_q = qi * bq
        last_q = first_q + bq - 1
        first_k = ki * bk
        last_k = first_k + bk - 1
        runnable = True
        if causal:
            runnable = jnp.asarray(first_k <= last_q)
        if window is not None:
            runnable &= jnp.asarray(last_k > first_q - window)

        @pl.when(runnable)
        def _run():
            compute()
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _store():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(out_dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "bq", "bk", "scale"))
def flash_attention(
    q: jnp.ndarray,                 # (B, H, Sq, d)
    k: jnp.ndarray,                 # (B, KVH, Skv, d)
    v: jnp.ndarray,                 # (B, KVH, Skv, d)
    *,
    causal: bool = True,
    window: int | None = None,
    bq: int = 512,
    bk: int = 512,
    scale: float | None = None,
) -> jnp.ndarray:
    b, h, sq, d = q.shape
    _, kvh, skv, _ = k.shape
    g = h // kvh
    scale = scale if scale is not None else d ** -0.5
    bq = min(bq, max(sq, 16))
    bk = min(bk, max(skv, 16))
    qp = pad_to(q, 2, bq)
    kp = pad_to(k, 2, bk)
    vp = pad_to(v, 2, bk)
    nq, nk = cdiv(qp.shape[2], bq), cdiv(kp.shape[2], bk)
    # flatten (B, H) into the leading grid axis; kv head = head // g
    qf = qp.reshape(b * h, qp.shape[2], d)
    kf = kp.reshape(b * kvh, kp.shape[2], d)
    vf = vp.reshape(b * kvh, vp.shape[2], d)

    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk, bq=bq, bk=bk, causal=causal,
                          window=window, skv=skv, scale=scale,
                          out_dtype=q.dtype),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda bh, qi, ki, g=g, kvh=kvh:
                         ((bh // (g * kvh)) * kvh + (bh % (g * kvh)) // g,
                          ki, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda bh, qi, ki, g=g, kvh=kvh:
                         ((bh // (g * kvh)) * kvh + (bh % (g * kvh)) // g,
                          ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, qp.shape[2], d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=compat.interpret_mode(),
        **compat.pallas_call_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qf, kf, vf)
    return out.reshape(b, h, qp.shape[2], d)[:, :, :sq]
