"""Common layers: norms, RoPE, GLU MLPs, embeddings — pure JAX, functional.

Params are plain dict pytrees; every layer is (init, apply) pair style.
Initialization is truncated-normal / scaled per standard LM practice.

Numerics discipline from the paper threads through here:
  * matmuls accumulate in fp32 (`preferred_element_type`) — the wide
    accumulator (paper §3.2) is non-negotiable;
  * the logits head computes in fp32 — the "wider anchor" rule for the
    cancellation-heavy step (paper §3.4/§3.9).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import dispatched as dsp

Params = dict[str, Any]


def dot(x: jnp.ndarray, w: jnp.ndarray, dims=None) -> jnp.ndarray:
    """Matmul with a wide (fp32) accumulator, output in x.dtype.

    The `dims=None` form is one routed linear (dense `anemm` row, or the
    packed `palette`/`sparse` row for a tagged weight); explicit `dims`
    callers (SSM/RG-LRU internals) keep the raw dot_general."""
    if dims is None:
        return dsp.linear(x, w)
    out = jax.lax.dot_general(x, w, dims, preferred_element_type=jnp.float32)
    return out.astype(x.dtype)


def einsum32(subscript: str, *args) -> jnp.ndarray:
    out = jnp.einsum(subscript, *args, preferred_element_type=jnp.float32)
    return out.astype(args[0].dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(cfg: ModelConfig, dim: int) -> Params:
    p = {"scale": jnp.ones((dim,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((dim,), jnp.float32)
    return p


def apply_norm(cfg: ModelConfig, p: Params, x: jnp.ndarray,
               eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = x32.mean(-1, keepdims=True)
        var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"] + p.get("bias", 0.0)
    else:  # rmsnorm
        ms = (x32 * x32).mean(-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(ms + eps) * p["scale"]
    return y.astype(dt)


def rms_head_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6):
    """Per-head RMS norm used by QK-norm (chameleon stability recipe)."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps) * scale
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, dh); positions: (..., S)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                       # (dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., S, dh/2)
    cos = jnp.cos(angles)[..., None, :]                 # (..., S, 1, dh/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

_ACTS = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}


def init_mlp(key, cfg: ModelConfig, d: int, f: int, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    std_in = d ** -0.5
    std_out = f ** -0.5
    if cfg.act == "gelu_mlp":                           # plain 2-matrix MLP
        p = {"wi": jax.random.normal(k1, (d, f), dtype) * std_in,
             "wo": jax.random.normal(k2, (f, d), dtype) * std_out}
        if cfg.use_bias:
            p["bi"] = jnp.zeros((f,), dtype)
            p["bo"] = jnp.zeros((d,), dtype)
        return p
    return {"wg": jax.random.normal(k1, (d, f), dtype) * std_in,
            "wu": jax.random.normal(k2, (d, f), dtype) * std_in,
            "wd": jax.random.normal(k3, (f, d), dtype) * std_out}


def apply_mlp(cfg: ModelConfig, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    if "wi" in p:                                       # plain MLP
        h = dsp.linear(x, p["wi"], bias=p.get("bi"))
        h = jax.nn.gelu(h)
        return dsp.linear(h, p["wo"], bias=p.get("bo"))
    act = _ACTS.get(cfg.act, jax.nn.silu)
    g = act(dsp.linear(x, p["wg"]))
    u = dsp.linear(x, p["wu"])
    return dsp.linear(g * u, p["wd"])


# ---------------------------------------------------------------------------
# Embedding / logits head
# ---------------------------------------------------------------------------


def init_embed(key, cfg: ModelConfig, dtype) -> Params:
    v = cfg.padded_vocab
    p = {"table": jax.random.normal(key, (v, cfg.d_model), dtype) * 0.02}
    if not cfg.tie_embeddings:
        k2 = jax.random.fold_in(key, 1)
        p["unembed"] = jax.random.normal(
            k2, (cfg.d_model, v), dtype) * (cfg.d_model ** -0.5)
    return p


def embed_tokens(p: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(p["table"], tokens, axis=0)


def logits(cfg: ModelConfig, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """Always fp32 out: the cancellation-heavy step gets the wide anchor.
    Operands stay in the activation dtype — a product of two bf16 values
    is exact in fp32, so fp32 accumulation is the whole anchor."""
    if cfg.tie_embeddings:
        w, layout = p["table"], "nk"             # (V, d), read as stored
    else:
        w, layout = p["unembed"], "kn"           # (d, V)
    if isinstance(w, dsp.DispatchedWeight):
        # packed head: the palette/sparse kernels store in the activation
        # dtype, so the fp32 anchor comes from fp32 activations
        return dsp.linear(x.astype(jnp.float32), w)
    if dsp.active_dispatcher() is not None:
        return dsp.head(x, w, layout=layout)
    dims = (((x.ndim - 1,), (1 if layout == "nk" else 0,)), ((), ()))
    return jax.lax.dot_general(x, w.astype(x.dtype), dims,
                               preferred_element_type=jnp.float32)


def sinusoidal_positions(length: int, dim: int) -> jnp.ndarray:
    """Whisper-style sinusoidal embeddings for the encoder frames."""
    pos = np.arange(length)[:, None]
    i = np.arange(dim // 2)[None, :]
    angle = pos / np.power(10000.0, 2 * i / dim)
    out = np.concatenate([np.sin(angle), np.cos(angle)], axis=-1)
    return jnp.asarray(out, jnp.float32)
