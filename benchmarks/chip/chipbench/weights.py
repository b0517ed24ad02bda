"""Seeded random weights of a dense GQA / SwiGLU decoder, by name.

The benchmark owns its weights: the program under test is given them in
its own layout (`serving.program_params`), and the reference regenerates
them layer by layer from the same seed, so it takes nothing that the
program made. Matrices are drawn uniform (bit manipulation and one
multiply-add, so every backend and every fusion gives the same values) at
the usual scales, then rounded to the served type, bf16. Norm scales are
fp32 around 1.

Canonical shapes (d = d_model, H/KV heads, dh head size, f = d_ff, V vocab):
  embed (V, d); final_norm (d,)
  per layer: attn_norm (d,), wq (d, H*dh), wk (d, KV*dh), wv (d, KV*dh),
  wo (H*dh, d), mlp_norm (d,), wg (d, f), wu (d, f), wd (f, d)
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

SERVED = jnp.bfloat16
LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm",
                "wg", "wu", "wd")


def root_key(seed: int):
    """A key from a seed of up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _matrix(key, shape, fan_in: int):
    a = math.sqrt(3.0 / fan_in)               # std fan_in ** -0.5
    return jax.random.uniform(key, shape, jnp.float32, -a, a).astype(SERVED)


def _norm(key, dim: int):
    return jax.random.uniform(key, (dim,), jnp.float32, 0.8, 1.2)


def layer(key, i, m: dict) -> dict:
    """Layer ``i``'s weights (``i`` may be traced, for a vmap over layers)."""
    d, h, kv, dh, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["d_head"], m["d_ff"])
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, 1), i),
                          len(LAYER_LEAVES))
    k = dict(zip(LAYER_LEAVES, ks))
    return {
        "attn_norm": _norm(k["attn_norm"], d),
        "wq": _matrix(k["wq"], (d, h * dh), d),
        "wk": _matrix(k["wk"], (d, kv * dh), d),
        "wv": _matrix(k["wv"], (d, kv * dh), d),
        "wo": _matrix(k["wo"], (h * dh, d), h * dh),
        "mlp_norm": _norm(k["mlp_norm"], d),
        "wg": _matrix(k["wg"], (d, f), d),
        "wu": _matrix(k["wu"], (d, f), d),
        "wd": _matrix(k["wd"], (f, d), f),
    }


def outer(key, m: dict) -> dict:
    """The embedding (tied to the logits head) and the final norm."""
    k_e, k_n = jax.random.split(jax.random.fold_in(key, 0))
    a = 0.02 * math.sqrt(3.0)                 # std 0.02
    table = jax.random.uniform(k_e, (m["vocab"], m["d_model"]), jnp.float32,
                               -a, a).astype(SERVED)
    return {"embed": table, "final_norm": _norm(k_n, m["d_model"])}


def stacked(key, m: dict) -> dict:
    """Every layer's weights stacked on a leading layer axis."""
    return jax.vmap(lambda i: layer(key, i, m))(jnp.arange(m["n_layers"]))
