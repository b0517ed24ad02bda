"""Seeded random weights: the primitives every reference module draws from.

The benchmark owns its weights. A configuration's reference module
(`references/<name>.py`) knows its architecture's leaves and draws each
from the seed's key with the primitives here; the program under test is
given them in its own layout (`serving.program_params` asks the module
for every leaf of the program's tree), and the reference regenerates them
layer by layer from the same seed, so it takes nothing that the program
made. Matrices are drawn uniform (bit manipulation and one multiply-add,
so every backend and every fusion gives the same values) at the usual
scales, then rounded to the served type, bf16. Norm scales are fp32
around 1.

The rule for a module's keys (`named_key`): a leaf's key is the root key
folded with the leaf's name, then its layer index, then, for an expert
bank, each expert's global index. A leaf drawn for one layer or one
expert is then the same numbers whatever else is drawn beside it, so a
chip's share of the experts gets the same numbers as the same experts of
the uncut model.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

SERVED = jnp.bfloat16


def root_key(seed: int):
    """A key from a seed of up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def named_key(key, name: str, *indices):
    """``key`` folded with ``name`` (its CRC-32), then with each index in
    order (layer, expert; an index may be traced)."""
    key = jax.random.fold_in(key, zlib.crc32(name.encode()))
    for i in indices:
        key = jax.random.fold_in(key, i)
    return key


def matrix(key, shape, fan_in: int):
    """Uniform at std ``fan_in ** -0.5``, in the served type."""
    a = math.sqrt(3.0 / fan_in)
    return jax.random.uniform(key, shape, jnp.float32, -a, a).astype(SERVED)


def norm(key, dim: int):
    """A norm scale: uniform in [0.8, 1.2], fp32."""
    return jax.random.uniform(key, (dim,), jnp.float32, 0.8, 1.2)


def stacked(layer_fn, n_layers: int):
    """``layer_fn(i)`` for every layer, stacked on a leading layer axis."""
    return jax.vmap(layer_fn)(jnp.arange(n_layers))
