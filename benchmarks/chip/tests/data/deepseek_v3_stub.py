"""A reference module's weights for DeepSeek-V3's parameter layout, for the
tests: the leading dense layers stacked in ``layers/0``, the MoE layers in
``layers/1`` (router, expert banks, a shared expert), MLA projections, the
MTP block and an untied head, each filled through ``program_leaf`` by the
key rule of `chipbench.weights`. It draws weights only: no reference
forward.

``m`` holds the program's sizes (``n_layers``, ``n_dense_layers``,
``dtype``) and, for a chip's share of the experts, ``expert_first``: the
global index of the first expert held here (0 when absent).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import weights

NORM, MATRIX, EXPERTS = "norm", "matrix", "experts"
# leaves of one layer (the path past ``layers/<group>/sub0/`` or
# ``mtp/layer/``) and of the model outside the layers
LAYER_LEAVES = {
    "ln1/scale": NORM, "ln2/scale": NORM,
    "mix/q_norm": NORM, "mix/kv_norm": NORM,
    "mix/wq_a": MATRIX, "mix/wq_b": MATRIX, "mix/wkv_a": MATRIX,
    "mix/wkv_b": MATRIX, "mix/wo": MATRIX,
    "mlp/wg": MATRIX, "mlp/wu": MATRIX, "mlp/wd": MATRIX,
    "moe/router": MATRIX,
    "moe/shared/wg": MATRIX, "moe/shared/wu": MATRIX,
    "moe/shared/wd": MATRIX,
    "moe/wg": EXPERTS, "moe/wu": EXPERTS, "moe/wd": EXPERTS,
}
OUTER_LEAVES = {
    "embed/table": MATRIX, "embed/unembed": MATRIX, "final_ln/scale": NORM,
    "mtp/proj": MATRIX, "mtp/ln_e/scale": NORM, "mtp/ln_h/scale": NORM,
}


def _draw(key, kind: str, shape, dtype):
    if kind == NORM:
        return weights.norm(key, shape[-1])
    fan_in = math.prod(shape[:-1])
    return weights.matrix(key, shape, fan_in).astype(dtype)


def _layer_leaf(key, m: dict, name: str, layer, shape):
    """One layer's leaf; an expert bank draws each expert from its global
    index, so a share of the experts is a slice of the whole bank."""
    kind = LAYER_LEAVES[name]
    dtype = jnp.dtype(m["dtype"])
    if kind != EXPERTS:
        return _draw(weights.named_key(key, name, layer), kind, shape, dtype)
    first = m.get("expert_first", 0)
    return jax.vmap(lambda e: _draw(weights.named_key(key, name, layer, e),
                                    MATRIX, shape[1:], dtype))(
        first + jnp.arange(shape[0]))


def program_leaf(key, m: dict, path: str, shape):
    dtype = jnp.dtype(m["dtype"])
    if path in OUTER_LEAVES:
        return _draw(weights.named_key(key, path), OUTER_LEAVES[path], shape,
                     dtype)
    if path.startswith("mtp/layer/"):
        # the MTP block is one layer past the last
        return _layer_leaf(key, m, path[len("mtp/layer/"):], m["n_layers"],
                           shape)
    parts = path.split("/", 3)
    if len(parts) != 4 or parts[0] != "layers" or parts[2] != "sub0":
        raise KeyError(path)
    group, name = parts[1], parts[3]
    lo, hi = ((0, m["n_dense_layers"]) if group == "0"
              else (m["n_dense_layers"], m["n_layers"]))
    if shape[0] != hi - lo:
        raise ValueError(f"{path}: {shape[0]} layers stacked, not {hi - lo}")
    return weights.stacked(
        lambda i: _layer_leaf(key, m, name, lo + i, shape[1:]), hi - lo)
