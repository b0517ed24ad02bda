"""The JAX/Pallas surface the kernels and the model stack share.

Written against one installation: jax/jaxlib 0.9.0 with libtpu 0.0.34.
Every name here is a thin alias of the 0.9 API, kept so call sites name one
module and nothing else in the tree names a Pallas TPU params class
(enforced by the conformance suite):

    compiler_params(dimension_semantics=..., ...)  -> pltpu.CompilerParams
    pallas_call_params(...)                        -> {"compiler_params": ...}
    prefetch_grid_spec(...)                        -> pltpu.PrefetchScalarGridSpec
    tree_flatten_with_path / tree_map_with_path    -> jax.tree.*_with_path
    axis_size(name)                                -> jax.lax.axis_size
    interpret_mode()                               -> True off-TPU

Nothing here falls back: a field the params class does not know raises, and
a backend that cannot be probed raises instead of quietly interpreting.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
from jax.experimental.pallas import tpu as pltpu


def compiler_params(**kwargs: Any) -> pltpu.CompilerParams:
    """The Pallas TPU compiler params; an unknown field is a TypeError."""
    return pltpu.CompilerParams(**kwargs)


def pallas_call_params(**kwargs: Any) -> dict[str, Any]:
    """``compiler_params=...`` kwargs for ``pl.pallas_call``."""
    return {"compiler_params": compiler_params(**kwargs)}


def prefetch_grid_spec(*, num_scalar_prefetch: int, grid, in_specs,
                       out_specs, scratch_shapes=()) -> pltpu.PrefetchScalarGridSpec:
    """Scalar-prefetch grid spec: the prefetched operands land in SMEM before
    the body runs, and every BlockSpec index map receives them as trailing
    arguments — how the paged decode path resolves its arena blocks."""
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch, grid=grid,
        in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch_shapes)


tree_flatten_with_path = jax.tree.flatten_with_path
tree_map_with_path = jax.tree.map_with_path
axis_size = jax.lax.axis_size


def tree_path_str(path: Any) -> str:
    """A stable ``a/b/0/c`` rendering of a key path."""
    parts = []
    for entry in path:
        for attr in ("key", "idx", "name"):
            if hasattr(entry, attr):
                parts.append(str(getattr(entry, attr)))
                break
        else:
            parts.append(str(entry))
    return "/".join(parts)


@functools.cache
def interpret_mode() -> bool:
    """Pallas ``interpret=True`` everywhere except a TPU backend: the kernel
    body runs as XLA ops and the oracle sweeps check it against ref.py on any
    host. Kernels read it as ``compat.interpret_mode()`` at trace time, so
    this module attribute is the one place a test switches Mosaic on."""
    return jax.default_backend() != "tpu"
