"""Meshes.

Every mesh in the repo is built by `make_mesh`, which asks for `Auto` axes:
on jax 0.9 `jax.make_mesh` defaults to `Explicit` axes, and
`with_sharding_constraint` (`ParallelContext.constrain`) and GSPMD
placement accept only `Auto` ones.

FUNCTIONS, not module-level constants: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before any device query).
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], names: Sequence[str], *,
              devices=None) -> Mesh:
    """A mesh with `Auto` axes. `devices=None` lets JAX lay the visible
    devices out; an explicit list (e.g. the survivors of a host loss) is
    used in the order given."""
    shape, names = tuple(shape), tuple(names)
    types = (AxisType.Auto,) * len(shape)
    if devices is None:
        return jax.make_mesh(shape, names, axis_types=types)
    return Mesh(np.asarray(devices).reshape(shape), names, axis_types=types)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    Under the dry-run's 512 forced host devices, the single-pod mesh takes
    the first 256 (one pod's worth)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) == need:
        return make_mesh(shape, axes)
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devices)} — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count "
            "before importing jax (see launch/dryrun.py)")
    return make_mesh(shape, axes, devices=devices[:need])


def make_host_mesh(model_parallel: int | None = None) -> Mesh:
    """Whatever this host has (tests / examples): (data, model)."""
    n = len(jax.devices())
    if model_parallel is None:
        model_parallel = 2 if n % 2 == 0 and n > 1 else 1
    return make_mesh((n // model_parallel, model_parallel), ("data", "model"))
