"""Elastic scaling: resume a checkpoint on a different mesh.

When a pod is lost (or gained), the job re-plans: a new mesh is built from
the surviving device count, every parameter/optimizer leaf gets the sharding
the *new* mesh prescribes, and the checkpoint restores through a placer that
device_puts each full array with its new sharding. Batch and learning-rate
re-scaling follow the linear rule.

The expensive part on a real fleet — resharding in-memory state without
going through the filesystem — maps to `jax.device_put` with the new
sharding (XLA moves only the bytes that change owner). Here we validate the
plan + restore logic; the dry-run validates that both mesh shapes compile.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from repro.launch.mesh import make_mesh


@dataclasses.dataclass(frozen=True)
class RescalePlan:
    old_devices: int
    new_devices: int
    new_mesh_shape: tuple[int, ...]
    new_axis_names: tuple[str, ...]
    batch_scale: float          # keep global batch (1.0) or scale with fleet
    lr_scale: float

    @property
    def shrinking(self) -> bool:
        return self.new_devices < self.old_devices


def plan_rescale(old_devices: int, new_devices: int,
                 *, model_parallel: int = 16,
                 keep_global_batch: bool = True) -> RescalePlan:
    """Choose the new mesh: keep the model axis (sharding invariants of the
    params), flex the data axis, split off a pod axis when the data axis
    would exceed one pod's worth."""
    if new_devices % model_parallel != 0:
        raise ValueError(f"{new_devices} devices not divisible by "
                         f"model={model_parallel}")
    data = new_devices // model_parallel
    if data >= 32 and data % 2 == 0:
        shape = (2, data // 2, model_parallel)
        names = ("pod", "data", "model")
    else:
        shape = (data, model_parallel)
        names = ("data", "model")
    scale = 1.0 if keep_global_batch else new_devices / old_devices
    return RescalePlan(old_devices, new_devices, shape, names,
                       batch_scale=scale, lr_scale=scale)


def build_mesh(plan: RescalePlan, devices=None) -> Mesh:
    """Mesh for the plan. `devices` (e.g. the survivors of a host loss, in
    placement order) restricts where the mesh lands; the default uses every
    visible device — which after a *real* host loss is exactly the survivor
    set, but in single-process simulation still contains the "failed"
    rows, so the elastic supervisor passes the survivors explicitly."""
    if devices is None:
        return make_mesh(plan.new_mesh_shape, plan.new_axis_names)
    devices = np.asarray(devices).reshape(-1)
    need = int(np.prod(plan.new_mesh_shape))
    if devices.size < need:
        raise ValueError(f"plan wants {need} devices, "
                         f"got {devices.size} survivors")
    return make_mesh(plan.new_mesh_shape, plan.new_axis_names,
                     devices=devices[:need])


def make_placer(mesh: Mesh, spec_fn):
    """Placer for CheckpointManager.restore: device_put each leaf with the
    sharding the new mesh prescribes (spec_fn(path, shape) -> PartitionSpec).
    """
    def place(path: str, arr: np.ndarray):
        spec = spec_fn(path, arr.shape)
        return jax.device_put(arr, NamedSharding(mesh, spec))
    return place
