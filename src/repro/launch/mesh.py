"""Production mesh builders.

``make_production_mesh`` is the spec-mandated entry: single-pod 16x16
('data','model') or multi-pod 2x16x16 ('pod','data','model'). It is a FUNCTION
(never a module-level constant) so importing this module never touches jax
device state.

``make_byz_mesh`` derives the ByzSGD training view over the *same* devices:
('rep', 'fsdp', 'model') where 'rep' indexes the n_groups co-located
worker/server groups (failure domains — DESIGN.md §Worker granularity) and
'fsdp' the ZeRO-style intra-group shard. Groups are consecutive dp slices, so
for n_groups >= n_pods every group nests inside one pod (DMC crosses pods,
scatter-phase traffic stays intra-pod).
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh

#: every mesh axis is Auto: shardings propagate through GSPMD
_AUTO = jax.sharding.AxisType.Auto

#: context manager installing a mesh as the ambient mesh
use_mesh = jax.set_mesh


def make_mesh(shape, axes):
    """``jax.make_mesh`` over the available devices, all axes Auto."""
    return jax.make_mesh(shape, axes, axis_types=(_AUTO,) * len(axes))


def _mk_mesh(devs, names):
    return Mesh(devs, names, axis_types=(_AUTO,) * len(names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def dp_size(mesh) -> int:
    """Total data-parallel slices R (pod x data)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("pod", 1) * sizes["data"]


def model_size(mesh) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape))["model"]


def make_byz_mesh(mesh, n_groups: int) -> Mesh:
    """('rep', 'fsdp', 'model') view over the production mesh's devices."""
    R, M = dp_size(mesh), model_size(mesh)
    if R % n_groups:
        raise ValueError(f"n_groups={n_groups} must divide dp slices R={R}")
    K = R // n_groups
    devs = mesh.devices.reshape(n_groups, K, M)
    return _mk_mesh(devs, ("rep", "fsdp", "model"))


def make_protocol_mesh(n_groups: int, devices=None, *,
                       fsdp: int | None = None) -> Mesh:
    """('rep', 'fsdp', 'model') mesh over the *available* devices for a
    G-group protocol run (the ``Experiment.runner="protocol"`` path).

    Unlike :func:`make_byz_mesh` (which carves a production mesh whose dp
    slices must divide into the groups), this places 'rep' on the largest
    divisor of ``n_groups`` that the device count can host — down to a
    1-device (1,1,1) mesh, where all G replica stacks live on one chip and the
    protocol is oracle-checked against the single-host simulator. Devices left
    over after the 'rep' placement become the intra-group 'fsdp' (ZeRO) axis:
    8 devices at G=4 give a (4, 2, 1) mesh — each group's replica + optimizer
    state sharded over its 2 chips. ``fsdp`` overrides the inferred axis size
    (must fit ``rep * fsdp <= len(devices)``)."""
    devices = list(jax.devices()) if devices is None else list(devices)
    if not devices:
        raise ValueError("no jax devices available for the protocol mesh")
    rep = max(d for d in range(1, min(n_groups, len(devices)) + 1)
              if n_groups % d == 0)
    K = len(devices) // rep if fsdp is None else fsdp
    if rep * K > len(devices):
        raise ValueError(f"fsdp={K} needs {rep * K} devices for rep={rep}, "
                         f"have {len(devices)}")
    devs = np.asarray(devices[:rep * K]).reshape(rep, K, 1)
    return _mk_mesh(devs, ("rep", "fsdp", "model"))


def make_serve_mesh(mesh) -> Mesh:
    """('data', 'model') flat view for serving (no replica axis)."""
    R, M = dp_size(mesh), model_size(mesh)
    return _mk_mesh(mesh.devices.reshape(R, M), ("data", "model"))
