"""Meshes: the one constructor every library, test and benchmark site uses.

Functions, not module-level constants — importing this module never touches
jax device state.  The dry-run (and only the dry-run) forces 512 host
devices; tests and benches see the real single CPU device.

Every mesh is built by :func:`make_mesh` with ``Auto`` axis types.  JAX's
own ``jax.make_mesh`` defaults to ``Explicit`` axes, on which the sharded
backend's in-place delta scatters and ``ShardPlan.constrain`` are refused;
building all meshes here keeps library and test meshes the same kind of
object.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

from repro.distributed.sharding import (
    MULTI_POD_PLAN,
    SINGLE_POD_PLAN,
    ShardPlan,
)

__all__ = ["make_mesh", "make_production_mesh", "make_plan",
           "make_cell_meshes"]


def make_mesh(shape, axes, *, devices=None) -> Mesh:
    """``Auto``-axis mesh of ``shape`` over the first devices of
    ``devices`` (default: ``jax.devices()``)."""
    shape = tuple(shape)
    n = int(np.prod(shape))
    devs = list(jax.devices()) if devices is None else list(devices)
    if len(devs) < n:
        raise RuntimeError(
            f"a {shape} mesh needs {n} devices, found {len(devs)} — force "
            "host devices via XLA_FLAGS=--xla_force_host_platform_device_"
            "count before importing jax")
    return Mesh(np.asarray(devs[:n]).reshape(shape), tuple(axes),
                axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single pod (256 chips) or 2x16x16 two pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_plan(mesh: Mesh) -> ShardPlan:
    """Bind the role plan matching a mesh's axis names."""
    if "pod" in mesh.axis_names:
        return MULTI_POD_PLAN.with_mesh(mesh)
    return SINGLE_POD_PLAN.with_mesh(mesh)


def make_cell_meshes(n_cells: int, *, shape=None, axes=None, devices=None,
                     share_devices: bool = False) -> list:
    """Partition the device pool into ``n_cells`` disjoint submeshes.

    The fleet tier (``repro.serve.fleet``) gives each serving cell its
    own mesh so a straggling or failed mesh cannot stall its siblings
    and a cross-cell hedge really rides different hardware.  Cells are
    carved as *consecutive* device blocks (cell i gets devices
    ``[i*per_cell, (i+1)*per_cell)``), which keeps each cell's devices
    physically adjacent under the usual torus enumeration.

    ``shape``/``axes`` describe ONE cell's mesh (default: all of the
    cell's devices on a flat ``("data",)`` axis — the serving scan
    shards the corpus over it).  ``share_devices=True`` relaxes
    disjointness and assigns devices round-robin — meshes are still
    *logically* separate (separate jit caches, separate backends), for
    tests and single-host benchmarks where the pool is smaller than the
    fleet; production fleets must keep the default.
    """
    if n_cells <= 0:
        raise ValueError("n_cells must be positive")
    devs = list(jax.devices()) if devices is None else list(devices)
    if shape is None:
        if share_devices:
            per_cell = max(len(devs) // n_cells, 1)
        else:
            per_cell = len(devs) // n_cells
            if per_cell == 0:
                raise RuntimeError(
                    f"{n_cells} disjoint cells need at least {n_cells} "
                    f"devices, found {len(devs)} — pass "
                    "share_devices=True for logically-separate meshes "
                    "over a shared pool (tests/single-host)")
        shape = (per_cell,)
    n_per = int(np.prod(shape))
    if axes is None:
        axes = ("data", "model")[:len(shape)] if len(shape) <= 2 else \
            ("pod", "data", "model")[:len(shape)]
    need = n_cells * n_per
    if len(devs) < need and not share_devices:
        raise RuntimeError(
            f"{n_cells} disjoint cells of shape {tuple(shape)} need "
            f"{need} devices, found {len(devs)} — pass "
            "share_devices=True for logically-separate meshes over a "
            "shared pool (tests/single-host), or force more host "
            "devices via XLA_FLAGS=--xla_force_host_platform_device_count")
    meshes = []
    for i in range(n_cells):
        if share_devices and len(devs) < need:
            block = [devs[(i * n_per + j) % len(devs)]
                     for j in range(n_per)]
        else:
            block = devs[i * n_per:(i + 1) * n_per]
        meshes.append(make_mesh(shape, axes, devices=block))
    return meshes
