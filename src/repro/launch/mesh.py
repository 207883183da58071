"""Production mesh construction.

The target is a TPU v5e deployment: one pod = a 16x16 mesh of 256 chips
(axes ("data","model")); the multi-pod config stacks 2 pods on a leading
"pod" axis (512 chips) connected by the slower pod-to-pod interconnect.
The paper's hierarchy maps onto these axes (DESIGN.md S2):

    pod   — static example partition (NUMA-node analogue, slowest link)
    data  — dynamic example partition within a pod (thread analogue)
    model — feature / tensor-parallel sharding (new axis at this scale)

Everything is a FUNCTION (no module-level device touching) so importing
this module never locks jax's device count; only the dry-run entrypoint
sets XLA_FLAGS for 512 host devices.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AbstractMesh, AxisType

PEAK_FLOPS = 197e12          # bf16 FLOP/s per v5e chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link (~ICI); pod-to-pod is slower

# host->device ingest link (streamed feeds) — defined ONCE in
# core.planner next to the h2d byte model; re-exported here next to
# its sibling bandwidths
from repro.core.planner import H2D_BW  # noqa: E402,F401


def abstract_mesh(shape, axis_names) -> AbstractMesh:
    """Device-free mesh with `Auto` axes, like the meshes below — for
    validating the production 256/512-chip shardings without device
    allocation."""
    return AbstractMesh(tuple(shape), tuple(axis_names),
                        axis_types=(AxisType.Auto,) * len(shape))


def _auto_mesh(shape, axes, devices):
    # jax.make_mesh defaults to Explicit axes, under which
    # with_sharding_constraint and sharded contractions outside
    # shard_map raise; the engine is written for Auto (GSPMD) axes.
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for {shape}, have {len(devs)}; the dry-run "
            "entrypoint must set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=512 before any jax import")
    return _auto_mesh(shape, axes, devs[:n])


def make_host_mesh(*, data: int = 1, model: int = 1, pod: int = 1):
    """Small mesh over however many (host) devices exist — for tests."""
    shape = (pod, data, model)
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(f"need {n} devices, have {len(devs)}")
    return _auto_mesh(shape, ("pod", "data", "model"), devs[:n])


def mesh_chips(mesh) -> int:
    return math.prod(mesh.devices.shape)
