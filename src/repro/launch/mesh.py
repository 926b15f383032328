"""Mesh construction on the modern ``jax.sharding.Mesh`` API.

Functions, not module-level constants: importing this module never touches
jax device state (device count is locked at first jax init; only
``dryrun.py`` forces 512 host devices, and CPU testing of the data-parallel
path forces a small count via ``XLA_FLAGS`` — see :func:`host_device_flag`).

The data-parallel GNN scale-out (PR 10) builds 1-D ``("data",)`` meshes via
:func:`data_parallel_mesh`; the LM dry-run keeps its 2-D/3-D production
shapes. All constructors go through :func:`make_mesh`, which builds a
``jax.sharding.Mesh`` from an explicit device array — the stale
``jax.make_mesh``-era helpers required the mesh to cover *every* visible
device, which breaks the 1/2/4/8-device scaling sweeps run inside one
forced-8-device host process.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

HOST_DEVICE_FLAG = "--xla_force_host_platform_device_count"


def host_device_flag(n: int) -> str:
    """The ``XLA_FLAGS`` fragment that forces ``n`` host (CPU) devices.

    Must be set in the environment *before* jax initialises its backends;
    the CPU mesh tests and ``benchmarks/dist_scaling.py`` use it to emulate
    an ``n``-device data-parallel mesh on one host.
    """
    return f"{HOST_DEVICE_FLAG}={n}"


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None) -> Mesh:
    """A ``jax.sharding.Mesh`` of ``shape`` over the first devices.

    Unlike the all-devices-only convenience constructor, a sub-mesh over a
    prefix of ``jax.devices()`` is allowed — the scaling benchmark builds
    1/2/4/8-device meshes inside a single forced-8-device process.
    """
    shape = tuple(int(s) for s in shape)
    axes = tuple(axes)
    if len(shape) != len(axes):
        raise ValueError(f"mesh shape {shape} and axes {axes} disagree")
    need = math.prod(shape)
    if devices is None:
        devices = jax.devices()
    if len(devices) < need:
        raise ValueError(
            f"mesh shape {shape} needs {need} devices but only "
            f"{len(devices)} are visible; on CPU, relaunch with "
            f"XLA_FLAGS={host_device_flag(need)} (set before jax "
            f"initialises) to emulate a {need}-device host platform")
    dev = np.asarray(devices[:need], dtype=object).reshape(shape)
    return Mesh(dev, axes)


def data_parallel_mesh(num_devices: Optional[int] = None,
                       axis_name: str = "data") -> Mesh:
    """1-D data-parallel mesh over ``num_devices`` (default: all) devices.

    This is the mesh the ``shard_map``'d GNN train step runs on: loader
    batches shard along the leading (shard) axis, parameters replicate,
    gradients reduce with one fused ``psum`` over ``axis_name``.
    """
    if num_devices is None:
        num_devices = len(jax.devices())
    return make_mesh((num_devices,), (axis_name,))


def make_local_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small 2-D mesh over however many (host) devices exist — tests only."""
    return make_mesh((data, model), ("data", "model"))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape: Tuple[int, ...] = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


# Per-chip peaks for roofline analysis, keyed by ``device.device_kind``.
# "TPU v5 lite" is TPU v5e (Google Cloud documentation, "TPU v5e"): 197
# TFLOP/s bf16, HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
# interconnect over 4 links (50 GB/s each).
CHIP_PEAKS = {
    "TPU v5 lite": {"peak_flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_bytes_per_s_per_link": 50e9},
}


def chip_peaks(device_kind: str) -> dict:
    """Published peaks of one chip; an unknown ``device_kind`` raises."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak table for device_kind {device_kind!r}; known: "
            f"{sorted(CHIP_PEAKS)}. Add its published peaks to "
            f"repro.launch.mesh.CHIP_PEAKS with their source.") from None
