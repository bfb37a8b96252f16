"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state. TPU v5e numbers assumed throughout:
256 chips/pod on a 16×16 ICI torus; multi-pod runs span pods over DCN.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh


def _axis_type_kwargs(n_axes: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "the dry-run entry point must set "
            "xla_force_host_platform_device_count before any jax import")
    return jax.make_mesh(shape, axes, devices=devices,
                         **_axis_type_kwargs(len(axes)))


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """Arbitrary mesh for tests/examples (e.g. (1,1) on one CPU device)."""
    n = math.prod(shape)
    devices = list(jax.devices() if devices is None else devices)[:n]
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         **_axis_type_kwargs(len(axes)))


# v5e hardware constants (roofline denominators).
PEAK_FLOPS_BF16 = 197e12      # FLOP/s per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link (~per-chip effective)
VMEM_BYTES = 128 * 2 ** 20    # ~128 MiB VMEM per chip
HBM_BYTES = 16 * 2 ** 30      # 16 GiB HBM per chip
