"""Production mesh construction (DESIGN.md Sec. 6).

Axes: ("pod", "data", "model") -- pod = cross-DCN data parallelism,
data = intra-pod ICI data parallelism, model = ICI tensor parallelism.
A FUNCTION (not a module constant) so importing never touches jax device
state; the dry-run sets XLA_FLAGS before calling this.
"""
from __future__ import annotations

from typing import Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    types = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=types)


def make_host_mesh():
    """1-device mesh with the same axis names (tests / examples on CPU)."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def serving_mesh(n_devices: int):
    """1-D ("data",) mesh over the first ``n_devices`` local devices --
    the data-parallel serving topology (runtime/sharded.py).  On CPU CI,
    XLA_FLAGS=--xla_force_host_platform_device_count=N provides the
    devices; the flag must be set before jax initializes."""
    import numpy as np

    avail = jax.devices()
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    if n_devices > len(avail):
        raise ValueError(
            f"serving_mesh: {n_devices} devices requested but only "
            f"{len(avail)} visible; on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_devices} "
            f"before the process starts")
    return jax.sharding.Mesh(np.asarray(avail[:n_devices]), ("data",))


def require_devices(n: int, context: str = "") -> list:
    """Validate that ``n`` local devices are visible BEFORE any sharded /
    staged computation is built, so a short device count fails with the
    fix (the XLA host-device flag) instead of a shape-mismatch deep in
    shard_map.  Returns the first ``n`` devices."""
    avail = jax.devices()
    if n < 1:
        raise ValueError(f"need at least 1 device, got request for {n}")
    if n > len(avail):
        where = f" ({context})" if context else ""
        raise ValueError(
            f"{n} devices requested{where} but only {len(avail)} visible; "
            f"on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            f"before the process starts")
    return list(avail[:n])


def data_axes(mesh) -> Tuple[str, ...]:
    """Axes that carry batch parallelism on this mesh."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def model_axis(mesh) -> str:
    return "model"


def n_chips(mesh) -> int:
    return int(mesh.devices.size)
