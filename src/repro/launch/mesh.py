"""Meshes.

Functions, not module-level constants, so importing this module never touches
jax device state (device count is locked at first backend init — the dry-run
must set XLA_FLAGS before any of this runs).

Every mesh is built through ``make_mesh``, which gives each axis
``AxisType.Auto``: the installed ``jax.make_mesh`` defaults to Explicit axes,
under which the shard_map engines' sharding constraints and gathers are
rejected.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

__all__ = ["make_mesh", "make_device_mesh", "make_production_mesh",
           "make_smoke_mesh"]


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """``jax.make_mesh`` with every axis Auto."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_device_mesh() -> Mesh:
    """(data=n, model=1) over the ``n`` devices present.

    Under ``dist_mode="replica"`` every device is one full model replica
    (one chip: data=1; a four-chip host: data=4). Under ``"fsdp"`` the same
    shape is one replica FSDP-sharded over ``data``."""
    devs = jax.devices()
    return make_mesh((len(devs), 1), ("data", "model"), devices=devs)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Dry-run mesh. Single pod: 16x16 = 256 chips (data, model). Multi-pod:
    2 pods = 512 chips (pod, data, model) — the ``pod`` axis is the gossip
    domain for the hierarchical (fsdp-mode) architectures and part of the
    replica domain for the rest."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"production mesh needs {n} devices, found {len(devs)} — the "
            "dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count"
            "=512 before any jax import (see launch/dryrun.py)")
    return make_mesh(shape, axes, devices=devs[:n])


def make_smoke_mesh(data: int = 1, model: int = 1, pod: int = 1) -> Mesh:
    """Tiny mesh over however many (possibly forced-host) devices exist.

    ``pod > 1`` adds a leading ``pod`` axis — the hierarchical (fsdp-mode)
    gossip domain — so the shard-local packed engine can run on forced-host
    CPU devices (set ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    before any jax import)."""
    if pod > 1:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
