"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module touches no jax device state — required because the dry-run must set
XLA_FLAGS before the first jax initialization.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np

from repro.config.base import MeshConfig


def auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices=None):
    """``jax.make_mesh`` with every axis Auto: the logical-axis
    ShardingPolicy places activations with ``with_sharding_constraint``,
    which only Auto axes accept (make_mesh defaults to Explicit)."""
    types = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=types, devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model). Multi-pod: 2 pods =
    512 chips with a leading 'pod' (pure-DP / DCN) axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def mesh_config(*, multi_pod: bool = False) -> MeshConfig:
    if multi_pod:
        return MeshConfig(shape=(2, 16, 16), axes=("pod", "data", "model"))
    return MeshConfig(shape=(16, 16), axes=("data", "model"))


def make_mesh_from_config(cfg: MeshConfig):
    return auto_mesh(cfg.shape, cfg.axes)


def make_host_mesh(shape: Tuple[int, ...] = None,
                   axes: Tuple[str, ...] = None):
    """Small mesh over whatever devices exist (tests / examples).
    Defaults to (n_devices,) over axis 'data'."""
    n = len(jax.devices())
    if shape is None:
        shape = (n,)
        axes = axes or ("data",)
    assert int(np.prod(shape)) <= n, (shape, n)
    return auto_mesh(shape, axes)
