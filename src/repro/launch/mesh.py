"""Production mesh definitions (DESIGN.md §5).

Functions, not module-level constants: importing this module never touches
jax device state (required by the dry-run's XLA_FLAGS ordering).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis of Auto type (sharding left to the
    partitioner, which the models' constraint helpers assume)."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host offers (CPU smoke tests: 1 device)."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))


def make_engine_mesh(devices: int = 0, axis: str = "macro"):
    """1-D mesh over the first `devices` host devices for the CIM engine's
    sharded multi-macro dispatch (runtime.engine.ShardingConfig).

    `devices=0` takes every visible device.  CPU-only dev/CI emulates a
    bank of macros with XLA_FLAGS=--xla_force_host_platform_device_count=N
    (set before jax import).  Raises ValueError when asking for more
    devices than jax reports."""
    import numpy as np

    devs = jax.devices()
    n = devices if devices > 0 else len(devs)
    if n > len(devs):
        raise ValueError(
            f"sharded engine dispatch wants {n} devices but jax reports "
            f"{len(devs)}; on CPU, relaunch with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n}")
    return jax.sharding.Mesh(np.asarray(devs[:n]), (axis,))
