"""Elastic mesh management: rebuild the mesh from whatever devices exist.

Checkpoints store logical arrays (checkpoint/ckpt.py), so scaling the job
up or down between restarts is: rebuild mesh -> re-device_put with the new
shardings -> continue.  `choose_mesh_shape` keeps the model axis as close
to the requested TP degree as the device count allows and gives the rest
to data (then pod) parallelism.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_mesh as _auto_mesh


def choose_mesh_shape(n_devices: int, tp: int = 16,
                      pods: int = 1) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
    tp = math.gcd(tp, n_devices)
    rest = n_devices // tp
    if pods > 1 and rest % pods == 0:
        return (pods, rest // pods, tp), ("pod", "data", "model")
    return (rest, tp), ("data", "model")


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> jax.sharding.Mesh:
    return _auto_mesh(shape, axes)


def reshard_tree(tree, shardings):
    """device_put a logical pytree onto (possibly new) shardings."""
    return jax.tree.map(
        lambda x, s: jax.device_put(np.asarray(x), s), tree, shardings)


def replicated(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
