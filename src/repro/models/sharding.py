"""Mesh-aware sharding helpers.

All model code annotates activations/params with *logical* specs through
`shard(...)`; the helper silently drops axes that the current mesh does not
have, so the same model runs on the 1-device CPU smoke tests, the 16x16
single-pod mesh and the 2x16x16 multi-pod mesh without change.
"""
from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
from jax.sharding import PartitionSpec as P, get_abstract_mesh

# logical axis groups
BATCH = ("pod", "data")     # pure data-parallel axes
TP = "model"                # tensor-parallel axis

AxisEl = Union[None, str, Sequence[str]]


def _filter(el: AxisEl, names) -> AxisEl:
    if el is None:
        return None
    if isinstance(el, str):
        return el if el in names else None
    kept = tuple(a for a in el if a in names)
    return kept if kept else None


def mesh_spec(*elems: AxisEl, shape: Optional[Sequence[int]] = None
              ) -> Optional[P]:
    """PartitionSpec with axes absent from the ambient mesh dropped; if
    `shape` is given, axes whose product does not divide the corresponding
    dim are also dropped (e.g. batch=1 long-context decode, odd vocabs)."""
    mesh = get_abstract_mesh()
    if mesh.empty:
        return None
    names = set(mesh.axis_names)
    filtered = [_filter(e, names) for e in elems]
    if shape is not None:
        for i, e in enumerate(filtered):
            if e is None or i >= len(shape):
                continue
            axes = (e,) if isinstance(e, str) else tuple(e)
            prod = 1
            for a in axes:
                prod *= mesh.shape[a]
            if shape[i] % prod != 0:
                # keep the largest prefix of axes that still divides
                kept = []
                prod = 1
                for a in axes:
                    if shape[i] % (prod * mesh.shape[a]) == 0:
                        kept.append(a)
                        prod *= mesh.shape[a]
                filtered[i] = tuple(kept) if kept else None
    return P(*filtered)


def shard(x: jax.Array, *elems: AxisEl) -> jax.Array:
    spec = mesh_spec(*elems, shape=x.shape)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def axis_size(name: str) -> int:
    mesh = get_abstract_mesh()
    if mesh.empty or name not in mesh.axis_names:
        return 1
    return mesh.shape[name]
