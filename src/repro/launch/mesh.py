"""Mesh construction.

``make_production_mesh`` builds the assignment's target topology:
  single-pod:  (16, 16)          axes ("data", "model")   = 256 chips
  multi-pod:   (2, 16, 16)       axes ("pod", "data", "model") = 512 chips

Functions (not module constants) so importing never touches jax device
state.  ``pod`` is an outer data-parallel axis (hierarchical gradient
reduction; optionally int8-compressed — dist/collectives.py).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType

from repro.dist.sharding import MeshAxes


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding code here
    places arrays with ``NamedSharding``s and ``with_sharding_constraint``
    and lets the partitioner propagate the rest (jax's default axis type
    is ``Explicit``, sharding-in-types, which that code does not use)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1):
    """1-device (or tiny) mesh so the distributed code paths run in tests."""
    n = len(jax.devices())
    data = max(1, n // model_axis)
    return make_mesh((data, model_axis), ("data", "model"))


def parse_mesh(spec: str):
    """'DPxTP' (e.g. "4x2") or 'PODxDPxTP' -> a named host-device mesh.

    Axis names: ("data", "model") for two factors, ("pod", "data",
    "model") for three.  The factor product must equal the local device
    count (on CPU use ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    to fake an N-device host).
    """
    try:
        dims = tuple(int(d) for d in spec.lower().replace("×", "x").split("x"))
    except ValueError as exc:
        raise ValueError(f"bad mesh spec {spec!r}; want e.g. '4x2'") from exc
    if len(dims) not in (2, 3) or any(d < 1 for d in dims):
        raise ValueError(f"bad mesh spec {spec!r}; want 'DPxTP' or "
                         "'PODxDPxTP' with positive factors")
    n = len(jax.devices())
    prod = 1
    for d in dims:
        prod *= d
    if prod != n:
        raise ValueError(
            f"mesh {spec!r} needs {prod} devices but the host has {n}; "
            "set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{prod} (CPU) or pick a matching topology")
    axes = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
    return make_mesh(dims, axes)


def mesh_axes_for(mesh, *, batch_size: Optional[int] = None) -> MeshAxes:
    """MeshAxes bound to a mesh; batch axes shrink to () for batch=1 cells
    (long-context decode replicates the single sequence and shards heads)."""
    names = mesh.axis_names
    has_pod = "pod" in names
    batch: Tuple[str, ...] = (("pod", "data") if has_pod else ("data",))
    if batch_size is not None:
        # drop batch axes that cannot divide the global batch
        usable = []
        remaining = batch_size
        for ax in batch:
            size = mesh.shape[ax]
            if remaining % size == 0 and remaining >= size:
                usable.append(ax)
                remaining //= size
        batch = tuple(usable)
    return MeshAxes(mesh=mesh, data=("data",), model="model", batch=batch)
