"""Sharding-hint seam between model code and the mesh.

Model code annotates activations with *logical* axes; the hints resolve
against whatever mesh is active (``jax.sharding.use_mesh``) and silently
drop axes the mesh doesn't have — so the same model runs on a laptop
(no mesh), a single pod ``(data, model)``, or multi-pod
``(pod, data, model)`` without edits.

Logical axis vocabulary:
* ``BATCH``  -> ``("pod", "data")``  (data parallel, pods included)
* ``TP``     -> ``"model"``          (tensor / expert parallel)
* ``SEQ``    -> ``"data"``           (sequence parallelism for long ctx)
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

BATCH: tuple[str, ...] = ("pod", "data")
TP = "model"
SEQ = "data"

AxisLike = str | tuple[str, ...] | None


def _active_axis_names() -> tuple[str, ...] | None:
    """Axis names usable in sharding constraints: Auto axes of the
    active mesh (Manual axes — e.g. the DP axes inside a Torrent
    subset-shard_map region — must not appear in constraints)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return None
    auto = jax.sharding.AxisType.Auto
    return tuple(
        name
        for name, kind in zip(mesh.axis_names, mesh.axis_types)
        if kind == auto
    )


def resolve_spec(*axes: AxisLike) -> P | None:
    """Resolve logical axes to a PartitionSpec on the active mesh, or
    None when no mesh is active."""
    names = _active_axis_names()
    if names is None:
        return None
    out: list[AxisLike] = []
    for ax in axes:
        if ax is None:
            out.append(None)
        elif isinstance(ax, tuple):
            kept = tuple(a for a in ax if a in names)
            out.append(kept if kept else None)
        else:
            out.append(ax if ax in names else None)
    return P(*out)


def maybe_shard(x: jax.Array, *axes: AxisLike) -> jax.Array:
    """``with_sharding_constraint`` if a mesh is active; no-op otherwise."""
    spec = resolve_spec(*axes)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def dp_axes(axis_names) -> tuple[str, ...]:
    """The data-parallel subset of ``axis_names``, in canonical
    (:data:`BATCH`) order — the ONE definition shared by the gradient
    reduction seam and the MoE expert-parallel dispatch."""
    return tuple(a for a in BATCH if a in axis_names)


def manual_axis_names() -> tuple[str, ...]:
    """Axis names currently in Manual (shard_map) mode."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return ()
    manual = jax.sharding.AxisType.Manual
    return tuple(
        name
        for name, kind in zip(mesh.axis_names, mesh.axis_types)
        if kind == manual
    )
