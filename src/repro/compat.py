"""Mesh helpers with no direct JAX equivalent.

Everything else — ``jax.shard_map``, ``jax.set_mesh``,
``jax.make_mesh(..., axis_types=...)``, ``lax.axis_size`` — is called
directly at its call sites.
"""
from __future__ import annotations

import jax


def ambient_axis_size(axis: str) -> int | None:
    """Size of ``axis`` in the ambient mesh, outside any traced region.

    Unlike ``lax.axis_size`` (trace-time, inside ``shard_map``), this
    reads the ``with jax.set_mesh(...)`` context so constructors can
    validate mesh-shape preconditions up front.  Returns ``None`` when no
    ambient mesh is installed or the mesh has no such axis — callers then
    defer validation to trace time.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or axis not in mesh.shape:
        return None
    return int(mesh.shape[axis])


def axis_tuple(axes) -> tuple[str, ...]:
    """Normalize a single axis name or a sequence of names to a tuple."""
    return (axes,) if isinstance(axes, str) else tuple(axes)


def ambient_axis_sizes(axes) -> tuple[int, ...] | None:
    """Sizes of several ambient-mesh axes, or None if any is unknown.

    The tuple form of :func:`ambient_axis_size`, used by constructors
    that validate multi-axis (hierarchical-schedule) preconditions up
    front; ``None`` defers validation to trace time.
    """
    sizes = tuple(ambient_axis_size(a) for a in axes)
    if any(s is None for s in sizes):
        return None
    return sizes
