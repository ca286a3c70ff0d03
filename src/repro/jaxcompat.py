"""The repo's spellings of mesh construction and partial-manual shard_map.

Every call site goes through these three helpers, so the choices they make
(auto-partitioned axes, replication checks off) live in one place.  They
target the JAX pinned in ``requirements.txt``.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax

__all__ = ["make_auto_mesh", "set_mesh", "shard_map"]


def make_auto_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with every axis AUTO-partitioned, over all of
    ``jax.devices()`` in the platform's preferred order."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def set_mesh(mesh):
    """Context manager making ``mesh`` the ambient mesh."""
    return jax.set_mesh(mesh)


def shard_map(f, mesh, in_specs, out_specs, manual_axes: Optional[Sequence[str]] = None):
    """Partial-manual shard_map without replication checking.

    ``manual_axes`` names the axes stripped inside ``f`` (the rest stay
    AUTO-partitioned).  ``None`` means fully manual — every mesh axis.
    Replication checking is disabled (``check_vma=False``): the compressed
    reducers return unreplicated per-worker payloads mid-graph.
    """
    kwargs = {"check_vma": False}
    if manual_axes is not None:
        kwargs["axis_names"] = frozenset(manual_axes)
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kwargs)
