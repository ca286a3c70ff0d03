"""Explicit collective schedules on jax.lax primitives (shard_map context).

XLA's built-in all_reduce/all_gather are the production path; the explicit
ring implementations here exist because the paper's contribution lives in the
collective schedule: a ring step is a ``ppermute``, and interleaving
compression work between permute steps is how compute/comm overlap is
expressed on TPU (paper §IV-C).  They are also the reference for the
collective-bytes accounting in the roofline (analysis/hlo.py counts these ops
in lowered HLO).
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

__all__ = ["AxisSpec", "normalize_axes", "axis_size", "axis_sizes",
           "axis_linear_index", "ring_all_gather", "ring_reduce_scatter",
           "ring_all_reduce"]

# A gradient-sync axis spec: one mesh axis name, or a tuple of names for the
# multi-axis collectives the two-level transports ride (DESIGN.md §18).
AxisSpec = Union[str, Sequence[str]]


def normalize_axes(axis: AxisSpec) -> Union[str, Tuple[str, ...]]:
    """Canonicalize an axis spec: str passes through, any other sequence
    becomes a tuple of names (lists from JSON-ish config land here).  A
    single-name tuple stays a tuple — collectives treat both spellings
    identically, so no silent unwrapping."""
    if isinstance(axis, str):
        return axis
    axes = tuple(axis)
    if not axes or not all(isinstance(a, str) for a in axes):
        raise ValueError(
            f"axis spec must be a name or a non-empty sequence of names, "
            f"got {axis!r}")
    return axes


def axis_size(axis_name: AxisSpec) -> int:
    """Worker count over one mesh axis OR a tuple of axes (their product).

    ``jax.lax.psum`` accepts a tuple of axis names natively; this wrapper
    only normalizes the spelling (lists become tuples) so callers holding a
    config-provided axis spec never trip the silent single-axis assumption
    the pre-topology code had.
    """
    return jax.lax.psum(1, normalize_axes(axis_name))


def axis_sizes(axes: AxisSpec) -> Tuple[int, ...]:
    """Per-axis worker counts, in spec order (shard_map context)."""
    norm = normalize_axes(axes)
    if isinstance(norm, str):
        norm = (norm,)
    return tuple(jax.lax.psum(1, a) for a in norm)


def axis_linear_index(axes: AxisSpec):
    """Row-major linear worker index over one axis or a tuple of axes."""
    norm = normalize_axes(axes)
    if isinstance(norm, str):
        return jax.lax.axis_index(norm)
    idx = jax.lax.axis_index(norm[0])
    for a in norm[1:]:
        idx = idx * jax.lax.psum(1, a) + jax.lax.axis_index(a)
    return idx


def ring_all_gather(x: jnp.ndarray, axis_name: str, *, reverse: bool = False):
    """All-gather via n-1 ppermute steps; returns (n, *x.shape).

    Equivalent to jax.lax.all_gather(x, axis_name) but with an explicit ring
    schedule a caller can interleave work into (see ``on_step``-style usage in
    reducers).
    """
    n = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    out = jnp.zeros((n,) + x.shape, x.dtype)
    out = out.at[idx].set(x)
    buf = x
    step = -1 if reverse else 1
    for i in range(1, n):
        perm = [(j, (j + step) % n) for j in range(n)]
        buf = jax.lax.ppermute(buf, axis_name, perm)
        src = (idx - step * i) % n
        out = out.at[src].set(buf)
    return out


def ring_reduce_scatter(x: jnp.ndarray, axis_name: str):
    """Reduce-scatter via n-1 ppermute+add steps.

    ``x`` (n*s, ...) is viewed as n shards of s rows; returns this device's
    reduced shard (s, ...).
    """
    n = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    assert x.shape[0] % n == 0, "leading dim must divide the axis size"
    shards = x.reshape((n, x.shape[0] // n) + x.shape[1:])
    # The accumulator passed around at step i carries chunk (d + n-1-i) mod n
    # on device d; each device adds its local copy of that chunk.  After n-1
    # steps device d holds the fully reduced chunk d.
    acc = shards[(idx + n - 1) % n]
    for i in range(1, n):
        perm = [(j, (j + 1) % n) for j in range(n)]
        acc = jax.lax.ppermute(acc, axis_name, perm)
        acc = acc + shards[(idx + n - 1 - i) % n]
    return acc


def ring_all_reduce(
    x: jnp.ndarray,
    axis_name: str,
    *,
    shard_hook: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None,
):
    """Ring all-reduce = reduce-scatter + all-gather (the classic 2(n-1)/n).

    ``shard_hook`` runs on the reduced shard between the two phases — this is
    where per-shard compression slots in so only compressed bytes ride the
    all-gather half of the ring.
    """
    n = axis_size(axis_name)
    pad = (-x.shape[0]) % n
    xp = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    shard = ring_reduce_scatter(xp, axis_name)
    if shard_hook is not None:
        shard = shard_hook(shard)
    full = ring_all_gather(shard, axis_name)
    full = full.reshape((-1,) + x.shape[1:])
    return full[: x.shape[0]]
