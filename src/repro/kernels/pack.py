"""Pallas TPU kernel: sparse->dense pack / dense->sparse unpack.

The paper's GPU pack is: status bitmap -> parallel prefix sum -> scattered
write (689x speedup over 1 thread on V100).  TPUs have no efficient in-VMEM
scatter, so the adaptation (DESIGN.md §2) builds the same compaction from
lane rotations (``fused_compress.compact_lanes``: a log-step prefix count,
then log-step shifts of each kept lane by the bits of its gap), which move
values without arithmetic on them.

Unpack is the transpose: ``dense[i] = sum_j vals[j] * [idx[j] == i]``, one
one-hot matmul per row and 512-column tile (each output receives at most
one slot).  Round-trips exactly against the jnp oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.fused_compress import compact_lanes, lane_pad
from repro.kernels.runtime import resolve_interpret

__all__ = ["pack_pallas", "unpack_pallas"]

_K_TILE = 128
_F_TILE = 512


def _pack_body(x_ref, tau_ref, vals_ref, idx_ref, *, cols: int, k: int):
    col = jax.lax.broadcasted_iota(jnp.int32, x_ref.shape, 1)
    valid = col < cols  # lanes past the array edge hold garbage
    x = jnp.where(valid, x_ref[...], 0.0)
    keep = valid & (jnp.abs(x) >= tau_ref[...])
    (vals, idx), _ = compact_lanes([x, col], keep)
    vals_ref[...] = vals[:, :k]
    idx_ref[...] = idx[:, :k]


@functools.partial(jax.jit, static_argnames=("k", "block_rows", "interpret"))
def pack_pallas(
    x2d: jnp.ndarray,
    tau: jnp.ndarray,
    *,
    k: int,
    block_rows: int = 8,
    interpret: bool = None,
):
    """Compact per-row elements with |x| >= tau into (vals, idx) of width k.

    ``k`` must be padded to a multiple of 128 by the caller (ops.py does).
    Slots beyond the actual kept count hold (0.0, 0) — dequant-neutral.
    """
    interpret = resolve_interpret(interpret)
    rows, cols = x2d.shape
    assert k % _K_TILE == 0, "pad k to a multiple of 128 (see ops.pad_k)"
    assert k <= lane_pad(cols), "k exceeds the row width"
    block_rows = min(block_rows, rows)
    grid = (pl.cdiv(rows, block_rows),)
    return pl.pallas_call(
        functools.partial(_pack_body, cols=cols, k=k),
        grid=grid,
        in_specs=[
            # overhangs the (rows, cols) array to whole lane tiles
            pl.BlockSpec((block_rows, lane_pad(cols)), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, k), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, k), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, k), jnp.float32),
            jax.ShapeDtypeStruct((rows, k), jnp.int32),
        ],
        interpret=interpret,
    )(x2d.astype(jnp.float32), tau.astype(jnp.float32))


def _unpack_body(vals_ref, idx_ref, dense_ref):
    vals = vals_ref[...]  # (r, k)
    idx = idx_ref[...]
    r, k = vals.shape
    # slots with vals == 0 are padding; idx 0 collisions are harmless (add 0)
    for t in range(dense_ref.shape[-1] // _F_TILE):
        col = jax.lax.broadcasted_iota(jnp.int32, (_F_TILE, k), 0) + t * _F_TILE
        for i in range(r):  # static unroll: the one-hot differs per row
            onehot = (idx[i:i + 1] == col).astype(jnp.float32)  # (F_TILE, k)
            dense_ref[i:i + 1, t * _F_TILE:(t + 1) * _F_TILE] = (
                jax.lax.dot_general(
                    vals[i:i + 1], onehot, (((1,), (1,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32))


@functools.partial(jax.jit, static_argnames=("cols", "block_rows", "interpret"))
def unpack_pallas(
    vals: jnp.ndarray,
    idx: jnp.ndarray,
    *,
    cols: int,
    block_rows: int = 8,
    interpret: bool = None,
):
    """Scatter (vals, idx) of width k back to a dense (rows, cols) array."""
    interpret = resolve_interpret(interpret)
    rows, k = vals.shape
    assert cols % _F_TILE == 0, "pad cols to a multiple of 512 (see ops.pad_cols)"
    block_rows = min(block_rows, rows)
    grid = (pl.cdiv(rows, block_rows),)
    return pl.pallas_call(
        _unpack_body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, k), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, k), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, cols), jnp.float32),
        interpret=interpret,
    )(vals.astype(jnp.float32), idx.astype(jnp.int32))
