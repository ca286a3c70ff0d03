"""Pallas TPU kernel: range-based N-bit float encode/decode (paper Alg. 1).

The precision conversion is one of the four compression primitives the paper
optimizes on GPU ("embarrassingly data parallel ... take the benefit of GPU").
On TPU it is a pure VPU elementwise pass: grid over row-blocks, each block a
``(block_rows, cols)`` VMEM tile; quantizer parameters (eps, P, n_neg) ride in
SMEM as scalars.

Codes are emitted as uint8 (n_bits <= 8) — the memory-bandwidth win (4 bytes ->
1 byte) is the entire point of the pass; see EXPERIMENTS.md §Perf for the
fused variant that removes this pass's HBM round-trip altogether.

Matches :mod:`repro.core.quantizer` bit-for-bit: the kernel bodies run its
``encode_math``/``decode_math`` (tests/test_kernels.py sweeps shapes x dtypes
against the oracle).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.quantizer import decode_math, encode_math
from repro.kernels.runtime import resolve_interpret

__all__ = ["encode_pallas", "decode_pallas"]

_LANE = 128  # TPU lane tile; width of the per-row params plane


def _unpack_params(params_ref, per_row: bool):
    """(eps, P, n_neg) from SMEM scalars or a per-row VMEM plane.

    Per-row mode carries one quantizer fit PER ROW (col 0/1/2 of a lane-tile
    plane) — the batched bucket executor's layout, where each bucket's fit is
    repeated onto its chunk rows (DESIGN.md §14).  The (r, 1) slices
    broadcast against the (r, cols) data tile, so the math below is shared.
    """
    if per_row:
        return params_ref[:, 0:1], params_ref[:, 1:2], params_ref[:, 2:3]
    return params_ref[0], params_ref[1], params_ref[2]


def _encode_body(params_ref, x_ref, codes_ref, *, m_bits: int,
                 per_row: bool = False):
    eps, p_codes, n_neg = _unpack_params(params_ref, per_row)
    code = encode_math(x_ref[...], eps, p_codes, n_neg, m_bits)
    codes_ref[...] = code.astype(codes_ref.dtype)


def _decode_body(params_ref, codes_ref, x_ref, *, m_bits: int,
                 per_row: bool = False):
    eps, p_codes, _ = _unpack_params(params_ref, per_row)
    val = decode_math(codes_ref[...].astype(jnp.int32), eps, p_codes, m_bits)
    x_ref[...] = val.astype(x_ref.dtype)


def _params_vec(eps, p_codes, n_codes: int):
    """Quantizer params for the kernels: SMEM scalars, or — when ``eps`` /
    ``p_codes`` are ``(rows,)`` vectors — a per-row VMEM plane."""
    n_neg = n_codes - 1 - p_codes
    if jnp.ndim(eps) == 1:
        rows = eps.shape[0]
        plane = jnp.zeros((rows, _LANE), jnp.float32)
        return (plane.at[:, 0].set(jnp.asarray(eps, jnp.float32))
                .at[:, 1].set(p_codes.astype(jnp.float32))
                .at[:, 2].set(n_neg.astype(jnp.float32)))
    return jnp.stack(
        [
            jnp.asarray(eps, jnp.float32),
            p_codes.astype(jnp.float32),
            n_neg.astype(jnp.float32),
        ]
    )


@functools.partial(jax.jit, static_argnames=("n_bits", "m_bits", "block_rows", "interpret"))
def encode_pallas(
    x2d: jnp.ndarray,
    eps: jnp.ndarray,
    p_codes: jnp.ndarray,
    *,
    n_bits: int = 8,
    m_bits: int = 3,
    block_rows: int = 8,
    interpret: bool = None,
) -> jnp.ndarray:
    """f32 (rows, cols) -> uint8/uint16 codes, tiled over rows.

    ``eps``/``p_codes`` may be scalars (one fit for the whole plane) or
    ``(rows,)`` vectors (one fit per row — the batched bucket executor)."""
    interpret = resolve_interpret(interpret)
    rows, cols = x2d.shape
    block_rows = min(block_rows, rows)
    grid = (pl.cdiv(rows, block_rows),)
    out_dtype = jnp.uint8 if n_bits <= 8 else jnp.uint16
    per_row = jnp.ndim(eps) == 1
    params = _params_vec(eps, p_codes, 1 << n_bits)
    data = lambda c: pl.BlockSpec((block_rows, c), lambda i: (i, 0),
                                  memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_encode_body, m_bits=m_bits, per_row=per_row),
        grid=grid,
        in_specs=[
            data(_LANE) if per_row else pl.BlockSpec(memory_space=pltpu.SMEM),
            data(cols),
        ],
        out_specs=data(cols),
        out_shape=jax.ShapeDtypeStruct((rows, cols), out_dtype),
        interpret=interpret,
    )(params, x2d.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("n_bits", "m_bits", "block_rows", "interpret"))
def decode_pallas(
    codes2d: jnp.ndarray,
    eps: jnp.ndarray,
    p_codes: jnp.ndarray,
    *,
    n_bits: int = 8,
    m_bits: int = 3,
    block_rows: int = 8,
    interpret: bool = None,
) -> jnp.ndarray:
    """codes (rows, cols) -> f32, tiled over rows.

    ``eps``/``p_codes`` may be scalars or per-row ``(rows,)`` vectors, as in
    :func:`encode_pallas`."""
    interpret = resolve_interpret(interpret)
    rows, cols = codes2d.shape
    block_rows = min(block_rows, rows)
    grid = (pl.cdiv(rows, block_rows),)
    per_row = jnp.ndim(eps) == 1
    params = _params_vec(jnp.float32(0) + eps, p_codes, 1 << n_bits)
    data = lambda c: pl.BlockSpec((block_rows, c), lambda i: (i, 0),
                                  memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_decode_body, m_bits=m_bits, per_row=per_row),
        grid=grid,
        in_specs=[
            data(_LANE) if per_row else pl.BlockSpec(memory_space=pltpu.SMEM),
            data(cols),
        ],
        out_specs=data(cols),
        out_shape=jax.ShapeDtypeStruct((rows, cols), jnp.float32),
        interpret=interpret,
    )(params, codes2d)
