"""Pallas TPU kernel: fold P gathered payloads into the MEAN spectrum planes.

The receive side of the gather transports turns every worker's quantized
payload into one dense rFFT spectrum and takes their mean.  Written as
``row.at[idx].add(v)``, XLA cannot see that a row's slots land only in that
row's bins, so on the TPU it sorts every (index, value) pair of the payload
and runs its generic scatter — most of the compressed step's device time.
This kernel does the same additions in one VMEM pass per block of chunk
rows:

    for each worker w (grid axis 1, in worker order):
        codes  = one-hot scatter of w's kept CODES into the row's bins
        acc   += decode(codes)                      (shared quantizer math)
    mean = acc * (1/P)                              (P > 1 only)

The scatter is one MXU matmul per row and worker, ``S = L @ R^T`` over a
``(bins/128, 128)`` view of the row (``bin = a*128 + b``; one more row of 128
holds the Nyquist bin): L = (rows of 128, k) code-weighted row selectors and
R = (128, k) column selectors, both built by comparing the slot indices with
an iota (``fused_decompress._scatter_view`` without the Hermitian mirror).
Each bin receives at most one slot per worker, so the matmul moves codes and
sums none; integer codes of at most 8 bits are exact in one bf16 pass.
Code 0 decodes to 0.0, so empty bins and the code-0/index-0 padding slots add
exactly nothing.

The planes are BITWISE equal to the jnp scatter fold
(``CompressorBackend.mean_spectrum``): the same decode of the same codes, the
workers added in the same order onto zeros, and the same ``1/P`` multiply.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.quantizer import decode_math
from repro.kernels.runtime import resolve_interpret

__all__ = ["spectrum_fold_pallas", "fold_mean_spectrum", "MAX_CODE_BITS"]

_LANES = 128
_LANE_BITS = _LANES.bit_length() - 1  # bin >> _LANE_BITS == bin // 128
_SUBLANES = 8
_BLOCK_ROWS = 32
MAX_CODE_BITS = 8  # codes up to 255 are exact in bfloat16


def _view_rows(f_bins: int) -> int:
    """Rows of 128 lanes that hold ``f_bins`` rFFT bins."""
    return -(-f_bins // _LANES)


def _dot_nt(a, b):
    """a (m, k) . b (n, k)^T -> (m, n) f32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _fold_body(params_ref, rec_ref, imc_ref, idx_ref, re_ref, im_ref, *,
               workers: int, m_bits: int, per_row: bool):
    w = pl.program_id(1)
    block_rows = idx_ref.shape[1]
    a_rows = re_ref.shape[0] // block_rows
    sel_rows = -(-a_rows // _SUBLANES) * _SUBLANES  # sublane-aligned L half

    @pl.when(w == 0)
    def _():
        re_ref[...] = jnp.zeros(re_ref.shape, jnp.float32)
        im_ref[...] = jnp.zeros(im_ref.shape, jnp.float32)

    if per_row:
        # stacked payloads: one quantizer fit per bucket, spread over rows and
        # repeated over the lanes (Mosaic broadcasts a (1, 1) value along
        # sublanes or lanes, not both)
        prm = params_ref[0]  # (block_rows, 2 * 128)
        eps, p_codes = prm[:, :_LANES], prm[:, _LANES:]
    else:
        eps, p_codes = params_ref[w], params_ref[workers + w]
    # Mosaic widens u8 codes only to integers, not to floats
    rec = rec_ref[0].astype(jnp.int32).astype(jnp.float32)  # (block_rows, k)
    imc = imc_ref[0].astype(jnp.int32).astype(jnp.float32)
    idx = idx_ref[0].astype(jnp.int32)
    k = idx.shape[-1]
    row_of = jax.lax.broadcasted_iota(jnp.int32, (sel_rows, k), 0)
    col_of = jax.lax.broadcasted_iota(jnp.int32, (_LANES, k), 0)
    for i in range(block_rows):  # static unroll over the block's rows
        bins = idx[i:i + 1]
        on_row = (bins >> _LANE_BITS) == row_of
        lhs = jnp.concatenate([jnp.where(on_row, rec[i:i + 1], 0.0),
                               jnp.where(on_row, imc[i:i + 1], 0.0)], axis=0)
        rhs = jnp.where((bins & (_LANES - 1)) == col_of, 1.0, 0.0)
        codes = _dot_nt(lhs.astype(jnp.bfloat16), rhs.astype(jnp.bfloat16))
        if per_row:
            vals = decode_math(codes.astype(jnp.int32), eps[i:i + 1],
                               p_codes[i:i + 1], m_bits)
        else:
            vals = decode_math(codes.astype(jnp.int32), eps, p_codes, m_bits)
        view = pl.ds(i * a_rows, a_rows)
        re_ref[view, :] = re_ref[view, :] + vals[:a_rows]
        im_ref[view, :] = im_ref[view, :] + vals[sel_rows:sel_rows + a_rows]

    if workers > 1:
        @pl.when(w == workers - 1)
        def _():
            inv_p = 1.0 / workers
            re_ref[...] = re_ref[...] * inv_p
            im_ref[...] = im_ref[...] * inv_p


@functools.partial(jax.jit, static_argnames=("f_bins", "m_bits", "block_rows",
                                             "interpret"))
def spectrum_fold_pallas(
    re_codes: jnp.ndarray,  # (P, rows, k) uint8 codes
    im_codes: jnp.ndarray,  # (P, rows, k)
    idx: jnp.ndarray,  # (P, rows, k) int16 bin indices in [0, f_bins)
    eps: jnp.ndarray,  # (P,) or (P, rows)
    p_codes: jnp.ndarray,  # (P,) or (P, rows)
    *,
    f_bins: int,
    m_bits: int = 3,
    block_rows: int = _BLOCK_ROWS,
    interpret: bool = None,
):
    """P workers' quantized payload planes -> the mean spectrum's real and
    imaginary planes, each ``(rows * _view_rows(f_bins), 128)`` f32: row
    ``r``'s bin ``a*128 + b`` at ``[r * _view_rows(f_bins) + a, b]`` (unpadded
    in HBM; the bins past ``f_bins`` read 0).

    A block holds whole slot rows, of any width.  ``eps``/``p_codes`` hold
    one fit per worker, or one per worker and row (a stacked payload's
    per-bucket fits).
    """
    interpret = resolve_interpret(interpret)
    workers, rows, k = re_codes.shape
    block_rows = min(block_rows, rows)
    a_rows = _view_rows(f_bins)
    per_row = jnp.ndim(eps) == 2
    if per_row:
        params = jnp.repeat(
            jnp.stack([jnp.asarray(eps, jnp.float32),
                       p_codes.astype(jnp.float32)], axis=-1), _LANES, axis=-1)
        params_spec = pl.BlockSpec((1, block_rows, 2 * _LANES),
                                   lambda i, w: (w, i, 0),
                                   memory_space=pltpu.VMEM)
    else:
        params = jnp.concatenate([jnp.asarray(eps, jnp.float32).reshape(-1),
                                  p_codes.astype(jnp.float32).reshape(-1)])
        params_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    payload = pl.BlockSpec((1, block_rows, k), lambda i, w: (w, i, 0),
                           memory_space=pltpu.VMEM)
    plane = pl.BlockSpec((block_rows * a_rows, _LANES), lambda i, w: (i, 0),
                         memory_space=pltpu.VMEM)
    shape = jax.ShapeDtypeStruct((rows * a_rows, _LANES), jnp.float32)
    return pl.pallas_call(
        functools.partial(_fold_body, workers=workers, m_bits=m_bits,
                          per_row=per_row),
        grid=(pl.cdiv(rows, block_rows), workers),
        in_specs=[params_spec, payload, payload, payload],
        out_specs=[plane, plane],
        out_shape=[shape, shape],
        interpret=interpret,
    )(params, re_codes, im_codes, idx)


def fold_mean_spectrum(re_codes, im_codes, idx, eps, p_codes, *, f_bins: int,
                       m_bits: int) -> jnp.ndarray:
    """:func:`spectrum_fold_pallas`, then the complex64 ``(rows, f_bins)``
    mean spectrum the inverse FFT takes (one XLA slice-and-combine)."""
    re, im = spectrum_fold_pallas(re_codes, im_codes, idx, eps, p_codes,
                                  f_bins=f_bins, m_bits=m_bits)
    rows = re_codes.shape[1]
    return jax.lax.complex(re.reshape(rows, -1)[:, :f_bins],
                           im.reshape(rows, -1)[:, :f_bins])
