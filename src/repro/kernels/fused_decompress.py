"""Pallas TPU kernel: FUSED decompress — dequantize -> scatter-unpack ->
inverse FFT in one VMEM-resident pass.

Closes the asymmetry left by ``fused_compress``: the compress side had a
single fused kernel while decompress was three staged passes
(``range_quant.decode`` -> ``pack.unpack`` -> ``fft4step`` inverse), each
round-tripping the dense spectrum through HBM:

    read codes (~0.9 B/bin) + write re,im (8) + read re,im (8)
  + write full spectrum (8) + read full spectrum (8) + write signal (4)
    ~ 37 B/bin
vs
    read codes+idx (~0.9 B/bin) + write signal (4 B/bin)

Everything between — decode, the Hermitian scatter, and the 4-step iFFT
matmuls — stays in VMEM.  The Hermitian completion is folded into the
scatter itself: each kept rfft coefficient (value v at bin i) contributes

    spectrum[i]        += v          (direct)
    spectrum[4096 - i] += conj(v)    (mirror, interior bins 1..2047 only)

DC (0) and Nyquist (2048) are their own mirrors and contribute once.

The scatter lands directly in the iFFT's (32, 128) input view
``S[k1, k2] = X[k1 + 32*k2]`` (``fft4step.dft_rows``) as one matmul per
chunk: slot j contributes ``v_j * [k1_j == row] * [k2_j == col]``, i.e.
``S = L @ R^T`` with L = (32, k) value-weighted row selectors and
R = (128, k) column selectors — both built by comparing the slot indices
against an iota, and each S entry receives exactly one slot, so the matmul
moves values without summing them.  Any slot order works (reference
payloads are magnitude-ordered, pallas payloads index-ordered).  Padding
slots (code 0 at index 0) decode to 0.0 and add nothing, so payload widths
padded to the 128-lane tile are harmless.

Numerics match the unfused three-stage path to f32 matmul-FFT tolerance
(tests/test_engine.py::test_fused_decompress_matches_unfused).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.quantizer import decode_math
from repro.kernels import fft4step
from repro.kernels.runtime import resolve_interpret

__all__ = ["fused_decompress_pallas"]

_K_TILE = 128
_CHUNK = fft4step.CHUNK
_NYQUIST = _CHUNK // 2
_N1, _N2 = fft4step.N1, fft4step.N2
_K1_BITS = _N1.bit_length() - 1  # bin >> _K1_BITS == bin // 32
_BLOCK_ROWS = 8


def _dot_nt(a, b):
    """a (m, k) . b (n, k)^T -> (m, n), exact f32 for one-hot ``b``."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _scatter_view(re_k, im_k, idx):
    """One chunk's kept coefficients (1, k) -> Hermitian-completed spectrum
    in the iFFT input view, stacked [S_re; S_im] (64, 128)."""
    interior = (idx >= 1) & (idx <= _NYQUIST - 1)
    mirror = jnp.where(interior, _CHUNK - idx, -1)  # -1: no mirror slot
    k = idx.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (_N1, k), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (_N2, k), 0)

    def part(bins, im_sign):
        # bin = k1 + 32*k2; a bin of -1 selects no column (-1 >> 5 == -1)
        on_row = (bins & (_N1 - 1)) == row
        on_col = ((bins >> _K1_BITS) == col).astype(jnp.float32)
        lhs = jnp.concatenate([jnp.where(on_row, re_k, 0.0),
                               jnp.where(on_row, im_sign * im_k, 0.0)], axis=0)
        return _dot_nt(lhs, on_col)

    return part(idx, 1.0) + part(mirror, -1.0)


def _fused_decompress_body(params_ref, rec_ref, imc_ref, idx_ref,
                           f_re_ref, f_im_ref, t_re_ref, t_im_ref, g_ref,
                           out_ref, *, m_bits: int, per_row: bool = False):
    if per_row:
        # batched-bucket mode: one quantizer fit per row (DESIGN.md §14)
        eps = params_ref[:, 0:1]  # (r, 1), broadcasts against (r, k) codes
        p_codes = params_ref[:, 1:2]
    else:
        eps = params_ref[0]
        p_codes = params_ref[1]
    # 1. dequantize both code planes for the whole block (shared quantizer
    # math; Mosaic widens u8 codes only to integers, not to floats)
    re_k = decode_math(rec_ref[...].astype(jnp.int32), eps, p_codes, m_bits)
    im_k = decode_math(imc_ref[...].astype(jnp.int32), eps, p_codes, m_bits)
    idx = idx_ref[...]
    consts = (f_re_ref[...], f_im_ref[...], t_re_ref[...], t_im_ref[...],
              g_ref[...])

    # 2. per chunk: Hermitian scatter into the iFFT view, then 3. the
    # inverse 4-step FFT on the MXU; hermitian input -> real output
    for i in range(out_ref.shape[0]):  # static unroll over the block's rows
        s = _scatter_view(re_k[i:i + 1], im_k[i:i + 1], idx[i:i + 1])
        out_ref[i] = fft4step.dft_rows(s, *consts)[:_N1]


@functools.partial(jax.jit, static_argnames=("m_bits", "block_rows", "interpret"))
def fused_decompress_pallas(
    re_codes: jnp.ndarray,  # (rows, k) uint8/uint16 codes
    im_codes: jnp.ndarray,  # (rows, k)
    idx: jnp.ndarray,  # (rows, k) int16/int32 bin indices in [0, 2048]
    eps: jnp.ndarray,
    p_codes: jnp.ndarray,
    *,
    m_bits: int = 3,
    block_rows: int = _BLOCK_ROWS,
    interpret: bool = None,
) -> jnp.ndarray:
    """Quantized payload planes -> (rows, 4096) f32 time-domain chunks.

    Accepts any payload width; pads to the 128-lane tile internally with
    code-0/index-0 slots (decode-neutral, see module docstring).

    ``eps``/``p_codes`` may be scalars (one fit for every row) or ``(rows,)``
    vectors (one fit per row — the batched bucket executor decompresses every
    bucket of a stacked payload in this one launch; DESIGN.md §14).
    """
    interpret = resolve_interpret(interpret)
    rows, k = re_codes.shape
    k_pad = max(_K_TILE, ((k + _K_TILE - 1) // _K_TILE) * _K_TILE)
    if k_pad != k:
        pad = [(0, 0), (0, k_pad - k)]
        re_codes = jnp.pad(re_codes, pad)
        im_codes = jnp.pad(im_codes, pad)
        idx = jnp.pad(idx, pad)
    block_rows = min(block_rows, rows)
    grid = (pl.cdiv(rows, block_rows),)
    per_row = jnp.ndim(eps) == 1
    if per_row:
        params = jnp.zeros((rows, _K_TILE), jnp.float32)
        params = (params.at[:, 0].set(jnp.asarray(eps, jnp.float32))
                  .at[:, 1].set(p_codes.astype(jnp.float32)))
    else:
        params = jnp.stack([
            jnp.asarray(eps, jnp.float32),
            p_codes.astype(jnp.float32),
        ])
    consts = [jnp.asarray(c) for c in fft4step.dft_constants(inverse=True)]
    data = lambda c: pl.BlockSpec((block_rows, c), lambda i: (i, 0),
                                  memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_fused_decompress_body, m_bits=m_bits,
                          per_row=per_row),
        grid=grid,
        in_specs=[data(_K_TILE) if per_row
                  else pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [data(k_pad)] * 3
        + [pl.BlockSpec(c.shape, lambda i: (0, 0), memory_space=pltpu.VMEM)
           for c in consts],
        out_specs=pl.BlockSpec((block_rows, _N1, _N2), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, _N1, _N2), jnp.float32),
        interpret=interpret,
    )(params, re_codes, im_codes, idx.astype(jnp.int32), *consts)
    return out.reshape(rows, _CHUNK)
