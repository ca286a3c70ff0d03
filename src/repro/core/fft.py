"""Chunked real FFT used by the frequency-domain sparsifier (paper §III-B.1).

The paper runs cuFFT over the flattened per-layer gradient.  On TPU we chunk
the signal into fixed-size pieces (default 4096) and transform each chunk
independently:

* static shapes (XLA requirement) regardless of layer size;
* each chunk's working set fits VMEM, and the Pallas ``fft4step`` kernel
  implements the transform as batches of 128- and 32-point DFT matmuls on
  the MXU;
* chunks are embarrassingly parallel => trivially shardable.

Because the input is real we use rFFT: a chunk of C reals produces F = C/2+1
complex coefficients.  Parseval with Hermitian symmetry means bin energies are

    E = (|X_0|^2 + 2*sum_{1..F-2} |X_k|^2 + |X_{F-1}|^2) / C

so DC and Nyquist carry weight 1 and interior bins weight 2
(:func:`hermitian_weights`).  Sparsification ranks bins by *weighted* magnitude
so the dropped-energy accounting behind Assumption 3.1 is exact (DESIGN.md §6).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "DEFAULT_CHUNK",
    "FFT_BLOCK_ROWS",
    "pad_to_chunks",
    "rfft_rows",
    "irfft_rows",
    "chunked_rfft",
    "chunked_irfft",
    "hermitian_weights",
    "chunk_energy",
]

DEFAULT_CHUNK = 4096

# XLA's TPU FFT keeps scratch several times the size of its operand (a
# (rows, 128, 32) view padded to whole (8, 128) tiles): ~11 GB for the
# ~1e5 chunks of a 385M-parameter gradient, more than fits beside the
# training state on a 16 GB chip.  Longer row stacks are transformed in
# blocks of this many rows, one block at a time.
FFT_BLOCK_ROWS = 4096


def _by_row_blocks(fn, x: jnp.ndarray) -> jnp.ndarray:
    """Apply a last-axis transform to every row of ``x``, at most
    ``FFT_BLOCK_ROWS`` rows per call."""
    lead = x.shape[:-1]
    rows = x.reshape(-1, x.shape[-1])
    if rows.shape[0] > FFT_BLOCK_ROWS:
        out = jax.lax.map(fn, rows, batch_size=FFT_BLOCK_ROWS)
    else:
        out = fn(rows)
    return out.reshape(lead + out.shape[-1:])


def rfft_rows(x: jnp.ndarray) -> jnp.ndarray:
    """Real (..., chunk) -> complex64 (..., chunk//2+1), row by row."""
    return _by_row_blocks(
        lambda r: jnp.fft.rfft(r.astype(jnp.float32), axis=-1).astype(
            jnp.complex64), x)


def irfft_rows(spectrum: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """Complex (..., chunk//2+1) -> f32 (..., chunk), row by row."""
    return _by_row_blocks(
        lambda r: jnp.fft.irfft(r, n=chunk, axis=-1).astype(jnp.float32),
        spectrum)


def pad_to_chunks(x_flat: jnp.ndarray, chunk: int = DEFAULT_CHUNK) -> Tuple[jnp.ndarray, int]:
    """Zero-pad a flat vector to a multiple of ``chunk`` and reshape.

    Returns (chunks_2d, original_length).  Padding with zeros is exact for the
    transform (adds no energy) and the tail is sliced off on inverse.
    """
    n = x_flat.shape[0]
    n_chunks = max(1, -(-n // chunk))
    padded = jnp.zeros((n_chunks * chunk,), x_flat.dtype).at[:n].set(x_flat)
    return padded.reshape(n_chunks, chunk), n


def chunked_rfft(x_flat: jnp.ndarray, chunk: int = DEFAULT_CHUNK) -> Tuple[jnp.ndarray, int]:
    """Flat f32 -> (n_chunks, chunk//2+1) complex64, plus the original length."""
    x2d, n = pad_to_chunks(x_flat.astype(jnp.float32), chunk)
    return rfft_rows(x2d), n


def chunked_irfft(freqs: jnp.ndarray, orig_len: int, chunk: int = DEFAULT_CHUNK) -> jnp.ndarray:
    """(n_chunks, chunk//2+1) complex64 -> flat f32 of ``orig_len``."""
    return irfft_rows(freqs, chunk).reshape(-1)[:orig_len]


def hermitian_weights(chunk: int = DEFAULT_CHUNK) -> jnp.ndarray:
    """Energy weights per rfft bin: [1, 2, 2, ..., 2, 1] (len chunk//2+1)."""
    f = chunk // 2 + 1
    w = jnp.full((f,), 2.0, jnp.float32)
    w = w.at[0].set(1.0)
    if chunk % 2 == 0:
        w = w.at[-1].set(1.0)
    return w


def chunk_energy(freqs: jnp.ndarray, chunk: int = DEFAULT_CHUNK) -> jnp.ndarray:
    """Per-chunk signal energy from rfft coefficients (Parseval)."""
    w = hermitian_weights(chunk)
    return jnp.sum(w * jnp.abs(freqs) ** 2, axis=-1) / chunk
