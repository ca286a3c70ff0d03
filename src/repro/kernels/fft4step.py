"""Pallas TPU kernel: 4096-point FFT via Bailey's 4-step algorithm on the MXU.

The paper leans on cuFFT.  TPUs have no FFT unit — but the MXU is a 128x128
systolic matmul array, and Bailey's 4-step factorization turns an N-point DFT
into two batches of small DFT *matmuls*.  With N = 32 * 128, input bin
k = k1 + 32*k2 and output sample n = 128*a + b:

    S[k1, k2] = X[k1 + 32*k2]          (32, 128) input view          [stage 0]
    C = S @ F128                       (DFT along k2)                [stage 1]
    D = C * T,  T[k1, b] = w^(k1*b)    (twiddle, elementwise)        [stage 2]
    O = F32 @ D                        (DFT along k1)                [stage 3]
    x[128*a + b] = O[a, b]             (row-major read-out)

The factor order is chosen for the TPU's (8, 128) vreg tiling: every matrix
is 128 lanes wide, and the output O is the transformed chunk in row-major
order, so no stage reshapes or transposes inside the kernel (Mosaic cannot
lower the 64-lane 3-D views a 64 x 64 split needs).  Only the input view is
a transpose; the fused decompress kernel builds it for free in its scatter,
and the standalone kernel takes it from XLA.

Complex arithmetic is carried as separate real/imag planes stacked along
sublanes ([re; im], (64, 128)) so that each stage is one or two real
matmuls: stage 1 multiplies the stack by F128's real and imaginary parts,
stage 3 multiplies the stacked twiddled planes by the block matrix
[[F32re, -F32im], [F32im, F32re]].

Napkin math (why this beats a "ported" radix-2 FFT on TPU): 4-step does
2*(2*64*128*128 + 64*64*128) = 5.2 MFLOP per 4096-chunk vs ~0.25 MFLOP for
radix-2 — 20x more FLOPs — but runs on the MXU with zero shuffle/bit-reverse
ops, vs the VPU's ~4 TFLOP/s with heavy lane crossings, and the chunk never
leaves VMEM.

The inverse uses conjugate factors and folds 1/N into stage 3.  ``rfft``
semantics (first 2049 bins) are applied by the ops.py wrapper; the kernel
produces/consumes the full 4096-bin spectrum.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.runtime import resolve_interpret

__all__ = ["fft4096_pallas", "dft_rows", "dft_constants", "CHUNK", "N1", "N2"]

CHUNK = 4096
N1 = 32   # sublane factor (k1, a)
N2 = 128  # lane factor (k2, b)


@functools.lru_cache(maxsize=4)
def dft_constants(inverse: bool):
    """(F128_re, F128_im, T_re, T_im, G) as float32 numpy arrays.

    G is the (64, 64) real block form of F32 (scaled by 1/N for the
    inverse): [[re, -im], [im, re]] maps stacked [D_re; D_im] to [O_re; O_im].
    """
    sign = 2.0 if inverse else -2.0
    j2 = np.arange(N2)
    f128 = np.exp(sign * 1j * np.pi * np.outer(j2, j2) / N2)
    t = np.exp(sign * 1j * np.pi * np.outer(np.arange(N1), j2) / CHUNK)
    j1 = np.arange(N1)
    f32 = np.exp(sign * 1j * np.pi * np.outer(j1, j1) / N1)
    if inverse:
        f32 = f32 / CHUNK
    g = np.block([[f32.real, -f32.imag], [f32.imag, f32.real]])
    return tuple(a.astype(np.float32)
                 for a in (f128.real, f128.imag, t.real, t.imag, g))


def _dot(a, b):
    return jax.lax.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)


def dft_rows(s, f_re, f_im, t_re, t_im, g):
    """One chunk's 4-step DFT: stacked [S_re; S_im] (64, 128) in the
    ``S[k1, k2] = X[k1 + 32*k2]`` view -> stacked [O_re; O_im] (64, 128),
    each half the transformed chunk in row-major order.

    Shared by the standalone FFT kernel and the fused decompress kernel
    (``kernels/fused_decompress.py``), which runs it as the last stage of
    one VMEM-resident pass."""
    sf_re = _dot(s, f_re)  # [S_re F_re; S_im F_re]
    sf_im = _dot(s, f_im)  # [S_re F_im; S_im F_im]
    c_re = sf_re[:N1] - sf_im[N1:]
    c_im = sf_im[:N1] + sf_re[N1:]
    d_re = c_re * t_re - c_im * t_im
    d_im = c_re * t_im + c_im * t_re
    return _dot(g, jnp.concatenate([d_re, d_im], axis=0))


def _fft_body(f_re_ref, f_im_ref, t_re_ref, t_im_ref, g_ref, s_ref, o_ref):
    consts = (f_re_ref[...], f_im_ref[...], t_re_ref[...], t_im_ref[...],
              g_ref[...])
    for i in range(s_ref.shape[0]):  # static unroll over the block's chunks
        o_ref[i] = dft_rows(s_ref[i], *consts)


@functools.partial(jax.jit, static_argnames=("inverse", "block_chunks", "interpret"))
def fft4096_pallas(
    x_re: jnp.ndarray,
    x_im: jnp.ndarray,
    *,
    inverse: bool = False,
    block_chunks: int = 8,
    interpret: bool = None,
):
    """Batched 4096-pt complex FFT: (rows, 4096) re/im -> (rows, 4096) re/im.

    XLA builds the (32, 128) input view of each chunk; the kernel runs the
    matmul stages.  VMEM per block at block_chunks=8: 8 chunks x 2 planes x
    (input + output) x 32 KiB ≈ 1 MiB plus ~200 KiB of constants.
    """
    interpret = resolve_interpret(interpret)
    rows, n = x_re.shape
    assert n == CHUNK, f"kernel is specialized to {CHUNK}-pt chunks"
    block_chunks = min(block_chunks, rows)
    grid = (pl.cdiv(rows, block_chunks),)
    consts = [jnp.asarray(c) for c in dft_constants(inverse)]

    def view(x):  # S[k1, k2] = x[k1 + 32*k2]
        return x.astype(jnp.float32).reshape(rows, N2, N1).transpose(0, 2, 1)

    s = jnp.concatenate([view(x_re), view(x_im)], axis=1)  # (rows, 64, 128)
    data_spec = pl.BlockSpec((block_chunks, 2 * N1, N2), lambda i: (i, 0, 0),
                             memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        _fft_body,
        grid=grid,
        in_specs=[pl.BlockSpec(c.shape, lambda i: (0, 0),
                               memory_space=pltpu.VMEM) for c in consts]
        + [data_spec],
        out_specs=data_spec,
        out_shape=jax.ShapeDtypeStruct((rows, 2 * N1, N2), jnp.float32),
        interpret=interpret,
    )(*consts, s)
    return out[:, :N1].reshape(rows, CHUNK), out[:, N1:].reshape(rows, CHUNK)
