"""Walk the paper's Fig. 5 pipeline stage by stage and print what each does,
including the Pallas-kernel path (interpret mode on CPU).

    PYTHONPATH=src python examples/compression_demo.py
"""

import jax
import jax.numpy as jnp

from repro.core import fft as cfft
from repro.core import packing, sparsify
from repro.core.quantizer import RangeQuantConfig, fit_quantizer
from repro.kernels import ops

THETA = 0.7
grad = jax.random.normal(jax.random.PRNGKey(0), (8 * 4096,)) * 0.05
print(f"gradient: {grad.size} floats = {grad.size * 4 / 1e3:.0f} KB")

# 1. chunked rFFT (TPU: fft4step Pallas kernel — 128- and 32-point DFT matmuls)
freqs, n = cfft.chunked_rfft(grad)
print(f"1. rFFT -> {freqs.shape} complex bins per chunk")

# 2. theta-drop: keep top 30% of bins by weighted magnitude
k = sparsify.keep_count(freqs.shape[-1], THETA)
mag = jnp.abs(freqs) * cfft.hermitian_weights()
idx = sparsify.topk_select(mag, k)
kept = packing.pack_by_indices(freqs, idx)
dropped_energy = 1 - float((jnp.abs(kept) ** 2 * 2).sum() / (mag**2 / cfft.hermitian_weights()).sum())
print(f"2. sparsify theta={THETA}: keep {k}/{freqs.shape[-1]} bins")

# 3. range-based 8-bit quantization (paper Alg. 1)
q = fit_quantizer(jnp.real(kept).min(), jnp.real(kept).max(), RangeQuantConfig(8, 3))
re_codes = q.encode(jnp.real(kept))
im_codes = q.encode(jnp.imag(kept))
print(f"3. quantize: eps={float(q.eps):.2e}, P={int(q.p_codes)} positive codes")

# 4. wire size
wire = re_codes.size + im_codes.size + idx.size * 2
print(f"4. payload: {wire / 1e3:.0f} KB -> ratio {grad.size * 4 / wire:.1f}x")

# 5. reconstruct (receiver side, reverse order)
re = q.decode(re_codes).astype(jnp.float32)
im = q.decode(im_codes).astype(jnp.float32)
spectrum = packing.unpack_by_indices(re + 1j * im, idx, freqs.shape[-1])
grad_hat = cfft.chunked_irfft(spectrum, n)
rel = float(jnp.linalg.norm(grad - grad_hat) / jnp.linalg.norm(grad))
sign = float(jnp.mean(jnp.sign(grad_hat) == jnp.sign(grad)))
print(f"5. reconstruct: rel err {rel:.3f}, sign agreement {sign:.3f}")

# 6. the same pipeline through the Pallas TPU kernels (interpret mode here)
payload = ops.compress_chunks(grad.reshape(8, 4096), k, q)
grad_hat_k = ops.decompress_chunks(payload[0], payload[1], payload[2], q, n)
print(f"6. Pallas kernel path matches: "
      f"{float(jnp.max(jnp.abs(grad_hat_k - grad_hat))):.2e} max diff")
