"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantizer import RangeQuantConfig, fit_quantizer
from repro.kernels import ops, ref


@pytest.mark.parametrize("rows,cols", [(1, 256), (4, 2049), (16, 4096), (3, 512)])
@pytest.mark.parametrize("n_bits,m_bits", [(8, 3), (8, 2), (6, 3)])
def test_quant_kernel_vs_ref(rows, cols, n_bits, m_bits):
    q = fit_quantizer(-1.5, 2.0, RangeQuantConfig(n_bits, m_bits))
    x = jax.random.normal(jax.random.PRNGKey(rows * cols), (rows, cols))
    codes_k = ops.quant_encode(x, q)
    codes_r = ref.quant_encode_ref(x, q.eps, q.p_codes, n_bits, m_bits)
    np.testing.assert_array_equal(np.array(codes_k, np.int32), np.array(codes_r, np.int32))
    dec_k = ops.quant_decode(codes_k, q)
    dec_r = ref.quant_decode_ref(codes_r, q.eps, q.p_codes, n_bits, m_bits)
    np.testing.assert_allclose(np.array(dec_k), np.array(dec_r), rtol=1e-6)


@pytest.mark.parametrize("rows,cols,k", [(2, 2049, 615), (8, 4096, 128), (1, 512, 500)])
def test_threshold_kernel_vs_ref(rows, cols, k):
    mag = jnp.abs(jax.random.normal(jax.random.PRNGKey(k), (rows, cols)))
    tau_k, cnt_k = ops.threshold_select(mag, k)
    tau_r, cnt_r = ref.threshold_ref(mag, k)
    # continuous data: bisection converges to the exact k-th order statistic
    np.testing.assert_array_equal(np.array(cnt_k).ravel(), np.array(cnt_r).ravel())
    np.testing.assert_allclose(np.array(tau_k), np.array(tau_r), rtol=1e-4)


def test_threshold_kernel_with_ties():
    """Ties at the threshold: count >= k, never < k (budget is preserved)."""
    mag = jnp.concatenate([jnp.full((1, 64), 2.0), jnp.full((1, 64), 1.0)], axis=1)
    tau, cnt = ops.threshold_select(mag, 32)
    assert int(cnt[0, 0]) >= 32
    assert float(tau[0, 0]) <= 2.0


@pytest.mark.parametrize("rows,cols,k", [(2, 2049, 615), (4, 1024, 100)])
def test_pack_unpack_kernel_vs_ref(rows, cols, k):
    x = jax.random.normal(jax.random.PRNGKey(7), (rows, cols))
    tau, _ = ops.threshold_select(jnp.abs(x), k)
    vals_k, idx_k = ops.pack_threshold(x, tau, k)
    vals_r, idx_r = ref.pack_ref(x, tau, ops.pad_k(k))
    np.testing.assert_allclose(np.array(vals_k), np.array(vals_r), atol=1e-7)
    np.testing.assert_array_equal(np.array(idx_k), np.array(idx_r))
    dense_k = ops.unpack_dense(vals_k, idx_k, cols)
    dense_r = ref.unpack_ref(vals_r, idx_r, cols)
    np.testing.assert_allclose(np.array(dense_k), np.array(dense_r), atol=1e-7)


@pytest.mark.parametrize("rows", [1, 4, 9])
@pytest.mark.parametrize("scale", [1.0, 1e-3, 1e3])
def test_fft_kernel_forward_vs_ref(rows, scale):
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, 4096)) * scale
    re_k, im_k = ops.rfft4096(x)
    z = jnp.fft.rfft(x, axis=-1)
    tol = 2e-5 * scale * 64  # fp32 matmul accumulation over 4096 points
    np.testing.assert_allclose(np.array(re_k), np.array(jnp.real(z)), atol=tol)
    np.testing.assert_allclose(np.array(im_k), np.array(jnp.imag(z)), atol=tol)


def test_fft_kernel_inverse_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(11), (4, 4096))
    re, im = ops.rfft4096(x)
    xr = ops.irfft4096(re, im)
    np.testing.assert_allclose(np.array(xr), np.array(x), atol=1e-4)


def test_fft_kernel_full_vs_ref_complex():
    """Full complex transform against jnp.fft (both directions)."""
    from repro.kernels import fft4step

    xr = jax.random.normal(jax.random.PRNGKey(1), (2, 4096))
    xi = jax.random.normal(jax.random.PRNGKey(2), (2, 4096))
    for inverse in (False, True):
        kr, ki = fft4step.fft4096_pallas(xr, xi, inverse=inverse, interpret=True)
        rr, ri = ref.fft4096_ref(xr, xi, inverse=inverse)
        np.testing.assert_allclose(np.array(kr), np.array(rr), atol=3e-3)
        np.testing.assert_allclose(np.array(ki), np.array(ri), atol=3e-3)


def test_composed_kernel_pipeline_matches_core():
    """compress_chunks/decompress_chunks == core FFTCompressor bit-for-bit."""
    from repro.core.compressor import FFTCompressor, FFTCompressorConfig

    g = jax.random.normal(jax.random.PRNGKey(3), (8 * 4096,)) * 0.05
    q = fit_quantizer(-3.0, 3.0, RangeQuantConfig(8, 3))
    payload = ops.compress_chunks(g.reshape(8, 4096), 615, q)
    ghat_k = ops.decompress_chunks(payload[0], payload[1], payload[2], q, g.shape[0])
    comp = FFTCompressor(FFTCompressorConfig(
        theta=0.7, range_mode="fixed", fixed_range=(-3.0, 3.0)))
    ghat_c = comp.decompress(comp.compress(g))
    np.testing.assert_allclose(np.array(ghat_k), np.array(ghat_c), atol=1e-5)


@pytest.mark.parametrize("k_keep", [127, 128, 129])
def test_fused_golden_at_tile_boundary_keep_counts(k_keep):
    """Golden-value check of the fused kernel vs the ref.py oracles at keep
    counts straddling the 128-lane tile: 127 (pad fills one slot), 128
    (exact), 129 (spills into a second tile).  The payload slots beyond the
    true keep count must stay code-0/index-0 padding."""
    from repro.core import fft as cfft
    from repro.kernels import fused_compress

    cols = 513  # 1024-chunk rfft bins: tests a non-4096 plane too
    q = fit_quantizer(-2.0, 2.0, RangeQuantConfig(8, 3))
    key = jax.random.PRNGKey(k_keep)
    re = jax.random.normal(key, (3, cols)) * 0.05
    im = jax.random.normal(jax.random.fold_in(key, 1), (3, cols)) * 0.05
    w = cfft.hermitian_weights(1024)

    # oracle: exact k-th order statistic threshold, then index-ordered pack
    mag = jnp.sqrt(re * re + im * im) * w[None, :]
    rec_f, imc_f, idx_f, tau_f = fused_compress.fused_compress_pallas(
        re, im, mag, q.eps, q.p_codes, k_keep=k_keep, interpret=True)

    tau_r, _ = ref.threshold_ref(mag, k_keep)
    k_pad = ops.pad_k(k_keep)
    mvals, idx_r = ref.pack_ref(mag, tau_r, k_pad)
    valid = mvals != 0
    re_k = jnp.take_along_axis(re, idx_r, axis=-1) * valid
    im_k = jnp.take_along_axis(im, idx_r, axis=-1) * valid
    rec_r = jnp.where(valid, ref.quant_encode_ref(re_k, q.eps, q.p_codes), 0)
    imc_r = jnp.where(valid, ref.quant_encode_ref(im_k, q.eps, q.p_codes), 0)

    assert rec_f.shape == (3, k_pad)  # 127->128, 128->128, 129->256
    np.testing.assert_allclose(
        np.array(tau_f).ravel(), np.array(tau_r).ravel(), rtol=1e-4)
    np.testing.assert_array_equal(np.array(idx_f), np.array(idx_r))
    np.testing.assert_array_equal(np.array(rec_f), np.array(rec_r))
    np.testing.assert_array_equal(np.array(imc_f), np.array(imc_r))
    # padding slots beyond k_keep carry no payload
    n_kept = int(np.sum(np.array(mag) >= np.array(tau_r), axis=-1).max())
    assert n_kept == k_keep  # continuous data: no threshold ties
    assert not np.any(np.array(rec_f)[:, k_keep:])
    assert not np.any(np.array(idx_f)[:, k_keep:])


def test_fused_matches_unfused():
    """fused_compress (threshold+pack+quant in one VMEM pass) == unfused."""
    from repro.core import fft as cfft
    from repro.kernels import fused_compress

    q = fit_quantizer(-2.0, 2.0, RangeQuantConfig(8, 3))
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 4096)) * 0.05
    re, im = ops.rfft4096(x)
    w = cfft.hermitian_weights(4096)

    mag = jnp.sqrt(re * re + im * im) * w
    rec_f, imc_f, idx_f, tau_f = fused_compress.fused_compress_pallas(
        re, im, mag, q.eps, q.p_codes, k_keep=615, interpret=True)

    tau_u, _ = ops.threshold_select(mag, 615)
    mvals, idx_u = ops.pack_threshold(mag, tau_u, 615)
    re_k = jnp.take_along_axis(re, idx_u, axis=-1) * (mvals != 0)
    im_k = jnp.take_along_axis(im, idx_u, axis=-1) * (mvals != 0)
    rec_u = ops.quant_encode(re_k, q)
    imc_u = ops.quant_encode(im_k, q)

    np.testing.assert_allclose(np.array(tau_f), np.array(tau_u), rtol=1e-5)
    np.testing.assert_array_equal(np.array(idx_f), np.array(idx_u))
    np.testing.assert_array_equal(np.array(rec_f), np.array(rec_u))
    np.testing.assert_array_equal(np.array(imc_f), np.array(imc_u))
