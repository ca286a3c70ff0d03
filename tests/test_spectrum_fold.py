"""The fold kernel (kernels/spectrum_fold.py) against the shared jnp fold.

Contract under test: the pallas backend's ``mean_spectrum`` and
``decompress_spectrum`` return the jnp scatter fold's spectrum BITWISE —
same decode, workers added in the same order onto zeros, the same ``1/P``
multiply — whatever the slot order, with code-0/index-0 padding slots and
with the DC and Nyquist bins kept, for one fit per worker and for a stacked
payload's per-bucket fits.  Payloads the kernel does not take fall back to
the jnp scatter with a logged reason.  Kernels run in interpret mode here;
``tests/test_mosaic_compile.py`` compiles them for a v5e.
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.compressor import (
    FFTCompressor,
    FFTCompressorConfig,
    FFTPayload,
    StackedPayload,
    stack_bucket_quant,
)
from repro.core.quantizer import RangeQuantConfig, fit_quantizer
from repro.kernels import engine

REF, PAL = engine.get_backend("reference"), engine.get_backend("pallas")


def _rows(rng, n_rows, chunk, k, order, n_bits):
    """Codes and indices of ``n_rows`` rows: DC and Nyquist kept, a few
    code-0/index-0 padding slots, unique bins otherwise."""
    f = chunk // 2 + 1
    n_pad = 3
    codes = np.zeros((2, n_rows, k), np.int64)
    idx = np.zeros((n_rows, k), np.int64)
    for r in range(n_rows):
        kept = np.concatenate([[0, f - 1], rng.choice(
            np.arange(1, f - 1), k - n_pad - 2, replace=False)])
        if order == "ascending":  # the pallas backend's compaction order
            kept = np.sort(kept)
        slots = np.concatenate([kept, np.zeros(n_pad, np.int64)])
        vals = np.concatenate([rng.integers(1, 2 ** n_bits, (2, k - n_pad)),
                               np.zeros((2, n_pad), np.int64)], axis=1)
        if order == "magnitude":  # any order: padding mixed in
            perm = rng.permutation(k)
            slots, vals = slots[perm], vals[:, perm]
        idx[r], codes[:, r] = slots, vals
    dtype = RangeQuantConfig(n_bits, 3).code_dtype
    return (jnp.asarray(codes[0], dtype), jnp.asarray(codes[1], dtype),
            jnp.asarray(idx, jnp.int16))


def _gathered(workers, kind, order="ascending", chunk=4096, n_bits=8, seed=0):
    """``workers`` payloads stacked on a leading worker axis, as the
    all-gather hands them to the fold: monolithic (one fit per worker) or
    stacked over 2 buckets of 3 chunk rows (one fit per bucket)."""
    rng = np.random.default_rng(seed)
    qcfg = RangeQuantConfig(n_bits, 3)
    k = round(0.3 * (chunk // 2 + 1))  # theta 0.7: 615 slots at 4096
    payloads = []
    for w in range(workers):
        if kind == "scalar":
            re, im, idx = _rows(rng, 5, chunk, k, order, n_bits)
            q = fit_quantizer(jnp.float32(-0.01 * (w + 1)), jnp.float32(0.02), qcfg)
            payloads.append(FFTPayload(re, im, idx, q, 5 * chunk - 7, chunk))
        else:
            re, im, idx = (a.reshape(2, 3, k)
                           for a in _rows(rng, 6, chunk, k, order, n_bits))
            q = stack_bucket_quant(fit_quantizer(
                jnp.asarray([-0.01, -0.3], jnp.float32) * (w + 1),
                jnp.asarray([0.02, 0.1], jnp.float32), qcfg))
            payloads.append(StackedPayload(re, im, idx, q,
                                           (3 * chunk, 2 * chunk + 5), chunk))
    return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *payloads)


def _assert_bitwise(a, b):
    bits = lambda z: np.stack([np.real(z), np.imag(z)]).view(np.uint32)
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape and a.dtype == b.dtype == np.complex64
    np.testing.assert_array_equal(bits(a), bits(b))


@pytest.mark.parametrize("kind", ["scalar", "per_row"])
@pytest.mark.parametrize("order", ["ascending", "magnitude"])
@pytest.mark.parametrize("workers", [1, 3])
def test_fold_kernel_mean_spectrum_is_the_jnp_folds_bitwise(workers, order, kind):
    g = _gathered(workers, kind, order)
    _assert_bitwise(PAL.mean_spectrum(g), REF.mean_spectrum(g))


def test_fold_kernel_takes_smaller_chunks():
    g = _gathered(2, "per_row", "magnitude", chunk=1024)
    _assert_bitwise(PAL.mean_spectrum(g), REF.mean_spectrum(g))


@pytest.mark.parametrize("kind", ["scalar", "per_row"])
@pytest.mark.parametrize("into", [False, True])
def test_fold_kernel_decompress_spectrum_is_the_jnp_scatters_bitwise(into, kind):
    """One payload folds with the kernel; onto a running spectrum ``into``
    (the shared worker loop's call) it keeps the jnp scatter."""
    g = _gathered(2, kind, "magnitude")
    one, other = (jax.tree_util.tree_map(lambda a: a[w], g) for w in (0, 1))
    base = REF.decompress_spectrum(other) if into else None
    jaxpr = str(jax.make_jaxpr(PAL.decompress_spectrum)(one, base))
    assert ("pallas_call" in jaxpr) is not into
    _assert_bitwise(PAL.decompress_spectrum(one, base),
                    REF.decompress_spectrum(one, base))


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_fold_kernel_on_compressed_payloads(backend):
    """Real payloads: the reference packs magnitude-descending, the pallas
    backend index-ascending; both fold to the jnp fold's spectrum."""
    comp = FFTCompressor(FFTCompressorConfig(backend=backend, theta=0.7))
    grads = jax.random.normal(jax.random.PRNGKey(3), (3, 2 * 4096 + 517)) * 0.05
    g = jax.tree_util.tree_map(lambda *a: jnp.stack(a),
                               *[comp.compress(x) for x in grads])
    _assert_bitwise(PAL.mean_spectrum(g), REF.mean_spectrum(g))


def test_auto_backend_folds_with_the_kernel_where_mosaic_compiles(monkeypatch):
    monkeypatch.setattr(engine, "mosaic_available", lambda: True)
    g = _gathered(2, "scalar")
    auto = engine.get_backend("auto")
    assert "pallas_call" in str(jax.make_jaxpr(auto.mean_spectrum)(g))
    one = jax.tree_util.tree_map(lambda a: a[0], g)
    assert "pallas_call" in str(jax.make_jaxpr(auto.decompress_spectrum)(one))
    reference = str(jax.make_jaxpr(REF.mean_spectrum)(g))
    assert "pallas_call" not in reference and "scatter" in reference
    _assert_bitwise(auto.mean_spectrum(g), REF.mean_spectrum(g))


def test_auto_backend_folds_with_jnp_off_tpu():
    g = _gathered(2, "scalar")
    assert "pallas_call" not in str(
        jax.make_jaxpr(engine.get_backend("auto").mean_spectrum)(g))


@pytest.mark.parametrize("case, reason", [
    ("16-bit codes", "16-bit codes"),
    ("chunk 384", "chunked at 384"),
    ("unquantized", "unquantized"),
])
def test_fold_kernel_refuses_with_a_logged_reason(case, reason, monkeypatch, caplog):
    monkeypatch.setattr(engine, "_logged_reasons", set())
    if case == "16-bit codes":
        g = _gathered(2, "scalar", n_bits=16)
    elif case == "chunk 384":
        g = _gathered(2, "scalar", chunk=384)
    else:  # values shipped as f32
        g = _gathered(2, "scalar")
        g = FFTPayload(g.re.astype(jnp.float32), g.im.astype(jnp.float32),
                       g.idx, None, g.orig_len, g.chunk)
    with caplog.at_level(logging.INFO, logger=engine.__name__):
        assert "pallas_call" not in str(jax.make_jaxpr(PAL.mean_spectrum)(g))
        _assert_bitwise(PAL.mean_spectrum(g), REF.mean_spectrum(g))
    assert any(reason in r.getMessage() and "mean_spectrum" in r.getMessage()
               for r in caplog.records), caplog.text
