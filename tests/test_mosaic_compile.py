"""The Pallas kernels compile for a TPU v5e chip (Mosaic, not interpret mode).

Each case AOT-compiles one kernel at the training path's real shapes — 4096-
point chunks (2049 rFFT bins), the theta=0.7 keep count (615, padded to 640
slots) and 1024 chunk rows — for a v5e chip that is described, not attached.
The compile runs the chip's own compiler, so it refuses what the chip would:
block shapes off the (8, 128) tiling, primitives Mosaic cannot lower, and
kernels that need more VMEM than a kernel may use.  Nothing runs; results
are covered by the interpret-mode suites (test_kernels.py, test_engine.py).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (
    fft4step,
    fused_compress,
    fused_decompress,
    pack,
    range_quant,
    sampled_threshold,
    spectrum_fold,
    topk_threshold,
)

ROWS, BINS, K_KEEP, K_PAD = 1024, 2049, 615, 640


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile cannot be read back from the persistent cache without a
    # chip; keep the cache out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


F32, I32, I16, U8 = jnp.float32, jnp.int32, jnp.int16, jnp.uint8


def _fold_case(workers, per_row):
    q = (workers, ROWS) if per_row else (workers,)
    payload = (workers, ROWS, K_KEEP)
    return (
        lambda rec, imc, idx, eps, p: spectrum_fold.spectrum_fold_pallas(
            rec, imc, idx, eps, p, f_bins=BINS, interpret=False),
        [(payload, U8), (payload, U8), (payload, I16), (q, F32), (q, I32)])


CASES = {
    "fused_compress_per_row_tau": (
        lambda re, im, mag, eps, p, tau: fused_compress.fused_compress_pallas(
            re, im, mag, eps, p, tau, k_keep=K_KEEP, interpret=False),
        [((ROWS, BINS), F32), ((ROWS, BINS), F32), ((ROWS, BINS), F32),
         ((ROWS,), F32), ((ROWS,), I32), ((ROWS, 1), F32)]),
    "fused_compress_scalar_bisect": (
        lambda re, im, mag, eps, p: fused_compress.fused_compress_pallas(
            re, im, mag, eps, p, k_keep=K_KEEP, interpret=False),
        [((ROWS, BINS), F32), ((ROWS, BINS), F32), ((ROWS, BINS), F32),
         ((), F32), ((), I32)]),
    "fused_decompress_per_row": (
        lambda rec, imc, idx, eps, p: fused_decompress.fused_decompress_pallas(
            rec, imc, idx, eps, p, interpret=False),
        [((ROWS, K_KEEP), U8), ((ROWS, K_KEEP), U8), ((ROWS, K_KEEP), I16),
         ((ROWS,), F32), ((ROWS,), I32)]),
    "fused_decompress_scalar": (
        lambda rec, imc, idx, eps, p: fused_decompress.fused_decompress_pallas(
            rec, imc, idx, eps, p, interpret=False),
        [((ROWS, K_KEEP), U8), ((ROWS, K_KEEP), U8), ((ROWS, K_KEEP), I16),
         ((), F32), ((), I32)]),
    **{f"spectrum_fold_p{w}_{'per_row' if per_row else 'scalar'}":
       _fold_case(w, per_row) for w in (1, 4) for per_row in (False, True)},
    "sampled_threshold": (
        lambda mag, lo, hi: sampled_threshold.sampled_threshold_pallas(
            mag, lo, hi, k=K_KEEP, interpret=False),
        [((ROWS, BINS), F32), ((ROWS,), F32), ((ROWS,), F32)]),
    "threshold": (
        lambda mag: topk_threshold.threshold_pallas(
            mag, k=K_KEEP, interpret=False),
        [((ROWS, BINS), F32)]),
    "range_quant_encode": (
        lambda x, eps, p: range_quant.encode_pallas(x, eps, p, interpret=False),
        [((ROWS, K_PAD), F32), ((), F32), ((), I32)]),
    "range_quant_decode": (
        lambda c, eps, p: range_quant.decode_pallas(c, eps, p, interpret=False),
        [((ROWS, K_PAD), U8), ((), F32), ((), I32)]),
    "fft4096": (
        lambda re, im: fft4step.fft4096_pallas(re, im, interpret=False),
        [((ROWS, 4096), F32), ((ROWS, 4096), F32)]),
    "pack": (
        lambda x, tau: pack.pack_pallas(x, tau, k=K_PAD, interpret=False),
        [((ROWS, BINS), F32), ((ROWS, 1), F32)]),
    "unpack": (
        lambda vals, idx: pack.unpack_pallas(vals, idx, cols=2560,
                                             interpret=False),
        [((ROWS, K_PAD), F32), ((ROWS, K_PAD), I32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()


def test_mean_spectrum_compiles_to_the_fold_kernel_for_v5e(one_chip, monkeypatch):
    """The transports' fold, compiled for the described chip with Mosaic on,
    is the ``spectrum_fold_pallas`` launch under ``exchange.fold``: no sort
    and no scatter (XLA sorts the pairs of a scatter this large, above 2**20
    kept coefficients)."""
    import re

    from repro.core.compressor import FFTCompressor, FFTCompressorConfig, StackedPayload
    from repro.core.quantizer import FittedQuantizer, RangeQuantConfig
    from repro.kernels import engine, runtime

    monkeypatch.setattr(engine, "mosaic_available", lambda: True)
    monkeypatch.setattr(runtime, "mosaic_available", lambda: True)
    workers, rows = 2, 2048
    arg = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    plane = (workers, 1, rows, K_KEEP)
    quant = FittedQuantizer(RangeQuantConfig(8, 3), *(
        arg((workers, 1, 1, 1), d) for d in (F32, I32, F32, F32)))
    gathered = StackedPayload(arg(plane, U8), arg(plane, U8), arg(plane, I16),
                              quant, (rows * 4096,), 4096)
    comp = FFTCompressor(FFTCompressorConfig(backend="auto"))
    text = jax.jit(comp.mean_spectrum).lower(
        gathered).compile().as_text()
    launches = [line for line in text.splitlines()
                if re.match(r"\s*(ROOT )?%spectrum_fold_pallas", line)]
    assert len(launches) == 1 and "/exchange.fold/" in launches[0], launches
    assert not re.search(r"= \S+ (sort|scatter)\(", text)
