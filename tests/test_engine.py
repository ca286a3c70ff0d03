"""Backend parity: the pallas engine backend must be payload-compatible and
numerically interchangeable with the reference backend (DESIGN.md §13).

Contract under test:

* CODES are bitwise-identical across backends (the pallas compress keeps the
  exact XLA rfft and the in-register quantizer matches the jnp oracle
  bit-for-bit); only the slot ORDER differs (reference packs top_k
  magnitude-descending, pallas packs index-ascending), so comparisons sort
  by index first.
* RECONSTRUCTIONS agree within the matmul-FFT tolerance of the fused
  decompress kernel (the 4-step iFFT is ~1e-5-approximate; codes are exact).
* Payloads are backend-PORTABLE: either backend decompresses the other's
  payload, and the transports exchange pallas payloads unchanged.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import run_with_devices
from repro.core.compressor import FFTCompressor, FFTCompressorConfig, TimeDomainCompressor
from repro.kernels import engine, ops

G = jax.random.normal(jax.random.PRNGKey(42), (3 * 4096 + 517,)) * 0.05


def _cfg(backend, **kw):
    return FFTCompressorConfig(backend=backend, **kw)


def _sorted_planes(payload):
    """Canonical (index-ascending) view of the payload planes."""
    order = np.argsort(np.array(payload.idx), axis=-1, kind="stable")
    return tuple(
        np.take_along_axis(np.array(plane), order, axis=-1)
        for plane in (payload.re, payload.im, payload.idx)
    )


@pytest.mark.parametrize("theta", [0.5, 0.7, 0.9])
@pytest.mark.parametrize("n_bits,quantize", [(4, True), (8, True), (8, False)])
def test_backend_parity_codes_bitwise(theta, n_bits, quantize):
    ref = FFTCompressor(_cfg("reference", theta=theta, n_bits=n_bits, quantize=quantize))
    pal = FFTCompressor(_cfg("pallas", theta=theta, n_bits=n_bits, quantize=quantize))
    p_ref = jax.jit(ref.compress)(G)
    p_pal = jax.jit(pal.compress)(G)

    # identical layout: shapes, dtypes, statics
    assert p_ref.re.shape == p_pal.re.shape
    assert p_ref.re.dtype == p_pal.re.dtype
    assert p_ref.idx.dtype == p_pal.idx.dtype == jnp.int16
    assert (p_ref.orig_len, p_ref.chunk) == (p_pal.orig_len, p_pal.chunk)

    # identical quantizer fit (masked min/max == packed min/max, order-free)
    if quantize:
        assert float(p_ref.quant.eps) == float(p_pal.quant.eps)
        assert int(p_ref.quant.p_codes) == int(p_pal.quant.p_codes)
    else:
        assert p_ref.quant is None and p_pal.quant is None

    # identical codes once both payloads are in canonical index order
    for a, b, what in zip(_sorted_planes(p_ref), _sorted_planes(p_pal),
                          ("re", "im", "idx")):
        np.testing.assert_array_equal(a, b, err_msg=f"{what} codes diverge")

    # reconstructions within the fused-iFFT tolerance; same sparsify bound
    x_ref = np.array(ref.decompress(p_ref))
    x_pal = np.array(pal.decompress(p_pal))
    np.testing.assert_allclose(x_pal, x_ref, atol=5e-5)

    # payloads are backend-portable: cross-decompression works unchanged
    np.testing.assert_allclose(
        np.array(ref.decompress(p_pal)), x_ref, atol=5e-5)
    np.testing.assert_allclose(
        np.array(pal.decompress(p_ref)), x_ref, atol=5e-5)


def test_backend_parity_codes_bitwise_under_exact_ties():
    """Exact magnitude ties at the k-th largest bin (here by construction;
    in f32 gradients a few rows in 1e5 have them): the pallas backend keeps
    the bins top_k keeps — all strictly larger ones plus the lowest-index
    tied ones — not the lowest-index k of every bin at or above tau."""
    chunk = np.zeros(4096, np.float32)
    chunk[0], chunk[1024], chunk[3072], chunk[2048] = 1.0, 0.25, 0.25, 0.125
    g = jnp.asarray(np.tile(chunk, 3)) * 0.05
    ref = FFTCompressor(_cfg("reference", theta=0.7))
    pal = FFTCompressor(_cfg("pallas", theta=0.7))
    p_ref, p_pal = jax.jit(ref.compress)(g), jax.jit(pal.compress)(g)
    mag = np.abs(np.fft.rfft(np.asarray(g[:4096])))
    assert np.sum(mag == np.sort(mag)[-p_ref.re.shape[-1]]) > 1  # ties
    assert float(p_ref.quant.eps) == float(p_pal.quant.eps)
    for a, b, what in zip(_sorted_planes(p_ref), _sorted_planes(p_pal),
                          ("re", "im", "idx")):
        np.testing.assert_array_equal(a, b, err_msg=f"{what} codes diverge")


def test_backend_spectra_bitwise_identical():
    """The exchange path (decompress_spectrum) is shared: payloads from
    either backend produce the SAME dense spectrum bit-for-bit — this is why
    transports and reducers are backend-oblivious."""
    ref = FFTCompressor(_cfg("reference"))
    pal = FFTCompressor(_cfg("pallas"))
    s_ref = np.array(ref.decompress_spectrum(ref.compress(G)))
    s_pal = np.array(pal.decompress_spectrum(pal.compress(G)))
    np.testing.assert_array_equal(s_ref, s_pal)


def test_fused_decompress_matches_unfused():
    """Golden check: the fused decompress kernel (dequant -> Hermitian
    scatter -> 4-step iFFT, one VMEM pass) equals the unfused three-stage
    path (quant_decode kernel -> scatter -> XLA irfft) on the same payload."""
    from repro.core import fft as cfft
    from repro.kernels import fused_decompress

    comp = FFTCompressor(_cfg("pallas", theta=0.7))
    payload = comp.compress(G)
    fused = fused_decompress.fused_decompress_pallas(
        payload.re, payload.im, payload.idx,
        payload.quant.eps, payload.quant.p_codes,
        m_bits=payload.quant.config.m_bits,
    ).reshape(-1)[: payload.orig_len]

    re = ops.quant_decode(payload.re, payload.quant)
    im = ops.quant_decode(payload.im, payload.quant)
    spectrum = jax.vmap(
        lambda i, v: jnp.zeros((2049,), jnp.complex64).at[i].add(v)
    )((payload.idx).astype(jnp.int32), (re + 1j * im).astype(jnp.complex64))
    unfused = cfft.chunked_irfft(spectrum, payload.orig_len, payload.chunk)

    np.testing.assert_allclose(np.array(fused), np.array(unfused), atol=2e-6)


def test_fused_decompress_tolerates_tile_padding():
    """Payload widths are padded to the 128-lane tile inside the kernel with
    code-0/index-0 slots; those must contribute NOTHING (the scatter is
    additive, so a padding slot may not clobber a genuinely-kept DC bin)."""
    from repro.kernels import fused_decompress

    comp = FFTCompressor(_cfg("pallas", theta=0.7))
    payload = comp.compress(G)  # width 615: kernel pads to 640 internally
    k = payload.re.shape[-1]
    pad = ops.pad_k(k) - k
    padded = [jnp.pad(p, [(0, 0), (0, pad)]) for p in
              (payload.re, payload.im, payload.idx)]
    out_sliced = fused_decompress.fused_decompress_pallas(
        payload.re, payload.im, payload.idx,
        payload.quant.eps, payload.quant.p_codes)
    out_padded = fused_decompress.fused_decompress_pallas(
        *padded, payload.quant.eps, payload.quant.p_codes)
    np.testing.assert_array_equal(np.array(out_sliced), np.array(out_padded))


def test_auto_backend_selects_reference_off_tpu():
    """On this host Mosaic is unavailable, so auto must resolve to the
    reference path (same payloads bit-for-bit, including slot order)."""
    auto = FFTCompressor(_cfg("auto"))
    ref = FFTCompressor(_cfg("reference"))
    p_auto, p_ref = auto.compress(G), ref.compress(G)
    for a, b in ((p_auto.re, p_ref.re), (p_auto.im, p_ref.im),
                 (p_auto.idx, p_ref.idx)):
        np.testing.assert_array_equal(np.array(a), np.array(b))


def test_auto_backend_selects_pallas_where_mosaic_compiles(monkeypatch):
    """Where Mosaic compiles (a TPU), auto runs the fused kernels for every
    kernel-eligible config, compress and decompress alike; only an
    ineligible config (no fused kernel for it) goes to the reference path."""
    monkeypatch.setattr(engine, "mosaic_available", lambda: True)
    auto = engine.get_backend("auto")
    assert auto._pick(_cfg("auto", theta=0.7)) is auto._pallas
    assert auto._pick(_cfg("auto", chunk=1024)) is auto._reference
    payload = FFTCompressor(_cfg("pallas")).compress(G)
    np.testing.assert_array_equal(
        np.array(auto.decompress(payload)),
        np.array(engine.get_backend("pallas").decompress(payload)))


def test_spec_backend_names_mirror_engine_registry():
    """lab/spec.py is jax-free by design so it cannot import the engine; its
    hardcoded backend list must track engine.BACKEND_NAMES (adding a backend
    to the registry must also open it to the convergence-lab sweep)."""
    import inspect

    from repro.lab import spec as lab_spec

    src = inspect.getsource(lab_spec.ExperimentSpec.__post_init__)
    for name in engine.BACKEND_NAMES:
        assert f'"{name}"' in src, (
            f"engine backend {name!r} missing from ExperimentSpec validation")


def test_engine_eligibility_rules():
    ok, why = engine.kernel_eligibility(_cfg("pallas"))
    assert ok and not why
    ok, why = engine.kernel_eligibility(_cfg("pallas", chunk=1024))
    assert not ok and "chunk" in why
    ok, why = engine.kernel_eligibility(_cfg("pallas", quantize=False))
    assert not ok and "quantize" in why
    with pytest.raises(ValueError, match="backend"):
        FFTCompressorConfig(backend="cuda")


def test_pallas_per_stage_fallback_on_non_kernel_chunk():
    """chunk != 4096 has no fused iFFT: the pallas backend must fall back
    per-stage and still round-trip correctly."""
    ref = FFTCompressor(_cfg("reference", theta=0.7, chunk=1024))
    pal = FFTCompressor(_cfg("pallas", theta=0.7, chunk=1024))
    p_ref, p_pal = ref.compress(G), pal.compress(G)
    for a, b in zip(_sorted_planes(p_ref), _sorted_planes(p_pal)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(
        np.array(pal.decompress(p_pal)), np.array(ref.decompress(p_ref)),
        atol=1e-6)


def test_timedomain_payload_ships_no_imaginary_plane():
    """The time-domain payload is purely real: the im plane must be EMPTY
    (not a zeros plane silently doubling wire traffic) and the wire
    accounting must describe the payload actually shipped."""
    comp = TimeDomainCompressor(FFTCompressorConfig(theta=0.7))
    payload = comp.compress(G)
    assert payload.has_im is False
    assert payload.im.shape == (payload.re.shape[0], 0)
    # round-trip unaffected
    x_hat = comp.decompress(payload)
    assert x_hat.shape == G.shape
    err = float(jnp.linalg.norm(G - x_hat) / jnp.linalg.norm(G))
    assert err <= 0.7 ** 0.5 + 0.05
    # shipped value bits == billed value bits (single plane + indices)
    k = payload.re.shape[-1]
    c = payload.re.shape[0]
    shipped = c * k * (8 + 16)  # uint8 codes + int16 indices
    billed = comp.wire_bits(G.shape[0]) - 4 * 32  # minus quantizer params
    assert shipped == billed
    # FFT payloads still carry both planes
    fp = FFTCompressor(FFTCompressorConfig(theta=0.7)).compress(G)
    assert fp.has_im is True and fp.im.shape == fp.re.shape


def test_bucketed_wire_accounting_matches_transport_granularity():
    from repro.comms import cost_model as cm

    comp = FFTCompressor(FFTCompressorConfig(theta=0.7))
    sizes = [4096 * 2, 4096 * 2, 4096 + 173]
    total = sum(sizes)
    mono = cm.bucketed_payload_bits(comp.wire_bits, sizes, "allgather")
    per_bucket = cm.bucketed_payload_bits(comp.wire_bits, sizes, "sequenced")
    assert mono == comp.wire_bits(total)
    assert per_bucket == sum(comp.wire_bits(s) for s in sizes)
    # one quantizer-param overhead (4*32 bits) per PAYLOAD: the bucketed
    # exchange carries exactly one extra per additional bucket
    assert per_bucket - mono == (len(sizes) - 1) * 4 * 32
    assert (cm.bucketed_payload_bits(comp.wire_bits, sizes, "psum")
            == per_bucket)
    with pytest.raises(ValueError):
        cm.bucketed_payload_bits(comp.wire_bits, sizes, "carrier-pigeon")


def test_interpret_default_unified():
    """Every kernel entry point resolves interpret=None through the shared
    runtime policy (True on this CPU-only host)."""
    from repro.kernels import runtime

    assert runtime.default_interpret() is True
    assert runtime.resolve_interpret(None) is True
    assert runtime.resolve_interpret(False) is False
    assert ops.default_interpret is runtime.default_interpret
    # the fused kernels accept the shared default (no hardcoded True):
    # running them with interpret=None must succeed on CPU
    comp = FFTCompressor(_cfg("pallas"))
    comp.decompress(comp.compress(G))


def test_backend_parity_through_transports_with_error_feedback():
    """Bucketed + error-feedback reduction through every transport, pallas vs
    reference backends, on 4 fake devices: non-EF means must be bitwise
    equal (codes identical, shared spectral exchange); EF means/residuals
    agree within the fused-iFFT tolerance."""
    out = run_with_devices("""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.jaxcompat import make_auto_mesh, shard_map as smap
from repro.comms import ReducerConfig, make_reducer

mesh = make_auto_mesh((4,), ("data",))
grads = {"w": jax.random.normal(jax.random.PRNGKey(0), (4, 2 * 4096 + 173)) * 0.1}
n = 2 * 4096 + 173

def run(cfg):
    r = make_reducer(cfg)
    f = smap(lambda g: r(jax.tree.map(lambda x: x[0], g)),
             mesh=mesh, in_specs=P("data"), out_specs=P())
    return np.asarray(jax.jit(f)(grads)["w"])

def run_ef(cfg):
    r = make_reducer(cfg)
    def step(g, res):
        out, new_res = r(jax.tree.map(lambda x: x[0], g), res[0])
        return out["w"], new_res[None]
    f = smap(step, mesh=mesh, in_specs=(P("data"), P("data")),
             out_specs=(P(), P("data")))
    res = jnp.zeros((4, n))
    outs = []
    for _ in range(2):
        got, res = jax.jit(f)(grads, res)
        outs.append(np.asarray(got))
    return outs, np.asarray(res)

for transport in ("allgather", "sequenced", "psum"):
    base = ReducerConfig(kind="fft", axis="data", theta=0.7, quantize=True,
                         transport=transport, bucket_bytes=4096 * 4)
    dev = np.abs(run(base) - run(dataclasses.replace(base, backend="pallas"))).max()
    assert dev == 0.0, (transport, dev)  # bitwise: shared exchange numerics

    ef = dataclasses.replace(base, error_feedback=True)
    o_ref, r_ref = run_ef(ef)
    o_pal, r_pal = run_ef(dataclasses.replace(ef, backend="pallas"))
    for a, b in zip(o_ref, o_pal):
        assert np.abs(a - b).max() < 1e-3, transport
    assert np.abs(r_ref - r_pal).max() < 1e-2, transport
    assert np.linalg.norm(r_pal) > 0.0  # EF is live under pallas too
print("BACKEND_TRANSPORTS_OK")
""", devices=4)
    assert "BACKEND_TRANSPORTS_OK" in out
