"""Compressor engine: pluggable stage-execution backends for the paper's
pipeline (the "swappable fusion schedule" move — SSFusion's schedule registry
applied to our compress/decompress hot path).

``FFTCompressor`` (core/compressor.py) owns the *protocol* — payload format,
wire accounting, config — and delegates stage execution here.  A backend
implements the entry points the compressor exposes:

    compress(cfg, x_flat)            -> FFTPayload
    compress_buckets(cfg, buckets)   -> [FFTPayload]        (per-bucket loop)
    compress_stacked(cfg, mat, sizes)-> StackedPayload      (batched executor,
                                        DESIGN.md §14: every bucket in ONE
                                        launch, bitwise-equal to the loop)
    decompress(payload)              -> flat f32
    decompress_stacked(payload)      -> (n_buckets, padded) f32
    decompress_spectrum(payload)     -> dense complex spectrum (batch-aware)
    mean_spectrum(gathered)          -> mean spectrum of P gathered payloads
    wire_bits(cfg, n)                -> static wire estimate (shared accounting)

Backends (``FFTCompressorConfig.backend``):

* ``reference`` — the pure-``jnp`` path (the seed's staged pipeline; its
  ranking magnitude is now the canonical kernel-native form, see
  ``_weighted_magnitude`` — kept sets can differ from pre-engine output at
  1-ulp boundaries).
* ``pallas``    — the fused device kernels: compress runs the bisection
  threshold + ``fused_compress`` (threshold -> pack -> quantize in one VMEM
  pass); decompress runs ``fused_decompress`` (dequantize -> Hermitian
  scatter -> 4-step iFFT in one VMEM pass); the exchange's fold of gathered
  payloads into the mean spectrum runs ``spectrum_fold`` (dequantize ->
  scatter -> worker mean in one VMEM pass).  Stages with no kernel-eligible
  shape fall back per-stage with a logged reason.
* ``auto``      — ``pallas`` when the platform compiles Mosaic
  (``runtime.mosaic_available``) and the config is kernel-eligible
  (``kernel_eligibility``), else ``reference``; the choice is logged once.

Payload compatibility contract: every backend emits the SAME ``FFTPayload``
layout — ``(c, k)`` planes, int16 indices, one fitted quantizer — so the
transports (comms/transport.py) accept engine-produced payloads unchanged
and backends can be mixed across workers.  The only licensed difference is
slot ORDER: reference packs kept coefficients magnitude-descending
(``top_k`` order) while pallas packs index-ascending (compaction order);
both decompress identically because unpacking is a scatter.

Forward FFT note: the fused win the paper measures is in the *post*-FFT
stages (its own §III-D model weights the elementwise pass 4x), so the pallas
compress backend keeps XLA's exact native rfft for the forward transform —
this is also what makes reference/pallas CODES bitwise-identical (the
matmul-based 4-step FFT is ~1e-5-approximate and would perturb codes near
quantization bin edges).  The inverse transform sits inside the fused
decompress kernel, where reconstructions are compared by tolerance, not
bitwise (tests/test_engine.py).
"""

from __future__ import annotations

import logging
import math
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import fft as cfft
from repro.core import packing, selection, sparsify
from repro.core.quantizer import (
    RangeQuantConfig,
    decode as q_decode,
    encode as q_encode,
    fit_quantizer,
)
from repro.kernels import (
    fused_compress,
    fused_decompress,
    ops,
    sampled_threshold,
    spectrum_fold,
)
from repro.kernels.fft4step import CHUNK as KERNEL_CHUNK
from repro.kernels.runtime import mosaic_available

__all__ = [
    "BACKEND_NAMES",
    "CompressorBackend",
    "ReferenceBackend",
    "PallasBackend",
    "AutoBackend",
    "get_backend",
    "kernel_eligibility",
    "wire_bits",
]

BACKEND_NAMES = ("reference", "pallas", "auto")

_LOG = logging.getLogger(__name__)
_logged_reasons: set = set()


def _log_once(reason: str) -> None:
    if reason not in _logged_reasons:
        _logged_reasons.add(reason)
        _LOG.info("engine backend fallback: %s", reason)


def _payload_cls():
    # deferred: core.compressor imports this module's consumers; the class is
    # only needed at trace time, long after both modules finished importing
    from repro.core.compressor import FFTPayload

    return FFTPayload


def _stacked_cls():
    from repro.core.compressor import StackedPayload

    return StackedPayload


# ---------------------------------------------------------------------------
# shared helpers (config math used by every backend)
# ---------------------------------------------------------------------------


def _keep_k(cfg) -> int:
    return sparsify.keep_count(cfg.chunk // 2 + 1, cfg.theta)


def _weighted_magnitude(re, im, w):
    """Canonical Hermitian-weighted ranking magnitude: sqrt(re²+im²)·w.

    This is the KERNEL-NATIVE form (Pallas carries complex data as separate
    real planes, so the fused kernel computes exactly this in-register).
    ``jnp.abs(complex)`` disagrees with it by 1 ulp on ~a third of bins
    (XLA's complex abs is hypot-style), which is enough to flip kept-set
    boundaries — so EVERY backend ranks with this one definition, keeping
    the kept set, the threshold tau, and the quantizer-range fit
    bitwise-identical across backends (DESIGN.md §13).
    """
    return jnp.sqrt(re * re + im * im) * w


def _qcfg(cfg) -> RangeQuantConfig:
    return RangeQuantConfig(cfg.n_bits, cfg.m_bits)


def _selector_tau(cfg, mag, k: int, sel: str):
    """Pure-jnp threshold for a resolved threshold selector (…, 1)."""
    return selection.selector_tau(
        mag, k, sel, sample_rate=cfg.sample_rate,
        refine_iters=cfg.tau_refine_iters, seed=cfg.selector_seed)


def _pallas_select(cfg, mag2d, k: int, sel: str):
    """Threshold-kernel dispatch for the pallas backend: (tau (r,1), the
    magnitude plane whose ``mag >= tau`` bins are the kept set).

    ``sort`` and ``bisect`` both map to the full bisection kernel — on this
    backend the "sort" selector has always BEEN count-based selection
    (``threshold_pallas``); ``bisect`` just names it explicitly.  ``sampled``
    runs the sampled-bracket kernel, whose body calls the same
    ``core/selection`` math the reference selector runs (DESIGN.md §16).
    """
    if sel == "sampled":
        tau, _ = sampled_threshold.sampled_select(
            mag2d, k=k, sample_rate=cfg.sample_rate,
            refine_iters=cfg.tau_refine_iters, seed=cfg.selector_seed)
        return tau, mag2d
    tau, _ = ops.threshold_select(mag2d, k)
    if sel == "sort":
        mag2d = _top_k_ties(mag2d, tau, k)
    return tau, mag2d


def _top_k_ties(mag, tau, k: int):
    """The magnitude plane with ``lax.top_k``'s choice among exact ties.

    ``tau`` is each row's k-th largest magnitude.  When several bins tie at
    it, ``mag >= tau`` keeps more than k of them, and the static payload
    budget would truncate the highest-INDEX kept bins — possibly large
    coefficients.  ``top_k`` instead keeps the lowest-index tied bins until
    k are kept; the tied bins past that quota are demoted below every
    threshold here, so ``mag >= tau`` is exactly the top-k set.
    """
    tie = mag == tau
    quota = k - jnp.sum(mag > tau, axis=-1, keepdims=True)
    rank = jnp.cumsum(tie.astype(jnp.int32), axis=-1)
    return jnp.where(tie & (rank > quota), -1.0, mag)


def _scatter_spectrum(idx, re, im, f_bins: int, into=None) -> jnp.ndarray:
    """Additive scatter of kept coefficients (f32 ``re``/``im`` planes) into
    dense complex ``(..., f_bins)`` rows (zeros, or the dense spectrum
    ``into``).

    Shape-polymorphic over LEADING axes (chunk, bucket, worker — any stack of
    them): the row scatter is defined once over a flattened row axis, so the
    transports' worker-axis ``vmap`` composes with the executor's bucket axis
    without re-tracing per composition (the old per-call ``jnp.zeros`` target
    was rebuilt for every distinct leading shape).  ``.add`` tolerates the
    code-0/index-0 padding slots of tile- and bucket-padded payloads.  The
    real and imaginary planes scatter separately: XLA's TPU scatter of f32
    is ~5x faster than of complex64, and the sums are the same.
    """
    lead = re.shape[:-1]
    k = re.shape[-1]
    rows_i = idx.reshape(-1, k)
    scatter = jax.vmap(lambda row, i, v: row.at[i].add(v))

    def plane(v, base):
        if base is None:
            base = jnp.zeros((rows_i.shape[0], f_bins), jnp.float32)
        return scatter(base.reshape(-1, f_bins), rows_i, v.reshape(-1, k))

    out = jax.lax.complex(
        plane(re, None if into is None else jnp.real(into)),
        plane(im, None if into is None else jnp.imag(into)))
    return out.reshape(lead + (f_bins,))


def _fold_refusal(payload):
    """Why ``spectrum_fold`` cannot fold this payload, or ``None``."""
    if payload.quant is None:
        return "payload is unquantized (the fold kernel scatters codes)"
    if payload.quant.config.n_bits > spectrum_fold.MAX_CODE_BITS:
        return (f"{payload.quant.config.n_bits}-bit codes are not exact in "
                "one bf16 MXU pass")
    if payload.chunk % 256 or payload.chunk > KERNEL_CHUNK:
        return (f"chunked at {payload.chunk}: the fold kernel takes chunks "
                f"of a multiple of 256 up to {KERNEL_CHUNK}")
    return None


def _kernel_mean_spectrum(gathered) -> jnp.ndarray:
    """``spectrum_fold`` over payload leaves with a leading worker axis ->
    the complex mean spectrum ``(*lead, chunk//2 + 1)``."""
    p, *lead, k = gathered.re.shape
    rows = math.prod(lead)
    q = gathered.quant
    n_fits = q.eps.size // p  # one per worker, or one per bucket
    if n_fits == 1:
        eps, p_codes = q.eps.reshape(p), q.p_codes.reshape(p)
    else:  # a stacked payload's buckets: each fit spread over its rows
        per_row = lambda a: jnp.repeat(a.reshape(p, n_fits), rows // n_fits, axis=1)
        eps, p_codes = per_row(q.eps), per_row(q.p_codes)
    f_bins = gathered.chunk // 2 + 1
    planes = [a.reshape(p, rows, k)
              for a in (gathered.re, gathered.im, gathered.idx)]
    spec = spectrum_fold.fold_mean_spectrum(
        *planes, eps, p_codes, f_bins=f_bins, m_bits=q.config.m_bits)
    return spec.reshape(*lead, f_bins)


def _valid_chunk_mask(sizes, max_chunks: int, chunk: int) -> jnp.ndarray:
    # canonical padding-mask rule lives next to StackedPayload (deferred
    # import, same reason as _payload_cls)
    from repro.core.compressor import valid_chunk_mask

    return valid_chunk_mask(sizes, max_chunks, chunk)


def _stack_quant(q):
    from repro.core.compressor import stack_bucket_quant

    return stack_bucket_quant(q)


def wire_bits(cfg, n: int) -> int:
    """Static wire estimate of one monolithic payload (backend-independent:
    every backend ships the same layout).  Bucketed exchanges fit one
    quantizer PER bucket — price those with
    ``comms.cost_model.bucketed_payload_bits``, not one call of this."""
    n_chunks = max(1, -(-n // cfg.chunk))
    k = _keep_k(cfg)
    value_bits = 2 * (cfg.n_bits if cfg.quantize else 32)  # re + im
    per_chunk = k * (value_bits + cfg.index_bits)
    overhead = 4 * 32  # quantizer params (eps, P, vmin, vmax)
    return n_chunks * per_chunk + overhead


def kernel_eligibility(cfg) -> Tuple[bool, str]:
    """Is the FULLY fused kernel pipeline available for this config?

    Returns (eligible, reason).  Ineligible configs still run under the
    ``pallas`` backend — each stage falls back individually (see
    ``PallasBackend``) — but ``auto`` only prefers pallas when the whole
    pipeline fuses.
    """
    reasons = []
    if cfg.chunk != KERNEL_CHUNK:
        reasons.append(
            f"chunk={cfg.chunk} != {KERNEL_CHUNK} (fft4step/fused_decompress "
            "are specialized to 4096-pt chunks)")
    if not cfg.quantize:
        reasons.append("quantize=False (the fused kernels quantize in-register)")
    return (not reasons, "; ".join(reasons))


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------


class CompressorBackend:
    """Stage-execution strategy behind the compressor protocol."""

    name: str = "base"

    # -- compress ----------------------------------------------------------
    def compress(self, cfg, x_flat: jnp.ndarray):
        raise NotImplementedError

    def compress_buckets(self, cfg, bucket_flats: Sequence[jnp.ndarray]) -> List:
        """Per-bucket compression: each bucket fits its OWN quantizer range.

        The monolithic path fits one (min, max) over the whole gradient, so a
        small bucket whose spectrum lives in a narrow band inherits a global
        range and wastes most of its codes.  Compressing per bucket keeps the
        range local (DESIGN.md §8); the bucketed transports rely on this.
        """
        return [self.compress(cfg, b) for b in bucket_flats]

    def compress_stacked(self, cfg, stacked: jnp.ndarray, sizes):
        """Batched bucket executor (DESIGN.md §14): compress a uniform
        ``(n_buckets, padded_size)`` matrix (``bucketing.stack_buckets``) in
        one batched pass, one quantizer fit per bucket row, producing a
        ``StackedPayload`` bitwise-equal to :meth:`compress_buckets` on the
        same layout."""
        raise NotImplementedError

    # -- decompress --------------------------------------------------------
    def decompress_spectrum(self, payload, into=None) -> jnp.ndarray:
        """Payload -> dense complex spectrum (..., chunk//2+1), or that
        spectrum added onto the dense spectrum ``into`` (the gather
        transports fold P payloads into one buffer this way).

        The shared plain-jnp dequantize + scatter (the pallas backend runs
        its fold kernel instead where the payload allows).  Batch-aware over
        leading axes: accepts the monolithic (c, k) payload, the stacked
        (n_buckets, max_chunks, k) payload, and any worker-vmap of either
        (see ``_scatter_spectrum``).
        """
        with jax.named_scope("exchange.fold"):
            re, im = payload.re, payload.im
            if payload.quant is not None:
                re, im = q_decode(re, payload.quant), q_decode(im, payload.quant)
            return _scatter_spectrum(payload.idx, re.astype(jnp.float32),
                                     im.astype(jnp.float32),
                                     payload.chunk // 2 + 1, into)

    def mean_spectrum(self, gathered) -> jnp.ndarray:
        """Mean dense spectrum of P gathered payloads (every leaf carries a
        leading worker axis), the workers added left to right onto one
        running spectrum and multiplied by ``1/P`` — the order the
        transports' bitwise contract rests on (``_ordered_worker_mean``).
        One running spectrum: P gradient-sized spectra do not fit beside a
        large model's training state."""
        p = jax.tree_util.tree_leaves(gathered)[0].shape[0]
        worker = lambda w: jax.tree_util.tree_map(lambda a: a[w], gathered)
        acc = self.decompress_spectrum(worker(0))
        for w in range(1, p):
            acc = self.decompress_spectrum(worker(w), into=acc)
        with jax.named_scope("exchange.fold"):
            return acc * (1.0 / p)

    def decompress(self, payload) -> jnp.ndarray:
        spectrum = self.decompress_spectrum(payload)
        with jax.named_scope("exchange.irfft"):
            return cfft.chunked_irfft(spectrum, payload.orig_len, payload.chunk)

    def decompress_stacked(self, payload) -> jnp.ndarray:
        """StackedPayload -> ``(n_buckets, padded_size)`` time-domain matrix
        (``bucketing.unstack_buckets`` recovers the flat buffer).  Padding
        rows decode to exact zeros, so each row's prefix is bitwise-equal to
        the per-bucket ``decompress``."""
        spectrum = self.decompress_spectrum(payload)  # (B, max_chunks, f)
        with jax.named_scope("exchange.irfft"):
            x = cfft.irfft_rows(spectrum, payload.chunk)
        return x.reshape(spectrum.shape[0], -1)


class ReferenceBackend(CompressorBackend):
    """The pure-jnp path: XLA rfft -> top_k -> gather -> range-quant encode.
    Packs kept coefficients in top_k (magnitude descending) order.  Ranks by
    the canonical ``_weighted_magnitude`` so its kept set is bitwise-equal to
    the fused kernel's."""

    name = "reference"

    def compress(self, cfg, x_flat: jnp.ndarray):
        with jax.named_scope("exchange.rfft"):
            freqs, n = cfft.chunked_rfft(x_flat, cfg.chunk)
        k = _keep_k(cfg)
        w = cfft.hermitian_weights(cfg.chunk)
        with jax.named_scope("exchange.select"):
            re_p = jnp.real(freqs).astype(jnp.float32)
            im_p = jnp.imag(freqs).astype(jnp.float32)
            mag = _weighted_magnitude(re_p, im_p, w)
            sel = selection.resolve_selector(cfg.selector, mag.shape[-1])
            if sel == "sort":
                idx = sparsify.topk_select(mag, k)
                tau = None
            else:
                # threshold selector (DESIGN.md §16): O(n) tau + one count-
                # and-compact pass; slots come out index-ascending (pallas
                # order)
                tau = _selector_tau(cfg, mag, k, sel)
                idx = selection.count_compact(mag, tau, k)
        with jax.named_scope("exchange.pack"):
            kept = packing.pack_by_indices(freqs, idx)
            re, im = jnp.real(kept), jnp.imag(kept)
            if cfg.quantize:
                if tau is None:
                    quant = self._fit(cfg, re, im)
                else:
                    # fit over the PRE-truncation tau mask — the same set the
                    # pallas backend fits over, so cross-backend codes stay
                    # bitwise-equal under every selector (tie caveat as in
                    # PallasBackend.compress)
                    quant = self._fit_masked(cfg, re_p, im_p, mag >= tau)
                re, im = q_encode(re, quant), q_encode(im, quant)
            else:
                quant = None
            # int16 indices: 2049 rfft bins fit; halves the index wire bytes
            return _payload_cls()(re, im, idx.astype(jnp.int16), quant, n,
                                  cfg.chunk)

    def _fit(self, cfg, re: jnp.ndarray, im: jnp.ndarray):
        if cfg.range_mode == "fixed":
            lo, hi = cfg.fixed_range
            return fit_quantizer(lo, hi, _qcfg(cfg))
        lo = jnp.minimum(re.min(), im.min())
        hi = jnp.maximum(re.max(), im.max())
        return fit_quantizer(lo, hi, _qcfg(cfg))

    def _fit_masked(self, cfg, re_p, im_p, mask):
        """Range fit over masked spectrum PLANES — expression-for-expression
        the fit the pallas backend runs, so the two backends' quantizer
        params are bitwise-identical whenever their tau is."""
        if cfg.range_mode == "fixed":
            lo, hi = cfg.fixed_range
            return fit_quantizer(lo, hi, _qcfg(cfg))
        lo = jnp.minimum(jnp.where(mask, re_p, jnp.inf).min(),
                         jnp.where(mask, im_p, jnp.inf).min())
        hi = jnp.maximum(jnp.where(mask, re_p, -jnp.inf).max(),
                         jnp.where(mask, im_p, -jnp.inf).max())
        return fit_quantizer(lo, hi, _qcfg(cfg))

    def compress_stacked(self, cfg, stacked: jnp.ndarray, sizes):
        """ONE executable for every bucket: the per-bucket loop's exact math
        as a ``lax.map`` over the bucket axis of the (n_buckets, max_chunks,
        chunk) tensor.  The rolled grid keeps the program size (and compile
        time) independent of the bucket count — the unrolled loop compiles
        one subgraph PER BUCKET — while each iteration's working set stays
        one bucket wide (cache-resident on hosts; the pallas backend flattens
        the same math into one kernel grid instead).  Per-bucket quantizer
        ranges are per-bucket reductions with the zero-padding chunks masked
        out (min/max over a subset is order-free, so each bucket's fit — and
        hence its codes — is bitwise-equal to the loop's)."""
        sizes = tuple(int(s) for s in sizes)
        n_buckets, padded = stacked.shape
        c_max = padded // cfg.chunk
        k = _keep_k(cfg)
        w = cfft.hermitian_weights(cfg.chunk)
        counts = jnp.asarray([-(-s // cfg.chunk) for s in sizes])
        sel = selection.resolve_selector(cfg.selector, cfg.chunk // 2 + 1)

        def one_bucket(args):
            x2d, c_b = args  # (max_chunks, chunk) rows, true chunk count
            # row-for-row the same transform the looped path runs via
            # cfft.chunked_rfft
            with jax.named_scope("exchange.rfft"):
                freqs = cfft.rfft_rows(x2d)
            with jax.named_scope("exchange.select"):
                re_p = jnp.real(freqs).astype(jnp.float32)
                im_p = jnp.imag(freqs).astype(jnp.float32)
                mag = _weighted_magnitude(re_p, im_p, w)
                if sel == "sort":
                    idx = sparsify.topk_select(mag, k)
                    tau = None
                else:
                    # per-row threshold selection is bucket-independent, so
                    # the stacked result matches the looped compress
                    # row-for-row
                    tau = _selector_tau(cfg, mag, k, sel)
                    idx = selection.count_compact(mag, tau, k)
            with jax.named_scope("exchange.pack"):
                kept = packing.pack_by_indices(freqs, idx)
                re, im = jnp.real(kept), jnp.imag(kept)
                if not cfg.quantize:
                    return re, im, idx
                if cfg.range_mode == "fixed":
                    lo, hi = cfg.fixed_range
                    quant = fit_quantizer(lo, hi, _qcfg(cfg))
                elif tau is None:
                    valid = (jnp.arange(c_max) < c_b)[:, None]
                    lo = jnp.minimum(jnp.where(valid, re, jnp.inf).min(),
                                     jnp.where(valid, im, jnp.inf).min())
                    hi = jnp.maximum(jnp.where(valid, re, -jnp.inf).max(),
                                     jnp.where(valid, im, -jnp.inf).max())
                    quant = fit_quantizer(lo, hi, _qcfg(cfg))
                else:
                    # pre-truncation tau mask, with the all-zero PADDING rows
                    # (tau 0 -> mask all-true) excluded so the fit sees exactly
                    # what the looped per-bucket fit saw
                    m = (mag >= tau) & (jnp.arange(c_max) < c_b)[:, None]
                    lo = jnp.minimum(jnp.where(m, re_p, jnp.inf).min(),
                                     jnp.where(m, im_p, jnp.inf).min())
                    hi = jnp.maximum(jnp.where(m, re_p, -jnp.inf).max(),
                                     jnp.where(m, im_p, -jnp.inf).max())
                    quant = fit_quantizer(lo, hi, _qcfg(cfg))
                return q_encode(re, quant), q_encode(im, quant), idx, quant

        x3 = stacked.reshape(n_buckets, c_max, cfg.chunk)
        if cfg.quantize:
            re, im, idx, quant = jax.lax.map(one_bucket, (x3, counts))
            quant = _stack_quant(quant)
        else:
            re, im, idx = jax.lax.map(one_bucket, (x3, counts))
            quant = None
        return _stacked_cls()(re, im, idx.astype(jnp.int16), quant, sizes,
                              cfg.chunk)


class PallasBackend(CompressorBackend):
    """Fused Pallas kernels on the hot stages, per-stage fallback elsewhere.

    compress:   exact XLA rfft (see module docstring) -> threshold kernel
                (quantizer range fit over the kept set) ->
                ``fused_compress_pallas`` (threshold+pack+quantize, one VMEM
                pass) -> slice the 128-lane padding down to the true keep
                count so the payload layout matches ``reference`` exactly.
    decompress: ``fused_decompress_pallas`` (dequantize + Hermitian scatter +
                4-step iFFT, one VMEM pass) when the payload is quantized and
                chunked at 4096; otherwise per-stage (the fold below + XLA
                irfft) with a logged reason.
    fold:       ``spectrum_fold_pallas`` (dequantize + scatter + worker
                mean, one VMEM pass) for ``mean_spectrum`` and
                ``decompress_spectrum`` when the payload is quantized to at
                most 8 bits and chunked at a multiple of 256 up to 4096;
                otherwise the shared jnp scatter with a logged reason.

    Packs kept coefficients in index-ascending (compaction) order.
    """

    name = "pallas"

    def compress(self, cfg, x_flat: jnp.ndarray):
        with jax.named_scope("exchange.rfft"):
            freqs, n = cfft.chunked_rfft(x_flat, cfg.chunk)
        k = _keep_k(cfg)
        w = cfft.hermitian_weights(cfg.chunk)
        # ONE threshold pass defines the kept set; its tau and the magnitude
        # plane it was computed on go to the fused kernel (no second
        # in-kernel search, no in-register recompute), so the mask the kernel
        # packs provably equals the set the quantizer range was fitted over.
        # Under selector=sort, exact ties at the k-th magnitude are resolved
        # as top_k resolves them (``_top_k_ties``).  Under selector=sampled
        # the same contract holds with the sampled-bracket tau: count(>= tau)
        # >= k is guaranteed by the in-kernel clamp, the surplus (a few
        # near-tau values the short refinement didn't split) truncates
        # index-ascending, and the fit below covers the full pre-truncation
        # mask — exactly what the reference selector path fits (DESIGN.md
        # §16).
        with jax.named_scope("exchange.select"):
            re = jnp.real(freqs).astype(jnp.float32)
            im = jnp.imag(freqs).astype(jnp.float32)
            mag = _weighted_magnitude(re, im, w)
            sel = selection.resolve_selector(cfg.selector, mag.shape[-1])
            tau, mag = _pallas_select(cfg, mag, k, sel)

        if not cfg.quantize:
            _log_once("pallas compress: quantize=False -> per-stage "
                      "threshold+pack kernels (no fused quantization)")
            with jax.named_scope("exchange.pack"):
                mvals, idx = ops.pack_threshold(mag, tau, k)  # width pad_k(k)
                valid = mvals != 0
                re_k = jnp.take_along_axis(re, idx, axis=-1) * valid
                im_k = jnp.take_along_axis(im, idx, axis=-1) * valid
                return _payload_cls()(
                    re_k[:, :k], im_k[:, :k], idx[:, :k].astype(jnp.int16),
                    None, n, cfg.chunk)

        with jax.named_scope("exchange.pack"):
            if cfg.range_mode == "fixed":
                lo, hi = cfg.fixed_range
                quant = fit_quantizer(lo, hi, _qcfg(cfg))
            else:
                mask = mag >= tau
                lo = jnp.minimum(jnp.where(mask, re, jnp.inf).min(),
                                 jnp.where(mask, im, jnp.inf).min())
                hi = jnp.maximum(jnp.where(mask, re, -jnp.inf).max(),
                                 jnp.where(mask, im, -jnp.inf).max())
                quant = fit_quantizer(lo, hi, _qcfg(cfg))

            rec, imc, idx, _tau = fused_compress.fused_compress_pallas(
                re, im, mag, quant.eps, quant.p_codes, tau,
                k_keep=k, n_bits=cfg.n_bits, m_bits=cfg.m_bits)
            # slice the tile padding off: payload layout == reference layout.
            # Under the threshold selectors a kept surplus (ties, a sampled
            # tau) truncates the highest-INDEX kept slots here —
            # bucketSelect's static-budget semantics, and what the
            # reference's count_compact does.
            return _payload_cls()(
                rec[:, :k], imc[:, :k], idx[:, :k].astype(jnp.int16),
                quant, n, cfg.chunk)

    def compress_stacked(self, cfg, stacked: jnp.ndarray, sizes):
        """ONE kernel launch for every bucket: all bucket rows ride a single
        grid, and the per-bucket quantizer params become per-ROW planes inside
        the fused kernel (``fused_compress_pallas`` with vector eps/p_codes).
        The shared tau and masked range fit keep codes bitwise-equal
        to the per-bucket loop (and to the reference backend, slot order
        aside)."""
        sizes = tuple(int(s) for s in sizes)
        n_buckets, padded = stacked.shape
        c_max = padded // cfg.chunk
        rows = n_buckets * c_max
        x2d = stacked.reshape(rows, cfg.chunk).astype(jnp.float32)
        with jax.named_scope("exchange.rfft"):
            freqs = cfft.rfft_rows(x2d)
        k = _keep_k(cfg)
        w = cfft.hermitian_weights(cfg.chunk)
        # same one-threshold contract as the looped compress, batched over
        # every bucket's chunks in one threshold-kernel launch
        with jax.named_scope("exchange.select"):
            re = jnp.real(freqs).astype(jnp.float32)
            im = jnp.imag(freqs).astype(jnp.float32)
            mag = _weighted_magnitude(re, im, w)
            sel = selection.resolve_selector(cfg.selector, mag.shape[-1])
            tau, mag = _pallas_select(cfg, mag, k, sel)

        if not cfg.quantize:
            _log_once("pallas compress_stacked: quantize=False -> per-stage "
                      "threshold+pack kernels (no fused quantization)")
            with jax.named_scope("exchange.pack"):
                mvals, idx = ops.pack_threshold(mag, tau, k)
                valid = mvals != 0
                re_k = jnp.take_along_axis(re, idx, axis=-1) * valid
                im_k = jnp.take_along_axis(im, idx, axis=-1) * valid
                return _stacked_cls()(
                    re_k[:, :k].reshape(n_buckets, c_max, k),
                    im_k[:, :k].reshape(n_buckets, c_max, k),
                    idx[:, :k].astype(jnp.int16).reshape(n_buckets, c_max, k),
                    None, sizes, cfg.chunk)

        with jax.named_scope("exchange.pack"):
            if cfg.range_mode == "fixed":
                lo = jnp.full((n_buckets,), cfg.fixed_range[0], jnp.float32)
                hi = jnp.full((n_buckets,), cfg.fixed_range[1], jnp.float32)
            else:
                # per-bucket fit over the kept set; padding rows (all-zero chunks,
                # tau 0, mask all-true) are excluded so the fit sees exactly the
                # values the looped per-bucket fit saw
                mask = ((mag >= tau)
                        & _valid_chunk_mask(sizes, c_max, cfg.chunk).reshape(
                            rows, 1))
                m3 = mask.reshape(n_buckets, c_max, -1)
                re3 = re.reshape(n_buckets, c_max, -1)
                im3 = im.reshape(n_buckets, c_max, -1)
                lo = jnp.minimum(
                    jnp.where(m3, re3, jnp.inf).min(axis=(1, 2)),
                    jnp.where(m3, im3, jnp.inf).min(axis=(1, 2)))
                hi = jnp.maximum(
                    jnp.where(m3, re3, -jnp.inf).max(axis=(1, 2)),
                    jnp.where(m3, im3, -jnp.inf).max(axis=(1, 2)))
            quant = _stack_quant(fit_quantizer(lo, hi, _qcfg(cfg)))
            # per-bucket params -> per-row planes for the single fused launch
            eps_rows = jnp.repeat(quant.eps.reshape(n_buckets), c_max)
            p_rows = jnp.repeat(quant.p_codes.reshape(n_buckets), c_max)
            rec, imc, idx, _tau = fused_compress.fused_compress_pallas(
                re, im, mag, eps_rows, p_rows, tau,
                k_keep=k, n_bits=cfg.n_bits, m_bits=cfg.m_bits)
            return _stacked_cls()(
                rec[:, :k].reshape(n_buckets, c_max, k),
                imc[:, :k].reshape(n_buckets, c_max, k),
                idx[:, :k].astype(jnp.int16).reshape(n_buckets, c_max, k),
                quant, sizes, cfg.chunk)

    def decompress_stacked(self, payload) -> jnp.ndarray:
        if payload.quant is not None and payload.chunk == KERNEL_CHUNK:
            n_buckets, c_max, k = payload.re.shape
            rows = n_buckets * c_max
            eps_rows = jnp.repeat(payload.quant.eps.reshape(n_buckets), c_max)
            p_rows = jnp.repeat(
                payload.quant.p_codes.reshape(n_buckets), c_max)
            # the fused kernel runs its inverse FFT in the same pass: the
            # whole launch counts as the fold
            with jax.named_scope("exchange.fold"):
                x2d = fused_decompress.fused_decompress_pallas(
                    payload.re.reshape(rows, k), payload.im.reshape(rows, k),
                    payload.idx.reshape(rows, k), eps_rows, p_rows,
                    m_bits=payload.quant.config.m_bits)
            return x2d.reshape(n_buckets, c_max * KERNEL_CHUNK)
        _log_once(
            "pallas decompress_stacked: payload is "
            + ("unquantized" if payload.quant is None
               else f"chunked at {payload.chunk} != {KERNEL_CHUNK}")
            + " -> per-stage (fold + XLA irfft)")
        return super().decompress_stacked(payload)

    def mean_spectrum(self, gathered) -> jnp.ndarray:
        """Every worker's payload folded into the mean spectrum in ONE
        ``spectrum_fold`` launch, bitwise equal to the shared jnp fold."""
        reason = _fold_refusal(gathered)
        if reason is not None:
            _log_once(f"pallas mean_spectrum: {reason} -> shared jnp scatter")
            return super().mean_spectrum(gathered)
        with jax.named_scope("exchange.fold"):
            return _kernel_mean_spectrum(gathered)

    def decompress_spectrum(self, payload, into=None) -> jnp.ndarray:
        """The fold kernel with one worker (the psum-shaped transports, the
        per-stage decompress and the serve publisher).  A running spectrum
        ``into`` is only ever given by the shared worker loop, which runs
        where the kernel refused: it keeps the jnp scatter."""
        reason = _fold_refusal(payload)
        if into is not None or reason is not None:
            if reason is not None:
                _log_once(f"pallas decompress_spectrum: {reason} -> shared "
                          "jnp scatter")
            return super().decompress_spectrum(payload, into)
        one = jax.tree_util.tree_map(lambda a: a[None], payload)
        with jax.named_scope("exchange.fold"):
            return _kernel_mean_spectrum(one)

    def decompress(self, payload) -> jnp.ndarray:
        if payload.quant is not None and payload.chunk == KERNEL_CHUNK:
            with jax.named_scope("exchange.fold"):  # with its inverse FFT
                x2d = fused_decompress.fused_decompress_pallas(
                    payload.re, payload.im, payload.idx,
                    payload.quant.eps, payload.quant.p_codes,
                    m_bits=payload.quant.config.m_bits)
            return x2d.reshape(-1)[: payload.orig_len].astype(jnp.float32)
        _log_once(
            "pallas decompress: payload is "
            + ("unquantized" if payload.quant is None
               else f"chunked at {payload.chunk} != {KERNEL_CHUNK}")
            + " -> per-stage (fold + XLA irfft)")
        return super().decompress(payload)


class AutoBackend(CompressorBackend):
    """Per-call choice: pallas when Mosaic compiles AND the config fuses
    end-to-end, reference otherwise (with the reason logged once)."""

    name = "auto"

    def __init__(self):
        self._reference = ReferenceBackend()
        self._pallas = PallasBackend()

    def _pick(self, cfg) -> CompressorBackend:
        if not mosaic_available():
            _log_once("auto backend -> reference: platform does not compile "
                      "Mosaic (pallas would run in interpret mode)")
            return self._reference
        eligible, reason = kernel_eligibility(cfg)
        if not eligible:
            _log_once(f"auto backend -> reference: {reason}")
            return self._reference
        return self._pallas

    def compress(self, cfg, x_flat: jnp.ndarray):
        return self._pick(cfg).compress(cfg, x_flat)

    def compress_buckets(self, cfg, bucket_flats):
        return self._pick(cfg).compress_buckets(cfg, bucket_flats)

    def compress_stacked(self, cfg, stacked, sizes):
        return self._pick(cfg).compress_stacked(cfg, stacked, sizes)

    def decompress(self, payload) -> jnp.ndarray:
        # payloads carry no backend tag (they are backend-portable); route by
        # the same platform gate — the pallas backend degrades per-stage on
        # shapes its fused kernel cannot take
        if mosaic_available():
            return self._pallas.decompress(payload)
        return self._reference.decompress(payload)

    def decompress_stacked(self, payload) -> jnp.ndarray:
        if mosaic_available():
            return self._pallas.decompress_stacked(payload)
        return self._reference.decompress_stacked(payload)

    def decompress_spectrum(self, payload, into=None) -> jnp.ndarray:
        if mosaic_available():
            return self._pallas.decompress_spectrum(payload, into)
        return self._reference.decompress_spectrum(payload, into)

    def mean_spectrum(self, gathered) -> jnp.ndarray:
        if mosaic_available():
            return self._pallas.mean_spectrum(gathered)
        return self._reference.mean_spectrum(gathered)


_BACKENDS = {
    "reference": ReferenceBackend(),
    "pallas": PallasBackend(),
    "auto": AutoBackend(),
}


def get_backend(name: str) -> CompressorBackend:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown compressor backend {name!r}; expected one of {BACKEND_NAMES}"
        ) from None
