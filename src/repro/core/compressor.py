"""The composed gradient-compression pipeline (paper Fig. 5).

    gradient --rFFT--> spectrum --theta-drop--> sparse --range-quant--> codes
             --pack--> (values, indices) payload --> wire

and the exact reverse on the receiver.  All stages are jit-compatible with
static shapes; the payload is a registered pytree so it flows through
``shard_map`` collectives unchanged.

Key property used by the distributed reducer (beyond-paper, DESIGN.md §10):
the FFT is linear, so workers can sum *spectra* after dequantize/unpack and run
a single inverse FFT — ``decompress_spectrum`` exposes that path.

Compressor protocol (duck-typed; baselines implement the same):

    payload = comp.compress(x_flat, key=None)
    x_hat   = comp.decompress(payload)
    bits    = comp.wire_bits(n)         # static wire size estimate
    ratio   = comp.ratio(n)             # 32*n / wire_bits

Stage execution is delegated to a pluggable ENGINE BACKEND
(``kernels/engine.py``): ``reference`` (pure jnp, seed behavior), ``pallas``
(the fused device kernels), or ``auto`` (pallas when the platform compiles
Mosaic and the config is kernel-eligible).  Every backend emits the same
payload layout, so transports and reducers are backend-oblivious.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import fft as cfft
from repro.core import packing, selection, sparsify
from repro.core.quantizer import (
    FittedQuantizer,
    RangeQuantConfig,
    decode as q_decode,
    encode as q_encode,
    fit_quantizer,
)

__all__ = [
    "FFTCompressorConfig",
    "FFTPayload",
    "StackedPayload",
    "stack_bucket_quant",
    "valid_chunk_mask",
    "FFTCompressor",
    "TimeDomainCompressor",
    "QuantOnlyCompressor",
    "NoCompression",
]


def valid_chunk_mask(sizes, max_chunks: int, chunk: int) -> jnp.ndarray:
    """(n_buckets, max_chunks, 1) mask of REAL chunk rows in a stacked bucket
    matrix — False on the zero-padding rows the uniform width added.  The
    canonical padding-mask rule of the batched executor (DESIGN.md §14):
    every stacked quantizer fit masks with this, so the fit sees exactly the
    values the per-bucket loop saw."""
    counts = jnp.asarray([-(-int(s) // chunk) for s in sizes])
    return (jnp.arange(max_chunks)[None, :] < counts[:, None])[:, :, None]


def stack_bucket_quant(q: FittedQuantizer) -> FittedQuantizer:
    """Reshape a vector quantizer fit (leaves ``(n_buckets,)``) to the
    StackedPayload leaf layout ``(n_buckets, 1, 1)`` so its params broadcast
    against ``(n_buckets, max_chunks, k)`` payload planes."""
    return FittedQuantizer(
        q.config, q.eps.reshape(-1, 1, 1), q.p_codes.reshape(-1, 1, 1),
        q.vmax.reshape(-1, 1, 1), q.vmin.reshape(-1, 1, 1))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class FFTPayload:
    """Wire payload: quantized kept spectrum + indices + quantizer params.

    ``has_im`` (static) marks whether the imaginary plane carries data.
    Time-domain payloads are purely real: they ship an EMPTY ``im`` array
    (shape (c, 0)) with ``has_im=False`` so the collectives move half the
    value bytes — matching ``TimeDomainCompressor.wire_bits``, which has
    always billed a single value plane.
    """

    re: jnp.ndarray  # (c, k) codes (uintN) or f32 when quantization is off
    im: jnp.ndarray  # (c, k), or (c, 0) when has_im=False (time domain)
    idx: jnp.ndarray  # (c, k) int16 bin indices (chunk <= 4096 fits; 16 wire bits)
    quant: Optional[FittedQuantizer]  # None when quantization is off
    orig_len: int = dataclasses.field(metadata={"static": True})
    chunk: int = dataclasses.field(metadata={"static": True})
    has_im: bool = dataclasses.field(default=True, metadata={"static": True})

    def tree_flatten(self):
        return (self.re, self.im, self.idx, self.quant), (
            self.orig_len, self.chunk, self.has_im)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, *aux)

    def validate(self, level: str = "cheap") -> jnp.ndarray:
        """Traced structural sanity check -> bool scalar (DESIGN.md §19).

        ``cheap`` (and ``full``, whose extra checksum comparison lives in
        ``comms.faults`` where the compress-time reference is known):
        index bounds vs the chunk width, finiteness of float value planes,
        and quantizer-param sanity.  O(payload) elementwise work; no
        collectives.
        """
        return _validate_planes(self, level)

    def to_bytes(self) -> bytes:
        """Self-describing binary blob (core.bytecodec, DESIGN.md §20)."""
        from repro.core import bytecodec

        return bytecodec.to_bytes(self)

    @staticmethod
    def from_bytes(blob: bytes) -> "FFTPayload":
        from repro.core import bytecodec

        payload = bytecodec.from_bytes(blob)
        if not isinstance(payload, FFTPayload):
            raise ValueError("blob holds a StackedPayload, not an FFTPayload")
        return payload


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class StackedPayload:
    """Struct-of-arrays payload of one WHOLE bucketed exchange (DESIGN.md §14).

    Where the per-bucket loop emits ``n_buckets`` :class:`FFTPayload` objects,
    the batched executor emits ONE of these: every plane carries a leading
    bucket axis (``(n_buckets, max_chunks, k)``), so a transport moves the
    entire exchange with a single collective per plane instead of one per
    bucket.  Per-bucket quantizer params are stacked the same way —
    ``quant`` leaves have shape ``(n_buckets, 1, 1)`` and broadcast against
    the code planes in encode/decode.

    Rows beyond a bucket's true chunk count (``chunk_counts``) are padding:
    their slots hold code 0 at index 0..k-1 and decode to nothing.  Slicing
    row ``b`` down to its true chunk count recovers the exact payload the
    per-bucket loop would have produced (:meth:`bucket_payloads` — the
    bitwise-parity contract, tests/test_stacked.py).
    """

    re: jnp.ndarray  # (n_buckets, max_chunks, k) codes or f32
    im: jnp.ndarray  # same, or (n_buckets, max_chunks, 0) when has_im=False
    idx: jnp.ndarray  # (n_buckets, max_chunks, k) int16 bin indices
    quant: Optional[FittedQuantizer]  # leaves (n_buckets, 1, 1); None when off
    sizes: Tuple[int, ...] = dataclasses.field(metadata={"static": True})
    chunk: int = dataclasses.field(metadata={"static": True})
    has_im: bool = dataclasses.field(default=True, metadata={"static": True})

    def tree_flatten(self):
        return (self.re, self.im, self.idx, self.quant), (
            self.sizes, self.chunk, self.has_im)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, *aux)

    @property
    def n_buckets(self) -> int:
        return len(self.sizes)

    @property
    def padded_size(self) -> int:
        return self.re.shape[-2] * self.chunk

    def chunk_counts(self) -> Tuple[int, ...]:
        return tuple(-(-s // self.chunk) for s in self.sizes)

    def bucket_quant(self, b: int) -> Optional[FittedQuantizer]:
        if self.quant is None:
            return None
        q = self.quant
        return FittedQuantizer(q.config, q.eps[b, 0, 0], q.p_codes[b, 0, 0],
                               q.vmax[b, 0, 0], q.vmin[b, 0, 0])

    def bucket_payloads(self) -> list:
        """Slice back to the per-bucket payloads the looped path emits."""
        out = []
        for b, (size, c_b) in enumerate(zip(self.sizes, self.chunk_counts())):
            out.append(FFTPayload(
                self.re[b, :c_b], self.im[b, :c_b], self.idx[b, :c_b],
                self.bucket_quant(b), size, self.chunk, has_im=self.has_im))
        return out

    def validate(self, level: str = "cheap") -> jnp.ndarray:
        """Traced structural sanity check -> bool scalar; see
        :meth:`FFTPayload.validate`."""
        return _validate_planes(self, level)

    def to_bytes(self) -> bytes:
        """Self-describing binary blob (core.bytecodec, DESIGN.md §20)."""
        from repro.core import bytecodec

        return bytecodec.to_bytes(self)

    @staticmethod
    def from_bytes(blob: bytes) -> "StackedPayload":
        from repro.core import bytecodec

        payload = bytecodec.from_bytes(blob)
        if not isinstance(payload, StackedPayload):
            raise ValueError("blob holds an FFTPayload, not a StackedPayload")
        return payload


def _validate_planes(payload, level: str) -> jnp.ndarray:
    """Shared structural checks for FFT/Stacked payloads (DESIGN.md §19)."""
    if level == "off":
        return jnp.bool_(True)
    ok = (payload.idx >= 0).all() & (payload.idx < payload.chunk).all()
    for plane in (payload.re, payload.im):
        if jnp.issubdtype(plane.dtype, jnp.floating) and plane.size:
            ok = ok & jnp.isfinite(plane).all()
    q = payload.quant
    if q is not None:
        ok = ok & jnp.isfinite(q.eps).all() & (q.eps > 0).all()
        ok = ok & jnp.isfinite(q.vmax).all() & jnp.isfinite(q.vmin).all()
        ok = ok & (q.vmin <= q.vmax).all()
        n_codes = q.config.n_codes
        ok = ok & ((q.p_codes >= 1) & (q.p_codes <= n_codes - 2)).all()
    return ok


@dataclasses.dataclass(frozen=True)
class FFTCompressorConfig:
    """Static knobs of the paper's pipeline."""

    theta: float = 0.7  # frequency drop-out ratio (paper's main knob)
    n_bits: int = 8  # range-based float width (paper uses 8)
    m_bits: int = 3
    chunk: int = cfft.DEFAULT_CHUNK
    quantize: bool = True
    range_mode: str = "auto"  # "auto": per-call min/max; "fixed": use fixed_range
    fixed_range: Tuple[float, float] = (-1.0, 1.0)  # paper: [-1,1] AlexNet, [-6,6] ResNet
    index_bits: int = 16
    # stage-execution engine: reference | pallas | auto (kernels/engine.py)
    backend: str = "reference"
    # selection engine (core/selection.py, DESIGN.md §16): how the top-k kept
    # set is found.  "sort" is the seed behavior (exact lax.top_k); "bisect"
    # and "sampled" are the O(n) threshold selectors; "auto" resolves per row
    # width.  sample_rate / tau_refine_iters / selector_seed parameterize the
    # sampled estimator and are inert under other selectors.
    selector: str = "sort"
    sample_rate: float = 1.0 / 64.0
    tau_refine_iters: int = 16
    selector_seed: int = 0

    def __post_init__(self):
        # payloads carry int16 indices (and bill index_bits=16 on the wire);
        # a chunk beyond int16 range would silently wrap top-k indices
        if self.chunk > 32767:
            raise ValueError(f"chunk must be <= 32767 (int16 indices), got {self.chunk}")
        if self.chunk < 1:
            raise ValueError(f"chunk must be positive, got {self.chunk}")
        from repro.core.selection import SELECTOR_NAMES

        if self.selector not in SELECTOR_NAMES:
            raise ValueError(
                f"unknown selector {self.selector!r}; expected one of {SELECTOR_NAMES}")
        if not 0.0 < self.sample_rate <= 1.0:
            raise ValueError(
                f"sample_rate must be in (0, 1], got {self.sample_rate}")
        if self.tau_refine_iters < 1:
            raise ValueError(
                f"tau_refine_iters must be >= 1, got {self.tau_refine_iters}")
        from repro.kernels.engine import BACKEND_NAMES

        if self.backend not in BACKEND_NAMES:
            raise ValueError(
                f"unknown backend {self.backend!r}; expected one of {BACKEND_NAMES}")

    def with_theta(self, theta: float) -> "FFTCompressorConfig":
        return dataclasses.replace(self, theta=theta)


class FFTCompressor:
    """Paper's full pipeline: FFT -> theta-drop -> range-quant -> pack.

    Owns the protocol and the config; STAGE EXECUTION is delegated to the
    engine backend named by ``config.backend`` (kernels/engine.py).  All
    backends emit the same payload layout, so a payload compressed by one
    backend decompresses under any other.
    """

    def __init__(self, config: FFTCompressorConfig = FFTCompressorConfig()):
        self.config = config
        from repro.kernels import engine as _engine

        self._engine_mod = _engine
        self._backend = _engine.get_backend(config.backend)

    @property
    def backend(self):
        """The engine backend executing this compressor's stages."""
        return self._backend

    # -- protocol ----------------------------------------------------------
    def compress(self, x_flat: jnp.ndarray, key=None) -> FFTPayload:
        return self._backend.compress(self.config, x_flat)

    def decompress_spectrum(self, payload: FFTPayload, into=None) -> jnp.ndarray:
        """Payload -> dense complex spectrum (c, chunk//2+1), added onto the
        dense spectrum ``into`` when one is given."""
        return self._backend.decompress_spectrum(payload, into)

    def mean_spectrum(self, gathered) -> jnp.ndarray:
        """Mean dense spectrum of P gathered payloads (leaves with a leading
        worker axis), the workers folded in order."""
        return self._backend.mean_spectrum(gathered)

    def decompress(self, payload: FFTPayload) -> jnp.ndarray:
        return self._backend.decompress(payload)

    def compress_buckets(self, bucket_flats) -> list:
        """Per-bucket compression: each bucket fits its OWN quantizer range
        (DESIGN.md §8); the bucketed transports rely on this."""
        return self._backend.compress_buckets(self.config, bucket_flats)

    def compress_stacked(self, stacked: jnp.ndarray, sizes) -> StackedPayload:
        """Batched bucket executor (DESIGN.md §14): compress EVERY bucket of a
        ``(n_buckets, padded_size)`` matrix (``bucketing.stack_buckets``) with
        one batched kernel pass, fitting one quantizer per bucket row.
        Bitwise-equal to :meth:`compress_buckets` on the same layout."""
        return self._backend.compress_stacked(self.config, stacked, sizes)

    def decompress_stacked(self, payload: StackedPayload) -> jnp.ndarray:
        """Inverse of :meth:`compress_stacked` -> ``(n_buckets, padded_size)``
        (``bucketing.unstack_buckets`` recovers the flat buffer)."""
        return self._backend.decompress_stacked(payload)

    # -- size accounting ----------------------------------------------------
    def wire_bits(self, n: int) -> int:
        return self._engine_mod.wire_bits(self.config, n)

    def ratio(self, n: int) -> float:
        return 32.0 * n / self.wire_bits(n)


class TimeDomainCompressor:
    """DGC/Aji-style top-k in the time domain + the same range quantizer.

    Used for the paper's Fig. 12 comparison (frequency vs time domain at the
    same theta).
    """

    def __init__(self, config: FFTCompressorConfig = FFTCompressorConfig()):
        self.config = config
        self._qcfg = RangeQuantConfig(config.n_bits, config.m_bits)

    def compress(self, x_flat: jnp.ndarray, key=None):
        cfg = self.config
        x2d, n = cfft.pad_to_chunks(x_flat, cfg.chunk)
        k = sparsify.keep_count(cfg.chunk, cfg.theta)
        idx, _ = selection.select_indices(
            jnp.abs(x2d), k, cfg.selector, sample_rate=cfg.sample_rate,
            refine_iters=cfg.tau_refine_iters, seed=cfg.selector_seed)
        vals = packing.pack_by_indices(x2d, idx)
        if cfg.quantize:
            quant = fit_quantizer(vals.min(), vals.max(), self._qcfg)
            vals = q_encode(vals, quant)
        else:
            quant = None
        # int16 indices, same as FFTPayload's frequency path: chunk <= 4096
        # fits and the wire accounting (index_bits=16) matches the payload.
        # The payload is purely real: ship an EMPTY im plane (has_im=False)
        # so collectives move exactly the bytes wire_bits bills — the old
        # zeros_like(vals) plane doubled the value bytes on every exchange.
        empty_im = jnp.zeros(vals.shape[:-1] + (0,), vals.dtype)
        return FFTPayload(vals, empty_im, idx.astype(jnp.int16), quant, n,
                          cfg.chunk, has_im=False)

    def decompress(self, payload: FFTPayload) -> jnp.ndarray:
        vals = payload.re
        if payload.quant is not None:
            vals = q_decode(vals, payload.quant)
        dense = packing.unpack_by_indices(
            vals.astype(jnp.float32), payload.idx, payload.chunk
        )
        return dense.reshape(-1)[: payload.orig_len]

    def compress_stacked(self, stacked: jnp.ndarray, sizes) -> StackedPayload:
        """Batched per-bucket top-k (DESIGN.md §14): one batched selection over
        the ``(n_buckets, padded_size)`` matrix, one quantizer fit per bucket
        row (padding chunks masked out of the range), bitwise-equal to the
        per-bucket loop."""
        cfg = self.config
        sizes = tuple(int(s) for s in sizes)
        n_buckets, padded = stacked.shape
        c_max = padded // cfg.chunk
        x3 = stacked.reshape(n_buckets, c_max, cfg.chunk).astype(jnp.float32)
        k = sparsify.keep_count(cfg.chunk, cfg.theta)
        idx, _ = selection.select_indices(
            jnp.abs(x3), k, cfg.selector, sample_rate=cfg.sample_rate,
            refine_iters=cfg.tau_refine_iters, seed=cfg.selector_seed)
        vals = packing.pack_by_indices(x3, idx)
        if cfg.quantize:
            valid = valid_chunk_mask(sizes, c_max, cfg.chunk)
            lo = jnp.where(valid, vals, jnp.inf).min(axis=(1, 2))
            hi = jnp.where(valid, vals, -jnp.inf).max(axis=(1, 2))
            quant = stack_bucket_quant(fit_quantizer(lo, hi, self._qcfg))
            vals = q_encode(vals, quant)
        else:
            quant = None
        empty_im = jnp.zeros(vals.shape[:-1] + (0,), vals.dtype)
        return StackedPayload(vals, empty_im, idx.astype(jnp.int16), quant,
                              sizes, cfg.chunk, has_im=False)

    def decompress_stacked(self, payload: StackedPayload) -> jnp.ndarray:
        vals = payload.re
        if payload.quant is not None:
            vals = q_decode(vals, payload.quant)
        n_buckets, c_max, k = vals.shape
        dense = packing.unpack_by_indices(
            vals.astype(jnp.float32).reshape(n_buckets * c_max, k),
            payload.idx.reshape(n_buckets * c_max, k), payload.chunk)
        return dense.reshape(n_buckets, c_max * payload.chunk)

    def wire_bits(self, n: int) -> int:
        cfg = self.config
        n_chunks = max(1, -(-n // cfg.chunk))
        k = sparsify.keep_count(cfg.chunk, cfg.theta)
        value_bits = cfg.n_bits if cfg.quantize else 32
        return n_chunks * k * (value_bits + cfg.index_bits) + 4 * 32

    def ratio(self, n: int) -> float:
        return 32.0 * n / self.wire_bits(n)


class QuantOnlyCompressor:
    """Range-based N-bit quantization without sparsification (ablation)."""

    def __init__(self, n_bits: int = 8, m_bits: int = 3):
        self._qcfg = RangeQuantConfig(n_bits, m_bits)
        self.n_bits = n_bits

    def compress(self, x_flat: jnp.ndarray, key=None):
        quant = fit_quantizer(x_flat.min(), x_flat.max(), self._qcfg)
        return (q_encode(x_flat, quant), quant)

    def decompress(self, payload):
        codes, quant = payload
        return q_decode(codes, quant)

    def wire_bits(self, n: int) -> int:
        return n * self.n_bits + 4 * 32

    def ratio(self, n: int) -> float:
        return 32.0 * n / self.wire_bits(n)


class NoCompression:
    """Identity compressor (the paper's 'orig' baseline)."""

    def compress(self, x_flat: jnp.ndarray, key=None):
        return x_flat

    def decompress(self, payload):
        return payload

    def wire_bits(self, n: int) -> int:
        return 32 * n

    def ratio(self, n: int) -> float:
        return 1.0
