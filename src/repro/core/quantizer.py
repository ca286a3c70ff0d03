"""Range-based N-bit floating point quantizer (paper §III-B.2, Algorithm 1).

The paper's offset-based representation: code "0...0" is 0, code "0...01" is
the smallest positive representable number ``eps`` (pbase), and successive
codes walk upward with an IEEE-like exponent/mantissa pattern — ``m`` mantissa
bits mean the spacing doubles every ``2**m`` codes.  Positive codes occupy
``1..P``; negative codes occupy ``P+1 .. 2**N - 1`` with the same pattern
mirrored.  Given the observed gradient range ``[min, max]`` the quantizer
allocates precision *where the gradients live* — exponentially denser around
zero (paper Fig. 8) — instead of uniformly (QSGD) or ternary (TernGrad).

Value of positive code ``c`` (1-indexed):

    idx = c - 1;  q = idx >> m;  r = idx & (2**m - 1)
    value(c) = eps * 2**q * (1 + r / 2**m)

so segment ``q`` covers ``[eps*2**q, eps*2**(q+1))`` with ``2**m`` evenly
spaced values — relative error ≤ 2**-(m+1) once above ``eps``.

Two ways to fit ``eps``:

* :func:`tune_eps_heuristic` — the paper's Algorithm 1: start from a guess,
  decode the most-negative code, and multiply/divide ``eps`` by 2 until the
  representable range straddles ``min``.  Converges to within a factor of 2.
* :func:`solve_eps` — closed form (beyond paper; see DESIGN.md §10).  Requiring
  value(P) = max and value_neg(2**N - 1 - P) = |min| gives

      P   = (2**N - 1 + 2**m * log2(max / |min|)) / 2
      eps = max / 2**(P / 2**m)

  which balances the positive/negative code budget exactly instead of to
  within ×2.  Both are exposed; the hot path uses the closed form.

Everything here is pure ``jnp`` and jit-compatible with dynamic ``min``/``max``
(the fit is branch-free math / a bounded ``while_loop``).  Encode and decode
are :func:`encode_math` / :func:`decode_math`, which the Pallas kernels
(``repro.kernels.range_quant``, ``fused_compress``, ``fused_decompress``)
call in-register: one definition, built only from compares, additions,
multiplications and bit operations.  A TPU's division, ``log2`` and ``exp2``
are approximations that XLA and Mosaic implement differently, so a code
computed with them could differ between the jnp reference and a kernel
whenever a value sits at a code boundary.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "RangeQuantConfig",
    "FittedQuantizer",
    "solve_eps",
    "tune_eps_heuristic",
    "fit_quantizer",
    "encode",
    "decode",
    "encode_math",
    "decode_math",
    "representable_values",
]


@dataclasses.dataclass(frozen=True)
class RangeQuantConfig:
    """Static configuration of the N-bit range-based float."""

    n_bits: int = 8
    m_bits: int = 3  # mantissa bits; paper: "pick m based on experience"

    def __post_init__(self):
        if not (1 < self.m_bits < self.n_bits):
            raise ValueError(f"need 1 < m_bits < n_bits, got {self}")
        if self.n_bits > 16:
            raise ValueError("n_bits > 16 not supported (codes stored u16)")

    @property
    def n_codes(self) -> int:
        return 1 << self.n_bits

    @property
    def mantissa_scale(self) -> int:
        return 1 << self.m_bits

    @property
    def code_dtype(self):
        return jnp.uint8 if self.n_bits <= 8 else jnp.uint16


# Dynamic (traced) parameters of a fitted quantizer: (eps, P) plus the clip
# range actually representable.  Kept as a small pytree-friendly tuple.
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class FittedQuantizer:
    """A fitted range quantizer: static config + dynamic (eps, P, vmin, vmax)."""

    config: RangeQuantConfig
    eps: jnp.ndarray  # scalar f32
    p_codes: jnp.ndarray  # scalar i32: number of positive codes
    vmax: jnp.ndarray  # largest positive representable
    vmin: jnp.ndarray  # most negative representable (≤ 0)

    def tree_flatten(self):
        return (self.eps, self.p_codes, self.vmax, self.vmin), self.config

    @classmethod
    def tree_unflatten(cls, config, leaves):
        return cls(config, *leaves)

    # -- convenience ------------------------------------------------------
    def encode(self, x: jnp.ndarray) -> jnp.ndarray:
        return encode(x, self)

    def decode(self, codes: jnp.ndarray) -> jnp.ndarray:
        return decode(codes, self)


def _pow2(q):
    """2**q as f32 for integer q in [-126, 127], from the exponent bits."""
    bits = (jnp.asarray(q, jnp.int32) + 127) << 23
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _exponent_mantissa(x):
    """(biased exponent, mantissa bits) of positive normal f32 values."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits >> 23, bits & 0x7FFFFF


def _value_of_index(idx, eps, m_bits):
    """value for 0-based positive index: eps * 2**q * (1 + r/2**m)."""
    idx = jnp.asarray(idx, jnp.int32)
    q = idx >> m_bits
    r = idx & ((1 << m_bits) - 1)
    return (eps * _pow2(jnp.minimum(q, 127))
            * (1.0 + r.astype(jnp.float32) * (1.0 / (1 << m_bits))))


def solve_eps(vmin, vmax, config: RangeQuantConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Closed-form (eps, P) balancing positive/negative code budgets.

    ``vmax`` must be > 0 and ``vmin`` < 0 (symmetric or asymmetric).  Degenerate
    one-sided ranges are handled by the caller (:func:`fit_quantizer`).
    """
    m_scale = config.mantissa_scale
    n_codes = config.n_codes
    vmax = jnp.maximum(vmax, 1e-30)
    vmag = jnp.maximum(-vmin, 1e-30)
    # P = (2^N - 1 + 2^m log2(max/|min|)) / 2, clipped to leave ≥1 code per side
    p_f = (n_codes - 1 + m_scale * (jnp.log2(vmax) - jnp.log2(vmag))) / 2.0
    p = jnp.clip(jnp.round(p_f), 1, n_codes - 2).astype(jnp.int32)
    # In the log-linear approximation value(idx) ≈ eps * 2**(idx / 2**m); pin
    # the TOP code (idx = P-1) to vmax so the clip gap at the range boundary is
    # at most one mantissa step (not a whole half-segment).  The exponent is
    # clamped so eps never underflows f32 (12-bit quantizers of wide ranges
    # would otherwise drive vmax / 2**(P/2**m) to zero).
    exponent = jnp.minimum((p.astype(jnp.float32) - 1.0) / m_scale, 96.0)
    eps = jnp.maximum(vmax / jnp.exp2(exponent), 1e-30)
    return eps, p


def tune_eps_heuristic(
    vmin,
    vmax,
    config: RangeQuantConfig,
    eps_init: float = 0.002,
    max_iters: int = 64,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Paper Algorithm 1: ×2/÷2 search on eps until the decoded "1...1" code
    (most negative representable) straddles ``vmin``.

    Faithful to the paper's loop: if ``actual_min < min`` there are too many
    negative codes → decrease ``eps`` (÷2) to spend more codes on the positive
    side; else increase (×2).  Stops when the sign of the error flips or after
    ``max_iters``.  Returns (eps, P).
    """
    m_scale = config.mantissa_scale
    n_codes = config.n_codes
    vmax = jnp.maximum(vmax, 1e-30)
    vmag = jnp.maximum(-vmin, 1e-30)

    def p_of_eps(eps):
        # codes needed to reach vmax from eps (ceil), ≥ 1
        steps = jnp.ceil(m_scale * (jnp.log2(vmax) - jnp.log2(eps)))
        return jnp.clip(steps, 1, n_codes - 2).astype(jnp.int32)

    def actual_min_of_eps(eps):
        p = p_of_eps(eps)
        n_neg = n_codes - 1 - p
        return -_value_of_index(jnp.maximum(n_neg - 1, 0), eps, config.m_bits)

    def body(state):
        eps, it, prev_sign, done = state
        actual_min = actual_min_of_eps(eps)
        # actual_min < vmin: negative range overshoots → too many negative
        # codes → decrease eps (paper: divide by 2); else multiply by 2.
        sign = jnp.where(actual_min < vmin, -1, 1)
        flipped = (prev_sign != 0) & (sign != prev_sign)
        new_eps = jnp.where(sign < 0, eps * 0.5, eps * 2.0)
        new_eps = jnp.clip(new_eps, 1e-30, vmax)
        done = done | flipped
        eps = jnp.where(done, eps, new_eps)
        return eps, it + 1, sign, done

    def cond(state):
        _, it, _, done = state
        return (~done) & (it < max_iters)

    eps0 = jnp.asarray(eps_init, jnp.float32)
    eps, _, _, _ = jax.lax.while_loop(
        cond, body, (eps0, jnp.asarray(0), jnp.asarray(0), jnp.asarray(False))
    )
    return eps, p_of_eps(eps)


def fit_quantizer(
    vmin,
    vmax,
    config: RangeQuantConfig = RangeQuantConfig(),
    method: str = "solve",
) -> FittedQuantizer:
    """Fit the quantizer to an observed range.

    Handles degenerate ranges: if the data is one-sided we still reserve one
    code on the empty side (the math needs vmin<0<vmax); callers see correct
    clipping behaviour either way.
    """
    vmin = jnp.asarray(vmin, jnp.float32)
    vmax = jnp.asarray(vmax, jnp.float32)
    # Guard: ensure a strictly two-sided, non-empty range.
    span = jnp.maximum(vmax - vmin, 1e-30)
    vmax_eff = jnp.maximum(vmax, span * 1e-6)
    vmin_eff = jnp.minimum(vmin, -span * 1e-6)
    if method == "solve":
        eps, p = solve_eps(vmin_eff, vmax_eff, config)
    elif method == "heuristic":
        eps, p = tune_eps_heuristic(vmin_eff, vmax_eff, config)
    else:
        raise ValueError(f"unknown fit method {method!r}")
    n_neg = config.n_codes - 1 - p
    vmax_rep = _value_of_index(p - 1, eps, config.m_bits)
    vmin_rep = -_value_of_index(jnp.maximum(n_neg - 1, 0), eps, config.m_bits)
    return FittedQuantizer(config, eps, p, vmax_rep, vmin_rep)


def encode_math(x, eps, p_codes, n_neg, m_bits: int):
    """f32 values -> int32 codes (0 = zero, 1..P positive, P+1.. negative).

    ``eps``, ``p_codes`` and ``n_neg`` are scalars or arrays broadcasting
    against ``x`` (one fit per row in the batched kernels).  Magnitudes
    round to the nearest of their segment's ``2**m`` values (halves up);
    below ``eps`` to the nearer of 0 and ``eps``; beyond the range they clip.
    """
    m_scale = 1 << m_bits
    a = jnp.abs(x)
    safe_a = jnp.maximum(a, eps)
    ea, ma = _exponent_mantissa(safe_a)
    ee, me = _exponent_mantissa(jnp.broadcast_to(eps, safe_a.shape))
    q = ea - ee - (ma < me).astype(jnp.int32)  # floor(log2(a / eps)) >= 0
    seg_base = eps * _pow2(jnp.minimum(q, 127))
    d = (safe_a - seg_base) * float(m_scale)  # exact: seg_base <= a < 2 seg_base
    r = sum((d >= (j - 0.5) * seg_base).astype(jnp.int32)
            for j in range(1, m_scale + 1))
    carry = r >= m_scale  # rounds up into the next segment
    q = jnp.where(carry, q + 1, q)
    r = jnp.where(carry, 0, r)
    idx = q * m_scale + r
    idx = jnp.where(a < eps, jnp.where(a * 2.0 >= eps, 0, -1), idx)
    p = jnp.asarray(p_codes).astype(jnp.int32)
    idx_pos = jnp.clip(idx, -1, p - 1)  # -1 encodes "zero"
    idx_neg = jnp.clip(idx, -1, jnp.maximum(jnp.asarray(n_neg).astype(
        jnp.int32), 1) - 1)
    return jnp.where(
        x >= 0,
        jnp.where(idx_pos < 0, 0, idx_pos + 1),
        jnp.where(idx_neg < 0, 0, p + idx_neg + 1),
    )


def decode_math(codes, eps, p_codes, m_bits: int):
    """int codes -> f32 values; inverse of :func:`encode_math`."""
    c = jnp.asarray(codes).astype(jnp.int32)
    p = jnp.asarray(p_codes).astype(jnp.int32)
    is_pos = (c >= 1) & (c <= p)
    idx = jnp.maximum(jnp.where(is_pos, c - 1, c - p - 1), 0)
    mag = _value_of_index(idx, eps, m_bits)
    return jnp.where(c == 0, 0.0, jnp.where(is_pos, mag, -mag))


def encode(x: jnp.ndarray, quant: FittedQuantizer) -> jnp.ndarray:
    """float32 -> N-bit codes (stored in the smallest unsigned dtype)."""
    cfg = quant.config
    n_neg = cfg.n_codes - 1 - quant.p_codes
    code = encode_math(x.astype(jnp.float32), quant.eps, quant.p_codes, n_neg,
                       cfg.m_bits)
    return code.astype(cfg.code_dtype)


def decode(codes: jnp.ndarray, quant: FittedQuantizer) -> jnp.ndarray:
    """N-bit codes -> float32."""
    return decode_math(codes, quant.eps, quant.p_codes,
                       quant.config.m_bits).astype(jnp.float32)


def representable_values(quant: FittedQuantizer) -> jnp.ndarray:
    """All 2**N representable values (paper Fig. 8); for tests/benchmarks."""
    cfg = quant.config
    codes = jnp.arange(cfg.n_codes, dtype=jnp.int32).astype(cfg.code_dtype)
    return decode(codes, quant)


def quantization_rtol(config: RangeQuantConfig) -> float:
    """Worst-case relative error for magnitudes in [eps, vmax]."""
    return 0.5 / config.mantissa_scale
