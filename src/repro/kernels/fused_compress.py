"""Pallas TPU kernel: FUSED threshold + pack + quantize (beyond paper).

The paper runs four separate GPU passes (§III-D's own cost model weights the
elementwise pass 4x: cost = M*(4/T_m + 1/T_f + 1/T_p + 1/T_s)).  On TPU the
spectrum tile can stay resident in VMEM through threshold -> compaction ->
range quantization, cutting the HBM round-trips of the compress stage from

    read re,im (8B/bin) + write mag (4) + read mag (4) + write tau
  + read re,im,mag (12) + write packed (..)    ~ 28 B/bin
to
    read re,im,mag (12 B/bin) + write codes+idx (~0.9 B/bin @ theta=0.7)

(EXPERIMENTS.md §Perf, hypothesis H-K1).  The ranking magnitude is an
input, not recomputed in-register: the kept set must be exactly the set the
caller's threshold and quantizer range were computed over, and Mosaic's
``sqrt`` need not round like XLA's (on a v5e the two disagreed often enough
to move the kept set of a few rows in 1e5).  Numerics identical to the
unfused kernels (tests/test_kernels.py::test_fused_matches_unfused).

Compaction is the paper's prefix-sum pack rebuilt from lane rotations, the
one data movement the TPU vector unit does natively (``compact_lanes``):

1. running count of the kept mask: a Hillis-Steele scan, log2(W) rotate+add
   steps over the lane axis;
2. each kept lane must move left by ``gap`` = the dropped lanes before it.
   Moving by the bits of ``gap`` from the lowest up — shift by 2^b where
   bit b is set — never lands two lanes on one slot, because ``gap`` is
   non-decreasing across kept lanes and any two kept lanes are further
   apart than their gaps differ.  log2(W) rotate+select steps.

Values move and are never summed, so the packed planes are bit-exact copies.
Rows are padded to a whole number of 128-lane tiles inside the kernel (the
block overhangs the array; the overhang is masked), so the rotations always
span full tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core import selection
from repro.core.quantizer import encode_math
from repro.kernels.runtime import resolve_interpret

__all__ = ["fused_compress_pallas", "compact_lanes", "lane_pad"]

_LANE = 128
# rows per grid step; at 2049 bins the double-buffered input blocks take
# ~1.7 MB of VMEM and the compaction's live planes a few MB more
_BLOCK_ROWS = 32


def lane_pad(cols: int) -> int:
    """``cols`` rounded up to whole 128-lane tiles."""
    return -(-cols // _LANE) * _LANE


def _prefix_count(keep, col):
    """Inclusive running count of ``keep`` along lanes (int32)."""
    w = keep.shape[-1]
    cnt = keep.astype(jnp.int32)
    s = 1
    while s < w:
        cnt = cnt + jnp.where(col >= s, pltpu.roll(cnt, s, 1), 0)
        s *= 2
    return cnt


def compact_lanes(planes, keep):
    """Stable left-compaction of every row's kept lanes.

    ``planes`` are (r, W) arrays and ``keep`` an (r, W) bool mask, W a
    multiple of 128.  Returns (packed planes, filled): kept values of each
    row occupy its first ``count`` lanes in lane order, every other lane
    holds 0, and ``filled`` marks the occupied lanes.
    """
    w = keep.shape[-1]
    col = jax.lax.broadcasted_iota(jnp.int32, keep.shape, 1)
    gap = jnp.where(keep, col + 1 - _prefix_count(keep, col), -1)
    planes = [jnp.where(keep, p, jnp.zeros_like(p)) for p in planes]
    s = 1
    while s < w:
        gap_in = pltpu.roll(gap, w - s, 1)  # lane c sees lane c + s
        arrive = (gap_in >= 0) & ((gap_in & s) != 0)
        stay = (gap >= 0) & ((gap & s) == 0)
        planes = [
            jnp.where(arrive, pltpu.roll(p, w - s, 1),
                      jnp.where(stay, p, jnp.zeros_like(p)))
            for p in planes
        ]
        gap = jnp.where(arrive, gap_in, jnp.where(stay, gap, -1))
        s *= 2
    return planes, gap >= 0


def _fused_body(params_ref, re_ref, im_ref, mag_ref, tau_in_ref,
                rec_ref, imc_ref, idx_ref, tau_ref, *, cols: int, k_keep: int,
                k_pad: int, m_bits: int, per_row: bool = False):
    if per_row:
        # batched-bucket mode (DESIGN.md §14): each row carries its own
        # quantizer fit — params ride a VMEM plane, one lane-tile wide
        eps = params_ref[:, 0:1]       # (r, 1), broadcasts against (r, cols)
        p_codes = params_ref[:, 1:2]
        n_neg = params_ref[:, 2:3]
    else:
        eps = params_ref[0]
        p_codes = params_ref[1]
        n_neg = params_ref[2]
    col = jax.lax.broadcasted_iota(jnp.int32, re_ref.shape, 1)
    valid = col < cols  # lanes past the array edge hold garbage
    re = jnp.where(valid, re_ref[...], 0.0)
    im = jnp.where(valid, im_ref[...], 0.0)
    mag = jnp.where(valid, mag_ref[...], -1.0)  # overhang is never kept

    # 1. threshold: caller-provided (the engine shares ONE bisection between
    # the quantizer range fit and this kernel), or bisected in-kernel
    # (invariant: count(>=lo) >= k > count(>=hi))
    if tau_in_ref is not None:
        tau = tau_in_ref[...][:, 0]
    else:
        # shared selection-engine math (DESIGN.md §16): identical arithmetic
        # to threshold_pallas and the pure-jnp bisect selector, including the
        # nextafter-widened upper bracket
        tau = selection.bisect_tau(mag, k_keep)
    tau_ref[...] = tau[:, None]

    # 2. compaction, then 3. quantize the first k_pad slots in registers
    # (shared quantizer math keeps codes bitwise-equal to the staged kernel);
    # empty slots stay code 0 / index 0
    (re_c, im_c, ix_c), filled = compact_lanes([re, im, col],
                                               mag >= tau[:, None])
    filled = filled[:, :k_pad]

    def codes(a):
        q = encode_math(a[:, :k_pad], eps, p_codes, n_neg, m_bits)
        return jnp.where(filled, q, 0).astype(rec_ref.dtype)

    rec_ref[...] = codes(re_c)
    imc_ref[...] = codes(im_c)
    idx_ref[...] = ix_c[:, :k_pad]


@functools.partial(jax.jit, static_argnames=("k_keep", "m_bits", "n_bits",
                                             "block_rows", "interpret"))
def fused_compress_pallas(
    re2d: jnp.ndarray,
    im2d: jnp.ndarray,
    mag2d: jnp.ndarray,  # (rows, cols) ranking magnitudes of the same bins
    eps: jnp.ndarray,
    p_codes: jnp.ndarray,
    tau: jnp.ndarray = None,  # optional (rows,) or (rows, 1) threshold
    *,
    k_keep: int,
    n_bits: int = 8,
    m_bits: int = 3,
    block_rows: int = _BLOCK_ROWS,
    interpret: bool = None,
):
    """(rows, cols) spectrum planes -> (re_codes u8, im_codes u8, idx i32, tau).

    Keeps, per row, the bins whose ``mag2d`` is at least tau.  With
    ``tau=None`` the kernel bisects for the keep count ``k_keep`` itself; a
    caller that already ran the threshold kernel (the engine does,
    to fit the quantizer range over the kept set) passes its tau in and the
    in-kernel search is skipped — one bisection per compress, and the mask
    provably matches the fit.  The payload width is padded to the 128-lane
    tile.

    Quantizer params may be scalars (one fit for every row — the monolithic
    path) or vectors of shape ``(rows,)`` (one fit PER ROW — the batched
    bucket executor maps each bucket's fit onto its chunk rows, so ALL
    buckets compress in this one launch; DESIGN.md §14).  Vector params ride
    a VMEM plane instead of SMEM scalars; the in-register math is identical.
    """
    interpret = resolve_interpret(interpret)
    rows, cols = re2d.shape
    lanes = lane_pad(cols)
    k = lane_pad(k_keep)
    block_rows = min(block_rows, rows)
    grid = (pl.cdiv(rows, block_rows),)
    n_neg = (1 << n_bits) - 1 - p_codes
    per_row = jnp.ndim(eps) == 1
    if per_row:
        # (rows, lane-tile) plane: col 0 = eps, 1 = P, 2 = n_neg, rest pad
        params = jnp.zeros((rows, _LANE), jnp.float32)
        params = (params.at[:, 0].set(jnp.asarray(eps, jnp.float32))
                  .at[:, 1].set(p_codes.astype(jnp.float32))
                  .at[:, 2].set(n_neg.astype(jnp.float32)))
    else:
        params = jnp.stack([
            jnp.asarray(eps, jnp.float32),
            p_codes.astype(jnp.float32),
            n_neg.astype(jnp.float32),
        ])
    data = lambda c: pl.BlockSpec((block_rows, c), lambda i: (i, 0),
                                  memory_space=pltpu.VMEM)
    out_dtype = jnp.uint8 if n_bits <= 8 else jnp.uint16
    in_specs = [
        data(_LANE) if per_row else pl.BlockSpec(memory_space=pltpu.SMEM),
        # the planes' blocks overhang the (rows, cols) arrays to whole tiles
        data(lanes), data(lanes), data(lanes),
    ]
    args = [params] + [a.astype(jnp.float32) for a in (re2d, im2d, mag2d)]
    statics = dict(cols=cols, k_keep=k_keep, k_pad=k, m_bits=m_bits,
                   per_row=per_row)
    if tau is None:
        def body(p_ref, re_ref, im_ref, mag_ref, *out_refs):
            _fused_body(p_ref, re_ref, im_ref, mag_ref, None, *out_refs,
                        **statics)
    else:
        body = functools.partial(_fused_body, **statics)
        in_specs.append(data(1))
        args.append(tau.reshape(rows, 1).astype(jnp.float32))
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=in_specs,
        out_specs=[data(k), data(k), data(k), data(1)],
        out_shape=[
            jax.ShapeDtypeStruct((rows, k), out_dtype),
            jax.ShapeDtypeStruct((rows, k), out_dtype),
            jax.ShapeDtypeStruct((rows, k), jnp.int32),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
