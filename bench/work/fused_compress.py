"""Work of one call of the fused threshold + pack + quantize kernel
(``kernels/fused_compress.py``) on a gradient of ``n`` values.

The kernel reads three (rows, bins) f32 planes -- real part, imaginary
part, ranking magnitude -- and each row's threshold, and writes two planes
of 8-bit codes and one of int32 indices, ``lane_pad(k)`` wide, and the
threshold back.  Operations: per bin one compare and one add of the running
count; per kept value about 20 for the range-float code.  The bytes bound it
by three orders of magnitude.
"""

from __future__ import annotations

LANE = 128
QUANT_OPS = 20


def keep_count(bins: int, theta: float) -> int:
    return max(1, int(round((1.0 - theta) * bins)))


def count(n: int, chunk: int, theta: float) -> tuple:
    """(operations, bytes) of one call."""
    rows = -(-n // chunk)
    bins = chunk // 2 + 1
    k = keep_count(bins, theta)
    k_pad = -(-k // LANE) * LANE
    read = rows * (3 * bins * 4 + 4)
    write = rows * (k_pad * (1 + 1 + 4) + 4)
    ops = rows * (2 * bins + 2 * k * QUANT_OPS)
    return float(ops), float(read + write)
