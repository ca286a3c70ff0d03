"""Work of the receive-side fold: ``workers`` payloads of ``k`` kept bins per
chunk row into the dense (rows, bins) real and imaginary spectrum planes.

The least it can move: read every payload once (two 8-bit code planes and
one int16 index plane) and write the two f32 planes once.  Operations: per
kept value about 10 to decode the range float and one add.  The count
depends only on the payload and spectrum shapes, so it reads the same work
whatever implements the fold.
"""

from __future__ import annotations

DECODE_OPS = 10


def keep_count(bins: int, theta: float) -> int:
    return max(1, int(round((1.0 - theta) * bins)))


def count(n: int, chunk: int, theta: float, workers: int) -> tuple:
    """(operations, bytes) of one fold."""
    rows = -(-n // chunk)
    bins = chunk // 2 + 1
    k = keep_count(bins, theta)
    read = workers * rows * k * (1 + 1 + 2)
    write = 2 * rows * bins * 4
    ops = workers * rows * k * 2 * (DECODE_OPS + 1)
    return float(ops), float(read + write)
