"""Bytes per device per step, in MB, that cross chip links: the compiled
step's collectives under the ring model (``bench/hlo.py``)."""

from bench import hlo


def read(run):
    b = hlo.link_bytes(run.hlo_text, run.chips)
    return b / 1e6 if b > 0 else None
