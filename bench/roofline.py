"""A kernel's share of its roofline: the least time the chip could take for
the work (the larger of operations over peak FLOP/s and bytes over peak HBM
bytes/s), over the device time the kernel's events took."""

from __future__ import annotations


def least_seconds(ops: float, nbytes: float, peaks: dict) -> tuple:
    """(seconds, the term that bounds them: "flops" or "bytes")."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "flops") if t_ops >= t_bytes else (t_bytes, "bytes")


def share_pct(calls: int, ops: float, nbytes: float, seconds: float,
              peaks: dict):
    """Percent of the roofline over ``calls`` calls that took ``seconds``;
    None when nothing was measured."""
    if calls == 0 or seconds <= 0:
        return None
    least, _ = least_seconds(ops, nbytes, peaks)
    return 100.0 * calls * least / seconds
