"""Roofline share, percent, of the fused threshold + pack + quantize Pallas
kernel (``kernels/fused_compress.py``), found by its kernel name; the work
is ``bench/work/fused_compress.py`` on the whole flat gradient, one call a
step.  Bytes bound it."""

from bench import roofline, spec, trace

KERNEL = r"^%?fused_compress_pallas"


def read(run):
    ex = run.traffic["exchange"]
    ops, nbytes = spec.work_counter("fused_compress").count(
        run.param_count, ex["chunk"], ex["theta"])
    shares = []
    for evs in run.device_events():
        hits = trace.select(evs, trace.named(KERNEL))
        s = roofline.share_pct(len(hits), ops, nbytes,
                               trace.device_seconds(hits), run.peaks)
        if s is not None:
            shares.append(s)
    return sum(shares) / len(shares) if shares else None
