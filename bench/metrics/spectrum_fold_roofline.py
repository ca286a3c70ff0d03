"""Roofline share, percent, of the receive-side fold of every worker's
payload into the dense spectrum planes (``engine._scatter_spectrum`` via
``transport._mean_spectrum``).  The work is ``bench/work/spectrum_fold.py``,
one fold a step.

Its operations are chosen by kind and shape, since the program names none
of them: XLA lowers the scatter-add on the TPU to a sort of the (index,
value) pairs of the payload (``s32[rows*k*j]``) and a fusion that writes
the flat (rows * (chunk/2+1)) f32 plane from them.  A named span in the
program should replace this rule.
"""

import re

from bench import roofline, spec, trace


def _is_fold(rows, bins, k, workers):
    payload = "|".join(str(rows * k * j) for j in range(1, workers + 1))
    uses_payload = re.compile(rf"s32\[({payload})\]")
    writes_plane = re.compile(rf"^%\S+ = f32\[({rows * bins}|{rows},{bins})\]")

    def pred(e):
        if not uses_payload.search(e.name):
            return False
        return bool(writes_plane.search(e.name)) or " sort(" in e.name
    return pred


def read(run):
    ex = run.traffic["exchange"]
    chunk, theta = ex["chunk"], ex["theta"]
    work = spec.work_counter("spectrum_fold")
    ops, nbytes = work.count(run.param_count, chunk, theta, run.chips)
    rows, bins = -(-run.param_count // chunk), chunk // 2 + 1
    pred = _is_fold(rows, bins, work.keep_count(bins, theta), run.chips)
    shares = []
    for evs in run.device_events():
        hits = trace.select(trace.innermost(evs), pred)
        s = roofline.share_pct(run.steps if hits else 0, ops, nbytes,
                               trace.device_seconds(hits), run.peaks)
        if s is not None:
            shares.append(s)
    return sum(shares) / len(shares) if shares else None
