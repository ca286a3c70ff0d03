"""Share of the traced window, percent, in which no operation ran on the
device: 1 - (union of the operation intervals / window), averaged over the
cell's chips."""

from bench import trace


def read(run):
    chips = run.device_events()
    if not chips or run.window_s <= 0:
        return None
    lo, hi = run.window_ns
    busy = [trace.busy_ns(evs, lo, hi) for evs in chips]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
