"""Device time, milliseconds per step, of the exchange's collectives: the
step's operations under the ``exchange.collective`` scope, asynchronous
ones (an all-gather from its start to its done, on the trace's
asynchronous line) included, as the union of their intervals on each chip,
averaged over the chips.  ``None`` where no operation of the step carries
the scope (a program that does not name its collectives)."""

from bench import scopes, trace

COLLECTIVE = "exchange.collective"


def read(run):
    if not run.steps or run.trace is None:
        return None
    step = scopes.for_run(run)
    lo, hi = run.window_ns
    per_chip, matched = [], False
    for evs in run.device_events(with_async=True):
        hits = [e for e in evs if step.owns(e) and COLLECTIVE in step.parts(e)]
        matched = matched or bool(hits)
        per_chip.append(trace.busy_ns(hits, lo, hi))
    if not matched:
        return None
    return 1e-6 * sum(per_chip) / len(per_chip) / run.steps
